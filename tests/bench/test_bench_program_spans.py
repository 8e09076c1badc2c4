"""The program's own spans on the small hand-written trace of
``test_bench_trace.py``, with the host reads (``d2h:*``), uploads
(``h2d:*``) and host passes (``emit``, ``chunk_prep``) the program now
names added: the trace reduction reads what it read before, and the
readers of the new per-layer metrics return the values worked out by
hand below."""
import types
from pathlib import Path

import pytest

import bench_tiny as BT
from harness import program_spans as PS
from harness import trace_reduce as TR
from test_bench_trace import OPS, SPANS

# busy: 1.0-1.5, 2.6-3.0, 4.6-5.4, 6.5-9.0; the extra spans' idle:
EXTRA = [
    (1.6, 1.9, "d2h:descriptor"),     # in descriptor: idle 0.3
    (3.1, 3.3, "d2h:probe_idx"),      # in probe:local: idle 0.2
    (3.6, 3.7, "d2h:hit"),            # in lookup: idle 0.1
    (4.0, 4.5, "chunk_prep"),         # in admit: idle 0.5, not a sync
    (4.5, 4.55, "h2d:chunk"),         # in prefill_chunk: idle 0.05
    (5.5, 5.7, "d2h:argmax"),         # in admit: idle 0.2
    (6.1, 6.2, "h2d:decode_table"),   # in decode: idle 0.1
    (9.0, 9.2, "d2h:argmax"),         # in decode: idle 0.2
    (9.3, 9.9, "emit"),
    (9.4, 9.5, "d2h:length"),         # in emit: idle 0.1
    (9.6, 9.7, "d2h:length"),         # in emit: idle 0.1
]
D2H = 7                               # d2h spans, all in the one step
SYNC_IDLE_S = 0.3 + 0.2 + 0.1 + 0.05 + 0.2 + 0.1 + 0.2 + 0.1 + 0.1
LADDER_D2H = 2                        # probe_idx and hit, in lookup 2-4


def _as_loaded(spans):
    """What ``trace_reduce.load`` keeps of the host spans."""
    return [s for s in spans if TR._is_span(s[2])]


def test_reduction_reads_what_it_read_before():
    before = TR.reduce([OPS], _as_loaded(SPANS), 0.0, 12.0)
    after = TR.reduce([OPS], _as_loaded(SPANS + EXTRA), 0.0, 12.0)
    assert _as_loaded(SPANS + EXTRA) == SPANS
    assert [(op.name, lb) for op, lb in after.ops] == \
        [(op.name, lb) for op, lb in before.ops]
    assert after.idle_by_label == before.idle_by_label
    assert after.device_s_by_label == before.device_s_by_label
    assert after.span_count == before.span_count
    assert after.breakdown() == before.breakdown()


def test_program_spans_take_idle_from_their_parents():
    t = PS.build([OPS], SPANS + EXTRA, (0.0, 12.0))
    idle = t.reduced.idle_by_label
    assert idle["descriptor"] == pytest.approx(1.0 - 0.3)
    assert idle["d2h:descriptor"] == pytest.approx(0.3)
    assert idle["chunk_prep"] == pytest.approx(0.5)
    assert idle["d2h:length"] == pytest.approx(0.2)
    assert idle["emit"] == pytest.approx(0.6 - 0.2)
    assert t.idle_s(PS.SYNC_PREFIXES) == pytest.approx(SYNC_IDLE_S)
    assert sum(idle.values()) == pytest.approx(12.0 - 4.2)
    # every moment of the step lies in a phase: no idle is the step's own
    assert idle.get("step", 0.0) == 0.0
    assert t.step_idle_s() == pytest.approx(12.0 - 4.2 - 2.0)


def _ctx(devices=1):
    red = types.SimpleNamespace(devices=devices)
    return types.SimpleNamespace(trace=red, run=types.SimpleNamespace(
        trace_dir="hand-written"))


def _readers():
    out = {}
    for name in ("host_syncs_per_step.tok_s", "host_syncs_per_step.hit",
                 "sync_idle_ms.tok_s", "sync_idle_ms.hit",
                 "ladder_syncs_per_lookup"):
        ns = {}
        exec((BT.BENCH / "metrics" / f"{name}.py").read_text(), ns)
        out[name] = ns["read"]
    return out


def test_new_readers_on_the_hand_written_trace(monkeypatch):
    monkeypatch.setattr(PS, "load", lambda d: PS.build(
        [OPS], SPANS + EXTRA, (0.0, 12.0)))
    got = {k: read(_ctx()) for k, read in _readers().items()}
    assert got == pytest.approx({
        "host_syncs_per_step.tok_s": D2H, "host_syncs_per_step.hit": D2H,
        "sync_idle_ms.tok_s": 1e3 * SYNC_IDLE_S,
        "sync_idle_ms.hit": 1e3 * SYNC_IDLE_S,
        "ladder_syncs_per_lookup": LADDER_D2H})


def test_new_readers_find_nothing_in_a_program_without_the_spans(
        monkeypatch):
    """A program that names no host read (the engine before these spans)
    or a trace with no device (off the chip): every reader returns
    ``None``, and off the chip the trace is not read at all."""
    monkeypatch.setattr(PS, "load", lambda d: PS.build(
        [OPS], SPANS, (0.0, 12.0)))
    assert {k: r(_ctx()) for k, r in _readers().items()} == \
        dict.fromkeys(_readers())

    def unread(d):
        raise AssertionError("read a trace with no device")
    monkeypatch.setattr(PS, "load", unread)
    assert {k: r(_ctx(devices=0)) for k, r in _readers().items()} == \
        dict.fromkeys(_readers())


def test_loaders_on_a_profiler_trace(tmp_path):
    """A real trace (CPU: host plane only): ``trace_reduce.load`` keeps
    its own names, ``program_spans.load`` every name the program gives,
    and neither keeps the runtime's own host events."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    ann = jax.profiler.TraceAnnotation
    with ann("window"), ann("step"):
        with ann("decode"):
            x = jnp.arange(4) + 1
        with ann("d2h:argmax"):
            jax.device_get(x)
        with ann("emit"), ann("h2d:tokens"):
            jnp.asarray([1, 2])
    jax.profiler.stop_trace()
    _, spans, window = TR.load_dir(str(tmp_path))
    assert sorted(s[2] for s in spans) == ["decode", "step"]
    t = PS.load(str(Path(tmp_path)))
    assert t.window == window and not t.devices
    assert sorted(s[2] for s in t.spans) == \
        ["d2h:argmax", "decode", "emit", "h2d:tokens", "step"]
