"""Every host read of a device value on the served path is a ``d2h:*``
span, and the span names the engine and the ladder emit keep the trace
reduction's vocabulary.

A host read of a ``jax.Array`` (``int()``, ``np.asarray``,
``jax.device_get``) goes through ``ArrayImpl._value``; the tests count
those reads during ``step()`` and hold the count to the ``d2h:*`` spans
a recording ``Tracer`` holds.
"""
import contextlib
import re
import time

import numpy as np
import pytest
from jax._src.array import ArrayImpl

from benchmarks.onchip.harness.trace_reduce import HOST_SPANS
from repro.core.coic import CoICConfig
from repro.obs.trace import NULL_TRACER, Tracer, to_host
from repro.serving.engine import ServingConfig, ServingEngine

# (kv_page, admission): the paged engine, and the dense one with its
# bucketed and chunked admissions; every admission policy that reads
# shard state on a peer hit
CASES = [(16, "always"), (16, "freq_weighted"), (0, "second_hit")]


@contextlib.contextmanager
def _counting_reads(reads):
    """Add one to ``reads[0]`` for each host read of a device array made
    inside the block."""
    value = vars(ArrayImpl)["_value"]

    @property
    def counted(self):
        reads[0] += 1
        return value.fget(self)

    ArrayImpl._value = counted
    try:
        yield
    finally:
        ArrayImpl._value = value


def _serve(model, params, *, kv_page, admission, tracer):
    """Misses on node 0, then the same prompts on node 0 (local hits) and
    node 1 (peer hits), twice.  Returns the engine, the host reads of
    device values its steps made, and the wall time it took."""
    reads = [0]
    t0 = time.perf_counter()
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=4, max_len=64, max_new_tokens=4, kv_page=kv_page,
        prefill_chunk=16,
        coic=CoICConfig(capacity=16, threshold=0.98, descriptor="prefix",
                        k_layers=1, num_nodes=2, admission=admission)),
        tracer=tracer)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.cfg.vocab_size, size=n).astype(np.int32)
               for n in (8, 12, 24, 40)]
    for node_ids in ([0] * 4, [0, 1, 0, 1], [1, 1, 1, 1]):
        for p, node in zip(prompts, node_ids):
            eng.submit(p, node_id=node)
        while eng.pending or eng.queue or eng.chunking or eng.active:
            with _counting_reads(reads):
                eng.step()
    return eng, reads[0], time.perf_counter() - t0


@pytest.fixture(scope="module")
def served(tiny_model):
    """Per case: (traced engine, its tracer, its reads) and (untraced
    engine, its reads, its wall time)."""
    model, params = tiny_model
    out = {}
    for kv_page, admission in CASES:
        tracer = Tracer()
        eng_t, n_t, _ = _serve(model, params, kv_page=kv_page,
                               admission=admission, tracer=tracer)
        eng_u, n_u, wall = _serve(model, params, kv_page=kv_page,
                                  admission=admission, tracer=None)
        out[kv_page, admission] = (eng_t, tracer, n_t, eng_u, n_u, wall)
    return out


def _names(tracer):
    return [e["name"] for e in tracer.events if e.get("ph") == "B"]


@pytest.mark.parametrize("case", CASES)
def test_every_device_read_in_a_step_is_a_d2h_span(served, case):
    eng, tracer, reads, *_ = served[case]
    names = _names(tracer)
    d2h = [n for n in names if n.startswith("d2h:")]
    assert reads == len(d2h) > 0
    st = eng.stats()
    assert st["edge_hits"] > 0 and st["peer_hits"] > 0 and st["cloud"] > 0
    # a decode reads its row lengths with its argmax, never row by row
    assert "d2h:length" not in names
    dispatched = (eng.dispatches["decode"] + eng.dispatches["prefill"]
                  + eng.dispatches["prefill_chunk"])
    assert 0 < names.count("decode") <= names.count("d2h:argmax") \
        <= dispatched
    for what in ("descriptor", "argmax", "probe_idx", "probe_score", "hit",
                 "score", "value"):
        assert f"d2h:{what}" in d2h, what


@pytest.mark.parametrize("case", CASES)
def test_untraced_steps_read_as_often_as_traced_ones(served, case):
    """The spans only name the reads: the untraced engine makes the same
    reads and serves the same tokens."""
    traced, _, n_traced, plain, n_plain, _ = served[case]
    assert n_plain == n_traced
    assert len(traced.results) == len(plain.results)
    for a, b in zip(traced.results, plain.results):
        assert (a.req_id, a.source) == (b.req_id, b.source)
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_span_names_keep_the_reductions_vocabulary(served):
    """Names the trace reduction keeps (its ten and ``probe:*``) are the
    engine phases as before; every other name is a host read, an upload,
    a host pass or a request marker, and none of those is in the
    reduction's set."""
    names = set(_names(served[CASES[0]][1]))
    kept = {n for n in names if n in HOST_SPANS or n.startswith("probe:")}
    assert kept == {"step", "schedule", "descriptor", "lookup",
                    "probe:local", "probe:peer", "admit", "prefill_chunk",
                    "decode", "retire"}
    new = names - kept
    assert {"emit", "chunk_prep", "h2d:tokens",
            "h2d:decode_table", "h2d:chunk", "h2d:row_state"} <= new
    for n in new:
        assert re.fullmatch(r"(d2h|h2d):\w+|emit|chunk_prep|request:\d+",
                            n), n
        assert n not in HOST_SPANS and not n.startswith("probe:"), n


@pytest.mark.parametrize("kv_page", [16, 0])
def test_a_decode_step_reads_once_however_many_rows_decode(tiny_model,
                                                           kv_page):
    """Once every row decodes, a step makes one host read, the argmax
    with the row lengths, for one row as for four."""
    model, params = tiny_model
    rng = np.random.default_rng(1)
    per_step = {}
    for rows in (1, 4):
        tracer = Tracer()
        eng = ServingEngine(model, params, ServingConfig(
            max_batch=4, max_len=64, max_new_tokens=8, kv_page=kv_page),
            tracer=tracer)
        for n in (8, 12, 24, 40)[:rows]:
            eng.submit(rng.integers(0, model.cfg.vocab_size,
                                    size=n).astype(np.int32))
        while eng.pending or eng.queue or eng.chunking:
            eng.step()
        assert len(eng.active) == rows
        reads = [0]
        n_events = len(tracer.events)
        with _counting_reads(reads):
            eng.step()
        assert len(eng.active) == rows
        d2h = [e["name"] for e in tracer.events[n_events:]
               if e.get("ph") == "B" and e["name"].startswith("d2h:")]
        assert d2h == ["d2h:argmax"]
        per_step[rows] = reads[0]
    assert per_step[1] == per_step[4] == 1


def test_hit_latency_is_measured_wall_time(served):
    *_, eng, _, wall = served[CASES[0]]
    hits = [r for r in eng.results if r.source != "cloud"]
    assert hits
    for r in eng.results:
        assert 0.0 < r.latency_s <= wall
    for r in hits:
        # the modeled total stays in the breakdown and completion_ms
        assert r.completion_ms == pytest.approx(r.breakdown.total_ms)


def test_to_host_reads_without_a_span_when_untraced():
    import jax.numpy as jnp
    x = jnp.arange(3)
    np.testing.assert_array_equal(to_host(NULL_TRACER, "x", x), [0, 1, 2])
    tr = Tracer()
    assert int(to_host(tr, "x", x[2])) == 2
    assert [e["name"] for e in tr.events if e.get("ph") == "B"] == ["d2h:x"]
