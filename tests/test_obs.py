"""Observability subsystem: registry parity, trace validity, null-cost path.

The telemetry contract (src/repro/obs/, docs/observability.md):

  * the MetricsRegistry is the single source of truth — every number a
    legacy ``stats()`` dict reports is a view over registry counters, so
    a ``snapshot()`` reproduces them bit-for-bit;
  * a recording Tracer exports valid Chrome trace-event JSON whose
    modeled request timelines reconstruct ``ServedResult.completion_ms``
    per tier (term spans tile the request span exactly);
  * the default NullTracer path changes NOTHING: decoded tokens stay
    bit-identical and the registry holds the same metric names (tracing
    adds spans, never metrics);
  * the per-step dispatch bounds (engine <= 2, federated ladder <= 4)
    re-pin straight from the registry snapshot.
"""
import dataclasses
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coic import CoICConfig
from repro.data.workload import SharedPrefixWorkload
from repro.models import build_model
from repro.obs.metrics import MetricsRegistry, export_prometheus
from repro.obs.trace import NULL_TRACER, PID_REQUESTS, NullTracer, Tracer
from repro.serving.engine import ServingConfig, ServingEngine
from repro.serving.kv_cache import PagedStats

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
from check_trace import TraceError, check_metrics, validate  # noqa: E402

N_REQUESTS = 14


def _drive(model, params, *, tracer=None, metrics=None, seed=0):
    """Seeded federated + paged + EDF run (the full pipeline: descriptor
    ladder, chunked prefill, prefix sharing, deadline accounting)."""
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=4, max_len=96, max_new_tokens=4, kv_page=16,
        prefill_chunk=32, prefix_share=True, step_ms=2.0,
        queue_policy="edf",
        coic=CoICConfig(capacity=32, threshold=0.98, descriptor="sketch",
                        descriptor_dim=64, num_nodes=2, num_clusters=2,
                        digest_size=16, digest_interval=4)),
        tracer=tracer, metrics=metrics)
    wl = SharedPrefixWorkload(num_sessions=4, prefix_len=64, suffix_min=4,
                              suffix_max=16, vocab_size=32, seed=seed)
    rids = []
    for i, (sess, prompt) in enumerate(wl.stream(N_REQUESTS, seed=seed + 1)):
        rids.append(eng.submit(prompt, node_id=i % 2, cluster_id=sess % 2,
                               deadline_ms=40.0 if i % 3 else None))
        eng.step()
    while eng.pending or eng.queue or eng.chunking or eng.active:
        eng.step()
    by = {r.req_id: r for r in eng.results}
    return eng, {rid: by[rid] for rid in rids}


@pytest.fixture(scope="module")
def obs_model():
    cfg = dataclasses.replace(get_config("coic-paper"), dtype="float32",
                              vocab_size=32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


@pytest.fixture(scope="module")
def obs_runs(obs_model):
    """One untraced (defaults: NULL_TRACER + private registry) and one
    traced run over the identical request stream, shared by every test."""
    model, params = obs_model
    eng_u, res_u = _drive(model, params)
    tracer, metrics = Tracer(), MetricsRegistry()
    eng_t, res_t = _drive(model, params, tracer=tracer, metrics=metrics)
    return eng_u, res_u, eng_t, res_t, tracer, metrics


# ---------------------------------------------------------------------------
# registry is the single source of truth
# ---------------------------------------------------------------------------


def test_registry_snapshot_reproduces_legacy_stats(obs_runs):
    """Every counter the legacy stats() dicts report must equal the
    corresponding registry snapshot entry bit-for-bit."""
    _, _, eng, _, _, metrics = obs_runs
    st = eng.stats()
    snap = metrics.snapshot()

    assert st["completed"] == snap["engine/completed"] == N_REQUESTS
    for tier, key in (("edge", "edge_hits"), ("peer", "peer_hits"),
                      ("remote", "remote_hits"), ("cloud", "cloud")):
        assert st[key] == snap.get(f"engine/hits/{tier}", 0)
    for k, v in st["dispatches"].items():
        assert v == snap[f"engine/dispatches/{k}"], k
    assert st["max_step_ladder"] == snap["engine/max_step_ladder"]
    assert st["prefill_tokens"]["computed"] == \
        snap["engine/prefill_tokens_computed"]
    assert st["prefill_tokens"]["shared"] == \
        snap["engine/prefill_tokens_shared"]
    for f in PagedStats.FIELDS:
        assert st["kv"][f] == snap[f"kv/{f}"], f
    for tier, n in st["deadline"]["met"].items():
        assert n == snap[f"deadline/met/{tier}"], tier
    for tier, n in st["deadline"]["missed"].items():
        assert n == snap[f"deadline/missed/{tier}"], tier
    # federated ladder counters (prefix "ladder/")
    fed = eng.sem_fed.stats()
    assert fed["max_ladder_dispatches"] == snap["ladder/max_ladder_dispatches"]
    for tier, n in st["ladder"]["rung_dispatches"].items():
        if tier != "cloud":   # cloud rung lives on the engine's own ladder
            assert n == snap[f"ladder/rung_dispatches/{tier}"], tier


def test_engines_share_one_registry_not_copies(obs_runs):
    """stats() is a thin view: bumping the registry counter must show up
    in the next stats() call (no cached/duplicated counters)."""
    _, _, eng, _, _, metrics = obs_runs
    c = metrics.counter("engine/completed")
    before = eng.stats()["completed"]
    c.inc(7)
    try:
        assert eng.stats()["completed"] == before + 7
    finally:
        c.set(before)


# ---------------------------------------------------------------------------
# trace export: valid Chrome trace-event JSON, reconstructs completion_ms
# ---------------------------------------------------------------------------


def test_trace_exports_valid_chrome_trace(obs_runs, tmp_path):
    *_, res_t, tracer, _ = obs_runs
    path = tmp_path / "trace.json"
    tracer.export(str(path))
    trace = json.loads(path.read_text())
    stats = validate(trace)      # raises TraceError on any violation
    assert stats["requests"] == N_REQUESTS
    # engine spans present and matched (validate checked nesting)
    for name in ("step", "schedule", "admit", "descriptor", "lookup"):
        assert stats["spans"].get(name, 0) > 0, name
    assert res_t


def test_request_spans_reconstruct_completion_ms(obs_runs, tmp_path):
    """Per request: the modeled-track span's duration is completion_ms
    (in us) and its term children sum to it within float rounding."""
    *_, res_t, tracer, _ = obs_runs
    path = tmp_path / "trace.json"
    tracer.export(str(path))
    events = json.loads(path.read_text())["traceEvents"]
    outer = {e["tid"]: e for e in events
             if e.get("cat") == "request_model"}
    terms = {}
    for e in events:
        if e.get("cat") == "request_term":
            terms.setdefault(e["tid"], []).append(e)
    assert set(outer) == set(res_t)
    for rid, r in res_t.items():
        e = outer[rid]
        assert e["pid"] == PID_REQUESTS
        assert e["args"]["tier"] == r.source
        assert abs(e["dur"] - r.completion_ms * 1e3) <= 1.0, rid
        total = sum(t["dur"] for t in terms[rid])
        assert abs(total - r.completion_ms * 1e3) <= 1.0, rid


def test_validator_rejects_malformed_traces():
    with pytest.raises(TraceError):
        validate({"traceEvents": "nope"})
    with pytest.raises(TraceError):   # E without B
        validate({"traceEvents": [
            {"ph": "E", "pid": 1, "tid": 0, "ts": 1.0}]})
    with pytest.raises(TraceError):   # unclosed span
        validate({"traceEvents": [
            {"ph": "B", "name": "step", "pid": 1, "tid": 0, "ts": 1.0}]})


# ---------------------------------------------------------------------------
# NullTracer default: zero effect on serving
# ---------------------------------------------------------------------------


def test_null_tracer_path_bit_identical(obs_runs):
    eng_u, res_u, eng_t, res_t, _, metrics = obs_runs
    assert isinstance(eng_u.trace, NullTracer) and not eng_u.trace.enabled
    assert res_u.keys() == res_t.keys()
    for rid in res_u:
        np.testing.assert_array_equal(res_u[rid].tokens, res_t[rid].tokens)
        assert res_u[rid].source == res_t[rid].source
        assert res_u[rid].completion_ms == res_t[rid].completion_ms
    # tracing adds spans, never registry entries: identical name sets
    assert set(eng_u.metrics.names()) == set(metrics.names())


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    assert NULL_TRACER.begin("x") is None
    assert NULL_TRACER.end() is None
    with NULL_TRACER.span("x"):
        pass
    tr = Tracer()
    with pytest.raises(RuntimeError):
        tr.end()                  # nothing open


def test_profiler_tracer_annotates_the_profilers_trace(tmp_path):
    """``ProfilerTracer`` spans land in the JAX profiler's trace under the
    engine's names, with their args as the events' stats; the modeled
    request timelines are dropped."""
    import glob

    import jax.numpy as jnp

    from repro.obs.trace import ProfilerTracer, to_host

    tr = ProfilerTracer()
    assert tr.enabled
    jax.profiler.start_trace(str(tmp_path))
    tr.begin("step", args={"step": 3})
    with tr.span("decode", args={"active": 4}):
        x = jnp.arange(4) + 1
    assert int(to_host(tr, "argmax", x)[3]) == 4
    tr.request_timeline(0, ts_ms=0.0, tier="edge", terms=[("uplink", 1.0)],
                        completion_ms=1.0)
    tr.end()
    jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = {ev.name: dict(ev.stats)
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    assert events["step"] == {"step": 3}
    assert events["decode"] == {"active": 4}
    assert "d2h:argmax" in events
    assert not {"request", "uplink"} & set(events)


# ---------------------------------------------------------------------------
# dispatch bounds re-pinned from the registry snapshot
# ---------------------------------------------------------------------------


def test_dispatch_bounds_hold_in_registry(obs_runs):
    *_, metrics = obs_runs
    snap = metrics.snapshot()
    assert snap["engine/max_step_ladder"] <= 2
    assert snap["ladder/max_ladder_dispatches"] <= 4
    check_metrics(snap)           # the CI gate's exact assertion


# ---------------------------------------------------------------------------
# probe byte models
# ---------------------------------------------------------------------------


def test_ivf_pq_scan_model_beats_brute_int8_at_board_scale():
    """At board scale the IVF-PQ scan model beats the brute int8 row model
    >= 4x (at toy sizes the one-time shared codebook dominates, so the
    comparison is pinned on the models at 1M advertised rows)."""
    from repro.obs.profile import digest_probe_bytes, ivf_pq_probe_bytes
    rows, L, S, Dm, nq, Km = 1_000_000, 1024, 8, 64, 64, 4
    ivf = ivf_pq_probe_bytes(nq, L, -(-rows // L), S, Dm)
    brute = digest_probe_bytes(nq // Km, Km, rows // Km, Dm, "int8")
    assert brute / ivf >= 4.0, (brute, ivf)


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def _golden_registry() -> MetricsRegistry:
    m = MetricsRegistry()
    m.counter("digest/refreshes").inc(5)
    m.counter("kernel/ivf_pq_probe/ref/calls").inc(2)
    m.counter("kernel/ivf_pq_probe/ref/modeled_bytes").inc(4096)
    m.gauge("engine/max_step_ladder").set(2)
    h = m.histogram("kernel/ivf_pq_probe/ref/wall_ms")
    for v in (0.0, 0.25, 1.0, 4.0, 4.0):
        h.observe(v)
    return m


def test_prometheus_export_matches_golden(tmp_path):
    """export_prometheus is deterministic text: sorted names, sanitized to
    the Prometheus grammar, cumulative le buckets — pinned to a committed
    golden file so the format can't drift silently."""
    out = tmp_path / "metrics.prom"
    text = export_prometheus(_golden_registry(), path=str(out))
    golden = os.path.join(os.path.dirname(__file__), "golden",
                          "metrics.prom")
    with open(golden) as f:
        assert text == f.read()
    assert out.read_text() == text
    # two registries fed the same observations render identical text
    assert export_prometheus(_golden_registry()) == text
    # grammar: no raw '/' survives sanitization outside label values
    for line in text.splitlines():
        if not line.startswith("#"):
            assert "/" not in line.split("{")[0], line


def test_export_metrics_script_renders_snapshot(tmp_path):
    """scripts/export_metrics.py turns a --metrics-out snapshot JSON into
    Prometheus text (histogram snapshots as summaries)."""
    from export_metrics import main as export_main

    snap = tmp_path / "metrics.json"
    out = tmp_path / "metrics.prom"
    _golden_registry().export(str(snap))
    assert export_main([str(snap), "-o", str(out)]) == 0
    text = out.read_text()
    assert "# TYPE digest_refreshes gauge" in text
    assert "digest_refreshes 5" in text
    assert 'kernel_ivf_pq_probe_ref_wall_ms{quantile="0.5"}' in text
    assert "kernel_ivf_pq_probe_ref_wall_ms_count 5" in text


# ---------------------------------------------------------------------------
# tracer ring: bounded host memory on long runs
# ---------------------------------------------------------------------------


def test_tracer_ring_keeps_last_n_steps(tmp_path):
    tr = Tracer(max_steps=3)
    for s in range(10):
        tr.begin("step", args={"step": s})
        with tr.span("lookup"):
            pass
        tr.request_timeline(s, ts_ms=float(s), tier="edge",
                            terms=[("uplink", 1.0)], completion_ms=1.0)
        tr.end()
    steps = [e for e in tr.events
             if e.get("ph") == "B" and e["name"] == "step"]
    assert [e["args"]["step"] for e in steps] == [7, 8, 9]
    path = tmp_path / "ring.json"
    tr.export(str(path))
    stats = validate(json.loads(path.read_text()))
    assert stats["spans"]["step"] == 3
    assert stats["requests"] == 3          # timelines evicted with their step

    # default: unbounded, original behavior
    tr_all = Tracer()
    for s in range(10):
        with tr_all.span("step"):
            pass
    assert sum(1 for e in tr_all.events
               if e.get("ph") == "B" and e["name"] == "step") == 10


def test_ring_truncated_engine_trace_validates(obs_model, tmp_path):
    """A real engine run traced through Tracer(max_steps=N) still exports
    a trace that passes every check_trace structural invariant — eviction
    drops whole steps, never half a span or an orphaned term."""
    model, params = obs_model
    tracer = Tracer(max_steps=6)
    _drive(model, params, tracer=tracer)
    path = tmp_path / "ring_engine.json"
    tracer.export(str(path))
    stats = validate(json.loads(path.read_text()))
    assert 0 < stats["spans"]["step"] <= 6
    assert 0 < stats["requests"] <= N_REQUESTS
