"""Two-stage IVF-PQ digest probe vs the brute board scans.

The ANN-index-PR benchmark: one region board holding ``rows`` advertised
keys across K clusters, probed three ways through the actual serving
entry points (``parallel/sharding.py``) —

  * brute fp32   ``federated_digest_lookup``          (D*4 bytes/row)
  * brute int8   ``federated_digest_lookup_quantized`` (D+4 bytes/row)
  * IVF-PQ       ``federated_digest_lookup_ivfpq``     (S+2 bytes/slot
                 + the one-time coarse table / codebook reads)

Every query is a stored key from a *remote* cluster, so ground truth is
known: brute fp32 confirms essentially all of them.  **recall@confirm**
is the fraction of brute-fp32-confirmed requests whose IVF-PQ candidate
ALSO survives the full-precision confirm (true cosine of the returned
row >= tau) — the end-to-end serve-rate ratio, not a raw top-k overlap,
because the confirm is what gates a remote serve either way.

Scanned bytes/row come from the ``obs/profile.py`` wire models; each
probe's latency is its wall time to ``block_until_ready``.  The 1M and
10M rows-per-region points are modeled with the same byte formulas (the
index layout is scale-free); latency is measured at the build scale.

The ``ann_accept`` row is what the nightly smoke pins:

  * IVF-PQ recall@confirm >= 0.95 against brute fp32
  * IVF-PQ scans >= 4x fewer bytes/row than brute int8 at region scale
    (1M rows/shard, the paper's 10M+ aggregate across a federation)
  * the ladder stays <= 4 dispatches/step with the ANN rung active

Emitted JSON record (``BENCH_ann_probe.json``): the acceptance numbers
plus the per-scale bytes/row table, for the perf-history artifact.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

REPO_ROOT = Path(__file__).resolve().parent.parent

TAU = 0.9


def _unit(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _time_us(fn, iters=4):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6, out


def _scale_knobs(rows: int, n_sub: int):
    """Per-scale index shape: ~sqrt(rows) lists (the usual IVF balance
    point, rounded to a power of two), capacity at the mean fill."""
    n_lists = int(2 ** round(np.log2(max(64.0, rows ** 0.5))))
    return n_lists, -(-rows // n_lists)


def _bytes_per_row(rows: int, K: int, B: int, D: int, n_sub: int):
    """The three wire models, per advertised row, at ``rows`` per region."""
    from repro.obs.profile import digest_probe_bytes, ivf_pq_probe_bytes

    n_lists, cap = _scale_knobs(rows, n_sub)
    nq = K * B
    return {
        "fp32": digest_probe_bytes(B, K, rows // K, D, "fp32") / rows,
        "int8": digest_probe_bytes(B, K, rows // K, D, "int8") / rows,
        "ivfpq": ivf_pq_probe_bytes(nq, n_lists, cap, n_sub, D) / rows,
    }


def _ladder_dispatches(seed: int) -> int:
    """Drive a small federation with the ANN rung forced on and report the
    max device dispatches any step needed (the <=4 acceptance)."""
    from repro.core.cluster import ClusterConfig
    from repro.core.federation import FederatedEdgeTier, FederationConfig

    rng = np.random.default_rng(seed)
    K, N, cap, d, p = 3, 2, 8, 32, 4
    fed = FederatedEdgeTier(FederationConfig(
        num_clusters=K, digest_size=N * cap, digest_interval=1,
        ann_mode="ivfpq", ann_min_rows=1, ann_lists=4, ann_sub=4,
        ann_probe=4, ann_admission=0.0,
        cluster=ClusterConfig(num_nodes=N, node_capacity=cap, key_dim=d,
                              payload_dim=p, threshold=0.85,
                              admission="never")))
    pool = _unit(rng, 24, d)
    pay = rng.standard_normal((24, p)).astype(np.float32)
    for k in range(K):
        for n in range(N):
            ids = rng.integers(0, 24, size=cap // 2)
            fed.insert(k, n, jnp.asarray(pool[ids]), jnp.asarray(pay[ids]))
    for _ in range(4):
        qids = rng.integers(0, 24, size=(K, N, 4))
        fed.lookup_grouped(pool[qids])
    assert fed.board.ann_codebook is not None
    return int(fed.max_ladder_dispatches)


def run(seed: int = 0, rows: int = 100_000, K: int = 4, B: int = 64,
        D: int = 64, n_sub: int = 8, n_probe: int = 16,
        train_rows: int = 8192, smoke: bool = False, json_path: str = ""):
    from repro.core.digest import (build_ivfpq_index, quantize_rows,
                                   train_pq_codebook)
    from repro.kernels.similarity.ops import resolve_impl
    from repro.parallel.sharding import (federated_digest_lookup,
                                         federated_digest_lookup_ivfpq,
                                         federated_digest_lookup_quantized)

    if smoke:
        rows, B = 32_768, 32

    rng = np.random.default_rng(seed)
    M = rows // K                                    # advertised rows/cluster
    keys = _unit(rng, K * M, D)
    owner = np.repeat(np.arange(K, dtype=np.int32), M)
    valid = np.ones(K * M, bool)

    # queries: stored keys from a REMOTE cluster per home group (ground
    # truth known — brute fp32 confirms these at cosine 1.0)
    qrid = np.stack([rng.choice(np.flatnonzero(owner != h), size=B)
                     for h in range(K)])             # (K, B) global row ids
    queries = jnp.asarray(keys[qrid])                # (K, B, D)

    digests = jnp.asarray(keys.reshape(K, M, D))
    dvalid = jnp.asarray(valid.reshape(K, M))
    codes8, scales8 = quantize_rows(keys)
    codes8 = jnp.asarray(codes8.reshape(K, M, D))
    scales8 = jnp.asarray(scales8.reshape(K, M))

    n_lists, _ = _scale_knobs(rows, n_sub)
    cb = train_pq_codebook(keys[:train_rows], n_lists=n_lists, n_sub=n_sub,
                           seed=seed, iters=4)
    index = build_ivfpq_index(cb, keys, valid, owner)

    us32, (i32, s32) = _time_us(
        lambda: federated_digest_lookup(queries, digests, dvalid, 1))
    us8, (i8, s8) = _time_us(
        lambda: federated_digest_lookup_quantized(
            queries, codes8, scales8, dvalid, 1))
    usq, (iq, sq) = _time_us(
        lambda: federated_digest_lookup_ivfpq(queries, index, 1,
                                              n_probe=n_probe))
    impl = resolve_impl("auto")

    # recall@confirm: would the candidate survive the full-precision
    # confirm (true cosine >= TAU)?  fp32's candidates are the baseline.
    def confirmed(idx):
        cand = keys[np.clip(np.asarray(idx)[..., 0], 0, K * M - 1)]
        return ((cand * keys[qrid]).sum(-1) >= TAU) & \
            (np.asarray(idx)[..., 0] >= 0)

    ok32 = confirmed(i32)
    okq = confirmed(iq)
    assert ok32.any()
    recall = float((ok32 & okq).sum() / ok32.sum())
    int8_recall = float((ok32 & confirmed(i8)).sum() / ok32.sum())

    bpr = _bytes_per_row(rows, K, B, D, n_sub)
    disp = _ladder_dispatches(seed)

    rows_out = []
    for name, us in (("fp32", us32), ("int8", us8), ("ivfpq", usq)):
        rec = {"fp32": 1.0, "int8": int8_recall, "ivfpq": recall}[name]
        rows_out.append((f"ann_probe_{name}", f"{us:.1f}",
                         f"rows={rows};impl={impl}"
                         f";bytes_per_row={bpr[name]:.2f}"
                         f";recall_confirm={rec:.4f}"))

    # the scale table: same wire models at region scale (latency is
    # measured above; the byte formulas are exact at any rows)
    table = {}
    for scale in (100_000, 1_000_000, 10_000_000):
        b = _bytes_per_row(scale, K, B, D, n_sub)
        table[scale] = b
        rows_out.append(
            (f"ann_bytes_model_{scale // 1000}k", "0.0",
             f"fp32={b['fp32']:.2f};int8={b['int8']:.2f}"
             f";ivfpq={b['ivfpq']:.2f}"
             f";int8_over_ivfpq={b['int8'] / b['ivfpq']:.2f}"))

    ratio_1m = table[1_000_000]["int8"] / table[1_000_000]["ivfpq"]
    rows_out.append(("ann_ladder_dispatches", "0.0",
                     f"max_ladder_dispatches={disp};bound=4"
                     f";ok={disp <= 4}"))
    ok = recall >= 0.95 and ratio_1m >= 4.0 and disp <= 4
    rows_out.append(("ann_accept", "0.0",
                     f"recall_confirm={recall:.4f};floor=0.95"
                     f";int8_over_ivfpq_1m={ratio_1m:.2f};bytes_floor=4.0"
                     f";max_ladder_dispatches={disp};ok={ok}"))

    if json_path:
        with open(json_path, "w") as f:
            json.dump({
                "bench": "ann_probe", "rows": rows, "clusters": K,
                "dim": D, "n_sub": n_sub, "n_lists": n_lists,
                "n_probe": n_probe, "impl": impl,
                "us_per_call": {"fp32": us32, "int8": us8, "ivfpq": usq},
                "recall_confirm": recall,
                "int8_recall_confirm": int8_recall,
                "bytes_per_row": {str(s): t for s, t in table.items()},
                "int8_over_ivfpq_1m": ratio_1m,
                "max_ladder_dispatches": disp,
                "ok": bool(ok),
            }, f, indent=2)
    return rows_out


def run_smoke():
    # anchor the perf record at the repo root so it lands in the same
    # place no matter where run.py is invoked from
    return run(smoke=True, json_path=str(REPO_ROOT / "BENCH_ann_probe.json"))


if __name__ == "__main__":
    import sys

    path = str(REPO_ROOT / "BENCH_ann_probe.json")
    for r in run(smoke="--smoke" in sys.argv, json_path=path):
        print(",".join(str(x) for x in r))
