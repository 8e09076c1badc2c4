"""Pallas TPU kernel for the two-stage IVF-PQ digest probe.

ONE dispatch runs both stages.  The grid walks the inverted lists; at the
first step the full centroid table (pinned in VMEM) is scored against the
resident query tile and the per-query top-``n_probe`` list ids land in a
pinned ``sel`` output block.  Every subsequent step streams one list's PQ
codes through VMEM, and — only when some query actually probed that list
(``@pl.when`` skips the decode + matmul for cold lists) — reconstructs the
list's keys as ``centroid + onehot(codes) @ codebook`` on the MXU and merges
the masked scores into the carried top-k, exactly the
``similarity/kernel.py::_topk_tile`` scheme.

HBM cost intuition vs the brute int8 board scan: the codes array is
``n_sub + 2`` bytes/row instead of ``D + 4``, and the compute for unprobed
lists (all but ``~n_probe`` of them per query tile) is skipped entirely.

Agreement with ``ref.py``: the coarse matmul is the identical dot_general;
the PQ decode is a one-hot matmul (copies codebook entries exactly); the
per-list score matmuls contract over the same D axis, in another tiling
(scores agree to f32 rounding); and the iterated-argmax selection/merge
resolves ties to the first occurrence, i.e. ``lax.top_k`` order over the
flat ``list * cap + slot`` axis.  Rows with fewer than k candidates pad
with NEG_INF scores whose indices are unspecified.

TPU layout: the wrapper reshapes every per-list row to ``(L, 1, ...)``
(centroid j ``(1, 1, D)``, slot masks ``(1, 1, cap)``), carries masks as
int32 and per-query ``home`` as a ``(Q, 1)`` column, so each block's last
two dims are whole; 1-D blocks and a second-minor block dim of 1 over an
axis of L > 1 are refused by Mosaic.  A list longer than ``CAP_TILE``
slots is walked in ``CAP_TILE``-slot tiles (a second grid axis; the list
is padded with dead slots to a whole tile), so the per-step decode
``(tile, D)`` and scores ``(Q, tile)`` fit VMEM whatever the board size.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
CAP_TILE = 512    # slots per grid step (a multiple of 128)


def _merge_topk(scores, local_idx, carry_s, carry_i, *, k: int):
    """Merge a pre-masked (Q, cap) score tile into the carried top-k.

    Same iterated masked-argmax as ``similarity/kernel.py::_topk_tile`` but
    the mask is applied by the caller (IVF validity is per query *and* slot:
    list selection x slot liveness x owner exclusion), so this just takes
    the finished scores.  Candidate order [carried | new tile] + argmax's
    first-occurrence tie break keep ``lax.top_k`` semantics on the flat row.
    """
    cand_s = jnp.concatenate([carry_s, scores], axis=1)
    cand_i = jnp.concatenate([carry_i, local_idx], axis=1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, cand_s.shape, 1)
    out_s, out_i = [], []
    for _ in range(k):
        arg = jnp.argmax(cand_s, axis=1).astype(jnp.int32)
        onehot = lanes == arg[:, None]
        out_s.append(jnp.max(cand_s, axis=1))
        out_i.append(jnp.sum(jnp.where(onehot, cand_i, 0), axis=1))
        cand_s = jnp.where(onehot, -jnp.inf, cand_s)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _ivfpq_kernel(q_ref, home_ref, cent_ref, centj_ref, cvalid_ref,
                  codes_ref, svalid_ref, sowner_ref, cb_ref,
                  sel_ref, idx_ref, score_ref, *, cap: int, tile: int,
                  k: int, n_probe: int):
    """One grid step = one ``tile``-slot tile of one inverted list (plus
    the coarse stage at the first step)."""
    j, t = pl.program_id(0), pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)                   # (Q, D)

    @pl.when((j == 0) & (t == 0))
    def _coarse():
        coarse = jax.lax.dot_general(
            q, cent_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Q, L)
        coarse = jnp.where(cvalid_ref[...] != 0, coarse, NEG_INF)
        lanes = jax.lax.broadcasted_iota(jnp.int32, coarse.shape, 1)
        picks = []
        for _ in range(n_probe):
            arg = jnp.argmax(coarse, axis=1).astype(jnp.int32)
            picks.append(arg)
            coarse = jnp.where(lanes == arg[:, None], -jnp.inf, coarse)
        sel_ref[...] = jnp.stack(picks, axis=1)
        score_ref[...] = jnp.full_like(score_ref, NEG_INF)
        # iota init: a candidate-free query yields indices 0..k-1, matching
        # the oracle's tie-break over an all-NEG_INF row
        idx_ref[...] = jax.lax.broadcasted_iota(jnp.int32, idx_ref.shape, 1)

    # (Q, 1): did query q probe list j?
    probed = jnp.max((sel_ref[...] == j).astype(jnp.int32), axis=1,
                     keepdims=True)

    @pl.when(jnp.max(probed) > 0)
    def _fine():
        codes = codes_ref[0]                             # (tile, S) int32
        parts = []
        for s in range(cb_ref.shape[0]):
            onehot = (codes[:, s:s + 1] == jax.lax.broadcasted_iota(
                jnp.int32, (tile, 256), 1)).astype(jnp.float32)
            parts.append(jax.lax.dot_general(
                onehot, cb_ref[s].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))     # (tile, D//S)
        decoded = jnp.concatenate(parts, axis=-1)        # (tile, D)
        keys_j = centj_ref[0].astype(jnp.float32) + decoded
        scores = jax.lax.dot_general(
            q, keys_j, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (Q, tile)
        ok = ((svalid_ref[0] != 0) & (sowner_ref[0] != home_ref[...])
              & (probed > 0))
        scores = jnp.where(ok, scores, NEG_INF)
        local_idx = (jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                     + j * cap + t * tile)
        s_out, i_out = _merge_topk(scores, local_idx, score_ref[...],
                                   idx_ref[...], k=k)
        score_ref[...] = s_out
        idx_ref[...] = i_out


@functools.partial(jax.jit, static_argnames=("k", "n_probe", "interpret"))
def ivf_pq_probe_kernel(queries: jax.Array, home: jax.Array,
                        centroids: jax.Array, cent_valid: jax.Array,
                        codes: jax.Array, slot_valid: jax.Array,
                        slot_owner: jax.Array, codebook: jax.Array, *,
                        k: int, n_probe: int, interpret: bool = False):
    """queries (Q, D) with Q a multiple of 8 (ops.py pads); index arrays as
    documented in ref.py.  Returns (idx (Q, k) int32 flat slot ids,
    score (Q, k) f32, sel (Q, n_probe) int32).
    """
    Q, D = queries.shape
    L, cap, S = codes.shape
    assert Q % 8 == 0, Q
    assert D % S == 0 and codebook.shape == (S, 256, D // S), (
        codebook.shape, (S, 256, D // S))
    assert n_probe <= L, (n_probe, L)

    tile = cap if cap <= CAP_TILE else CAP_TILE
    pad = (-cap) % tile
    codes = jnp.pad(codes.astype(jnp.int32), ((0, 0), (0, pad), (0, 0)))
    slot_valid = jnp.pad(slot_valid.astype(jnp.int32), ((0, 0), (0, pad)))
    slot_owner = jnp.pad(slot_owner.astype(jnp.int32), ((0, 0), (0, pad)),
                         constant_values=-1)
    n_t = (cap + pad) // tile

    kernel = functools.partial(_ivfpq_kernel, cap=cap, tile=tile, k=k,
                               n_probe=n_probe)
    whole = lambda j, t: (0, 0)
    sel, idx, score = pl.pallas_call(
        kernel,
        grid=(L, n_t),
        in_specs=[
            pl.BlockSpec((Q, D), whole),                     # queries
            pl.BlockSpec((Q, 1), whole),                     # home
            pl.BlockSpec((L, D), whole),                     # centroids
            pl.BlockSpec((1, 1, D), lambda j, t: (j, 0, 0)),  # centroid j
            pl.BlockSpec((1, L), whole),                     # cent_valid
            pl.BlockSpec((1, tile, S), lambda j, t: (j, t, 0)),  # codes
            pl.BlockSpec((1, 1, tile), lambda j, t: (j, 0, t)),  # slot_valid
            pl.BlockSpec((1, 1, tile), lambda j, t: (j, 0, t)),  # slot_owner
            pl.BlockSpec((S, 256, D // S), lambda j, t: (0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Q, n_probe), whole),
            pl.BlockSpec((Q, k), whole),
            pl.BlockSpec((Q, k), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, n_probe), jnp.int32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
        ],
        interpret=interpret,
        name="ivf_pq_probe",
    )(queries.astype(jnp.float32), home.astype(jnp.int32).reshape(Q, 1),
      centroids.astype(jnp.float32),
      centroids.astype(jnp.float32).reshape(L, 1, D),
      cent_valid.astype(jnp.int32).reshape(1, L), codes,
      slot_valid.reshape(L, 1, cap + pad),
      slot_owner.reshape(L, 1, cap + pad),
      codebook.astype(jnp.float32))
    return idx, score, sel
