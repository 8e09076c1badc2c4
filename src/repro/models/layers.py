"""Layer primitives shared by every architecture in the pool.

Pure functions over explicit parameter dicts — no module framework.  All
matmul-heavy ops accept an ``impl`` switch so the serving/training paths can
select the Pallas kernels (TPU target) or the XLA reference path (CPU smoke
tests and the dry-run, where Pallas TPU custom-calls cannot lower).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, MoEConfig, ModelConfig

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple                      # logical axis names, len == len(shape)
    init: str = "normal"             # normal | zeros | ones | small_normal
    dtype: Optional[str] = None      # None => model dtype


def init_leaf(spec: ParamSpec, rng: jax.Array, dtype) -> jax.Array:
    dt = spec.dtype or dtype
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    scale = 0.02 if spec.init == "normal" else 0.006
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    scale = min(scale, 1.0 / np.sqrt(max(1, fan_in)))
    return (jax.random.normal(rng, spec.shape, jnp.float32) * scale).astype(dt)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """(head_dim//2,) inverse frequencies."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE.  x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    inv_freq = rope_frequencies(d, theta)                       # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., S, d/2)
    cos = jnp.cos(angles)[..., None, :]                          # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core (GQA, causal / sliding window / cross, XLA path)
# ---------------------------------------------------------------------------


def attention_mask(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
                   window: int = 0, kv_len: Optional[jax.Array] = None) -> jax.Array:
    """(..., Sq, Sk) boolean mask.  q_pos/k_pos: (..., Sq)/(..., Sk) absolute
    positions.  window>0 adds sliding-window band.  kv_len masks unwritten
    cache slots (k_pos < kv_len)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        mask = mask & (kp <= qp)
    if window > 0:
        mask = mask & (kp > qp - window)
    if kv_len is not None:
        mask = mask & (kp < kv_len)
    return mask


def gqa_attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array,
                  *, scale: Optional[float] = None) -> jax.Array:
    """Grouped-query attention, XLA reference path.

    q: (B, Sq, H, D);  k/v: (B, Sk, K, D) with H % K == 0;
    mask: broadcastable to (B, Sq, Sk).  Returns (B, Sq, H, D).
    Softmax in fp32."""
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qg = q.reshape(B, Sq, K, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    m = mask[:, None, None, :, :]                               # (B,1,1,Sq,Sk)
    logits = jnp.where(m, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, Sq, H, D)


def mha_cross_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Unmasked cross attention (encoder-decoder)."""
    B, Sq, H, D = q.shape
    logits = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / np.sqrt(D)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


# Sequences at or above this length use the q-chunked attention path so the
# (Sq, Sk) logits / mask never materialize in full (Rabe & Staats '21 — the
# XLA analogue of flash attention; the Pallas kernel is the TPU fast path).
CHUNKED_ATTN_THRESHOLD = 8192
CHUNK_Q = 1024


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, k_pos: jax.Array, *,
                     causal: bool = True, window: int = 0,
                     chunk_q: int = 0) -> jax.Array:
    """GQA attention with mask built from positions; q-chunked when long.

    q: (B, Sq, H, D); k/v: (B, Sk, K, D); q_pos: (B, Sq); k_pos: (B, Sk).
    """
    B, Sq, H, D = q.shape
    if chunk_q == 0:
        chunk_q = CHUNK_Q if max(Sq, k.shape[1]) >= CHUNKED_ATTN_THRESHOLD else 0
    if chunk_q == 0 or Sq <= chunk_q or Sq % chunk_q != 0:
        mask = attention_mask(q_pos, k_pos, causal=causal, window=window)
        return gqa_attention(q, k, v, mask)

    nblk = Sq // chunk_q
    qb = q.reshape(B, nblk, chunk_q, H, D).swapaxes(0, 1)          # (nblk,B,cq,H,D)
    pb = q_pos.reshape(B, nblk, chunk_q).swapaxes(0, 1)            # (nblk,B,cq)

    def body(_, inp):
        q_blk, qp_blk = inp
        mask = attention_mask(qp_blk, k_pos, causal=causal, window=window)
        return None, gqa_attention(q_blk, k, v, mask)

    _, out = jax.lax.scan(body, None, (qb, pb))
    return out.swapaxes(0, 1).reshape(B, Sq, H, D)


# ---------------------------------------------------------------------------
# Standard GQA attention layer (projections + rope + core)
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig, prefix: str, *, cross: bool = False) -> dict:
    D, H, K, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        f"{prefix}/wq": ParamSpec((D, H, Dh), ("embed", "heads", "qk_dim")),
        f"{prefix}/wk": ParamSpec((D, K, Dh), ("embed", "kv_heads", "qk_dim")),
        f"{prefix}/wv": ParamSpec((D, K, Dh), ("embed", "kv_heads", "qk_dim")),
        f"{prefix}/wo": ParamSpec((H, Dh, D), ("heads", "qk_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs[f"{prefix}/bq"] = ParamSpec((H, Dh), ("heads", "qk_dim"), init="zeros")
        specs[f"{prefix}/bk"] = ParamSpec((K, Dh), ("kv_heads", "qk_dim"), init="zeros")
        specs[f"{prefix}/bv"] = ParamSpec((K, Dh), ("kv_heads", "qk_dim"), init="zeros")
    return specs


def attention_qkv(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array,
                  positions: Optional[jax.Array], *, rope: bool = True):
    """Project to q, k, v (+bias, +rope on q,k)."""
    q = jnp.einsum("bsd,dhe->bshe", x, p[f"{prefix}/wq"])
    k = jnp.einsum("bsd,dke->bske", x, p[f"{prefix}/wk"])
    v = jnp.einsum("bsd,dke->bske", x, p[f"{prefix}/wv"])
    if cfg.qkv_bias:
        q = q + p[f"{prefix}/bq"]
        k = k + p[f"{prefix}/bk"]
        v = v + p[f"{prefix}/bv"]
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_out(p: dict, prefix: str, attn: jax.Array) -> jax.Array:
    return jnp.einsum("bshe,hed->bsd", attn, p[f"{prefix}/wo"])


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_specs(cfg: ModelConfig, prefix: str) -> dict:
    m: MLAConfig = cfg.mla
    D, H = cfg.d_model, cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    r = m.kv_lora_rank
    return {
        f"{prefix}/wq": ParamSpec((D, H, dn + dr), ("embed", "heads", "qk_dim")),
        f"{prefix}/w_dkv": ParamSpec((D, r), ("embed", "kv_lora")),
        f"{prefix}/w_krope": ParamSpec((D, dr), ("embed", "qk_dim")),
        f"{prefix}/kv_norm": ParamSpec((r,), ("kv_lora",), init="ones"),
        f"{prefix}/w_uk": ParamSpec((r, H, dn), ("kv_lora", "heads", "qk_dim")),
        f"{prefix}/w_uv": ParamSpec((r, H, dv), ("kv_lora", "heads", "qk_dim")),
        f"{prefix}/wo": ParamSpec((H, dv, D), ("heads", "qk_dim", "embed")),
    }


def mla_latent(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array,
               positions: jax.Array):
    """Compute the cached quantities: normalized latent c_kv and shared k_rope."""
    m: MLAConfig = cfg.mla
    c_kv = jnp.einsum("bsd,dr->bsr", x, p[f"{prefix}/w_dkv"])
    c_kv = rms_norm(c_kv, p[f"{prefix}/kv_norm"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dr->bsr", x, p[f"{prefix}/w_krope"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_attention(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array,
                  c_kv: jax.Array, k_rope: jax.Array,
                  q_positions: jax.Array, *, mask: Optional[jax.Array] = None,
                  k_positions: Optional[jax.Array] = None) -> jax.Array:
    """MLA core.  x: (B,Sq,D) query-side activations; c_kv/k_rope cover the
    full key side (B,Sk,r)/(B,Sk,dr).

    Either an explicit ``mask`` (B,Sq,Sk) (decode: Sq=1, cheap) or
    ``k_positions`` for a causal mask built per q-chunk (prefill/train: the
    full (Sq,Sk) mask never materializes)."""
    m: MLAConfig = cfg.mla
    H = cfg.num_heads
    dn, dr = m.qk_nope_head_dim, m.qk_rope_head_dim
    q = jnp.einsum("bsd,dhe->bshe", x, p[f"{prefix}/wq"])
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = apply_rope(q_rope, q_positions, cfg.rope_theta)

    k_nope = jnp.einsum("btr,rhe->bthe", c_kv, p[f"{prefix}/w_uk"])   # (B,Sk,H,dn)
    v = jnp.einsum("btr,rhe->bthe", c_kv, p[f"{prefix}/w_uv"])        # (B,Sk,H,dv)

    scale = 1.0 / np.sqrt(dn + dr)

    def attend(qn, qr, msk):
        logits = (jnp.einsum("bshe,bthe->bhst", qn, k_nope)
                  + jnp.einsum("bshe,bte->bhst", qr, k_rope)).astype(jnp.float32) * scale
        logits = jnp.where(msk[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bthe->bshe", probs, v)

    B, Sq = x.shape[0], x.shape[1]
    Sk = c_kv.shape[1]
    if mask is not None:
        attn = attend(q_nope, q_rope, mask)
    elif (max(Sq, Sk) >= CHUNKED_ATTN_THRESHOLD and Sq > CHUNK_Q
          and Sq % CHUNK_Q == 0):
        nblk = Sq // CHUNK_Q
        qn_b = q_nope.reshape(B, nblk, CHUNK_Q, H, dn).swapaxes(0, 1)
        qr_b = q_rope.reshape(B, nblk, CHUNK_Q, H, dr).swapaxes(0, 1)
        qp_b = q_positions.reshape(B, nblk, CHUNK_Q).swapaxes(0, 1)

        def body(_, inp):
            qn, qr, qp = inp
            msk = attention_mask(qp, k_positions, causal=True)
            return None, attend(qn, qr, msk)

        _, attn = jax.lax.scan(body, None, (qn_b, qr_b, qp_b))
        attn = attn.swapaxes(0, 1).reshape(B, Sq, H, m.v_head_dim)
    else:
        msk = attention_mask(q_positions, k_positions, causal=True)
        attn = attend(q_nope, q_rope, msk)
    return jnp.einsum("bshe,hed->bsd", attn, p[f"{prefix}/wo"])


# ---------------------------------------------------------------------------
# Gated MLP (llama-style)
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, prefix: str, d_ff: Optional[int] = None) -> dict:
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    return {
        f"{prefix}/w_gate": ParamSpec((D, F), ("embed", "mlp")),
        f"{prefix}/w_up": ParamSpec((D, F), ("embed", "mlp")),
        f"{prefix}/w_down": ParamSpec((F, D), ("mlp", "embed")),
    }


def mlp_apply(p: dict, prefix: str, x: jax.Array) -> jax.Array:
    g = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}/w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}/w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsf,fd->bsd", h, p[f"{prefix}/w_down"])


def gelu_mlp_specs(cfg: ModelConfig, prefix: str) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    return {
        f"{prefix}/w_in": ParamSpec((D, F), ("embed", "mlp")),
        f"{prefix}/b_in": ParamSpec((F,), ("mlp",), init="zeros"),
        f"{prefix}/w_out": ParamSpec((F, D), ("mlp", "embed")),
        f"{prefix}/b_out": ParamSpec((D,), ("embed",), init="zeros"),
    }


def gelu_mlp_apply(p: dict, prefix: str, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p[f"{prefix}/w_in"]) + p[f"{prefix}/b_in"]
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("bsf,fd->bsd", h, p[f"{prefix}/w_out"]) + p[f"{prefix}/b_out"]


def dense_mlp_specs(cfg: ModelConfig, prefix: str) -> dict:
    """Per-config dense MLP: gated-SiLU (llama family) or 2-matrix GELU."""
    if cfg.mlp_kind == "gelu":
        return gelu_mlp_specs(cfg, prefix)
    return mlp_specs(cfg, prefix)


def dense_mlp_apply(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array) -> jax.Array:
    if cfg.mlp_kind == "gelu":
        return gelu_mlp_apply(p, prefix, x)
    return mlp_apply(p, prefix, x)


# ---------------------------------------------------------------------------
# Mixture of experts
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig, prefix: str) -> dict:
    m: MoEConfig = cfg.moe
    D = cfg.d_model
    specs = {
        f"{prefix}/router": ParamSpec((D, m.num_experts), ("embed", "experts"), init="small_normal"),
        f"{prefix}/we_gate": ParamSpec((m.num_experts, D, m.d_ff_expert), ("experts", "embed", "mlp")),
        f"{prefix}/we_up": ParamSpec((m.num_experts, D, m.d_ff_expert), ("experts", "embed", "mlp")),
        f"{prefix}/we_down": ParamSpec((m.num_experts, m.d_ff_expert, D), ("experts", "mlp", "embed")),
    }
    if m.num_shared_experts:
        specs.update(mlp_specs(cfg, f"{prefix}/shared", d_ff=m.d_ff_shared))
    return specs


def moe_router(p: dict, prefix: str, x: jax.Array, top_k: int):
    """Top-k softmax router.  x: (N, D) flat tokens.
    Returns (weights (N,k) fp32, ids (N,k) int32, aux_loss scalar)."""
    logits = jnp.einsum("nd,de->ne", x, p[f"{prefix}/router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # load-balancing aux loss (Switch-style): E * sum_e f_e * P_e
    E = logits.shape[-1]
    me = jnp.mean(probs, axis=0)                                  # mean router prob
    one_hot = jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(1)    # (N, E)
    fe = jnp.mean(one_hot, axis=0) / top_k
    aux = E * jnp.sum(me * fe)
    return weights, ids, aux


def moe_apply_dense(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array,
                    capacity_factor: float = 1.25):
    """GShard-style dense dispatch (einsum with one-hot).  Simple and exact for
    the *routing semantics*; used by CPU smoke tests and small models.  FLOP
    count is dominated by the dispatch einsums at scale, so the dry-run path
    uses ``moe_apply_dropless`` instead."""
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    weights, ids, aux = moe_router(p, prefix, xf, m.top_k)
    E = m.num_experts
    comb = jnp.zeros((B * S, E), jnp.float32)
    comb = comb.at[jnp.arange(B * S)[:, None], ids].add(weights)   # (N, E)
    # expert FFN on all tokens per expert (dense): fine at smoke scale
    g = jnp.einsum("nd,edf->enf", xf, p[f"{prefix}/we_gate"])
    u = jnp.einsum("nd,edf->enf", xf, p[f"{prefix}/we_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("enf,efd->end", h, p[f"{prefix}/we_down"])
    out = jnp.einsum("end,ne->nd", y.astype(jnp.float32), comb).astype(x.dtype)
    out = out.reshape(B, S, D)
    if m.num_shared_experts:
        out = out + mlp_apply(p, f"{prefix}/shared", x)
    return out, aux


def moe_apply_dropless(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array,
                       capacity_factor: float = 1.25):
    """Sort-free capacity-padded dropless-ish MoE.

    Tokens are scattered into per-expert capacity buffers (E, C, D) by
    (expert_id, position-in-expert); experts run as one batched matmul
    (E, C, D) x (E, D, F); results scatter back weighted by router probs.
    FLOPs ~= active-expert FLOPs * capacity_factor — honest for roofline —
    and the (E, C, D) buffer is the only materialized dispatch state.
    Tokens overflowing an expert's capacity are dropped (their router weight
    mass is lost), matching Switch/GShard semantics.
    """
    m: MoEConfig = cfg.moe
    B, S, D = x.shape
    N = B * S
    k = m.top_k
    E = m.num_experts
    C = max(8, int(np.ceil(N * k * capacity_factor / E)))
    xf = x.reshape(N, D)
    weights, ids, aux = moe_router(p, prefix, xf, k)               # (N,k)

    flat_ids = ids.reshape(N * k)                                  # assignment -> expert
    # position of each assignment within its expert, via cumsum over one-hot
    one_hot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)         # (N*k, E)
    pos_in_expert = (jnp.cumsum(one_hot, axis=0) - 1)
    pos = jnp.take_along_axis(pos_in_expert, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < C
    safe_pos = jnp.where(keep, pos, C - 1)

    # scatter tokens into (E, C, D); the buffer is sharded experts x capacity
    # (expert parallelism over 'model' when E divides, capacity over 'data')
    from repro.parallel.sharding import constrain

    src = jnp.repeat(xf, k, axis=0)                                # (N*k, D) token per assignment
    buf = jnp.zeros((E, C, D), x.dtype)
    buf = constrain(buf, ("experts", "moe_capacity", None))
    buf = buf.at[flat_ids, safe_pos].add(jnp.where(keep[:, None], src, 0))
    buf = constrain(buf, ("experts", "moe_capacity", None))

    g = jnp.einsum("ecd,edf->ecf", buf, p[f"{prefix}/we_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p[f"{prefix}/we_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, p[f"{prefix}/we_down"])      # (E, C, D)
    y = constrain(y, ("experts", "moe_capacity", None))

    gathered = y[flat_ids, safe_pos]                               # (N*k, D)
    wts = (weights.reshape(N * k) * keep).astype(jnp.float32)
    out = (gathered.astype(jnp.float32) * wts[:, None]).reshape(N, k, D).sum(1)
    out = out.astype(x.dtype).reshape(B, S, D)
    if m.num_shared_experts:
        out = out + mlp_apply(p, f"{prefix}/shared", x)
    return out, aux


def moe_apply_dropless_ep(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array,
                          capacity_factor: float = 1.25):
    """Expert-parallel dropless MoE via shard_map — the §Perf fix for the
    baseline's pathological dispatch.

    The plain dropless path computes position-in-expert with a GLOBAL cumsum,
    so the (E, C, D) capacity buffer receives scatter contributions from every
    data shard and GSPMD materializes it as a full-buffer all-reduce
    (measured 12.8 TB/device/step on deepseek-v2 train_4k).  Here each data
    shard dispatches into its own LOCAL capacity slice (local cumsum, zero
    cross-shard scatter) and the expert dimension (or the expert FFN dim when
    E doesn't divide the model axis) is sharded over 'model'; the only
    communication is the output psum over 'model' — the same all-reduce a
    tensor-parallel dense MLP needs anyway.

    Per-shard capacity makes drops per-shard rather than global (slightly
    more drops under cross-shard load imbalance; covered by the capacity
    factor).
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import current_sharder

    sh = current_sharder()
    if sh is None or sh.mesh is None:
        return moe_apply_dropless(cfg, p, prefix, x, capacity_factor)
    mesh = sh.mesh
    m: MoEConfig = cfg.moe
    E, k = m.num_experts, m.top_k
    D, F = cfg.d_model, m.d_ff_expert

    dp = tuple(a for a in ("pod", "data") if mesh.shape.get(a, 1) > 1)
    n_mp = mesh.shape.get("model", 1)
    mp = "model" if n_mp > 1 else None
    Bsz = x.shape[0]
    if (not dp and mp is None) or (dp and Bsz % int(np.prod([mesh.shape[a] for a in dp]))):
        return moe_apply_dropless(cfg, p, prefix, x, capacity_factor)
    ep = mp is not None and E % n_mp == 0            # expert-sharded
    fp = mp is not None and not ep and F % n_mp == 0  # expert-FFN tensor-sharded
    E_loc = E // n_mp if ep else E

    def local_fn(xl, wr, wg, wu, wd):
        B_loc, S, _ = xl.shape
        N = B_loc * S
        xf = xl.reshape(N, D)
        logits = jnp.einsum("nd,de->ne", xf, wr).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, ids = jax.lax.top_k(probs, k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
        # load-balance aux: me/fe are GLOBAL means (pmean before the product —
        # the aux is nonlinear in the stats)
        me = jnp.mean(probs, axis=0)
        oh = jax.nn.one_hot(ids, E, dtype=jnp.float32).sum(1)
        fe = jnp.mean(oh, axis=0) / k
        if dp:
            me = jax.lax.pmean(me, dp)
            fe = jax.lax.pmean(fe, dp)
        aux = E * jnp.sum(me * fe)

        C = max(8, int(np.ceil(N * k * capacity_factor / E)))
        flat_ids = ids.reshape(N * k)
        if ep:
            e0 = jax.lax.axis_index(mp) * E_loc
            mine = (flat_ids >= e0) & (flat_ids < e0 + E_loc)
            loc_ids = jnp.where(mine, flat_ids - e0, E_loc)
        else:
            mine = jnp.ones_like(flat_ids, bool)
            loc_ids = flat_ids
        one_hot = jax.nn.one_hot(loc_ids, E_loc, dtype=jnp.int32)
        pos = (jnp.cumsum(one_hot, axis=0) - 1)
        pos = jnp.take_along_axis(
            pos, jnp.minimum(loc_ids, E_loc - 1)[:, None], axis=1)[:, 0]
        keep = mine & (pos < C)
        safe_pos = jnp.where(keep, pos, C - 1)
        src = jnp.repeat(xf, k, axis=0)
        buf = jnp.zeros((E_loc, C, D), x.dtype)
        buf = buf.at[jnp.where(keep, loc_ids, E_loc), safe_pos].add(
            jnp.where(keep[:, None], src, 0), mode="drop")

        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        hmid = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        y = jnp.einsum("ecf,efd->ecd", hmid, wd)

        gathered = y[jnp.minimum(loc_ids, E_loc - 1), safe_pos]
        wts = (weights.reshape(N * k) * keep).astype(jnp.float32)
        out = (gathered.astype(jnp.float32) * wts[:, None]).reshape(N, k, D).sum(1)
        out = out.astype(x.dtype)
        if ep or fp:
            out = jax.lax.psum(out, mp)              # combine expert shards
        # (neither ep nor fp: every mp program computed the full routed output
        #  from replicated weights — already identical across 'model')
        return out.reshape(B_loc, S, D), aux

    x_spec = P(dp if len(dp) > 1 else (dp[0] if dp else None), None, None)
    if ep:
        w_spec = P("model", None, None)
    elif fp:
        w_spec = P(None, None, "model")
    else:
        w_spec = P(None, None, None)
    wd_spec = P(w_spec[0], w_spec[2], None) if (ep or fp) else P(None, None, None)

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p[f"{prefix}/router"], p[f"{prefix}/we_gate"],
      p[f"{prefix}/we_up"], p[f"{prefix}/we_down"])
    if m.num_shared_experts:
        out = out + mlp_apply(p, f"{prefix}/shared", x)
    return out, aux


def moe_apply(cfg: ModelConfig, p: dict, prefix: str, x: jax.Array,
              impl: str = "dense"):
    if impl == "ep":
        return moe_apply_dropless_ep(cfg, p, prefix, x)
    if impl == "dropless":
        return moe_apply_dropless(cfg, p, prefix, x)
    return moe_apply_dense(cfg, p, prefix, x)
