"""Plain float32 reference of the dense GQA/MQA decoder that both
configurations of this directory describe, written from the published
architecture and not from the program (it imports nothing of it).

    x = embed[tokens]
    per layer:  x += Wo . attn(rope(Wq rms(x)), rope(Wk rms(x)), Wv rms(x))
                x += mlp(rms(x))   gated SiLU: (silu(x Wg) * x Wu) Wd
                                   GELU (tanh): gelu(x Wi + bi) Wo + bo
    logits = rms(x) . head

RMSNorm with unit weights, rotate-half RoPE over the whole head, causal
softmax attention with 1/sqrt(head_dim) scaling, grouped query heads
(query head h reads key/value head h // (H / K)).

Weights come from ``harness.weights`` for the same seed, made again one
layer at a time, so the whole model never sits in float32 on the chip.
Every matrix product runs at ``Precision.HIGHEST``.

``precision="fp8"`` is the control: the same forward with every matrix
product's operands (weights and activations) rounded to float8 e4m3, the
precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30


def _q(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("model", "precision"))
def _layer(x, w, *, model, precision):
    """x: (B, S, D) float32; w: this layer's leaves (float32)."""
    m = dict(model)
    B, S, _ = x.shape
    H, K = m["num_heads"], m["num_kv_heads"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h = _rms(x, eps) * w["attn_norm"]
    q = _rope(_mm("bsd,dhe->bshe", h, w["wq"], precision), pos, theta)
    k = _rope(_mm("bsd,dke->bske", h, w["wk"], precision), pos, theta)
    v = _mm("bsd,dke->bske", h, w["wv"], precision)
    q = q.reshape(B, S, K, H // K, -1)
    s = _mm("bskge,btke->bkgst", q, k, precision) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("bkgst,btke->bskge", p, v, precision).reshape(B, S, H, -1)
    x = x + _mm("bshe,hed->bsd", a, w["wo"], precision)
    h = _rms(x, eps) * w["mlp_norm"]
    if m.get("mlp_kind", "gated_silu") == "gelu":
        u = _mm("bsd,df->bsf", h, w["w_in"], precision) + w["b_in"]
        y = _mm("bsf,fd->bsd", jax.nn.gelu(u, approximate=True), w["w_out"],
                precision) + w["b_out"]
    else:
        g = _mm("bsd,df->bsf", h, w["w_gate"], precision)
        u = _mm("bsd,df->bsf", h, w["w_up"], precision)
        y = _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"], precision)
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(x, pos, norm_w, head, *, eps, precision):
    """Logits at positions ``pos`` (B, P) of each row: (B, P, V)."""
    xs = jnp.take_along_axis(x, pos[..., None], axis=1)
    return _mm("bpd,dv->bpv", _rms(xs, eps) * norm_w, head, precision)


def _layer_weights(model, seed, layer, make_leaf):
    mlp = (("w_in", "b_in", "w_out", "b_out")
           if model.get("mlp_kind", "gated_silu") == "gelu"
           else ("w_gate", "w_up", "w_down"))
    names = (["attn_norm", "mlp_norm"]
             + [f"attn/{n}" for n in ("wq", "wk", "wv", "wo")]
             + [f"mlp/{n}" for n in mlp])
    return {n.split("/")[-1]: make_leaf(model, seed, f"blocks/0/{n}",
                                        layer).astype(jnp.float32)
            for n in names}


def score(model: dict, seed: int, seqs: Sequence[np.ndarray],
          starts: Sequence[int], make_leaf,
          precisions=("f32",)) -> List[dict]:
    """Run the reference over ``seqs`` (prompt + served tokens, int32) and
    read the logits that produced each served token: row i's positions
    ``starts[i] - 1 .. len - 2`` predict tokens ``starts[i] .. len - 1``.

    Returns, per precision, per row, at each of those positions: ``best``
    (the float32 reference's largest logit), ``served`` (its logit of the
    token that was served), ``top`` (the token this precision puts first)
    and ``at_top`` (the float32 logit of that token).  The float32 pass
    always runs.
    """
    model_t = tuple(sorted((k, v) for k, v in model.items()
                           if not isinstance(v, (dict, list))))
    n = len(seqs)
    S = int(-(-max(len(s) for s in seqs) // 128) * 128)
    toks = np.zeros((n, S), np.int32)
    P = max(len(s) - st for s, st in zip(seqs, starts))
    pos = np.zeros((n, P), np.int32)
    for i, (s, st) in enumerate(zip(seqs, starts)):
        toks[i, :len(s)] = s
        pos[i, :len(s) - st] = np.arange(st - 1, len(s) - 1)
    precs = ("f32",) + tuple(p for p in precisions if p != "f32")
    emb = make_leaf(model, seed, "embed/tokens").astype(jnp.float32)
    xs = {p: emb[jnp.asarray(toks)] for p in precs}
    del emb
    for r in range(model["num_layers"]):
        w = _layer_weights(model, seed, r, make_leaf)
        xs = {p: _layer(x, w, model=model_t, precision=p)
              for p, x in xs.items()}
        del w
    norm_w = make_leaf(model, seed, "final_norm/w").astype(jnp.float32)
    head = make_leaf(model, seed, "head/w").astype(jnp.float32)
    out = {}
    eps = model["norm_eps"]
    ref = np.asarray(_logits(xs["f32"], jnp.asarray(pos), norm_w, head,
                             eps=eps, precision="f32"))
    for p in precs:
        lg = ref if p == "f32" else np.asarray(
            _logits(xs[p], jnp.asarray(pos), norm_w, head, eps=eps,
                    precision=p))
        rows = []
        for i, (s, st) in enumerate(zip(seqs, starts)):
            m = len(s) - st
            r_i = ref[i, :m]
            top = lg[i, :m].argmax(-1)
            rows.append({"best": r_i.max(-1),
                         "served": r_i[np.arange(m), np.asarray(s[st:])],
                         "top": top, "at_top": r_i[np.arange(m), top]})
        out[p] = rows
    return out
