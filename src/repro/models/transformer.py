"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Layers are organized into *segments*: a (pattern, repeats) pair where the
pattern is a short list of sub-layer signatures (attention kind x MLP kind)
and repeats stacks the pattern parameters along a leading ``layers`` axis.
Homogeneous models are one segment; DeepSeek's leading dense layer is a
prefix segment; Jamba's 1:7 attn:mamba interleave with period-2 MoE is one
8-sub-layer pattern repeated 4x.  Segments iterate with ``lax.scan`` for
O(1) HLO size in depth (switchable for tiny smoke configs).

The KV cache is a flat dict of stacked leaves per (segment, position), with
per-row lengths so the serving engine can run continuous batching.  Sliding
-window layers keep a ring buffer of ``window`` slots; MLA caches the latent
``c_kv``/``k_rope`` pair (the memory win that makes 32k decode cheap); SSM
layers keep (conv_state, ssd_state).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import paged_attention, paged_gather_view
from repro.models import layers as L
from repro.models import ssm as S
from repro.parallel.sharding import constrain

# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SubLayer:
    kind: str                        # attn | mla | ssm
    mlp: str                         # dense | moe | none


@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    pattern: Tuple[SubLayer, ...]
    repeats: int


def _lcm(a: int, b: int) -> int:
    return a * b // np.gcd(a, b)


def build_plan(cfg: ModelConfig) -> Tuple[Segment, ...]:
    def sig(i: int) -> SubLayer:
        kind = cfg.layer_kind(i)
        if kind == "attn" and cfg.mla is not None:
            kind = "mla"
        if cfg.family == "ssm":
            mlp = "none"
        elif cfg.is_moe_layer(i):
            mlp = "moe"
        else:
            mlp = "dense"
        return SubLayer(kind, mlp)

    sigs = [sig(i) for i in range(cfg.num_layers)]
    prefix = cfg.moe.first_dense_layers if cfg.moe is not None else 0
    period = 1
    if cfg.moe is not None:
        period = _lcm(period, cfg.moe.expert_layer_period)
    if cfg.family == "hybrid" and cfg.attn_layer_period:
        period = _lcm(period, cfg.attn_layer_period)

    segments = []
    for i in range(prefix):
        segments.append(Segment(f"prefix{i}", (sigs[i],), 1))
    tail = sigs[prefix:]
    if len(tail) % period != 0:
        period = 1  # fall back to per-layer pattern check
    pattern = tuple(tail[:period])
    repeats = len(tail) // period
    for r in range(repeats):
        if tuple(tail[r * period:(r + 1) * period]) != pattern:
            raise ValueError(f"{cfg.name}: layer pattern is not periodic")
    segments.append(Segment("blocks", pattern, repeats))
    return tuple(segments)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class DecoderLM:
    """Decoder-only LM (dense / moe / ssm / hybrid / vlm)."""

    def __init__(self, cfg: ModelConfig, *, moe_impl: Optional[str] = None,
                 attention_impl: str = "xla"):
        self.cfg = cfg
        self.plan = build_plan(cfg)
        self.moe_impl = moe_impl or ("dropless" if cfg.d_model >= 1024 else "dense")
        self.attention_impl = attention_impl
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------
    # Parameter specs
    # ------------------------------------------------------------------
    def _sublayer_specs(self, sl: SubLayer, prefix: str) -> Dict[str, L.ParamSpec]:
        cfg = self.cfg
        specs: Dict[str, L.ParamSpec] = {}
        if sl.kind == "attn":
            specs[f"{prefix}/attn_norm"] = L.ParamSpec((cfg.d_model,), ("embed",), init="ones")
            specs.update(L.attention_specs(cfg, f"{prefix}/attn"))
        elif sl.kind == "mla":
            specs[f"{prefix}/attn_norm"] = L.ParamSpec((cfg.d_model,), ("embed",), init="ones")
            specs.update(L.mla_specs(cfg, f"{prefix}/attn"))
        elif sl.kind == "ssm":
            specs[f"{prefix}/ssm_norm"] = L.ParamSpec((cfg.d_model,), ("embed",), init="ones")
            specs.update(S.ssm_specs(cfg, f"{prefix}/ssm"))
        if sl.mlp == "dense":
            specs[f"{prefix}/mlp_norm"] = L.ParamSpec((cfg.d_model,), ("embed",), init="ones")
            specs.update(L.dense_mlp_specs(cfg, f"{prefix}/mlp"))
        elif sl.mlp == "moe":
            specs[f"{prefix}/mlp_norm"] = L.ParamSpec((cfg.d_model,), ("embed",), init="ones")
            specs.update(L.moe_specs(cfg, f"{prefix}/moe"))
        return specs

    def param_specs(self) -> Dict[str, L.ParamSpec]:
        cfg = self.cfg
        specs: Dict[str, L.ParamSpec] = {
            "embed/tokens": L.ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
            "final_norm/w": L.ParamSpec((cfg.d_model,), ("embed",), init="ones"),
        }
        if not cfg.tie_embeddings:
            specs["head/w"] = L.ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
        for seg in self.plan:
            for pos, sl in enumerate(seg.pattern):
                sub = self._sublayer_specs(sl, f"{seg.name}/{pos}")
                for name, sp in sub.items():
                    if seg.repeats > 1:
                        sp = L.ParamSpec((seg.repeats,) + sp.shape, ("layers",) + sp.axes,
                                         init=sp.init, dtype=sp.dtype)
                    specs[name] = sp
        return specs

    def init_shapes(self) -> Dict[str, jax.ShapeDtypeStruct]:
        return {k: jax.ShapeDtypeStruct(sp.shape, sp.dtype or self.dtype)
                for k, sp in self.param_specs().items()}

    def logical_axes(self) -> Dict[str, tuple]:
        return {k: sp.axes for k, sp in self.param_specs().items()}

    def init(self, rng: jax.Array) -> Dict[str, jax.Array]:
        specs = self.param_specs()
        params = {}
        for name, sp in sorted(specs.items()):
            key = jax.random.fold_in(rng, hash(name) % (2 ** 31))
            params[name] = L.init_leaf(sp, key, self.dtype)
        return params

    # ------------------------------------------------------------------
    # Segment param slicing
    # ------------------------------------------------------------------
    def _segment_params(self, params: dict, seg: Segment) -> dict:
        pre = seg.name + "/"
        return {k: v for k, v in params.items() if k.startswith(pre)}

    @staticmethod
    def _slice_layer(seg_params: dict, r) -> dict:
        return {k: v[r] for k, v in seg_params.items()}

    # ------------------------------------------------------------------
    # Sub-layer forward (full sequence)
    # ------------------------------------------------------------------
    def _sublayer_fwd(self, sl: SubLayer, p: dict, prefix: str, x: jax.Array,
                      positions: jax.Array, mask: Optional[jax.Array]):
        """Returns (x, aux_loss)."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if sl.kind == "attn":
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            q, k, v = L.attention_qkv(cfg, p, f"{prefix}/attn", h, positions)
            attn = L.causal_attention(q, k, v, positions, positions,
                                      causal=True, window=cfg.sliding_window)
            x = x + L.attention_out(p, f"{prefix}/attn", attn)
        elif sl.kind == "mla":
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            c_kv, k_rope = L.mla_latent(cfg, p, f"{prefix}/attn", h, positions)
            x = x + L.mla_attention(cfg, p, f"{prefix}/attn", h, c_kv, k_rope,
                                    positions, k_positions=positions)
        elif sl.kind == "ssm":
            h = L.rms_norm(x, p[f"{prefix}/ssm_norm"], cfg.norm_eps)
            x = x + S.ssm_apply(cfg, p, f"{prefix}/ssm", h)
        if sl.mlp == "dense":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            x = x + L.dense_mlp_apply(cfg, p, f"{prefix}/mlp", h)
        elif sl.mlp == "moe":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            y, a = L.moe_apply(cfg, p, f"{prefix}/moe", h, impl=self.moe_impl)
            x = x + y
            aux = aux + a
        return x, aux

    def _segment_fwd(self, seg: Segment, seg_params: dict, x: jax.Array,
                     positions: jax.Array, mask: Optional[jax.Array]):
        cfg = self.cfg

        def body_fn(x, layer_params):
            aux = jnp.zeros((), jnp.float32)
            x = constrain(x, ("batch", None, "act_embed"))
            for pos, sl in enumerate(seg.pattern):
                x, a = self._sublayer_fwd(sl, layer_params, f"{seg.name}/{pos}", x,
                                          positions, mask)
                aux = aux + a
            return x, aux

        if seg.repeats == 1:
            return body_fn(x, seg_params)

        body = body_fn
        if cfg.remat == "full":
            body = jax.checkpoint(body_fn, policy=jax.checkpoint_policies.nothing_saveable)
        elif cfg.remat == "dots":
            body = jax.checkpoint(body_fn, policy=jax.checkpoint_policies.checkpoint_dots)

        if cfg.scan_layers:
            def scan_body(carry, layer_params):
                x, aux = carry
                x, a = body(x, layer_params)
                return (x, aux + a), None

            (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)),
                                       seg_params)
            return x, aux
        aux = jnp.zeros((), jnp.float32)
        for r in range(seg.repeats):
            x, a = body(x, self._slice_layer(seg_params, r))
            aux = aux + a
        return x, aux

    # ------------------------------------------------------------------
    # Full-sequence forward (train / prefill-logits)
    # ------------------------------------------------------------------
    def embed(self, params: dict, tokens: jax.Array,
              image_embeds: Optional[jax.Array] = None) -> jax.Array:
        x = params["embed/tokens"][tokens]
        if image_embeds is not None:
            x = jnp.concatenate([image_embeds.astype(x.dtype), x], axis=1)
        return x

    def unembed(self, params: dict, x: jax.Array) -> jax.Array:
        x = L.rms_norm(x, params["final_norm/w"], self.cfg.norm_eps)
        if self.cfg.tie_embeddings:
            return jnp.einsum("bsd,vd->bsv", x, params["embed/tokens"])
        return jnp.einsum("bsd,dv->bsv", x, params["head/w"])

    def forward(self, params: dict, tokens: jax.Array, *,
                image_embeds: Optional[jax.Array] = None,
                return_aux: bool = False):
        cfg = self.cfg
        x = self.embed(params, tokens, image_embeds)
        Bsz, Stot = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Stot)[None, :], (Bsz, Stot))
        mask = None  # masks are built per q-chunk inside the attention fns
        aux_total = jnp.zeros((), jnp.float32)
        for seg in self.plan:
            x, aux = self._segment_fwd(seg, self._segment_params(params, seg), x,
                                       positions, mask)
            aux_total = aux_total + aux
        logits = self.unembed(params, x)
        if return_aux:
            return logits, aux_total
        return logits

    def forward_hidden(self, params: dict, tokens: jax.Array, *,
                       num_layers: int) -> jax.Array:
        """Partial forward: embedding + the first ``num_layers`` backbone
        layers; returns hidden states (B, S, D).  This is the CoIC
        descriptor-prefix path — cheap relative to the full model."""
        cfg = self.cfg
        x = self.embed(params, tokens)
        Bsz, Stot = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Stot)[None, :], (Bsz, Stot))
        mask = None  # masks are built per q-chunk inside the attention fns
        remaining = num_layers
        for seg in self.plan:
            if remaining <= 0:
                break
            take = min(remaining, seg.repeats)
            seg_params = self._segment_params(params, seg)
            if take == 1 and seg.repeats > 1:
                seg_params = {k: v[0] for k, v in seg_params.items()}
            elif take < seg.repeats:
                seg_params = {k: v[:take] for k, v in seg_params.items()}
            sub = Segment(seg.name, seg.pattern, take)
            x, _ = self._segment_fwd(sub, seg_params, x, positions, mask)
            remaining -= take
        return x

    def _backbone(self, params: dict, tokens: jax.Array, *,
                  image_embeds: Optional[jax.Array] = None):
        """Embedding + all layers (pre-unembed).  Returns (hidden, aux)."""
        cfg = self.cfg
        x = self.embed(params, tokens, image_embeds)
        Bsz, Stot = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Stot)[None, :], (Bsz, Stot))
        aux_total = jnp.zeros((), jnp.float32)
        for seg in self.plan:
            x, aux = self._segment_fwd(seg, self._segment_params(params, seg), x,
                                       positions, None)
            aux_total = aux_total + aux
        return x, aux_total

    def loss(self, params: dict, batch: dict):
        """Next-token CE.  batch: tokens (B,S) int32, optional loss_mask (B,S),
        optional image_embeds.  Prediction target at position i is token i+1.

        cfg.loss_chunk > 0 enables CHUNKED cross-entropy: the (B, S, V) fp32
        logits never materialize — per-chunk logits are computed, reduced to
        (logsumexp, target-logit) and rematerialized in backward.  On
        152k-vocab models this removes the single largest activation."""
        cfg = self.cfg
        tokens = batch["tokens"]
        hidden, aux = self._backbone(params, tokens,
                                     image_embeds=batch.get("image_embeds"))
        n_img = hidden.shape[1] - tokens.shape[1]
        if n_img > 0:
            hidden = hidden[:, n_img:]                             # text positions only
        targets = tokens[:, 1:]
        mask = batch.get("loss_mask")
        mask = jnp.ones_like(targets, jnp.float32) if mask is None else mask[:, 1:].astype(jnp.float32)

        hid = hidden[:, :-1]
        Bsz, Sm1, _ = hid.shape
        chunk = cfg.loss_chunk
        if chunk and Sm1 > chunk and Sm1 % chunk == 0:
            def chunk_ce(h_c, t_c, m_c):
                lg = self.unembed(params, h_c).astype(jnp.float32)
                logz = jax.nn.logsumexp(lg, axis=-1)
                tgt = jnp.take_along_axis(lg, t_c[..., None], axis=-1)[..., 0]
                return ((logz - tgt) * m_c).sum()

            chunk_ce = jax.checkpoint(chunk_ce)
            n = Sm1 // chunk
            h_b = hid.reshape(Bsz, n, chunk, -1).swapaxes(0, 1)
            t_b = targets.reshape(Bsz, n, chunk).swapaxes(0, 1)
            m_b = mask.reshape(Bsz, n, chunk).swapaxes(0, 1)

            def body(acc, xs):
                return acc + chunk_ce(*xs), None

            ce_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                     (h_b, t_b, m_b))
        else:
            logits = self.unembed(params, hid).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
            ce_sum = ((logz - tgt) * mask).sum()

        denom = jnp.maximum(mask.sum(), 1.0)
        loss = ce_sum / denom
        aux_coef = cfg.moe.router_aux_loss_coef if cfg.moe is not None else 0.0
        total = loss + aux_coef * aux
        metrics = {"loss": loss, "aux_loss": aux, "total_loss": total}
        return total, metrics

    # ------------------------------------------------------------------
    # KV / state cache
    # ------------------------------------------------------------------
    def _cache_len(self, sl: SubLayer, max_len: int) -> int:
        w = self.cfg.sliding_window
        if sl.kind == "attn" and w > 0:
            return min(w, max_len)
        return max_len

    def cache_specs(self, batch: int, max_len: int) -> Dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStructs for the decode cache (dry-run friendly)."""
        cfg = self.cfg
        specs: Dict[str, jax.ShapeDtypeStruct] = {}
        for seg in self.plan:
            R = seg.repeats
            for pos, sl in enumerate(seg.pattern):
                base = f"{seg.name}/{pos}"
                if sl.kind == "attn":
                    Sk = self._cache_len(sl, max_len)
                    shp = (R, batch, Sk, cfg.num_kv_heads, cfg.head_dim)
                    specs[f"{base}/k"] = jax.ShapeDtypeStruct(shp, self.dtype)
                    specs[f"{base}/v"] = jax.ShapeDtypeStruct(shp, self.dtype)
                elif sl.kind == "mla":
                    m = cfg.mla
                    specs[f"{base}/c_kv"] = jax.ShapeDtypeStruct(
                        (R, batch, max_len, m.kv_lora_rank), self.dtype)
                    specs[f"{base}/k_rope"] = jax.ShapeDtypeStruct(
                        (R, batch, max_len, m.qk_rope_head_dim), self.dtype)
                elif sl.kind == "ssm":
                    d_inner, H, conv_dim = S.ssm_dims(cfg)
                    s = cfg.ssm
                    specs[f"{base}/conv"] = jax.ShapeDtypeStruct(
                        (R, batch, s.d_conv - 1, conv_dim), self.dtype)
                    specs[f"{base}/state"] = jax.ShapeDtypeStruct(
                        (R, batch, H, s.head_dim, s.d_state), jnp.float32)
        return specs

    def cache_axes(self) -> Dict[str, tuple]:
        """Logical axes for each cache leaf (mirrors cache_specs)."""
        cfg = self.cfg
        axes: Dict[str, tuple] = {}
        for seg in self.plan:
            for pos, sl in enumerate(seg.pattern):
                base = f"{seg.name}/{pos}"
                if sl.kind == "attn":
                    a = ("layers", "batch", "cache_seq", "kv_heads", "qk_dim")
                    axes[f"{base}/k"] = a
                    axes[f"{base}/v"] = a
                elif sl.kind == "mla":
                    axes[f"{base}/c_kv"] = ("layers", "batch", "cache_seq", "kv_lora")
                    axes[f"{base}/k_rope"] = ("layers", "batch", "cache_seq", "qk_dim")
                elif sl.kind == "ssm":
                    axes[f"{base}/conv"] = ("layers", "batch", "conv_w", "ssm_inner")
                    axes[f"{base}/state"] = ("layers", "batch", "ssm_heads", "qk_dim", "ssm_state")
        return axes

    def init_cache(self, batch: int, max_len: int) -> Dict[str, jax.Array]:
        return {k: jnp.zeros(v.shape, v.dtype)
                for k, v in self.cache_specs(batch, max_len).items()}

    def paged_cache_specs(self, num_pages: int, page_size: int
                          ) -> Dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStructs for the PAGED decode cache: every seq-indexed
        leaf becomes a physical page pool shared by all batch rows through
        a per-row block table (see ``serving/kv_cache.py::PagedKVCache``):
        ``(layers, num_pages, kv_heads, page_size, head_dim)`` for k/v —
        head-major, so one head's page is the contiguous (page, head_dim)
        tile the paged-attention kernel DMAs — and ``(layers, num_pages,
        page_size, r)`` for latent caches.  Page ``num_pages`` is the
        out-of-bounds sink: scatters to it drop, gathers clamp — so an
        INVALID block-table entry can never corrupt a live page.

        Only linear attention-family caches page (the same restriction as
        chunked prefill): a sliding-window ring rotates by position and a
        recurrent SSM state is not seq-indexed, so those models raise.
        """
        cfg = self.cfg
        if cfg.sliding_window > 0:
            raise ValueError("paged KV needs linear caches (no SWA ring)")
        specs: Dict[str, jax.ShapeDtypeStruct] = {}
        for seg in self.plan:
            R = seg.repeats
            for pos, sl in enumerate(seg.pattern):
                base = f"{seg.name}/{pos}"
                if sl.kind == "attn":
                    shp = (R, num_pages, cfg.num_kv_heads, page_size,
                           cfg.head_dim)
                    specs[f"{base}/k"] = jax.ShapeDtypeStruct(shp, self.dtype)
                    specs[f"{base}/v"] = jax.ShapeDtypeStruct(shp, self.dtype)
                elif sl.kind == "mla":
                    m = cfg.mla
                    specs[f"{base}/c_kv"] = jax.ShapeDtypeStruct(
                        (R, num_pages, page_size, m.kv_lora_rank), self.dtype)
                    specs[f"{base}/k_rope"] = jax.ShapeDtypeStruct(
                        (R, num_pages, page_size, m.qk_rope_head_dim),
                        self.dtype)
                else:
                    raise ValueError("paged KV needs attention-family caches "
                                     f"(got {sl.kind} sub-layer)")
        return specs

    # ------------------------------------------------------------------
    # Prefill
    # ------------------------------------------------------------------
    def _sublayer_prefill(self, sl: SubLayer, p: dict, prefix: str, x, positions,
                          mask, cache_slices: dict, base: str, max_len: int):
        """Like _sublayer_fwd but also fills the cache leaves for this layer.
        cache_slices holds per-layer (no repeats dim) leaves to overwrite."""
        cfg = self.cfg
        new_cache = {}
        aux = jnp.zeros((), jnp.float32)
        if sl.kind == "attn":
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            q, k, v = L.attention_qkv(cfg, p, f"{prefix}/attn", h, positions)
            attn = L.causal_attention(q, k, v, positions, positions,
                                      causal=True, window=cfg.sliding_window)
            x = x + L.attention_out(p, f"{prefix}/attn", attn)
            Sk = cache_slices[f"{base}/k"].shape[1]
            if Sk < k.shape[1]:                                    # sliding window ring
                # decode expects slot = position % Sk; the last Sk positions
                # start at p0 = S - Sk, so rotate the tail into ring order.
                p0 = k.shape[1] - Sk
                new_cache[f"{base}/k"] = jnp.roll(k[:, -Sk:], p0 % Sk, axis=1)
                new_cache[f"{base}/v"] = jnp.roll(v[:, -Sk:], p0 % Sk, axis=1)
            else:
                pad = Sk - k.shape[1]
                new_cache[f"{base}/k"] = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                new_cache[f"{base}/v"] = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        elif sl.kind == "mla":
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            c_kv, k_rope = L.mla_latent(cfg, p, f"{prefix}/attn", h, positions)
            x = x + L.mla_attention(cfg, p, f"{prefix}/attn", h, c_kv, k_rope,
                                    positions, k_positions=positions)
            Sk = cache_slices[f"{base}/c_kv"].shape[1]
            pad = Sk - c_kv.shape[1]
            new_cache[f"{base}/c_kv"] = jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0)))
            new_cache[f"{base}/k_rope"] = jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0)))
        elif sl.kind == "ssm":
            h = L.rms_norm(x, p[f"{prefix}/ssm_norm"], cfg.norm_eps)
            y, (conv_state, ssd_state) = S.ssm_apply(cfg, p, f"{prefix}/ssm", h,
                                                     return_state=True)
            x = x + y
            new_cache[f"{base}/conv"] = conv_state
            new_cache[f"{base}/state"] = ssd_state
        if sl.mlp == "dense":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            x = x + L.dense_mlp_apply(cfg, p, f"{prefix}/mlp", h)
        elif sl.mlp == "moe":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            y, a = L.moe_apply(cfg, p, f"{prefix}/moe", h, impl=self.moe_impl)
            x = x + y
            aux = aux + a
        return x, new_cache, aux

    def prefill(self, params: dict, tokens: jax.Array, *,
                image_embeds: Optional[jax.Array] = None,
                max_len: Optional[int] = None,
                lengths: Optional[jax.Array] = None):
        """Run the full prompt, build the cache.  Returns (last-position
        logits (B, V), cache, lengths (B,)).

        ``lengths`` (B,) int32: true per-row prompt lengths for a
        right-padded batch (the bucketed batched-admission path).  Logits
        are taken at each row's true last token and the returned lengths
        echo the input, so decode overwrites the pad positions; causal
        attention keeps every valid position's hidden state independent of
        the trailing pads.  Only full (linear) attention caches support
        this — a recurrent (SSM) prefill state absorbs the pad tokens and
        a sliding-window ring cache rotates by the padded length; the
        serving engine admits those models at exact lengths only.
        """
        cfg = self.cfg
        x = self.embed(params, tokens, image_embeds)
        Bsz, Stot = x.shape[0], x.shape[1]
        max_len = max_len or Stot
        cache = {k: jnp.zeros(v.shape, v.dtype)
                 for k, v in self.cache_specs(Bsz, max_len).items()}
        positions = jnp.broadcast_to(jnp.arange(Stot)[None, :], (Bsz, Stot))
        mask = None  # masks are built per q-chunk inside the attention fns

        for seg in self.plan:
            seg_params = self._segment_params(params, seg)
            seg_cache = {k: v for k, v in cache.items()
                         if k.startswith(seg.name + "/")}

            def body(x, layer_params, layer_cache):
                new_cache = {}
                x = constrain(x, ("batch", None, "act_embed"))
                for pos, sl in enumerate(seg.pattern):
                    base = f"{seg.name}/{pos}"
                    x, nc, _ = self._sublayer_prefill(
                        sl, layer_params, base, x, positions, mask,
                        {k: v for k, v in layer_cache.items() if k.startswith(base)},
                        base, max_len)
                    new_cache.update(nc)
                return x, new_cache

            if seg.repeats > 1 and cfg.scan_layers:
                def scan_body(x, xs):
                    layer_params, layer_cache = xs
                    x, nc = body(x, layer_params, layer_cache)
                    return x, nc

                x, new_seg_cache = jax.lax.scan(
                    scan_body, x, (seg_params, seg_cache))
                cache.update(new_seg_cache)
            else:
                outs = {k: [] for k in seg_cache}
                for r in range(seg.repeats):
                    lp = self._slice_layer(seg_params, r) if seg.repeats > 1 else seg_params
                    lc = {k: v[r] for k, v in seg_cache.items()} if seg.repeats > 1 else \
                        {k: v[0] for k, v in seg_cache.items()}
                    x, nc = body(x, lp, lc)
                    for k, v in nc.items():
                        outs[k].append(v)
                cache.update({k: jnp.stack(v) for k, v in outs.items()})

        if lengths is None:
            logits = self.unembed(params, x[:, -1:])[:, 0]         # (B, V)
            lengths = jnp.full((Bsz,), Stot, jnp.int32)
        else:
            lengths = lengths.astype(jnp.int32)
            rows = jnp.arange(Bsz)
            x_last = x[rows, jnp.maximum(lengths - 1, 0)][:, None, :]
            logits = self.unembed(params, x_last)[:, 0]            # (B, V)
        return logits, cache, lengths

    # ------------------------------------------------------------------
    # Chunked prefill: extend an existing cache by one chunk of tokens.
    # Powers (a) paged/low-memory prefill and (b) per-layer KV-block reuse
    # (core/layer_reuse.py — the paper's §4 "result of a specific DNN layer").
    # ------------------------------------------------------------------
    @staticmethod
    def _page_write(leaf: jax.Array, pp: jax.Array, off: jax.Array,
                    vals: jax.Array) -> jax.Array:
        """Scatter per-token ``vals`` (..., [K,] D) into physical page
        ``pp`` at offset ``off``; out-of-bounds pages drop the write."""
        if leaf.ndim == 4:                  # head-major (P, K, page, D)
            return leaf.at[pp, :, off].set(vals, mode="drop")
        return leaf.at[pp, off].set(vals, mode="drop")

    @staticmethod
    def _page_targets(block_table: jax.Array, positions: jax.Array,
                      valid: Optional[jax.Array], page: int):
        """Physical (page, offset) scatter targets for token ``positions``
        (B, C) through ``block_table`` (B, n_pages).  Invalid positions are
        redirected out of bounds so ``mode="drop"`` discards them."""
        n_pages = block_table.shape[1]
        lp = jnp.clip(positions // page, 0, n_pages - 1)
        pp = jnp.take_along_axis(block_table, lp, axis=1)          # (B, C)
        oob = positions // page >= n_pages
        if valid is not None:
            oob = oob | ~valid
        # any OOB page index drops the write (pool has no physical page P)
        pp = jnp.where(oob, jnp.asarray(block_table.dtype.type(2 ** 30)), pp)
        return pp, positions % page

    def _sublayer_chunk(self, sl: SubLayer, p: dict, prefix: str, x, lengths,
                        layer_cache: dict, base: str, *,
                        valid: Optional[jax.Array] = None,
                        block_table: Optional[jax.Array] = None,
                        attn_impl: str = "gather"):
        """x: (B, C, D) chunk; lengths: (B,) cache fill before this chunk.

        ``valid`` (B, C) bool marks real tokens of a width-padded chunk
        (None == all valid): invalid positions never write the cache and
        their activations are discarded by the caller's per-row logit
        gather.  ``block_table`` (B, n_pages) switches the cache leaves to
        the paged pool layout (``paged_cache_specs``): writes scatter into
        physical pages, attention reads the pool — through the gathered
        dense view (``attn_impl="gather"``, the bit-exactness oracle) or
        in place via the Pallas paged-attention kernel (any
        ``kernels/paged_attention`` impl: auto / pallas / pallas_interpret
        / ref), which resolves the block table inside its grid and never
        materializes the (B, max_len) copy.  MLA layers always gather (the
        kernel is GQA-shaped; the latent cache stays on the oracle path)."""
        cfg = self.cfg
        Bsz, C, _ = x.shape
        new_cache = {}
        positions = lengths[:, None] + jnp.arange(C)[None, :]      # (B, C)
        rows = jnp.arange(Bsz)[:, None]

        def write(leaf, vals):
            if block_table is not None:
                pp, off = self._page_targets(block_table, positions, valid,
                                             leaf.shape[-2])
                return self._page_write(leaf, pp, off, vals)
            S = leaf.shape[1]
            wpos = positions if valid is None else \
                jnp.where(valid, positions, S)     # OOB rows drop
            return leaf.at[rows, wpos].set(vals, mode="drop")

        def view(leaf):
            return (paged_gather_view(leaf, block_table)
                    if block_table is not None else leaf)

        if sl.kind == "attn":
            if cfg.sliding_window > 0:
                raise NotImplementedError("chunked prefill with SWA ring caches")
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            q, k, v = L.attention_qkv(cfg, p, f"{prefix}/attn", h, positions)
            ck = write(layer_cache[f"{base}/k"], k)
            cv = write(layer_cache[f"{base}/v"], v)
            new_cache[f"{base}/k"], new_cache[f"{base}/v"] = ck, cv
            if block_table is not None and attn_impl != "gather":
                # in-place page read: the kernel's causal mask
                # k_pos <= lengths + c matches attention_mask over the
                # gathered view (pad-query rows read junk either way —
                # the caller's logit gather discards them)
                attn = paged_attention(q, ck, cv, block_table, lengths,
                                       impl=attn_impl)
            else:
                ck, cv = view(ck), view(cv)
                Sk = ck.shape[1]
                kpos = jnp.broadcast_to(jnp.arange(Sk)[None, :], (Bsz, Sk))
                mask = L.attention_mask(positions, kpos, causal=True)
                attn = L.gqa_attention(q, ck, cv, mask)
            x = x + L.attention_out(p, f"{prefix}/attn", attn)
        elif sl.kind == "mla":
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            c_kv, k_rope = L.mla_latent(cfg, p, f"{prefix}/attn", h, positions)
            ckv = write(layer_cache[f"{base}/c_kv"], c_kv)
            krope = write(layer_cache[f"{base}/k_rope"], k_rope)
            new_cache[f"{base}/c_kv"], new_cache[f"{base}/k_rope"] = ckv, krope
            ckv, krope = view(ckv), view(krope)
            Sk = ckv.shape[1]
            kpos = jnp.broadcast_to(jnp.arange(Sk)[None, :], (Bsz, Sk))
            mask = L.attention_mask(positions, kpos, causal=True)
            x = x + L.mla_attention(cfg, p, f"{prefix}/attn", h, ckv, krope,
                                    positions, mask=mask)
        elif sl.kind == "ssm":
            if block_table is not None:
                raise NotImplementedError("paged KV with recurrent caches")
            if valid is not None:
                # a recurrent state would absorb the pad tokens — the
                # serving engine only chunk-pads attention-family models
                raise NotImplementedError("width-padded chunks with "
                                          "recurrent caches")
            h = L.rms_norm(x, p[f"{prefix}/ssm_norm"], cfg.norm_eps)
            y, (conv_state, ssd_state) = S.ssm_apply(
                cfg, p, f"{prefix}/ssm", h,
                conv_state=layer_cache[f"{base}/conv"],
                ssd_state=layer_cache[f"{base}/state"].astype(jnp.float32),
                return_state=True)
            x = x + y
            new_cache[f"{base}/conv"] = conv_state
            new_cache[f"{base}/state"] = ssd_state
        if sl.mlp == "dense":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            x = x + L.dense_mlp_apply(cfg, p, f"{prefix}/mlp", h)
        elif sl.mlp == "moe":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            y, _ = L.moe_apply(cfg, p, f"{prefix}/moe", h, impl=self.moe_impl)
            x = x + y
        return x, new_cache

    def prefill_chunk(self, params: dict, tokens: jax.Array, cache: dict,
                      lengths: jax.Array, widths: Optional[jax.Array] = None,
                      *, block_table: Optional[jax.Array] = None,
                      attn_impl: str = "gather"):
        """Run one chunk of prompt tokens against an existing cache.

        tokens: (B, C); lengths: (B,) cache fill per row (the chunk occupies
        positions lengths..lengths+C-1).  Returns (last logits (B,V),
        new_cache, new_lengths).  Requires linear caches (no SWA ring).

        ``widths`` (B,) int32 <= C: number of VALID leading tokens per row
        of a width-padded chunk.  Pad tokens never write the cache (their
        scatters drop out of bounds) and the returned logits are gathered
        at each row's TRUE last token (``widths - 1``) instead of position
        C-1 — so ONE static (B, C) trace serves every tail-chunk remainder
        (the serving engine's tail-retrace fix) and every row of a mixed
        continuous-batching chunk dispatch.  ``widths=None`` keeps the
        legacy all-valid contract (logits at C-1, lengths + C) bit-exactly.

        ``block_table`` (B, n_pages) int32 switches ``cache`` to the paged
        pool layout of ``paged_cache_specs``: per-token writes scatter into
        physical pages, attention reads a per-row gathered dense view, and
        INVALID entries (>= num_pages) make a row inert (writes drop,
        reads are position-masked junk) — how pad rows and decode-phase
        rows coexist in one dispatch.

        ``attn_impl`` selects how paged attention reads the pool:
        ``"gather"`` (default) materializes the per-row dense view
        (``paged_gather_view``, the bit-exactness oracle); any
        ``kernels/paged_attention`` impl (``"auto"`` / ``"pallas"`` /
        ``"pallas_interpret"`` / ``"ref"``) reads pages in place through
        the fused Pallas kernel.  Ignored without a block table.
        """
        cfg = self.cfg
        Bsz, C = tokens.shape
        x = params["embed/tokens"][tokens]
        valid = (None if widths is None else
                 jnp.arange(C)[None, :] < widths[:, None])         # (B, C)
        new_cache = dict(cache)
        for seg in self.plan:
            seg_params = self._segment_params(params, seg)
            seg_cache = {k: v for k, v in cache.items() if k.startswith(seg.name + "/")}

            def body(x, layer_params, layer_cache):
                nc = {}
                x = constrain(x, ("batch", None, "act_embed"))
                for pos, sl in enumerate(seg.pattern):
                    base = f"{seg.name}/{pos}"
                    x, c = self._sublayer_chunk(
                        sl, layer_params, base, x, lengths,
                        {k: v for k, v in layer_cache.items() if k.startswith(base)},
                        base, valid=valid, block_table=block_table,
                        attn_impl=attn_impl)
                    nc.update(c)
                return x, nc

            if seg.repeats > 1 and cfg.scan_layers:
                def scan_body(x, xs):
                    lp, lc = xs
                    return body(x, lp, lc)

                x, nc = jax.lax.scan(scan_body, x, (seg_params, seg_cache))
                new_cache.update(nc)
            else:
                outs = {k: [] for k in seg_cache}
                for r in range(seg.repeats):
                    lp = self._slice_layer(seg_params, r) if seg.repeats > 1 else seg_params
                    lc = {k: v[r] for k, v in seg_cache.items()} if seg.repeats > 1 else \
                        {k: v[0] for k, v in seg_cache.items()}
                    x, nc = body(x, lp, lc)
                    for k, v in nc.items():
                        outs[k].append(v)
                new_cache.update({k: jnp.stack(v) for k, v in outs.items()})

        if widths is None:
            logits = self.unembed(params, x[:, -1:])[:, 0]
            return logits, new_cache, lengths + C
        rows = jnp.arange(Bsz)
        x_last = x[rows, jnp.maximum(widths - 1, 0)][:, None, :]
        logits = self.unembed(params, x_last)[:, 0]                # (B, V)
        return logits, new_cache, lengths + widths

    # ------------------------------------------------------------------
    # Decode step
    # ------------------------------------------------------------------
    def _sublayer_decode(self, sl: SubLayer, p: dict, prefix: str, x, lengths,
                         layer_cache: dict, base: str,
                         block_table: Optional[jax.Array] = None,
                         attn_impl: str = "gather"):
        """x: (B,1,D); lengths: (B,) current cache fill (also the position of
        the incoming token).  Returns (x, new_layer_cache).

        ``block_table`` (B, n_pages) switches the cache leaves to the paged
        pool layout: the new token scatters into its row's physical page
        (INVALID entries drop the write — how prefilling/idle rows ride a
        decode dispatch unharmed) and attention reads the pool — gathered
        (``attn_impl="gather"``) or in place via the paged-attention
        kernel (any ``kernels/paged_attention`` impl), whose INVALID-page
        skip makes idle rows finalize to zeros just as the gather path's
        position mask does.  MLA layers always gather."""
        cfg = self.cfg
        Bsz = x.shape[0]
        new_cache = {}
        positions = lengths[:, None]                               # (B,1)

        def write(leaf, vals):                     # vals: (B, ...) one token
            if block_table is not None:
                pp, off = self._page_targets(block_table, positions,
                                             None, leaf.shape[-2])
                return self._page_write(leaf, pp[:, 0], off[:, 0], vals)
            Sk = leaf.shape[1]
            return leaf.at[jnp.arange(Bsz), lengths % Sk].set(vals,
                                                              mode="drop")

        def view(leaf):
            return (paged_gather_view(leaf, block_table)
                    if block_table is not None else leaf)

        if sl.kind == "attn":
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            q, k, v = L.attention_qkv(cfg, p, f"{prefix}/attn", h, positions)
            ck = write(layer_cache[f"{base}/k"], k[:, 0])
            cv = write(layer_cache[f"{base}/v"], v[:, 0])
            new_cache[f"{base}/k"], new_cache[f"{base}/v"] = ck, cv
            if (block_table is not None and attn_impl != "gather"
                    and cfg.sliding_window == 0):
                attn = paged_attention(q, ck, cv, block_table, lengths,
                                       impl=attn_impl)
            else:
                ck, cv = view(ck), view(cv)
                Sk = ck.shape[1]
                # key absolute position per slot: for ring buffers the slot j
                # holds position p with p % Sk == j and p <= lengths;
                # reconstruct (for linear/paged caches Sk covers every
                # position, so this reduces to kpos == slot and the plain
                # causal mask kpos <= lengths):
                slots = jnp.arange(Sk)[None, :]
                cur = lengths[:, None]
                kpos = cur - ((cur - slots) % Sk)              # (B, Sk) abs pos
                valid = (kpos >= 0) & (kpos <= cur)
                if cfg.sliding_window > 0:
                    valid &= kpos > cur - cfg.sliding_window
                mask = valid[:, None, :]                       # (B,1,Sk)
                attn = L.gqa_attention(q, ck, cv, mask)
            x = x + L.attention_out(p, f"{prefix}/attn", attn)
        elif sl.kind == "mla":
            h = L.rms_norm(x, p[f"{prefix}/attn_norm"], cfg.norm_eps)
            c_kv_new, k_rope_new = L.mla_latent(cfg, p, f"{prefix}/attn", h, positions)
            ckv = write(layer_cache[f"{base}/c_kv"], c_kv_new[:, 0])
            krope = write(layer_cache[f"{base}/k_rope"], k_rope_new[:, 0])
            new_cache[f"{base}/c_kv"], new_cache[f"{base}/k_rope"] = ckv, krope
            ckv, krope = view(ckv), view(krope)
            Sk = ckv.shape[1]
            kpos = jnp.arange(Sk)[None, :]
            mask = (kpos <= lengths[:, None])[:, None, :]          # (B,1,Sk)
            x = x + L.mla_attention(cfg, p, f"{prefix}/attn", h, ckv, krope,
                                    positions, mask=mask)
        elif sl.kind == "ssm":
            h = L.rms_norm(x, p[f"{prefix}/ssm_norm"], cfg.norm_eps)
            y, conv_state, ssd_state = S.ssm_decode_step(
                cfg, p, f"{prefix}/ssm", h,
                layer_cache[f"{base}/conv"], layer_cache[f"{base}/state"])
            x = x + y
            new_cache[f"{base}/conv"] = conv_state
            new_cache[f"{base}/state"] = ssd_state
        if sl.mlp == "dense":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            x = x + L.dense_mlp_apply(cfg, p, f"{prefix}/mlp", h)
        elif sl.mlp == "moe":
            h = L.rms_norm(x, p[f"{prefix}/mlp_norm"], cfg.norm_eps)
            y, _ = L.moe_apply(cfg, p, f"{prefix}/moe", h, impl=self.moe_impl)
            x = x + y
        return x, new_cache

    def decode_step(self, params: dict, cache: dict, tokens: jax.Array,
                    lengths: jax.Array, *,
                    block_table: Optional[jax.Array] = None,
                    attn_impl: str = "gather"):
        """One decode step.  tokens: (B,) int32; lengths: (B,) int32 cache
        fill per row.  Returns (logits (B,V), new_cache, new_lengths).

        ``block_table`` (B, n_pages) int32 switches ``cache`` to the paged
        pool layout of ``paged_cache_specs`` (see ``_sublayer_decode``);
        ``attn_impl`` != "gather" additionally routes attention through the
        in-place ``kernels/paged_attention`` op with that impl string."""
        cfg = self.cfg
        x = params["embed/tokens"][tokens][:, None, :]             # (B,1,D)

        new_cache = dict(cache)
        for seg in self.plan:
            seg_params = self._segment_params(params, seg)
            seg_cache = {k: v for k, v in cache.items() if k.startswith(seg.name + "/")}

            def body(x, layer_params, layer_cache):
                nc = {}
                x = constrain(x, ("batch", None, "act_embed"))
                for pos, sl in enumerate(seg.pattern):
                    base = f"{seg.name}/{pos}"
                    x, c = self._sublayer_decode(
                        sl, layer_params, base, x, lengths,
                        {k: v for k, v in layer_cache.items() if k.startswith(base)},
                        base, block_table=block_table, attn_impl=attn_impl)
                    nc.update(c)
                return x, nc

            if seg.repeats > 1 and cfg.scan_layers:
                def scan_body(x, xs):
                    layer_params, layer_cache = xs
                    return body(x, layer_params, layer_cache)

                x, nc = jax.lax.scan(scan_body, x, (seg_params, seg_cache))
                new_cache.update(nc)
            else:
                outs = {k: [] for k in seg_cache}
                for r in range(seg.repeats):
                    lp = self._slice_layer(seg_params, r) if seg.repeats > 1 else seg_params
                    lc = {k: v[r] for k, v in seg_cache.items()} if seg.repeats > 1 else \
                        {k: v[0] for k, v in seg_cache.items()}
                    x, nc = body(x, lp, lc)
                    for k, v in nc.items():
                        outs[k].append(v)
                new_cache.update({k: jnp.stack(v) for k, v in outs.items()})

        logits = self.unembed(params, x)[:, 0]                     # (B, V)
        return logits, new_cache, lengths + 1
