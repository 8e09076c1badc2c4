"""Seeded random weights for the dense decoder configurations.

The benchmark owns the weights: it makes them from ``--seed`` and hands
them to the program, and the float32 reference makes the same values
again on its own, one layer at a time.  So the layout (which leaves, what
shape, how each is drawn) is written down here and not read from the
program; ``check_layout`` compares it with what the program expects
before a run starts.

Every leaf is drawn per layer, from ``fold_in(fold_in(key, leaf), layer)``,
as ``normal * min(0.02, fan_in ** -0.5)`` in float32 and rounded to the
served dtype, so one layer of one leaf can be made again without the
rest.  All leaves come out of one jitted call, on the device.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Leaf(NamedTuple):
    name: str               # the program's parameter name
    shape: Tuple[int, ...]  # per layer (no leading layer axis)
    init: str               # normal | zeros | ones
    layers: int             # 0: not stacked; R: stacked over R layers


def layout(model: dict) -> List[Leaf]:
    """The leaves of a dense GQA/MQA decoder (``model``: the ``model``
    block of a configuration file), sorted by name."""
    D, H, K = model["d_model"], model["num_heads"], model["num_kv_heads"]
    hd, F, V = model["head_dim"], model["d_ff"], model["vocab_size"]
    R = model["num_layers"]
    blk = "blocks/0"
    leaves = [
        Leaf("embed/tokens", (V, D), "normal", 0),
        Leaf("final_norm/w", (D,), "ones", 0),
        Leaf(f"{blk}/attn_norm", (D,), "ones", R),
        Leaf(f"{blk}/attn/wq", (D, H, hd), "normal", R),
        Leaf(f"{blk}/attn/wk", (D, K, hd), "normal", R),
        Leaf(f"{blk}/attn/wv", (D, K, hd), "normal", R),
        Leaf(f"{blk}/attn/wo", (H, hd, D), "normal", R),
        Leaf(f"{blk}/mlp_norm", (D,), "ones", R),
    ]
    if not model.get("tie_embeddings", False):
        leaves.append(Leaf("head/w", (D, V), "normal", 0))
    if model.get("mlp_kind", "gated_silu") == "gelu":
        leaves += [Leaf(f"{blk}/mlp/w_in", (D, F), "normal", R),
                   Leaf(f"{blk}/mlp/b_in", (F,), "zeros", R),
                   Leaf(f"{blk}/mlp/w_out", (F, D), "normal", R),
                   Leaf(f"{blk}/mlp/b_out", (D,), "zeros", R)]
    else:
        leaves += [Leaf(f"{blk}/mlp/w_gate", (D, F), "normal", R),
                   Leaf(f"{blk}/mlp/w_up", (D, F), "normal", R),
                   Leaf(f"{blk}/mlp/w_down", (F, D), "normal", R)]
    return sorted(leaves)


def check_layout(leaves: List[Leaf], program_shapes: Dict[str, tuple]) -> None:
    """Raise unless the program expects exactly these leaves and shapes."""
    ours = {lf.name: ((lf.layers,) if lf.layers else ()) + lf.shape
            for lf in leaves}
    theirs = {k: tuple(v) for k, v in program_shapes.items()}
    if ours != theirs:
        diff = sorted(set(ours.items()) ^ set(theirs.items()))
        raise ValueError(f"weight layout differs from the program's: {diff}")


def base_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, including ones past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _scale(shape) -> float:
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return min(0.02, 1.0 / float(np.sqrt(max(1, fan_in))))


def _draw(key, shape, init, dtype):
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            * _scale(shape)).astype(dtype)


def leaf_key(key, index: int, layer: int):
    return jax.random.fold_in(jax.random.fold_in(key, index), layer)


@functools.partial(jax.jit, static_argnames=("leaves", "dtype"))
def _make_all(key, leaves, dtype):
    out = {}
    for i, lf in enumerate(leaves):
        if lf.layers:
            keys = jax.vmap(lambda r, i=i: leaf_key(key, i, r))(
                jnp.arange(lf.layers))
            out[lf.name] = jax.vmap(
                lambda k, lf=lf: _draw(k, lf.shape, lf.init, dtype))(keys)
        else:
            out[lf.name] = _draw(leaf_key(key, i, 0), lf.shape, lf.init,
                                 dtype)
    return out


def make_params(model: dict, seed: int) -> Dict[str, jax.Array]:
    """Every weight of the program, made on the default device in one
    jitted call, in the served dtype."""
    return _make_all(base_key(seed), tuple(layout(model)),
                     jnp.dtype(model["dtype"]))


@functools.partial(jax.jit, static_argnames=("shape", "init", "dtype"))
def _make_one(key, index, layer, *, shape, init, dtype):
    return _draw(leaf_key(key, index, layer), shape, init, dtype)


def make_leaf(model: dict, seed: int, name: str, layer: int = 0) -> jax.Array:
    """One layer of one leaf, equal to ``make_params(...)[name][layer]``
    (or the whole leaf where it is not stacked)."""
    leaves = layout(model)
    index = next(i for i, lf in enumerate(leaves) if lf.name == name)
    lf = leaves[index]
    return _make_one(base_key(seed), index, layer, shape=lf.shape,
                     init=lf.init, dtype=jnp.dtype(model["dtype"]))
