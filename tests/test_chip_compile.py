"""Compile rehearsals: every Pallas kernel the serving engines reach,
compiled at real widths for a described (not attached) TPU v5e chip.

Interpret mode cannot see what Mosaic refuses (block tiling, VMEM), so
these compile the ``impl="pallas"`` path of each op for the chip and check
that the kernel is in the program (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture — never at import — so
only the test process that is handed this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ivf_pq import ops as ivf_ops
from repro.kernels.paged_attention import ops as attn_ops
from repro.kernels.similarity import ops as sim_ops

# llama3.2-1b served through the paged engine: 32 query heads over 8 KV
# heads of 64, pages of 16 tokens, max_len 512, 8 slots, 2x page pool
B, H, K, D, PAGE, MAX_LEN = 8, 32, 8, 64, 16, 512
N_PAGES = MAX_LEN // PAGE
POOL = 2 * B * N_PAGES
# the cooperative cache: prefix descriptors of d_model 2048, 4096 slots
KEY_DIM, CAP = 2048, 4096


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("C", [1, MAX_LEN], ids=["decode", "chunk"])
def test_paged_attention(one_chip, C):
    _compile(functools.partial(attn_ops._paged_attention, impl="pallas"),
             one_chip, ((B, C, H, D), jnp.bfloat16),
             ((POOL, K, PAGE, D), jnp.bfloat16),
             ((POOL, K, PAGE, D), jnp.bfloat16),
             ((B, N_PAGES), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("N", [1, 4])
def test_similarity_topk_batched(one_chip, N):
    fn = functools.partial(sim_ops._similarity_topk_batched, k=1,
                           impl="pallas", block_q=128, block_c=512)
    _compile(fn, one_chip, ((N, 16, KEY_DIM), jnp.float32),
             ((N, CAP, KEY_DIM), jnp.float32), ((N, CAP), jnp.bool_))


@pytest.mark.parametrize("op", ["lookup", "topk"])
def test_similarity_lookup_and_topk(one_chip, op):
    fn = (functools.partial(sim_ops._similarity_lookup, impl="pallas",
                            block_q=128, block_c=512) if op == "lookup" else
          functools.partial(sim_ops._similarity_topk, k=4, impl="pallas",
                            block_q=128, block_c=512))
    _compile(fn, one_chip, ((16, KEY_DIM), jnp.float32),
             ((CAP, KEY_DIM), jnp.float32), ((CAP,), jnp.bool_))


def test_similarity_topk_touch(one_chip):
    fn = functools.partial(sim_ops._similarity_topk_touch, k=4,
                           threshold=0.85, impl="pallas", block_c=512)
    _compile(fn, one_chip, ((16, KEY_DIM), jnp.float32),
             ((CAP, KEY_DIM), jnp.float32), ((CAP,), jnp.bool_),
             ((CAP,), jnp.int32), ((CAP,), jnp.int32), ((), jnp.int32),
             ((16,), jnp.bool_))


# (lists, slots per list) of real boards at build_ivfpq_index's 1.5x
# slack: the smallest board the ANN rung engages on (4096 rows over 64
# lists), a 768-slot list, and benchmarks/ann_probe.py's 100K- and 1M-row
# boards (~sqrt(rows) lists); lists over CAP_TILE slots walk in tiles
@pytest.mark.parametrize("L,cap", [(64, 96), (64, 768), (256, 592),
                                   (1024, 1472)],
                         ids=["min", "cap768", "rows100k", "rows1m"])
def test_ivf_pq_probe(one_chip, L, cap):
    S = 8
    fn = functools.partial(ivf_ops._ivf_pq_probe, k=4, n_probe=8,
                           impl="pallas")
    _compile(fn, one_chip, ((32, KEY_DIM), jnp.float32), ((32,), jnp.int32),
             ((L, KEY_DIM), jnp.float32), ((L,), jnp.bool_),
             ((L, cap, S), jnp.uint8), ((L, cap), jnp.bool_),
             ((L, cap), jnp.int32), ((S, 256, KEY_DIM // S), jnp.float32))
