#!/usr/bin/env python3
"""Compile a configuration's served programs for a described TPU v5e,
without a chip, and print each one's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 benchmarks/onchip/tools/rehearse.py \\
        llava-next-34b-8l [--chunk-rows 32] [--chunk 512] [--desc 8x1024]

Programs: the weight maker, the paged decode step at the configuration's
slots, the paged prefill chunk at ``--chunk-rows`` rows of ``--chunk``
tokens and the prefix descriptor at ``--desc`` (rows x padded length),
each as the engine jits it (``attn_impl="pallas"``).  Nothing runs; the
numbers say whether the weights, the page pool, the cache keys and each
program's temporaries fit one chip's memory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--chunk-rows", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=0)
    ap.add_argument("--desc", default="")
    ap.add_argument("--only", default="")
    args = ap.parse_args(argv)

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import weights as W
    from repro.configs.base import ModelConfig
    from repro.core.descriptor import PrefixDescriptor
    from repro.models import build_model

    cfg = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    sv = cfg["serving"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    model = build_model(ModelConfig(**cfg["model"]))
    params = {k: sds(v.shape, v.dtype) for k, v in model.init_shapes().items()}
    pool = {k: sds(v.shape, v.dtype) for k, v in
            model.paged_cache_specs(sv["kv_pages"], sv["kv_page"]).items()}
    B, pps = sv["slots"], sv["max_len"] // sv["kv_page"]
    rows = args.chunk_rows or B
    C = args.chunk or sv["prefill_chunk"]
    dr, ds = (int(x) for x in (args.desc or "8x1024").split("x"))
    i32 = jnp.int32
    progs = {
        "weights": (lambda k: W._make_all(k, tuple(W.layout(cfg["model"])),
                                          jnp.dtype(cfg["model"]["dtype"])),
                    (sds((2,), jnp.uint32),), ()),
        "decode": (lambda p, c, t, ln, bt: model.decode_step(
            p, c, t, ln, block_table=bt, attn_impl="pallas"),
            (params, pool, sds((B,), i32), sds((B,), i32),
             sds((B, pps), i32)), (1,)),
        "chunk": (lambda p, t, c, ln, w, bt: model.prefill_chunk(
            p, t, c, ln, w, block_table=bt, attn_impl="pallas"),
            (params, sds((rows, C), i32), pool, sds((rows,), i32),
             sds((rows,), i32), sds((rows, pps), i32)), (2,)),
        "descriptor": (lambda p, t: PrefixDescriptor(
            model, k_layers=cfg["coic"]["k_layers"])(p, t),
            (params, sds((dr, ds), i32)), ()),
    }
    gb = 1e9
    for name, (fn, shapes, donate) in progs.items():
        if args.only and name not in args.only.split(","):
            continue
        c = jax.jit(fn, donate_argnums=donate).lower(*shapes).compile()
        m = c.memory_analysis()
        print(f"{args.config} {name}: argument {m.argument_size_in_bytes / gb:.3f} GB,"
              f" output {m.output_size_in_bytes / gb:.3f}, alias "
              f"{m.alias_size_in_bytes / gb:.3f}, temp "
              f"{m.temp_size_in_bytes / gb:.3f}, code "
              f"{m.generated_code_size_in_bytes / 1e6:.1f} MB", flush=True)


if __name__ == "__main__":
    main()
