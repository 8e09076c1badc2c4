"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips
(TPU v5e-256).  Multi-pod: (pod=2, data=16, model=16) = 512 chips; the
``pod`` axis is the outer pure-DP axis crossing the inter-pod links.

``CacheMeshConfig`` is the cooperative-cache launch surface: one mesh
whose ``cache`` axis spans the cluster's shard holders, bound to
``parallel/sharding.py::sharded_topk_lookup`` so a multi-host launch gets
the peer rung as a shard_map collective (per-device local top-k + one
(k idx, k score) all-gather) instead of pooling shards on one host.

Every mesh here has ``Auto`` axes: placement is GSPMD's, steered by the
sharding rules and ``with_sharding_constraint``.  (``jax.make_mesh``
otherwise defaults to ``Explicit`` axes, whose sharding-in-types rejects
a gather like the embedding lookup under ZeRO-sharded tables.)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary ``Auto``-axis mesh (tests / elastic reconfiguration)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_cache_mesh(num_shards: Optional[int] = None,
                    axis_name: str = "cache"):
    """1-D mesh over the cache-shard holders.  ``num_shards`` defaults to
    every addressable device (a multi-host launch sees the global device
    set, so the axis spans hosts)."""
    n = len(jax.devices()) if num_shards is None else int(num_shards)
    return make_mesh((n,), (axis_name,))


@dataclasses.dataclass
class CacheMeshConfig:
    """Launch-time binding of the peer rung's collective lookup.

    ``lookup`` mirrors ``cluster_topk_lookup``'s signature with the mesh
    pre-bound; ``surviving_lookup`` is the membership-aware variant — it
    runs the shard_map collective whenever the survivor count matches the
    mesh's cache axis and falls back to the pooled single-dispatch probe
    otherwise (bit-identical results either way).  The mesh is built
    lazily on first use, never at import or config-construction time.
    """

    num_shards: Optional[int] = None
    axis_name: str = "cache"
    _mesh: object = dataclasses.field(default=None, repr=False)

    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_cache_mesh(self.num_shards, self.axis_name)
        return self._mesh

    def lookup(self, queries, keys, valid, k, *, impl: str = "auto"):
        from repro.parallel.sharding import sharded_topk_lookup
        return sharded_topk_lookup(queries, keys, valid, k, self.mesh,
                                   self.axis_name, impl=impl)

    def surviving_lookup(self, queries, keys, valid, alive, k, *,
                         impl: str = "auto"):
        from repro.parallel.sharding import surviving_topk_lookup
        return surviving_topk_lookup(queries, keys, valid, alive, k,
                                     self.mesh, self.axis_name, impl=impl)
