"""Modeled HBM bytes of the similarity-family probes.

``similarity_bytes`` is one probe's streaming read of its key matrix;
``digest_probe_bytes`` the same over the region board's digest replicas
in their wire format (``DigestConfig.row_bytes``'s int8-vs-fp32 model);
``ivf_pq_probe_bytes`` the two-stage IVF-PQ probe over packed code
lists.  ``benchmarks/ann_probe.py`` compares the three per advertised
row.  Kernel time comes from the device trace (``jax.profiler``), not
from here.
"""
from __future__ import annotations

from typing import Optional


def similarity_bytes(n_queries: int, n_keys: int, dim: int,
                     key_bytes_per_row: Optional[float] = None) -> float:
    """Modeled HBM traffic of one similarity probe: one read of the query
    block, one streaming read of the key matrix (+ validity byte per row),
    and the (Q, k) outputs (negligible, ignored).  ``key_bytes_per_row``
    overrides the fp32 ``dim * 4`` key row (the int8 digest probe passes
    ``DigestConfig.row_bytes``'s ``dim + 4``)."""
    row = (dim * 4.0 if key_bytes_per_row is None
           else float(key_bytes_per_row))
    return (n_queries * dim * 4.0            # query block read
            + n_keys * (row + 1.0))          # key rows + valid bytes


def digest_probe_bytes(n_queries: int, num_clusters: int, digest_size: int,
                       dim: int, quant: str) -> float:
    """Modeled bytes of one grouped region-board probe — the similarity
    model over K digest replicas in their wire format (int8 rows carry
    ``D + 4`` bytes, the ``DigestConfig.row_bytes`` model)."""
    row_bytes = dim + 4 if quant == "int8" else dim * 4
    return similarity_bytes(n_queries * num_clusters,
                            num_clusters * digest_size, dim,
                            key_bytes_per_row=row_bytes)


def ivf_pq_probe_bytes(n_queries: int, n_lists: int, list_cap: int,
                       n_sub: int, dim: int) -> float:
    """Modeled HBM traffic of one two-stage IVF-PQ board probe: the query
    tile, the pinned coarse table (centroids + validity byte per list), the
    shared residual codebook, and one streaming read of the packed code
    lists in their storage format — ``n_sub`` uint8 codes plus a validity
    and an owner byte per slot (vs ``D + 4`` for a brute int8 row; the
    4x-fewer-scanned-bytes acceptance in BENCH_ann_probe.json compares
    exactly these two models)."""
    return (n_queries * dim * 4.0                      # query tile
            + n_lists * (dim * 4.0 + 1.0)              # centroids + valid
            + n_sub * 256 * (dim // n_sub) * 4.0       # shared codebook
            + n_lists * list_cap * (n_sub + 2.0))      # codes+valid+owner
