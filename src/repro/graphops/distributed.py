"""Distributed graph aggregation (shard_map, dst-partitioned edges).

XLA SPMD cannot partition a scatter with data-dependent indices: the GNN
segment-sum over node-sharded outputs degenerates into replicated edge
buffers + giant all-gathers (26GB/device peaks on ogb_products; see
results/perf_log.md).  The scalable scheme — the same one the MV4PG
distributed executor uses for frontier hops — is written here by hand:

  * nodes shard over every mesh axis (row partition),
  * edges are pre-partitioned BY DESTINATION OWNER (host-side, amortized:
    the data loader sorts edges once, like any graph partitioner),
  * per device: all-gather node features once per layer, gather sources
    locally, segment-reduce into the LOCAL node range only — no cross-device
    scatter, no reduction collective at all.

Per-layer comm = one [N, D] feature all-gather (+ its reduce-scatter
transpose in backward).  Aggregation output is exactly node-sharded.
"""
from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def flat_axis_index(axes: Sequence[str]) -> jax.Array:
    """Linear shard index over a tuple of mesh axes (row-major, inside
    shard_map)."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def all_gather_axes(x: jax.Array, axes: Sequence[str], axis: int = 0
                    ) -> jax.Array:
    for a in reversed(axes):
        x = jax.lax.all_gather(x, a, axis=axis, tiled=True)
    return x


def dst_partitioned_aggregate(
    h: jax.Array,                 # [N, D] node-sharded over `axes`
    edge_src: jax.Array,          # [E] global ids, sharded over `axes`,
    edge_dst: jax.Array,          # partitioned by dst owner
    edge_mask: jax.Array,
    msg_and_reduce: Callable,     # (h_full, src_l, dst_local, mask_l, n_loc)
    mesh,
    axes: Sequence[str],
    out_width: int,
):
    """Generic sharded gather-aggregate.  Returns per-node outputs sharded
    like ``h``.  ``msg_and_reduce`` runs entirely device-local."""
    N = h.shape[0]
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    n_loc = N // total
    spec1 = P(tuple(axes))
    spec2 = P(tuple(axes), None)

    def local(h_l, src_l, dst_l, mask_l):
        h_full = all_gather_axes(h_l, axes, axis=0)          # [N, D]
        offset = flat_axis_index(axes) * n_loc
        dst_local = dst_l - offset                           # [E_l] in-range
        return msg_and_reduce(h_full, src_l, dst_local, mask_l, n_loc)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec2, spec1, spec1, spec1),
        out_specs=spec2,
        check_vma=False,
    )(h, edge_src, edge_dst, edge_mask)


def shard_owner(label_id: int, n_shards: int) -> int:
    """Deterministic owner shard for a label's maintenance routing.

    Edge *data* is dst-partitioned across every shard (see
    :func:`partition_hop_edges`); the owner shard is the scheduling anchor:
    delta sweeps and drain batches for a label group under its owner so
    maintenance work spreads round-robin over the mesh instead of all
    landing on device 0."""
    return int(label_id) % max(int(n_shards), 1)


def partition_hop_edges(gather_ids: np.ndarray, scatter_ids: np.ndarray,
                        weights: np.ndarray, n_pad: int, n_shards: int
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                   np.ndarray, np.ndarray]:
    """Host-side dst-partition of one hop's compact edge slice.

    A sharded hop gathers from the *full* (all-gathered) frontier and
    scatters only into the shard's local node-column range, so edges are
    partitioned by the owner of their **scatter-side** endpoint (the hop's
    traversal destination; callers pass ``(dst, src)`` swapped for reverse
    hops).  Returns stacked per-shard arrays, padded to a uniform per-shard
    width (padding rows are masked off — exact no-ops):

      * ``a``        [D, Ep]  gather-side endpoint, **global** node id
      * ``b_local``  [D, Ep]  scatter-side endpoint, **localized**
                              (global id − shard offset, in ``[0, n_loc)``)
      * ``w``        [D, Ep]  edge weights
      * ``mask``     [D, Ep]  real-edge mask
      * ``deg``      [D, N_pad] partial degree by gather-side endpoint over
                              the shard's local edges only — the per-shard
                              DBHit operand; the shard partials sum (one
                              psum) to the single-device degree vector
                              exactly (int32 sums commute).

    ``n_pad`` is the node-column capacity padded to a multiple of
    ``n_shards`` (``n_loc = n_pad // n_shards``).
    """
    gather_ids = np.asarray(gather_ids, np.int32)
    scatter_ids = np.asarray(scatter_ids, np.int32)
    weights = np.asarray(weights, np.int32)
    if n_pad % n_shards != 0:
        raise ValueError(f"n_pad={n_pad} not a multiple of n_shards={n_shards}")
    n_loc = n_pad // n_shards
    owner = np.minimum(scatter_ids // n_loc, n_shards - 1)
    order = np.argsort(owner, kind="stable")
    counts = np.bincount(owner, minlength=n_shards)
    width = max(int(counts.max()) if counts.size else 0, 1)
    a = np.zeros((n_shards, width), np.int32)
    b_local = np.zeros((n_shards, width), np.int32)
    w = np.zeros((n_shards, width), np.int32)
    mask = np.zeros((n_shards, width), bool)
    deg = np.zeros((n_shards, n_pad), np.int32)
    start = 0
    for s in range(n_shards):
        c = int(counts[s])
        sl = order[start:start + c]
        a[s, :c] = gather_ids[sl]
        b_local[s, :c] = scatter_ids[sl] - s * n_loc
        w[s, :c] = weights[sl]
        mask[s, :c] = True
        np.add.at(deg[s], gather_ids[sl], 1)
        start += c
    return a, b_local, w, mask, deg


def partition_edges_by_dst(src: np.ndarray, dst: np.ndarray, n_nodes: int,
                           n_shards: int
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host-side: order edges so shard i holds edges whose dst is in node
    shard i, padded per-shard to uniform length (returns perm, mask, counts).
    """
    n_loc = n_nodes // n_shards
    owner = np.minimum(dst // n_loc, n_shards - 1)
    order = np.argsort(owner, kind="stable")
    counts = np.bincount(owner, minlength=n_shards)
    width = int(counts.max()) if counts.size else 1
    E_pad = width * n_shards
    perm = np.zeros(E_pad, np.int64)
    mask = np.zeros(E_pad, bool)
    start = 0
    for s in range(n_shards):
        c = counts[s]
        sl = order[start:start + c]
        perm[s * width: s * width + c] = sl
        mask[s * width: s * width + c] = True
        start += c
    return perm, mask, counts
