"""PNA: Principal Neighbourhood Aggregation [arXiv:2004.05718].

Multi-aggregator (mean/max/min/std) × degree-scaler (identity/amplification/
attenuation) message passing.  The aggregation hot path can route through the
fused Pallas ``segment_agg`` kernel (bucketed layout) or the segment-op
substrate (default; handles power-law degree skew).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.graphops.segment import segment_mean
from repro.models.common import Params, dense, dense_init, mlp, mlp_init
from repro.models.gnn.graphdata import GraphBatch


@dataclass(frozen=True)
class PNAConfig:
    name: str = "pna"
    n_layers: int = 4
    d_hidden: int = 75
    d_in: int = 1433
    n_classes: int = 8
    avg_degree: float = 4.0          # delta, from the training graphs
    graph_level: bool = False        # molecule regime: pooled readout
    n_graphs: int = 1                # graphs per batch (molecule regime)
    dtype: object = jnp.float32
    # distributed aggregation (shard_map over dst-partitioned edges); when
    # set, edges MUST be partitioned by destination owner (the loader does
    # this; see graphops/distributed.py)
    mesh: object = None
    shard_axes: tuple = ()


def init_params(key, cfg: PNAConfig) -> Params:
    keys = jax.random.split(key, cfg.n_layers + 2)
    h = cfg.d_hidden
    layers = []
    for i in range(cfg.n_layers):
        k1, k2 = jax.random.split(keys[i])
        kid, kamp, katt = jax.random.split(k2, 3)
        layers.append({
            "msg": mlp_init(k1, [2 * h, h, h], dtype=cfg.dtype),
            # scaler-factored post projection: out = agg@W_id
            #   + s_amp*(agg@W_amp) + s_att*(agg@W_att)  — algebraically the
            # paper's [4h x 3 scalers -> h] linear, but the [N, 12h] concat is
            # never materialized (per-node scalers commute with the matmul)
            "post_id": dense_init(kid, 4 * h, h, dtype=cfg.dtype),
            "post_amp": dense_init(kamp, 4 * h, h, dtype=cfg.dtype),
            "post_att": dense_init(katt, 4 * h, h, dtype=cfg.dtype),
        })
    return {
        "proj": dense_init(keys[-2], cfg.d_in, h, dtype=cfg.dtype),
        "layers": layers,
        "head": mlp_init(keys[-1], [h, h, cfg.n_classes], dtype=cfg.dtype),
    }


def _aggregate(msg: jax.Array, dst: jax.Array, emask: jax.Array, n: int):
    """Mask-aware 4-way aggregation: padded edges must not count in the
    mean/std denominators (they do in the naive segment_mean helpers)."""
    w = emask.astype(msg.dtype)[:, None]
    m = msg * w
    deg = jax.ops.segment_sum(emask.astype(msg.dtype), dst, n)
    safe = jnp.maximum(deg, 1.0)[:, None]
    mean = jax.ops.segment_sum(m, dst, n) / safe
    meansq = jax.ops.segment_sum(msg * msg * w, dst, n) / safe
    std = jnp.sqrt(jnp.maximum(meansq - mean * mean, 0.0) + 1e-5)
    big = jnp.asarray(3.4e38, msg.dtype)
    mx = jax.ops.segment_max(jnp.where(w > 0, msg, -big), dst, n)
    mn = jax.ops.segment_min(jnp.where(w > 0, msg, big), dst, n)
    has = (deg > 0)[:, None]
    mx = jnp.where(has, mx, 0.0)
    mn = jnp.where(has, mn, 0.0)
    std = jnp.where(has, std, 0.0)
    return jnp.concatenate([mean, mx, mn, std], axis=-1), deg


def _layer_local(lp, h_full, h_l, src_l, dst_local, emask_l, nmask_l,
                 n_loc: int, delta: float):
    """Device-local PNA layer body (runs inside shard_map or single-device).

    h_full: [N, h] gathered features; everything else local-shard-sized."""
    hs = h_full[src_l]
    hd = h_full[dst_local] if n_loc == h_full.shape[0] else None
    # for sharded runs dst are local ids into the local range; gather the
    # destination features from the local slice
    if hd is None:
        hd = h_l[dst_local]
    msg = mlp(lp["msg"], jnp.concatenate([hs, hd], axis=-1), act=jax.nn.relu)
    agg, deg = _aggregate(msg, dst_local, emask_l, n_loc)
    logd = jnp.log1p(deg)[:, None]
    s_amp = logd / delta
    s_att = jnp.where(logd > 0, delta / jnp.maximum(logd, 1e-6), 0.0)
    upd = (dense(lp["post_id"], agg)
           + s_amp * dense(lp["post_amp"], agg)
           + s_att * dense(lp["post_att"], agg))
    return jax.nn.relu(h_l + upd) * nmask_l[:, None]


def _layer_sharded(lp, h, gb: GraphBatch, cfg: PNAConfig, delta: float):
    """Distributed layer: dst-partitioned edges, one feature all-gather."""
    from jax.sharding import PartitionSpec as P
    from repro.graphops.distributed import all_gather_axes, flat_axis_index
    mesh, axes = cfg.mesh, tuple(cfg.shard_axes)
    N = h.shape[0]
    total = 1
    for a in axes:
        total *= mesh.shape[a]
    n_loc = N // total
    spec1 = P(axes)
    spec2 = P(axes, None)

    def local(h_l, src_l, dst_l, emask_l, nmask_l, lp_l):
        h_full = all_gather_axes(h_l, axes, axis=0)
        offset = flat_axis_index(axes) * n_loc
        dst_local = jnp.clip(dst_l - offset, 0, n_loc - 1)
        return _layer_local(lp_l, h_full, h_l, src_l, dst_local, emask_l,
                            nmask_l, n_loc, delta)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec2, spec1, spec1, spec1, spec1, P()),
        out_specs=spec2, check_vma=False,
    )(h, gb.edge_src, gb.edge_dst, gb.edge_mask, gb.node_mask, lp)


def forward(params: Params, gb: GraphBatch, cfg: PNAConfig) -> jax.Array:
    n = gb.n_nodes
    x = gb.node_feat.astype(cfg.dtype)
    h = jax.nn.relu(dense(params["proj"], x))
    delta = max(math.log(cfg.avg_degree + 1.0), 1e-3)
    for lp in params["layers"]:
        if cfg.mesh is not None:
            h = _layer_sharded(lp, h, gb, cfg, delta)
            continue
        h = _layer_local(lp, h, h, gb.edge_src, gb.edge_dst, gb.edge_mask,
                         gb.node_mask, n, delta)
    if cfg.graph_level:
        pooled = segment_mean(h * gb.node_mask[:, None], gb.graph_id,
                              cfg.n_graphs)
        return mlp(params["head"], pooled, act=jax.nn.relu)
    return mlp(params["head"], h, act=jax.nn.relu)


def loss_fn(params: Params, gb: GraphBatch, cfg: PNAConfig) -> jax.Array:
    logits = forward(params, gb, cfg).astype(jnp.float32)
    labels = gb.labels
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    nll = (logz - gold) * gb.node_mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(gb.node_mask), 1.0)
