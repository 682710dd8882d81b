"""GraphSAGE-style convolution over :class:`GraphBatch`, with the neighbor
aggregation optionally routed through the :mod:`~repro.kernels.block_spmm`
Pallas kernel (DESIGN.md §14).

The padded batch shapes from :func:`~repro.models.gnn.graphdata.pad_graph`
(node and feature dims are 128-multiples) are exactly the MXU tiling the
kernel wants, so mean aggregation becomes one dense semiring SpMM per layer:
``agg = Adj @ H`` with ``Adj[dst, src] = w`` — the same kernel the query
engine uses for reachability hops, now on the training side.  A
``segment_sum`` fallback path is kept both for CPU speed and as the parity
twin (``tests/test_view_gnn.py`` asserts the two paths agree).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.block_spmm import block_spmm
from repro.models.common import Params, dense, dense_init
from repro.models.gnn.graphdata import GraphBatch


@dataclass(frozen=True)
class SAGEConfig:
    d_in: int = 11                # structural_features FEAT_DIM
    d_hidden: int = 128           # must be a 128-multiple for block_spmm
    n_classes: int = 8
    n_layers: int = 2
    use_block_spmm: bool = False  # route aggregation through the Pallas SpMM
    interpret: bool = False       # Pallas interpret mode (CPU tests only)


def init_params(key, cfg: SAGEConfig) -> Params:
    ks = jax.random.split(key, 2 * cfg.n_layers + 2)
    p: Params = {"enc": dense_init(ks[0], cfg.d_in, cfg.d_hidden, bias=True)}
    for i in range(cfg.n_layers):
        p[f"self{i}"] = dense_init(ks[2 * i + 1], cfg.d_hidden, cfg.d_hidden,
                                   bias=True)
        p[f"nbr{i}"] = dense_init(ks[2 * i + 2], cfg.d_hidden, cfg.d_hidden)
    p["head"] = dense_init(ks[-1], cfg.d_hidden, cfg.n_classes, bias=True)
    return p


def _aggregate(cfg: SAGEConfig, batch: GraphBatch, h: jax.Array
               ) -> jax.Array:
    """Mean of incoming neighbor messages: agg[i] = Σ_j w_ij h[j] / deg_i."""
    n = h.shape[0]
    w = (batch.edge_weight if batch.edge_weight is not None
         else jnp.ones(batch.edge_src.shape[0], jnp.float32))
    w = w * batch.edge_mask.astype(jnp.float32)
    if cfg.use_block_spmm:
        adj = jnp.zeros((n, n), jnp.float32).at[
            batch.edge_dst, batch.edge_src].add(w)
        tot = block_spmm(adj, h.astype(jnp.float32),
                         semiring="count", interpret=cfg.interpret)
        deg = jnp.sum(adj, axis=1, keepdims=True)
    else:
        msg = h[batch.edge_src] * w[:, None]
        tot = jax.ops.segment_sum(msg, batch.edge_dst, num_segments=n)
        deg = jax.ops.segment_sum(w, batch.edge_dst, num_segments=n)[:, None]
    return tot / jnp.maximum(deg, 1.0)


def embed(params: Params, cfg: SAGEConfig, batch: GraphBatch) -> jax.Array:
    """Node embeddings [N, d_hidden] (pre-classifier)."""
    h = jax.nn.relu(dense(params["enc"], batch.node_feat))
    h = h * batch.node_mask[:, None]
    for i in range(cfg.n_layers):
        agg = _aggregate(cfg, batch, h)
        h = jax.nn.relu(dense(params[f"self{i}"], h)
                        + dense(params[f"nbr{i}"], agg))
        h = h * batch.node_mask[:, None]
    return h


def forward(params: Params, cfg: SAGEConfig, batch: GraphBatch) -> jax.Array:
    """Per-node class logits [N, n_classes]."""
    return dense(params["head"], embed(params, cfg, batch))


def loss_fn(params: Params, cfg: SAGEConfig, batch: GraphBatch
            ) -> Tuple[jax.Array, jax.Array]:
    """Masked cross-entropy on node labels; returns (loss, accuracy)."""
    logits = forward(params, cfg, batch)
    labels = batch.labels % cfg.n_classes
    logp = jax.nn.log_softmax(logits, axis=-1)
    mask = batch.node_mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    loss = jnp.sum(nll * mask) / denom
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / denom
    return loss, acc
