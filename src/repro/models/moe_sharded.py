"""Explicit expert-parallel MoE layer (shard_map + all-to-all).

XLA SPMD cannot partition a data-dependent scatter (the token->expert
dispatch); it replicates the dispatch buffers and the layer degenerates into
all-gather soup (results/perf_log.md).  This module writes the collective
schedule by hand inside shard_map:

  1. tokens are already sharded over the data axes; each model-axis peer
     additionally takes a distinct 1/mp slice of the local tokens (sequence
     parallelism inside the layer — no duplicate routing work),
  2. local top-k routing + sort-based dispatch into [E, C_loc, D]
     (only [T_loc*K]-sized index arrays are materialized),
  3. all-to-all over the model axis: each device keeps its E/mp experts,
     receiving every peer's rows for them -> [E_l, mp*C_loc, D],
  4. expert weights are ZeRO-3-sharded over data and all-gathered
     just-in-time (transient = this layer's E_l experts only),
  5. grouped expert GEMMs, reverse all-to-all, local combine, all-gather of
     the token slices over the model axis.

Differentiable end-to-end: all_to_all/all_gather/dynamic-slice have exact
transposes, so the backward pass emits the mirrored collective schedule.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Params
from repro.models.moe import MoEConfig


def _local_dispatch(xt: jax.Array, router_w: jax.Array, cfg: MoEConfig,
                    C_loc: int):
    """Local routing + sort dispatch.  xt: [T_loc, D] -> buf [E, C_loc, D]."""
    T_loc, D = xt.shape
    E, K = cfg.e_alloc, cfg.top_k
    from repro.models.moe import _mask_padded
    logits = _mask_padded((xt @ router_w).astype(jnp.float32), cfg)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    TK = T_loc * K
    flat_e = gate_idx.reshape(TK)
    flat_t = jnp.arange(TK, dtype=jnp.int32) // K
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(TK, dtype=jnp.int32) - starts[sorted_e]
    keep = pos < C_loc
    slot = jnp.where(keep, sorted_e * C_loc + pos, E * C_loc - 1)
    gathered = jnp.where(keep[:, None], xt[flat_t[order]], 0)
    buf = jnp.zeros((E * C_loc, D), xt.dtype).at[slot].add(gathered)
    meta = (order, slot, keep, flat_t, gate_vals.reshape(TK), counts, probs)
    return buf.reshape(E, C_loc, D), meta


def _aux(meta, cfg, T_loc, data_axes, model_axis):
    counts, probs = meta[-2], meta[-1]
    E, K = cfg.n_experts, cfg.top_k  # aux over REAL experts only
    frac = counts.astype(jnp.float32) / jnp.float32(T_loc * K)
    aux = cfg.router_aux_weight * E * jnp.sum(
        frac * jnp.mean(probs, axis=0)) * K
    for a in data_axes:
        aux = jax.lax.pmean(aux, a)
    return jax.lax.pmean(aux, model_axis)


def moe_apply_sharded(p: Params, x: jax.Array, cfg: MoEConfig, mesh,
                      data_axes: Tuple[str, ...] = ("data",),
                      model_axis: str = "model"):
    """Drop-in replacement for moe_apply under a (data, model) mesh.

    x: [B, S, D] (batch sharded over ``data_axes``, replicated over model).
    Expert weights sharded P(model, data, None) per launch/sharding.py.
    """
    B, S, D = x.shape
    E, K = cfg.e_alloc, cfg.top_k
    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    mp = mesh.shape[model_axis]
    assert E % mp == 0, (E, mp)
    T_l = (B // dp) * S
    assert T_l % mp == 0, (T_l, mp)
    T_loc = T_l // mp
    C_loc = max(int(T_loc * K * cfg.capacity_factor / E), 4)
    dspec = data_axes[0] if len(data_axes) == 1 else data_axes

    def local(xl, router_w, wi, wg, wo, shared):
        # xl: [B/dp, S(/mp), D]; wi/wg: [E_l, D/dp, F]; wo: [E_l, F/dp, D]
        if cfg.seq_sharded:
            # sequence-parallel input: xl IS this peer's token slice
            xt_m = xl.reshape(T_loc, D)
            xt = None
        else:
            xt = xl.reshape(T_l, D)
            m_idx = jax.lax.axis_index(model_axis)
            xt_m = jax.lax.dynamic_slice_in_dim(xt, m_idx * T_loc, T_loc, 0)
        buf, meta = _local_dispatch(xt_m, router_w, cfg, C_loc)
        # [E, C_loc, D] -> [E_l, mp*C_loc, D]: keep my experts, all peers' rows
        xe = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                                tiled=True)
        # ZeRO-3 just-in-time weight gather over the data axes
        wi_f, wg_f, wo_f = wi, wg, wo
        for a in reversed(data_axes):
            wi_f = jax.lax.all_gather(wi_f, a, axis=1, tiled=True)
            wg_f = jax.lax.all_gather(wg_f, a, axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo_f, a, axis=1, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", xe, wi_f)
        g = jnp.einsum("ecd,edf->ecf", xe, wg_f)
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wo_f)
        # reverse exchange: [E_l, mp*C_loc, D] -> [E, C_loc, D] (my tokens)
        ye = jax.lax.all_to_all(ye, model_axis, split_axis=1, concat_axis=0,
                                tiled=True)
        order, slot, keep, flat_t, flat_g, counts, probs = meta
        contrib = ye.reshape(E * C_loc, D)[slot] \
            * (flat_g[order] * keep)[:, None].astype(ye.dtype)
        out_m = jnp.zeros((T_loc, D), xl.dtype).at[flat_t[order]].add(contrib)
        if cfg.seq_sharded:
            # stay sequence-sharded: no reassembly collective at all
            if shared is not None:
                sh_wi, sh_wg, sh_wo = shared
                hs = jax.nn.silu(xt_m @ sh_wg) * (xt_m @ sh_wi)
                out_m = out_m + hs @ sh_wo
            return (out_m.reshape(B // dp, S // mp, D),
                    _aux(meta, cfg, T_loc, data_axes, model_axis))
        # reassemble the token slices across the model axis
        out = jax.lax.all_gather(out_m, model_axis, axis=0, tiled=True)
        if shared is not None:
            sh_wi, sh_wg, sh_wo = shared
            hs = jax.nn.silu(xt @ sh_wg) * (xt @ sh_wi)
            out = out + hs @ sh_wo
        return (out.reshape(B // dp, S, D),
                _aux(meta, cfg, T_loc, data_axes, model_axis))

    shared_in = None
    shared_specs = None
    if "shared" in p:
        shared_in = (p["shared"]["wi"], p["shared"]["wg"], p["shared"]["wo"])
        shared_specs = (P(), P(), P())
    x_spec = (P(dspec, model_axis, None) if cfg.seq_sharded
              else P(dspec, None, None))
    out, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, P(),
                  P(model_axis, dspec, None), P(model_axis, dspec, None),
                  P(model_axis, dspec, None), shared_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"]["w"], p["wi"], p["wg"], p["wo"], shared_in)
    return out, aux
