"""Train-step builders: grad accumulation, mixed precision, DP compression.

``make_train_step`` produces a jit-able ``(state, batch) -> (state, metrics)``
for any ``loss_fn(params, batch) -> scalar``.  Gradient accumulation scans
microbatches (constant memory); the compressed-DP variant wraps the gradient
reduction in shard_map with int8 + error feedback.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.train import optimizer as opt
from repro.train.compression import (
    compressed_grad_reduce, init_error_feedback,
)

Params = Any


class TrainState(NamedTuple):
    params: Params
    opt_state: opt.AdamState
    ef: Optional[Params] = None      # error feedback (compressed DP only)


def init_train_state(params: Params, cfg: opt.AdamWConfig,
                     compressed_dp: bool = False) -> TrainState:
    return TrainState(
        params=params,
        opt_state=opt.init_state(params, cfg),
        ef=init_error_feedback(params) if compressed_dp else None,
    )


def make_train_step(loss_fn: Callable[[Params, Any], jax.Array],
                    cfg: opt.AdamWConfig,
                    grad_accum: int = 1) -> Callable:
    """Standard train step (XLA SPMD handles cross-device reduction)."""

    def step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        if grad_accum == 1:
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        else:
            def micro(carry, mb):
                acc_loss, acc_g = carry
                l, g = jax.value_and_grad(loss_fn)(state.params, mb)
                acc_g = jax.tree_util.tree_map(jnp.add, acc_g, g)
                return (acc_loss + l, acc_g), None
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            mbs = jax.tree_util.tree_map(
                lambda x: x.reshape((grad_accum, -1) + x.shape[1:]), batch)
            (loss, grads), _ = jax.lax.scan(micro, (0.0, zeros), mbs)
            loss = loss / grad_accum
            grads = jax.tree_util.tree_map(lambda g: g / grad_accum, grads)
        newp, new_opt, info = opt.apply_updates(state.params, grads,
                                                state.opt_state, cfg)
        metrics = {"loss": loss, **info}
        return TrainState(newp, new_opt, state.ef), metrics

    return step


def make_compressed_dp_step(loss_fn, cfg: opt.AdamWConfig, mesh,
                            data_axis: str = "data") -> Callable:
    """Train step with explicit int8-compressed DP gradient reduction.

    Used via shard_map over the data axis; params replicated across that
    axis, batch sharded.  Demonstrated at small scale in tests; the
    compression halves DP reduce bytes vs bf16 (see EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def local_step(params, opt_state, ef, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads, new_ef = compressed_grad_reduce(grads, ef, data_axis)
        loss = jax.lax.pmean(loss, data_axis)
        newp, new_opt, info = opt.apply_updates(params, grads, opt_state, cfg)
        return newp, new_opt, new_ef, {"loss": loss, **info}

    def step(state: TrainState, batch):
        rep = P()          # params/opt replicated over the data axis
        newp, new_opt, new_ef, metrics = shard_map(
            local_step, mesh=mesh,
            in_specs=(rep, rep, rep, P(data_axis)),
            out_specs=(rep, rep, rep, rep),
            check_vma=False,
        )(state.params, state.opt_state, state.ef, batch)
        return TrainState(newp, new_opt, new_ef), metrics

    return step
