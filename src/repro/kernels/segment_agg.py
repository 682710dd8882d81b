"""Fused multi-aggregator Pallas kernel — PNA's hot path.

PNA aggregates each node's neighbor messages with four reducers
(mean/max/min/std) before applying degree scalers.  The GPU realization is
four scatter-reduce passes; the TPU-native adaptation buckets neighbors into
a padded [N, W, D] layout (ELL-style) and computes all four reductions in a
single VMEM pass: sum, max, min and sum-of-squares are accumulated together,
then mean/std derive in the epilogue.  One read of the message tensor instead
of four.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _agg_kernel(msg_ref, valid_ref, mean_ref, max_ref, min_ref, std_ref,
                *, eps: float):
    m = msg_ref[...].astype(jnp.float32)          # [bn, W, bd]
    valid = valid_ref[...].astype(jnp.float32)    # [bn, W]
    v = valid[:, :, None]
    cnt = jnp.sum(valid, axis=1)[:, None]         # [bn, 1]
    safe = jnp.maximum(cnt, 1.0)
    s = jnp.sum(m * v, axis=1)
    mean = s / safe
    neg = jnp.float32(-3.4e38)
    pos = jnp.float32(3.4e38)
    mx = jnp.max(jnp.where(v > 0, m, neg), axis=1)
    mn = jnp.min(jnp.where(v > 0, m, pos), axis=1)
    nonempty = cnt > 0
    meansq = jnp.sum(m * m * v, axis=1) / safe
    std = jnp.sqrt(jnp.maximum(meansq - mean * mean, 0.0) + eps)
    mean_ref[...] = jnp.where(nonempty, mean, 0.0)
    max_ref[...] = jnp.where(nonempty, mx, 0.0)
    min_ref[...] = jnp.where(nonempty, mn, 0.0)
    std_ref[...] = jnp.where(nonempty, std, 0.0)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "eps",
                                             "interpret"))
def segment_multi_agg(msg: jax.Array, valid: jax.Array, *, block_n: int = 8,
                      block_d: int = 128, eps: float = 1e-5,
                      interpret: bool = False):
    """Fused (mean, max, min, std) over bucketed neighbor messages.

    msg:   [N, W, D]  padded neighbor messages
    valid: [N, W]     slot validity mask
    returns 4 arrays [N, D] (f32).
    """
    N, W, D = msg.shape
    assert valid.shape == (N, W)
    assert N % block_n == 0 and D % block_d == 0, (msg.shape, block_n, block_d)
    grid = (N // block_n, D // block_d)
    out = jax.ShapeDtypeStruct((N, D), jnp.float32)
    kernel = functools.partial(_agg_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, W, block_d), lambda i, j: (i, 0, j)),
            pl.BlockSpec((block_n, W), lambda i, j: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((block_n, block_d), lambda i, j: (i, j))] * 4,
        out_shape=[out] * 4,
        interpret=interpret,
    )(msg, valid)
