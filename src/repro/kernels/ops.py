"""jit'd public wrappers around the Pallas kernels (+ layout preparation)."""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.block_spmm import block_spmm as _block_spmm
from repro.kernels.flash_attention import flash_attention as _flash_attention
from repro.kernels.segment_agg import segment_multi_agg as _segment_multi_agg
from repro.utils import round_up


def block_spmm(F: jax.Array, A: jax.Array, col_mask: jax.Array | None = None,
               *, counting: bool = True, interpret: bool = False) -> jax.Array:
    """Semiring SpMM with automatic padding to MXU-aligned tiles."""
    S, K = F.shape
    _, N = A.shape
    Sp, Kp, Np = (max(round_up(S, 128), 128), max(round_up(K, 128), 128),
                  max(round_up(N, 128), 128))
    Fp = jnp.zeros((Sp, Kp), jnp.float32).at[:S, :K].set(F.astype(jnp.float32))
    Ap = jnp.zeros((Kp, Np), jnp.float32).at[:K, :N].set(A.astype(jnp.float32))
    mp = None
    if col_mask is not None:
        mp = jnp.zeros((Np,), jnp.float32).at[:N].set(
            col_mask.astype(jnp.float32))
    out = _block_spmm(Fp, Ap, mp, semiring="count" if counting else "bool",
                      interpret=interpret)
    return out[:S, :N]


def segment_multi_agg(msg: jax.Array, valid: jax.Array, *,
                      interpret: bool = False):
    """Fused PNA aggregators with padding to tile-aligned shapes."""
    N, W, D = msg.shape
    Np = max(round_up(N, 8), 8)
    Dp = max(round_up(D, 128), 128)
    msgp = jnp.zeros((Np, W, Dp), msg.dtype).at[:N, :, :D].set(msg)
    validp = jnp.zeros((Np, W), valid.dtype).at[:N].set(valid)
    outs = _segment_multi_agg(msgp, validp, interpret=interpret)
    return tuple(o[:N, :D] for o in outs)


def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False,
                    block_q: int = 128, block_k: int = 128):
    """GQA-aware flash attention: q [B,Hq,Sq,D], k/v [B,Hkv,Sk,D]."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    if Hkv != Hq:
        assert Hq % Hkv == 0, (Hq, Hkv)
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    bq = min(block_q, Sq)
    bk = min(block_k, k.shape[2])
    return _flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                            interpret=interpret)


def bucketize_messages(dst: np.ndarray, msg: np.ndarray, num_nodes: int,
                       width: int | None = None):
    """Host-side ELL bucketing: per-dst message rows padded to width W.

    Returns (bucketed [N, W, D], valid [N, W]).  The fused multi-agg kernel
    consumes this layout (see segment_agg.py).
    """
    dst = np.asarray(dst)
    msg = np.asarray(msg)
    deg = np.bincount(dst, minlength=num_nodes)
    W = int(width or max(int(deg.max(initial=0)), 1))
    D = msg.shape[1]
    out = np.zeros((num_nodes, W, D), msg.dtype)
    valid = np.zeros((num_nodes, W), bool)
    fill = np.zeros(num_nodes, np.int64)
    for e in range(dst.shape[0]):
        d = dst[e]
        k = fill[d]
        if k < W:
            out[d, k] = msg[e]
            valid[d, k] = True
            fill[d] += 1
    return out, valid
