"""Blocked semiring SpMM Pallas kernel — the MV4PG reachability hot path.

One variable-length-edge hop over a source-block frontier is
``F' = semiring(F @ A) ⊙ colmask`` where ``A`` is a label-masked adjacency
tile and ``colmask`` is the next node pattern's label mask.  The GPU/GDBMS
realization is pointer-chasing; the TPU-native adaptation tiles sources and
nodes into MXU-aligned dense blocks and fuses the semiring epilogue
(boolean clamp) and the node-label filter into the matmul:

  grid (i, j, k):   out[i, j] += F[i, k] @ A[k, j]        (MXU)
  at k == K-1:      out = min(out, 1) if bool; out *= colmask[j]   (VPU)

Counting uses f32 accumulation — walk counts are exact up to 2^24, which
exceeds any view multiplicity the maintenance engine stores (int32 weights).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _spmm_kernel(f_ref, a_ref, m_ref, o_ref, *, nk: int, semiring: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(f_ref[...].astype(jnp.float32),
                          a_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = o_ref[...]
        if semiring == "bool":
            acc = jnp.minimum(acc, 1.0)
        o_ref[...] = acc * m_ref[...]


@functools.partial(jax.jit, static_argnames=("semiring", "block_s", "block_n",
                                             "block_k", "interpret"))
def block_spmm(F: jax.Array, A: jax.Array, col_mask: jax.Array | None = None,
               *, semiring: str = "count", block_s: int = 128,
               block_n: int = 128, block_k: int = 128,
               interpret: bool = False) -> jax.Array:
    """``semiring(F @ A) * col_mask`` with explicit VMEM tiling.

    F: [S, K] frontier counts/bool (any float/int dtype)
    A: [K, N] adjacency tile (label-masked, weighted)
    col_mask: [N] destination node-label mask (defaults to all-ones)
    """
    S, K = F.shape
    K2, N = A.shape
    assert K == K2, (F.shape, A.shape)
    assert S % block_s == 0 and N % block_n == 0 and K % block_k == 0, (
        f"shapes ({S},{K},{N}) must tile by ({block_s},{block_k},{block_n})")
    if col_mask is None:
        col_mask = jnp.ones((N,), jnp.float32)
    mask2d = col_mask.astype(jnp.float32).reshape(1, N)
    nk = K // block_k
    grid = (S // block_s, N // block_n, nk)
    kernel = functools.partial(_spmm_kernel, nk=nk, semiring=semiring)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_s, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((S, N), jnp.float32),
        interpret=interpret,
    )(F, A, mask2d)
