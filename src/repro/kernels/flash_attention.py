"""Flash-attention forward Pallas kernel (TPU target, interpret-validated).

Online-softmax tiling: grid (B*H, Sq/bq, Sk/bk) with running (m, l, acc)
scratch carried across the kv grid dimension; causal blocks that lie fully
above the diagonal are skipped.  The decode offset (Sk > Sq) shifts the
causal diagonal so the same kernel serves prefill and chunked decode.

Training uses the pure-JAX chunked-scan attention in ``models/attention.py``
(differentiable, O(S) memory under remat); this kernel is the serving/prefill
hot path.  Backward kernel: see EXPERIMENTS.md §Perf (future iteration).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, nk: int, block_q: int, block_k: int, scale: float,
                  causal: bool, offset: int):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32)                     # [bq, d]
        k = k_ref[0].astype(jnp.float32)                     # [bk, d]
        v = v_ref[0].astype(jnp.float32)                     # [bk, d]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + offset
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(p, v, preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    if causal:
        # skip kv blocks strictly above the (offset-shifted) diagonal
        q_max = (iq + 1) * block_q - 1 + offset
        k_min = ik * block_k
        pl.when(k_min <= q_max)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False) -> jax.Array:
    """Fused attention forward.

    q: [B, H, Sq, D]; k, v: [B, H, Sk, D] (same H — expand GQA outside).
    Sk >= Sq; the causal diagonal is shifted by Sk - Sq (decode semantics).
    """
    B, H, Sq, D = q.shape
    _, _, Sk, _ = k.shape
    assert k.shape == (B, H, Sk, D) and v.shape == (B, H, Sk, D)
    assert Sq % block_q == 0 and Sk % block_k == 0, (q.shape, k.shape)
    offset = Sk - Sq
    scale = 1.0 / (D ** 0.5)
    nq, nk = Sq // block_q, Sk // block_k

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)

    from jax.experimental.pallas import tpu as pltpu  # scratch memory spaces

    kernel = functools.partial(
        _flash_kernel, nk=nk, block_q=block_q, block_k=block_k, scale=scale,
        causal=causal, offset=offset)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),     # running numerator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, D)
