"""Attention building blocks.

* ``chunked_attention`` — pure-JAX online-softmax over KV chunks via
  ``lax.scan``: differentiable, O(S·chunk) live memory (the training path;
  XLA keeps the logits tile-sized, the flash kernel is its serving twin).
* ``gqa_einsum_attention`` — GQA without materializing repeated KV heads
  (q reshaped to [B, Hkv, rep, S, D]).
* ``decode_attention_partial`` / ``combine_partials`` — split-KV
  (flash-decoding) decode: each KV shard produces (num, denom, max) partials
  that combine exactly via logsumexp; this is what shard_map reduces across
  the sequence-sharded KV cache for the 500k-context decode cell.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_logits(q, k):
    """q: [B,Hq,Sq,D], k: [B,Hkv,Sk,D] -> [B,Hq,Sq,Sk] without KV repeat."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    rep = Hq // Hkv
    qg = q.reshape(B, Hkv, rep, Sq, D)
    logits = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k)
    return logits.reshape(B, Hq, Sq, k.shape[2])


def _gqa_values(p, v):
    """p: [B,Hq,Sq,Sk], v: [B,Hkv,Sk,D] -> [B,Hq,Sq,D]."""
    B, Hq, Sq, Sk = p.shape
    Hkv = v.shape[1]
    rep = Hq // Hkv
    pg = p.reshape(B, Hkv, rep, Sq, Sk)
    out = jnp.einsum("bgrqk,bgkd->bgrqd", pg, v)
    return out.reshape(B, Hq, Sq, v.shape[3])


def gqa_einsum_attention(q, k, v, *, causal: bool = True) -> jax.Array:
    """Reference GQA attention (dense logits; small-S paths and oracles)."""
    D = q.shape[-1]
    logits = _gqa_logits(q, k).astype(jnp.float32) / (D ** 0.5)
    if causal:
        sq, sk = q.shape[-2], k.shape[-2]
        qi = jnp.arange(sq)[:, None]
        kj = jnp.arange(sk)[None, :]
        logits = jnp.where(kj <= qi + (sk - sq), logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return _gqa_values(p, v)


def chunked_attention(q, k, v, *, causal: bool = True,
                      chunk: int = 512, unroll: bool = False,
                      q_offset=None) -> jax.Array:
    """Online-softmax attention scanning KV chunks (train-path flash twin).

    q: [B,Hq,Sq,D], k/v: [B,Hkv,Sk,D]; Sk % chunk == 0.
    ``q_offset``: global position of q row 0 (context-parallel shards pass
    their slice offset; defaults to Sk - Sq, the decode alignment)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    assert Sk % chunk == 0, (Sk, chunk)
    nchunks = Sk // chunk
    scale = 1.0 / (D ** 0.5)
    offset = (Sk - Sq) if q_offset is None else q_offset

    kc = k.reshape(B, Hkv, nchunks, chunk, D).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(B, Hkv, nchunks, chunk, D).transpose(2, 0, 1, 3, 4)

    def step(carry, inp):
        m_prev, l_prev, acc_prev = carry
        idx, kb, vb = inp
        logits = _gqa_logits(q, kb).astype(jnp.float32) * scale  # [B,Hq,Sq,c]
        if causal:
            qi = jnp.arange(Sq)[:, None] + offset
            kj = idx * chunk + jnp.arange(chunk)[None, :]
            logits = jnp.where(kj <= qi, logits, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(logits - m_cur[..., None])
        l_cur = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc_prev * alpha[..., None] + _gqa_values(p.astype(v.dtype), vb
                                                        ).astype(jnp.float32)
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((B, Hq, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hq, Sq, D), jnp.float32)
    # remat per kv-chunk: the bwd pass recomputes each chunk's probability
    # tile instead of stacking [B,H,Sq,chunk] residuals for every chunk —
    # this is what makes long-sequence training fit (flash-style memory)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step), (m0, l0, a0), (jnp.arange(nchunks), kc, vc),
        unroll=nchunks if unroll else 1)
    safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe[..., None]).astype(q.dtype)


def context_parallel_attention(q, k, v, mesh, *, data_axes=("data",),
                               model_axis: str = "model",
                               causal: bool = True, chunk: int = 512,
                               unroll: bool = False) -> jax.Array:
    """Context-parallel attention: q/k/v sequence-sharded over the model axis.

    When head counts don't divide the model axis (yi-34b: 56 q / 8 kv heads
    on a 16-way axis), head-sharded attention degenerates to full replication
    (measured: 62GB/device peaks).  Instead each model-axis peer takes an
    S/mp query slice, all-gathers K/V once per layer (cheap: [B,Hkv,S,D]),
    and runs the chunked online-softmax locally with its global row offset.
    Backward emits the mirrored reduce-scatter automatically.
    """
    from jax.sharding import PartitionSpec as P
    B, Hq, S, D = q.shape
    mp = mesh.shape[model_axis]
    S_loc = S // mp
    dspec = data_axes[0] if len(data_axes) == 1 else data_axes

    def local(ql, kl, vl):
        m_idx = jax.lax.axis_index(model_axis)
        kf = jax.lax.all_gather(kl, model_axis, axis=2, tiled=True)
        vf = jax.lax.all_gather(vl, model_axis, axis=2, tiled=True)
        return chunked_attention(ql, kf, vf, causal=causal,
                                 chunk=min(chunk, S), unroll=unroll,
                                 q_offset=m_idx * S_loc)

    spec = P(dspec, None, model_axis, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


# ------------------------------------------------------------- decode paths

def decode_attention(q, k_cache, v_cache, kv_len) -> jax.Array:
    """One-token decode.  q: [B,Hq,D]; caches: [B,Hkv,S,D]; kv_len: [B]."""
    B, Hq, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = Hq // Hkv
    qg = q.reshape(B, Hkv, rep, D)
    logits = jnp.einsum("bgrd,bgsd->bgrs", qg, k_cache).astype(jnp.float32)
    logits = logits / (D ** 0.5)
    mask = jnp.arange(S)[None, None, None, :] < kv_len[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bgrs,bgsd->bgrd", p, v_cache)
    return out.reshape(B, Hq, D)


def decode_attention_partial(q, k_shard, v_shard, valid_mask
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-KV partial attention over one sequence shard of the cache.

    q: [B,Hq,D]; k/v_shard: [B,Hkv,Ss,D]; valid_mask: [B,Ss] bool.
    Returns (num [B,Hq,D], denom [B,Hq], max [B,Hq]) — exact flash-decoding
    partials that :func:`combine_partials` merges across shards.
    """
    B, Hq, D = q.shape
    Hkv = k_shard.shape[1]
    rep = Hq // Hkv
    qg = q.reshape(B, Hkv, rep, D)
    logits = jnp.einsum("bgrd,bgsd->bgrs", qg, k_shard).astype(jnp.float32)
    logits = logits / (D ** 0.5)
    logits = jnp.where(valid_mask[:, None, None, :], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)                         # [B,Hkv,rep]
    p = jnp.exp(logits - m[..., None])
    denom = jnp.sum(p, axis=-1)
    num = jnp.einsum("bgrs,bgsd->bgrd", p.astype(v_shard.dtype), v_shard
                     ).astype(jnp.float32)
    return (num.reshape(B, Hq, D), denom.reshape(B, Hq), m.reshape(B, Hq))


def combine_partials(num, denom, m, axis_name: str) -> jax.Array:
    """LSE-combine split-KV partials across a mesh axis (inside shard_map)."""
    m_glob = jax.lax.pmax(m, axis_name)
    scale = jnp.exp(m - m_glob)
    num_g = jax.lax.psum(num * scale[..., None], axis_name)
    den_g = jax.lax.psum(denom * scale, axis_name)
    safe = jnp.where(den_g == 0.0, 1.0, den_g)
    return num_g / safe[..., None]
