"""Per-kernel shape/dtype sweeps against the pure-jnp oracles.

Kernels default to compiling for the TPU; on the CPU every call here runs
the Pallas interpreter (``interpret=True``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------- block_spmm

@pytest.mark.parametrize("shape", [(8, 16, 12), (128, 128, 128),
                                   (100, 200, 150), (256, 384, 128)])
@pytest.mark.parametrize("semiring", ["count", "bool"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_block_spmm_matches_ref(shape, semiring, dtype):
    S, K, N = shape
    rng = np.random.default_rng(hash((S, K, N, semiring)) % 2 ** 31)
    F = jnp.asarray(rng.integers(0, 3, (S, K)), dtype)
    A = jnp.asarray((rng.random((K, N)) < 0.2).astype(np.float32), dtype)
    mask = jnp.asarray(rng.integers(0, 2, (N,)).astype(np.float32))
    got = ops.block_spmm(F, A, mask, counting=(semiring == "count"),
                         interpret=True)
    want = ref.block_spmm_ref(F, A, mask, semiring=semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_block_spmm_no_mask():
    rng = np.random.default_rng(0)
    F = jnp.asarray(rng.random((64, 64)), jnp.float32)
    A = jnp.asarray(rng.random((64, 64)), jnp.float32)
    got = ops.block_spmm(F, A, counting=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(F @ A), rtol=1e-5)


def test_block_spmm_hop_equivalence_with_executor():
    """The kernel computes exactly one executor hop on a dense adjacency."""
    from repro.core import ExecConfig, GraphBuilder, GraphSchema, PathExecutor
    from repro.core.parser import parse_query
    rng = np.random.default_rng(3)
    schema = GraphSchema()
    b = GraphBuilder(schema)
    n = 20
    for i in range(n):
        b.add_node("A")
    for u in range(n):
        for v in range(n):
            if u != v and rng.random() < 0.2:
                b.add_edge(u, v, "x")
    g = b.finalize()
    q = parse_query("MATCH (a:A)-[:x*1..2]->(b:A) RETURN a, b")
    res_plain = PathExecutor(g, schema, ExecConfig(backend="dense",
                                                   src_block=32)).run_query(q)
    res_kernel = PathExecutor(
        g, schema, ExecConfig(backend="dense", src_block=32,
                              use_pallas=True, interpret=True)).run_query(q)
    np.testing.assert_array_equal(res_plain.reach, res_kernel.reach)


# --------------------------------------------------------------- segment_agg

@pytest.mark.parametrize("shape", [(16, 4, 8), (64, 16, 128), (33, 7, 75)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_multi_agg_matches_ref(shape, dtype):
    N, W, D = shape
    rng = np.random.default_rng(hash(shape) % 2 ** 31)
    msg = jnp.asarray(rng.standard_normal((N, W, D)), dtype)
    valid = jnp.asarray(rng.random((N, W)) < 0.7)
    got = ops.segment_multi_agg(msg, valid, interpret=True)
    want = ref.segment_multi_agg_ref(msg.astype(jnp.float32), valid)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for g_, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w_),
                                   rtol=tol, atol=tol)


def test_segment_agg_empty_rows_are_zero():
    msg = jnp.ones((8, 4, 16), jnp.float32)
    valid = jnp.zeros((8, 4), bool)
    for out in ops.segment_multi_agg(msg, valid, interpret=True):
        np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_segment_agg_against_scatter_oracle():
    """Bucketed layout must agree with the segment_sum-style formulation."""
    import jax.ops as jops
    rng = np.random.default_rng(11)
    E, N, D = 200, 32, 16
    dst = rng.integers(0, N, E)
    msg = rng.standard_normal((E, D)).astype(np.float32)
    bucketed, valid = ops.bucketize_messages(dst, msg, N)
    mean_k, *_ = ops.segment_multi_agg(jnp.asarray(bucketed),
                                       jnp.asarray(valid), interpret=True)
    s = jops.segment_sum(jnp.asarray(msg), jnp.asarray(dst), N)
    cnt = jops.segment_sum(jnp.ones(E), jnp.asarray(dst), N)
    want = np.asarray(s) / np.maximum(np.asarray(cnt)[:, None], 1.0)
    np.testing.assert_allclose(np.asarray(mean_k), want, rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------- flash_attention

@pytest.mark.parametrize("shape", [
    (1, 2, 128, 64), (2, 4, 256, 128), (1, 1, 384, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(shape, causal, dtype):
    B, H, S, D = shape
    rng = np.random.default_rng(hash((shape, causal)) % 2 ** 31)
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype) * 0.5
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype) * 0.5
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
    got = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.mha_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                       v.astype(jnp.float32), causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_gqa_expansion():
    rng = np.random.default_rng(5)
    B, Hq, Hkv, S, D = 2, 8, 2, 128, 64
    q = jnp.asarray(rng.standard_normal((B, Hq, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Hkv, S, D)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    kr = jnp.repeat(k, Hq // Hkv, axis=1)
    vr = jnp.repeat(v, Hq // Hkv, axis=1)
    want = ref.mha_ref(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_decode_offset():
    """Sq < Sk: causal diagonal shifts (chunked decode semantics)."""
    rng = np.random.default_rng(6)
    B, H, Sq, Sk, D = 1, 2, 128, 384, 64
    q = jnp.asarray(rng.standard_normal((B, H, Sq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, Sk, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, Sk, D)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
