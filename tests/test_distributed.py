"""Multi-device correctness of the shard_map layers.

Runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the main test process must keep 1 device), and asserts that the explicit
collective implementations match their single-device references:

  * shard_map expert-parallel MoE  == pjit sort-dispatch MoE
  * dst-partitioned PNA aggregation == plain segment-op PNA
  * context-parallel attention      == chunked attention
  * int8-compressed DP psum ~= plain mean (error-feedback residual bounded)
"""
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

mesh = jax.make_mesh((2, 4), ("data", "model"))

# ---------------- MoE sharded == reference --------------------------------
from repro.models.moe import MoEConfig, moe_apply, moe_init
cfg = MoEConfig(n_experts=8, top_k=2, d_ff_expert=16, capacity_factor=8.0)
p = moe_init(jax.random.PRNGKey(0), 32, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
ref, _ = moe_apply(p, x, cfg)

cfg_sh = dataclasses.replace(cfg, mesh=mesh, data_axes=("data",),
                             model_axis="model")
# shard expert weights as the launch rules do
pshard = dict(p)
with mesh:
    sh = NamedSharding(mesh, P("model", "data", None))
    pshard = {
        "router": {"w": jax.device_put(p["router"]["w"],
                                       NamedSharding(mesh, P()))},
        "wi": jax.device_put(p["wi"], sh),
        "wg": jax.device_put(p["wg"], sh),
        "wo": jax.device_put(p["wo"], sh),
    }
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    out, _ = jax.jit(lambda pp, xx: moe_apply(pp, xx, cfg_sh))(pshard, xs)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                           atol=2e-4)
print("MOE_SHARDED_OK")

# ---------------- PNA sharded == reference --------------------------------
from repro.models.gnn import pna
from repro.models.gnn.graphdata import GraphBatch
from repro.graphops.distributed import partition_edges_by_dst
rng = np.random.default_rng(0)
N, D = 64, 16
E = 256
src = rng.integers(0, N, E).astype(np.int32)
dst = rng.integers(0, N, E).astype(np.int32)
feat = rng.standard_normal((N, D)).astype(np.float32)
labels = rng.integers(0, 4, N).astype(np.int32)

cfg_p = pna.PNAConfig(n_layers=2, d_hidden=16, d_in=D, n_classes=4,
                      avg_degree=4.0)
params = pna.init_params(jax.random.PRNGKey(2), cfg_p)
gb = GraphBatch(node_feat=jnp.asarray(feat), edge_src=jnp.asarray(src),
                edge_dst=jnp.asarray(dst), edge_mask=jnp.ones(E, bool),
                node_mask=jnp.ones(N, bool),
                graph_id=jnp.zeros(N, jnp.int32), positions=None,
                labels=jnp.asarray(labels))
ref_out = pna.forward(params, gb, cfg_p)

perm, emask, _ = partition_edges_by_dst(src, dst, N, 8)
gb_sh = GraphBatch(
    node_feat=jnp.asarray(feat), edge_src=jnp.asarray(src[perm]),
    edge_dst=jnp.asarray(dst[perm]), edge_mask=jnp.asarray(emask),
    node_mask=jnp.ones(N, bool), graph_id=jnp.zeros(N, jnp.int32),
    positions=None, labels=jnp.asarray(labels))
cfg_sh2 = dataclasses.replace(cfg_p, mesh=mesh,
                              shard_axes=("data", "model"))
with mesh:
    out_sh = jax.jit(lambda pp, g: pna.forward(pp, g, cfg_sh2))(params, gb_sh)
np.testing.assert_allclose(np.asarray(out_sh), np.asarray(ref_out),
                           rtol=2e-4, atol=2e-4)
print("PNA_SHARDED_OK")

# ---------------- context-parallel attention == chunked -------------------
from repro.models import attention as attn
q = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 32, 8))
k = jax.random.normal(jax.random.PRNGKey(4), (2, 2, 32, 8))
v = jax.random.normal(jax.random.PRNGKey(5), (2, 2, 32, 8))
ref_a = attn.chunked_attention(q, k, v, causal=True, chunk=8)
with mesh:
    got_a = jax.jit(lambda a, b, c: attn.context_parallel_attention(
        a, b, c, mesh, data_axes=("data",), causal=True, chunk=8))(q, k, v)
np.testing.assert_allclose(np.asarray(got_a), np.asarray(ref_a), rtol=2e-4,
                           atol=2e-4)
print("CP_ATTENTION_OK")

# ---------------- compressed DP reduce ------------------------------------
from repro.train.compression import compressed_psum
from jax import shard_map
def red(x):
    val, resid = compressed_psum(x, "data")
    return val, resid
xs = jax.random.normal(jax.random.PRNGKey(6), (8, 64))
with mesh:
    val, resid = jax.jit(shard_map(
        red, mesh=mesh, in_specs=P("data", None),
        out_specs=(P("data", None), P("data", None)),
        check_vma=False))(xs)
# mean over 2 shards: compare against exact mean within int8 tolerance
exact = (np.asarray(xs[:4]) + np.asarray(xs[4:])) / 2.0
err = np.abs(np.asarray(val[:4]) - exact).max()
amax = np.abs(np.asarray(xs)).max()
assert err <= 2.1 * amax / 127.0, (err, amax / 127.0)
print("COMPRESSED_PSUM_OK")
"""


@pytest.mark.parametrize("marker", ["MOE_SHARDED_OK", "PNA_SHARDED_OK",
                                    "CP_ATTENTION_OK", "COMPRESSED_PSUM_OK"])
def test_multidevice_shard_map_layers(marker, _cache={}):
    if "out" not in _cache:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                         "src")
        proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                              capture_output=True, text=True, timeout=600)
        _cache["out"] = proc.stdout + proc.stderr
        _cache["rc"] = proc.returncode
    assert _cache["rc"] == 0, _cache["out"][-3000:]
    assert marker in _cache["out"], _cache["out"][-3000:]
