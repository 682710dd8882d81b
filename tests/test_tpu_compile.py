"""Compile-only checks of the graph path's kernels for a TPU v5e.

The TPU compiler is installed with jax, so these compile for a described
``v5e:2x2`` topology without a chip attached.  Nothing runs: they catch what
the chip's compiler refuses (tiling, fast-memory limits, shard_map specs)
before any chip time is spent.  The topology is described inside a fixture,
so collecting this file never loads the TPU library; where it cannot be
described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

# an SNB arena at the generator's default sizes (data/synthetic.snb_like,
# slack 4.0): node_cap 63,488 and 176,128 edge slots
NODE_CAP = 63488
EDGE_CAP = 176128
BLOCK = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("counting", [False, True], ids=["bool", "count"])
def test_block_spmm_compiles_to_a_tpu_kernel(one_chip, counting):
    from repro.kernels import ops
    F = _spec((BLOCK, 4096), jnp.float32, one_chip)
    A = _spec((4096, 4096), jnp.float32, one_chip)
    fn = jax.jit(lambda f, a: ops.block_spmm(f, a, counting=counting))
    compiled = fn.lower(F, A).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_segment_hop_compiles_at_snb_width(one_chip):
    from repro.core.executor import _hop_segment
    F = _spec((BLOCK, NODE_CAP), jnp.bool_, one_chip)
    ids = _spec((EDGE_CAP,), jnp.int32, one_chip)
    mask = _spec((EDGE_CAP,), jnp.bool_, one_chip)
    compiled = _hop_segment.lower(F, ids, ids, mask, ids, counting=False,
                                  reverse=False).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == BLOCK * NODE_CAP


def test_sharded_hop_compiles_on_a_2x2_mesh(topo):
    """One halo exchange + local segment hop under shard_map over four
    chips, with the scalar convergence flag leaving as ``P()`` (the spec
    the sharded plans use)."""
    from repro.core.executor import _hop_segment_local
    shards = 4
    n_loc = NODE_CAP // shards
    e_loc = EDGE_CAP // shards
    mesh = Mesh(np.asarray(topo.devices).reshape(shards, 1),
                ("data", "model"))

    def body(F, a, b_local, emask, ew):
        F_full = jax.lax.all_gather(F, "data", axis=1, tiled=True)
        out = _hop_segment_local(F_full, a[0], b_local[0], emask[0], ew[0],
                                 counting=False, n_loc=n_loc)
        active = jax.lax.psum(jnp.sum(out.astype(jnp.int32)), "data")
        return out, active > 0

    edge = P("data", None)
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, "data"), edge, edge, edge, edge),
        out_specs=(P(None, "data"), P()), check_vma=False))
    sh = lambda spec: jax.sharding.NamedSharding(mesh, spec)  # noqa: E731
    F = _spec((BLOCK, NODE_CAP), jnp.bool_, sh(P(None, "data")))
    ids = _spec((shards, e_loc), jnp.int32, sh(edge))
    mask = _spec((shards, e_loc), jnp.bool_, sh(edge))
    compiled = fn.lower(F, ids, ids, mask, ids).compile()
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
