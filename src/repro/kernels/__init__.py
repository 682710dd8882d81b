"""Pallas TPU kernels for the compute hot paths.

  block_spmm      — blocked semiring SpMM (MV4PG reachability hops; GNN SpMM)
  segment_agg     — fused PNA multi-aggregator over bucketed neighbors
  flash_attention — fused online-softmax attention (LM prefill/decode)

Each kernel ships with a pure-jnp oracle in ``ref.py`` and a jit'd public
wrapper in ``ops.py``.  Every wrapper compiles for the TPU by default; the
CPU tests sweep shapes/dtypes with ``interpret=True``.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
