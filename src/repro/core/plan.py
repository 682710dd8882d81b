"""Compiled query plans + session plan cache: fingerprint → rewrite → physical.

The paper's bet (MV4PG §V) is that workloads repeat *patterns*, so duplicate
data work should be paid once and materialized.  This module makes the same
bet about *query compilation*: the read path used to re-parse, re-run the
Algorithm-3 rewrite against every view, and re-walk the hop list in Python
(per-hop jit dispatch + per-hop host syncs for DBHit/Rows) on every call.
A :class:`QueryPlanner` compiles a query once into a cached
:class:`CompiledPlan` and repeats cost only array work:

1. **normalize + fingerprint** — :func:`repro.core.parser.canonicalize_query`
   erases variable spelling and resolves labels to schema ids, producing a
   :class:`~repro.core.pattern.QueryFingerprint` cache key;
2. **memoized rewrite** — the Algorithm-3 rewrite result is cached per
   ``(fingerprint, view-set generation)``; the generation is bumped by
   ``create_view``/``drop_view``, so the rewrite runs once per distinct query
   shape per view catalog, not once per call;
3. **physical planning** — each hop picks its backend (``segment`` scatter,
   ``dense`` MXU matmul, or the Pallas ``block_spmm`` kernel) from cached
   per-label edge counts (the same |E_L| statistic the paper's Eq. 1–2
   bookkeeping maintains) instead of one global ``ExecConfig.backend``;
4. **fused execution** — the whole hop list runs as **one jitted program per
   (plan, shape)**, with DBHit/Rows accumulated device-side and synced once
   per query instead of once per hop.

Worked example (3-hop SNB query, ROOT_POST view materialized)::

    sess.create_view("CREATE VIEW ROOT_POST AS (CONSTRUCT (c)-[r:ROOT_POST]"
                     "->(p) MATCH (c:Comment)-[:replyOf*..]->(p:Post))")
    sess.query("MATCH (c:Comment)-[:replyOf*..]->(p:Post)-[:hasTag]->(t:Tag)"
               " RETURN c, t")

    call 1 (cold): parse → fingerprint F → rewrite miss → Algorithm 3 splices
      ROOT_POST, caches (F, gen=1) → physical plan: hop1 = segment over the
      ROOT_POST slice, hop2 = segment over hasTag (both too sparse for dense)
      → jit-compile the 2-hop fused program → execute.
    call 2+ (warm): parse → fingerprint F → plan-cache hit (epochs, caps and
      generation all unchanged) → execute the cached program.  Rewrite and
      planning cost ≈ 0; DBHit/Rows sync once.

**Invalidation.** A cached plan revalidates against exactly the machinery the
:class:`~repro.core.executor.ExecEngine` already uses: the
:class:`~repro.core.graph.LabelEpochs` epoch of every edge label the plan
touches (wildcard hops key off the base generation), the epochs'
``reset_generation`` (full invalidations: external graph swaps, node-arena
growth), the node capacity (frontier/adjacency shapes), and — for plans whose
rewrite consulted the view catalog — the session's view-set generation.  A
stale plan is recompiled and counted in ``plan_misses``; operand arrays are
re-fetched from the engine on *every* execution, so a valid plan always runs
against current data.

DBHit/Rows parity with the unfused per-hop executor is exact: the fused
program reuses the executor's own ``_hop_segment``/``_hop_dense``/
``_hop_cost``/``_active_rows`` jitted kernels in the same order, and hops a
host loop would have skipped via early exit contribute exactly zero to both
counters (empty frontiers expand to nothing).  Device-side counters are
int32; per-query totals beyond 2^31 storage touches would need the per-hop
host accumulation of :class:`~repro.core.executor.PathExecutor`.

Known trade-off: bounded hop ranges unroll fully into the trace, so a
``*1..m`` hop always executes ``m`` device hops even when the frontier
empties early (the unfused boolean path host-breaks at the first empty
frontier).  Results and metrics are unaffected — empty-frontier hops are
exact no-ops — but queries whose ``max_hops`` far exceeds the graph diameter
pay trace size and device work for the dead tail; keep such ranges unbounded
(``*n..``) instead, which compiles to a converging ``while_loop``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.executor import (
    ExecConfig, ExecEngine, Metrics, ReachResult, _active_rows_per_source,
    _hop_cost_per_source, _hop_cost_rows, _hop_dense, _hop_segment,
    _hop_segment_local, _hop_segment_rows, _hop_segment_rows_local,
)
from repro.core.graph import node_pred_mask
from repro.core.parser import query_fingerprint
from repro.core.pattern import (
    Direction, PathPattern, PropPred, Query, QueryFingerprint, _cmp,
    normalize_preds,
)
from repro.core.schema import GraphSchema, NO_LABEL
from repro.utils import INF_HOPS, round_up
from repro.utils.trace import count, span, to_host, value


# counter prefix of the fused programs' outputs copied to the host
PLAN_PULLS = "plan.rows_to_host"
# fused-program blocks dispatched, and at each wait for a block the blocks
# dispatched after it (over ServeStats.blocks: the mean pipeline depth)
BLOCKS_LAUNCHED = "plan.blocks_launched"
BLOCKS_AHEAD = "plan.blocks_ahead"


# ---------------------------------------------------------------------------
# physical plan IR
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ExpandStep:
    """One relationship expansion: hop range over one edge label.

    ``preds`` is the rel's normalized property-predicate conjunction; it is
    compiled away into the hop's edge mask / adjacency (the engine caches the
    predicate-filtered operands per (label, preds)), so the traced program is
    identical to the predicate-free one — predicates change operands, not
    structure."""

    label_id: int
    reverses: Tuple[bool, ...]      # per-direction reverse flags (BOTH = 2)
    min_hops: int
    max_hops: int                   # INF_HOPS for unbounded closure
    backend: str                    # "segment" | "dense" | "pallas"
    preds: Tuple[PropPred, ...] = ()


@dataclass(frozen=True)
class FilterStep:
    """Node label/key/predicate mask applied after an expansion.

    Node predicates are fused *into the trace* (masks over the node property
    columns passed as operands): node props have no engine-side epoch
    tracking, so baking values into cached state would go stale on property
    writes — operands are re-fetched per execution instead."""

    label_id: int
    key: Optional[int]
    preds: Tuple[PropPred, ...] = ()


def _choose_backend(engine: ExecEngine, cfg: ExecConfig, label_id: int) -> str:
    """Per-hop physical backend from cached degree/selectivity stats.

    Cost rule: a segment hop costs O(E_label) scatter work per frontier
    block; a dense hop costs O(node_cap^2) MXU work but wins once the label's
    adjacency is dense enough to keep the MXU busy.  We go dense (Pallas if
    enabled) when E_label / node_cap^2 >= ``cfg.dense_density`` and the tile
    fits (node_cap <= ``cfg.dense_node_limit``); ``cfg.plan_backend`` forces
    a specific backend when not "auto".  Above the node limit "auto" and the
    legacy ``backend="dense"`` override both stay on segment hops: a dense
    ``[node_cap, node_cap]`` tile at an SNB-sized arena (63,488 nodes) is
    16 GB.  A forced dense/pallas backend there fails in
    :meth:`ExecEngine.adj` instead.
    """
    if cfg.data_shards > 1:
        # sharded execution partitions the per-label compact slices across
        # the device mesh; dense/pallas hops would need replicated [N, N]
        # adjacency tiles per shard, defeating the partition — every hop of
        # a sharded plan is a segment hop (DESIGN.md §12)
        return "segment"
    mode = cfg.plan_backend
    if mode and mode != "auto":
        return mode
    n = engine.g.node_cap
    if n > cfg.dense_node_limit:
        return "segment"
    if cfg.backend == "dense":
        # legacy global override: sessions configured with the unfused
        # executor's backend="dense" (+ use_pallas) keep forcing the dense
        # path; only the default "segment" defers to the cost model
        return "pallas" if cfg.use_pallas else "dense"
    e = engine.label_edge_count(label_id)
    if e >= cfg.dense_density * n * n:
        return "pallas" if cfg.use_pallas else "dense"
    return "segment"


def _cfg_snapshot(cfg: ExecConfig) -> tuple:
    """The ExecConfig fields a compiled plan's trace or execution depends on;
    plans revalidate against it so in-place cfg mutation takes effect on the
    next query (as it did with the per-call unfused executor)."""
    return (cfg.plan_backend, cfg.backend, cfg.use_pallas, cfg.interpret,
            cfg.collect_metrics, cfg.max_closure_iters, cfg.src_block,
            cfg.dense_node_limit, cfg.dense_density, cfg.data_shards)


def block_sizes(rows: int, blk: int, adaptive: bool) -> List[int]:
    """Frontier-block launch plan for ``rows`` packed source rows.

    Fixed mode (the per-query read path) pads to whole ``blk`` blocks, at
    least one — the historical behavior every existing baseline was measured
    under.  Adaptive mode (the serve packing path) sizes a sub-block batch to
    the next power of two >= rows (min 8, capped at ``blk``), so a point-
    client group of 8 rows launches an 8-slot block instead of padding to
    256; batches larger than one block keep full ``blk`` blocks.  The
    power-of-two ladder bounds jit re-specialization to <= 6 small shapes.
    """
    if not adaptive or rows >= blk:
        r_pad = max(round_up(max(rows, 1), blk), blk)
        return [blk] * (r_pad // blk)
    b = 8
    while b < rows:
        b *= 2
    return [min(b, blk)]


def _scope_name(max_hops: int, i: int) -> str:
    """Name scope of the ``i``-th expand step (from 1) of a fused program:
    ``hop<i>`` for a bounded hop range, ``closure<i>`` for an unbounded one,
    so the device ops of a trace name the step they ran for."""
    return f"{'closure' if max_hops == INF_HOPS else 'hop'}{i}"


def _start_copies(out: tuple) -> tuple:
    """Start the device-to-host copy of one dispatched block's outputs, so it
    runs while later blocks compute, and count the block as launched."""
    for x in out:
        x.copy_to_host_async()
    count(BLOCKS_LAUNCHED)
    return out


def _rows_to_host(outs: Sequence[tuple], first_block: int, R: int,
                  node_cap: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Bring the launched blocks' ``(F, db, rows, ok)`` to the host: per
    block, wait for its outputs and take its copy (started at dispatch),
    then concatenate.  ``first_block`` is the process-wide launch number of
    ``outs[0]``; each wait counts the blocks launched after the awaited one
    under ``plan.blocks_ahead``.  Returns the first ``R`` rows of the reach
    rows (cut to ``node_cap`` columns, int32) and of the per-row DBHit/Rows
    vectors; raises if any closure did not converge."""
    parts = []
    for k, out in enumerate(outs):
        count(BLOCKS_AHEAD, value(BLOCKS_LAUNCHED) - (first_block + k) - 1)
        with span("mv4pg.plan.wait"):
            jax.block_until_ready(out)
        with span("mv4pg.plan.rows_to_host"):
            parts.append([to_host(x, PLAN_PULLS) for x in out])
    with span("mv4pg.plan.rows_to_host"):
        F_h, db_h, rows_h, ok_h = zip(*parts)     # one block at least
        # sharded F columns are padded to node_pad (a multiple of the shard
        # count); slice back to the arena width — identity when unsharded
        reach = np.concatenate(F_h, axis=0)[:R].astype(np.int32)
        reach = reach[:, :node_cap]
        db_vec = np.concatenate(db_h)[:R]
        rows_vec = np.concatenate(rows_h)[:R]
    if not all(bool(o) for o in ok_h):
        raise RuntimeError(
            "closure did not converge within max_closure_iters")
    return reach, db_vec, rows_vec


@dataclass
class RowResult:
    """Per-source-row outputs of one executed binding — the serve layer's
    currency.  Alongside the dense reach rows it keeps the *per-row*
    DBHit/Rows vectors the fused programs accumulate device-side, so any
    subset of rows can be re-attributed exactly (metrics are row-local sums)
    without re-executing: the serve engine memoizes these across windows and
    answers subsumed point bindings by gathering rows."""

    sources: np.ndarray    # [S] int32 source ids, in binding order
    reach: np.ndarray      # [S, N] int32 reach rows
    db_vec: np.ndarray     # [S] int32 per-row DBHit contributions
    rows_vec: np.ndarray   # [S] int32 per-row Rows contributions
    counting: bool

    def to_reach_result(self) -> ReachResult:
        """The :class:`ReachResult` a solo ``execute`` would have returned:
        per-query metrics are S + the row-vector sums (the source-row term
        plus every row's accumulated hop contributions)."""
        S = int(self.sources.shape[0])
        return ReachResult(
            src_ids=self.sources, reach=self.reach, counting=self.counting,
            metrics=Metrics(db_hits=S + int(self.db_vec.sum()),
                            rows=S + int(self.rows_vec.sum())))

    def covers(self, sources: np.ndarray) -> bool:
        """Is every id of ``sources`` a row of this result?  Requires
        ``self.sources`` sorted ascending (true of ``default_sources``
        bindings, the only ones the serve engine gathers from)."""
        own = self.sources
        if own.shape[0] == 0:
            return int(np.asarray(sources).shape[0]) == 0
        idx = np.searchsorted(own, sources)
        idx = np.clip(idx, 0, own.shape[0] - 1)
        return bool(np.all(own[idx] == sources))

    def gather(self, sources: np.ndarray) -> "RowResult":
        """Exact row-subset view for ``sources`` ⊆ ``self.sources`` (sorted
        ascending); duplicate ids map to the same row, like re-execution."""
        sources = np.asarray(sources, np.int32)
        idx = np.searchsorted(self.sources, sources)
        return RowResult(sources, self.reach[idx], self.db_vec[idx],
                         self.rows_vec[idx], self.counting)


@dataclass
class PendingRows:
    """A fused execution's blocks in flight: what the launch half hands the
    collect half.  Launching syncs nothing with the device, so a caller may
    launch several executions before it collects the first."""

    outs: List[tuple]          # per block (F, db, rows, ok), copies started
    first_block: int           # process-wide launch number of outs[0]
    sizes: List[int]           # block heights (row slots launched)
    R: int                     # rows packed, padding excluded
    node_cap: int
    counting: bool
    members: int
    layout: List[Tuple[int, int, np.ndarray]]   # (member, row offset, ids)

    def collect(self) -> List[List[RowResult]]:
        """Wait for the blocks, take their rows, and split them per member
        and binding in launch order; raises if a closure did not converge."""
        reach, db_vec, rows_vec = _rows_to_host(self.outs, self.first_block,
                                                self.R, self.node_cap)
        results: List[List[RowResult]] = [[] for _ in range(self.members)]
        for m, off, src in self.layout:
            S = int(src.shape[0])
            results[m].append(RowResult(
                sources=src, reach=reach[off:off + S],
                db_vec=db_vec[off:off + S], rows_vec=rows_vec[off:off + S],
                counting=self.counting))
        return results


# ---------------------------------------------------------------------------
# compiled plan
# ---------------------------------------------------------------------------

class CompiledPlan:
    """A physical plan compiled from a (rewritten) path pattern.

    Holds the step list, the validity snapshot (label epochs, reset
    generation, node capacity, view-set generation), and one jitted fused
    program.  ``jax.jit`` specializes the program per operand shape, so arena
    growth that changes slice shapes re-traces automatically — "one fused
    device program per (plan, shape)".
    """

    def __init__(self, engine: ExecEngine, cfg: ExecConfig,
                 path: PathPattern, counting: bool,
                 fingerprint: QueryFingerprint, view_gen: Optional[int],
                 reuse_from: Optional["CompiledPlan"] = None):
        self.engine = engine
        self.cfg = cfg
        self.path = path
        self.counting = counting
        self.fingerprint = fingerprint
        self.view_gen = view_gen          # None: rewrite never saw the catalog
        schema = engine.schema
        start = path.start
        self.start_label_id = schema.node_label_id(start.label)
        self.start_key = start.key
        self.start_preds = normalize_preds(start.preds)
        self.steps: List[object] = []
        for i, rel in enumerate(path.rels):
            lid = schema.edge_label_id(rel.label)
            revs = ((False,) if rel.direction is Direction.OUT
                    else (True,) if rel.direction is Direction.IN
                    else (False, True))
            self.steps.append(ExpandStep(
                label_id=lid, reverses=revs, min_hops=rel.min_hops,
                max_hops=rel.max_hops,
                backend=_choose_backend(engine, cfg, lid),
                preds=normalize_preds(rel.preds)))
            nxt = path.nodes[i + 1]
            self.steps.append(FilterStep(
                label_id=schema.node_label_id(nxt.label), key=nxt.key,
                preds=normalize_preds(nxt.preds)))
        # node property columns the trace reads (FilterStep predicates),
        # in a fixed order baked into the trace; operand arrays are fetched
        # per execution so property writes take effect without recompiling
        self._nprop_names: Tuple[str, ...] = tuple(sorted(
            {p.prop for s in self.steps if isinstance(s, FilterStep)
             for p in s.preds}))
        # (node label id, prop) pairs the plan's node filters read — the
        # serve engine's fence/conflict scoping unit (NO_LABEL = any label)
        self._nprop_pairs: FrozenSet[Tuple[int, str]] = frozenset(
            (s.label_id, p.prop)
            for s in self.steps if isinstance(s, FilterStep)
            for p in s.preds)
        # validity snapshot (same machinery the engine's caches key off)
        self.label_epochs: Dict[int, int] = {
            s.label_id: engine.epochs.of(s.label_id)
            for s in self.steps if isinstance(s, ExpandStep)}
        self.reset_gen = engine.epochs.reset_generation
        self.node_cap = engine.g.node_cap
        self._cfg_key = _cfg_snapshot(cfg)
        # an epoch-only recompile usually changes nothing the trace depends
        # on (steps, counting, config) — adopt the superseded plan's jitted
        # program so warm XLA executables survive write-interleaved
        # workloads instead of re-tracing per mutation
        if (reuse_from is not None
                and reuse_from.steps == self.steps
                and reuse_from.counting == self.counting
                and reuse_from._cfg_key == self._cfg_key):
            self._fn = reuse_from._fn
        elif cfg.data_shards > 1:
            self._fn = self._make_sharded_fn()
        else:
            self._fn = jax.jit(self._program_plan)

    # -- validity ----------------------------------------------------------

    def is_valid(self, view_gen: int) -> bool:
        eng = self.engine
        if self.node_cap != eng.g.node_cap:
            return False
        if self.reset_gen != eng.epochs.reset_generation:
            return False
        if self.view_gen is not None and self.view_gen != view_gen:
            return False
        if self._cfg_key != _cfg_snapshot(self.cfg):
            return False    # session cfg mutated since compile
        return all(eng.epochs.of(lid) == ep
                   for lid, ep in self.label_epochs.items())

    # -- fused program -----------------------------------------------------

    def _program_plan(self, ids, node_label, node_key, node_alive,
                      nprops, operands):
        """The whole query for one source block, as a single traced program.

        ``ids`` is the padded [blk] source-id block (-1 = padding); ``nprops``
        carries the node property columns FilterStep predicates read (ordered
        as ``self._nprop_names``); operands is a tuple (one entry per expand
        step) of per-direction array tuples.
        Returns (F, db_hits[blk], rows[blk], converged): metrics accumulate
        as **per-row** int32 vectors so a serving batch that packs rows from
        many queries into one block can attribute DBHit/Rows per query after
        the sync; summing a row range reproduces the scalar accumulation of
        the unfused executor exactly (padding and foreign rows contribute
        independently, and every hop kernel is row-local).
        """
        counting = self.counting
        collect = self.cfg.collect_metrics
        blk = ids.shape[0]
        N = node_label.shape[0]
        valid = ids >= 0
        cols = jnp.where(valid, ids, 0)
        if counting:
            F = jnp.zeros((blk, N), jnp.int32).at[
                jnp.arange(blk), cols].add(valid.astype(jnp.int32))
        else:
            F = jnp.zeros((blk, N), bool).at[
                jnp.arange(blk), cols].max(valid)
        db = jnp.zeros(blk, jnp.int32)
        rows = jnp.zeros(blk, jnp.int32)
        ok = jnp.bool_(True)

        def hop(Fc, step_ops, backend, reverses, db, rows, skip_db=False):
            """One expansion hop: mirrors PathExecutor._hop exactly."""
            out = None
            for rev, arrs in zip(reverses, step_ops):
                if collect and not skip_db:
                    # deg is the last operand of every backend's tuple
                    db = db + _hop_cost_per_source(Fc, arrs[-1])
                if backend == "segment":
                    esrc, edst, ew, emask, _ = arrs
                    nxt = _hop_segment(Fc, esrc, edst, emask, ew,
                                       counting=counting, reverse=rev)
                elif backend == "pallas":
                    from repro.kernels import ops as kops
                    A, _ = arrs
                    nxt = kops.block_spmm(Fc.astype(jnp.int32), A,
                                          counting=counting,
                                          interpret=self.cfg.interpret)
                    nxt = nxt if counting else nxt.astype(bool)
                else:
                    A, _ = arrs
                    nxt = _hop_dense(Fc, A, counting=counting)
                out = nxt if out is None else (
                    out + nxt if counting else out | nxt)
            if collect:
                rows = rows + _active_rows_per_source(out)
            return out, db, rows

        op_i = 0
        for step in self.steps:
            if isinstance(step, FilterStep):
                m = node_alive
                if step.label_id != NO_LABEL:
                    m = m & (node_label == step.label_id)
                if step.key is not None:
                    m = m & (node_key == step.key)
                for p in step.preds:   # fused device-side predicate mask
                    m = m & _cmp(nprops[self._nprop_names.index(p.prop)],
                                 p.op, p.value)
                F = F & m[None, :] if not counting else jnp.where(m[None, :],
                                                                 F, 0)
                continue
            step_ops = operands[op_i]
            op_i += 1
            with jax.named_scope(_scope_name(step.max_hops, op_i)):
                lo, hi = step.min_hops, step.max_hops
                if hi != INF_HOPS:
                    # bounded: acc = sum/or over k in [lo, hi] (lo may be 0).
                    # Hops past an empty frontier contribute zero to F and both
                    # metrics, so skipping the host executor's early break is
                    # result- and metric-identical.
                    acc = F if lo == 0 else None
                    cur = F
                    for k in range(1, hi + 1):
                        cur, db, rows = hop(cur, step_ops, step.backend,
                                            step.reverses, db, rows)
                        if k >= lo:
                            acc = cur if acc is None else (
                                acc + cur if counting else acc | cur)
                    F = acc if acc is not None else jnp.zeros_like(F)
                    continue
                # unbounded boolean closure as a device-side while loop
                cur = F
                for _ in range(max(lo, 0)):
                    cur, db, rows = hop(cur, step_ops, step.backend,
                                        step.reverses, db, rows)

                def cond(c):
                    i, _reach, frontier, _db, _rows = c
                    return jnp.logical_and(i < self.cfg.max_closure_iters,
                                           jnp.any(frontier))

                def body(c):
                    i, reach, frontier, db, rows = c
                    nxt, db, rows = hop(frontier, step_ops, step.backend,
                                        step.reverses, db, rows, skip_db=True)
                    return (i + 1, reach | nxt, nxt & ~reach, db, rows)

                _, reach, frontier, db, rows = jax.lax.while_loop(
                    cond, body, (jnp.int32(0), cur, cur, db, rows))
                ok = ok & ~jnp.any(frontier)   # nonempty at exit: not converged
                if collect:
                    # Successive closure frontiers are pairwise disjoint
                    # (frontier_{k+1} = nxt_k & ~reach_k) with union equal to the
                    # converged reach set, so the per-iteration DBHit sum
                    # telescopes to one matvec over ``reach`` — the same int32
                    # products summed in a different order, hoisted out of the
                    # while_loop where the [blk, N] cast dominated closure cost.
                    # A non-converged exit over-counts the residual frontier,
                    # but execute_rows raises before those metrics surface.
                    for arrs in step_ops:
                        db = db + _hop_cost_per_source(reach, arrs[-1])
                F = reach
        return F, db, rows, ok

    # -- sharded fused program (DESIGN.md §12) -----------------------------

    def _make_sharded_fn(self):
        """Compile :meth:`_program_sharded` as a jitted shard_map over the
        engine's (data_shards x 1) mesh.  Node columns (and therefore
        frontiers) shard over the data axis; edge operands are stacked
        ``[D, ...]`` with shard ``s``'s partition on device ``s``; the
        source-id block is replicated.  F comes back reassembled
        ``[blk, N_pad]``; db/rows/ok are replicated (psum-reduced)."""
        from jax.sharding import PartitionSpec as P
        mesh = self.engine.mesh()
        col = P("data")
        in_specs = (P(None), col, col, col, col, P("data", None))
        out_specs = (P(None, "data"), P(None), P(None), P())
        return jax.jit(jax.shard_map(
            self._program_sharded, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))

    def _program_sharded(self, ids, node_label, node_key, node_alive, nprops,
                         operands):
        """Per-device body of the sharded fused program.

        Same signature and step walk as :meth:`_program_plan`, but node arrays
        arrive as the shard's local column slice (``[n_loc]``), edge operands
        as the shard's dst-partition (leading shard axis of size 1), and F is
        the local column block ``[blk, n_loc]``.  Each hop all-gathers the
        frontier columns once (**the halo exchange — the only per-hop
        collective**), gathers edge sources from the full frontier, and
        scatters into the local column range only.  DBHit/Rows accumulate as
        per-shard partials (partial degree vectors / local-column row
        counts) and reduce with a **single psum** at program end, so
        per-query metric parity with :meth:`_program_plan` is exact — int32
        partial sums commute.  Unbounded closures carry a psum'd global
        frontier count so every shard agrees on the trip count."""
        counting = self.counting
        collect = self.cfg.collect_metrics
        blk = ids.shape[0]
        n_loc = node_label.shape[0]
        offset = jax.lax.axis_index("data") * n_loc
        lcol = ids - offset
        mine = (ids >= 0) & (lcol >= 0) & (lcol < n_loc)
        lcol = jnp.clip(lcol, 0, n_loc - 1)
        if counting:
            F = jnp.zeros((blk, n_loc), jnp.int32).at[
                jnp.arange(blk), lcol].add(mine.astype(jnp.int32))
        else:
            F = jnp.zeros((blk, n_loc), bool).at[
                jnp.arange(blk), lcol].max(mine)
        db = jnp.zeros(blk, jnp.int32)
        rows = jnp.zeros(blk, jnp.int32)
        ok = jnp.bool_(True)

        def hop(Fc, step_ops, db, rows, skip_db=False):
            F_full = jax.lax.all_gather(Fc, "data", axis=1, tiled=True)
            out = None
            for arrs in step_ops:
                a, b_local, ew, emask, deg = (x[0] for x in arrs)
                if collect and not skip_db:
                    db = db + _hop_cost_per_source(F_full, deg)
                nxt = _hop_segment_local(F_full, a, b_local, emask, ew,
                                         counting=counting, n_loc=n_loc)
                out = nxt if out is None else (
                    out + nxt if counting else out | nxt)
            if collect:
                rows = rows + _active_rows_per_source(out)
            return out, db, rows

        op_i = 0
        for step in self.steps:
            if isinstance(step, FilterStep):
                m = node_alive
                if step.label_id != NO_LABEL:
                    m = m & (node_label == step.label_id)
                if step.key is not None:
                    m = m & (node_key == step.key)
                for p in step.preds:
                    m = m & _cmp(nprops[self._nprop_names.index(p.prop)],
                                 p.op, p.value)
                F = F & m[None, :] if not counting else jnp.where(m[None, :],
                                                                 F, 0)
                continue
            step_ops = operands[op_i]
            op_i += 1
            lo, hi = step.min_hops, step.max_hops
            if hi != INF_HOPS:
                acc = F if lo == 0 else None
                cur = F
                for k in range(1, hi + 1):
                    cur, db, rows = hop(cur, step_ops, db, rows)
                    if k >= lo:
                        acc = cur if acc is None else (
                            acc + cur if counting else acc | cur)
                F = acc if acc is not None else jnp.zeros_like(F)
                continue
            cur = F
            for _ in range(max(lo, 0)):
                cur, db, rows = hop(cur, step_ops, db, rows)
            act = jax.lax.psum(jnp.sum(cur.astype(jnp.int32)), "data")

            def cond(c):
                i, _reach, _frontier, _db, _rows, act = c
                return jnp.logical_and(i < self.cfg.max_closure_iters,
                                       act > 0)

            def body(c):
                i, reach, frontier, db, rows, _act = c
                nxt, db, rows = hop(frontier, step_ops, db, rows,
                                    skip_db=True)
                new = nxt & ~reach
                act = jax.lax.psum(jnp.sum(new.astype(jnp.int32)), "data")
                return (i + 1, reach | nxt, new, db, rows, act)

            _, reach, frontier, db, rows, act = jax.lax.while_loop(
                cond, body, (jnp.int32(0), cur, cur, db, rows, act))
            ok = ok & (act == 0)
            if collect:
                # disjoint-frontier telescoping (see _program_plan): one matvec
                # over the converged reach replaces the in-loop accumulation;
                # per-device deg covers only the shard's edge partition, so
                # the end-of-program psum still sums exact partials
                reach_full = jax.lax.all_gather(reach, "data", axis=1,
                                                tiled=True)
                for arrs in step_ops:
                    db = db + _hop_cost_per_source(reach_full, arrs[4][0])
            F = reach
        met = jax.lax.psum(jnp.stack([db, rows]), "data")  # the single psum
        return F, met[0], met[1], ok

    # -- operands ----------------------------------------------------------

    def _gather_operands(self):
        """Fetch current device operands from the engine (epoch-checked
        lookups — warm entries are dict hits, so this is cheap per query and
        guarantees a valid plan always executes against current data)."""
        eng = self.engine
        out = []
        for step in self.steps:
            if not isinstance(step, ExpandStep):
                continue
            per_dir = []
            for rev in step.reverses:
                deg = eng.deg(step.label_id, rev, step.preds)
                if step.backend == "segment":
                    esrc, edst, ew, emask = eng.label_edges(step.label_id,
                                                            step.preds)
                    per_dir.append((esrc, edst, ew, emask, deg))
                else:
                    per_dir.append((eng.adj(step.label_id, self.counting,
                                            rev, step.preds), deg))
            out.append(tuple(per_dir))
        return tuple(out)

    def _gather_operands_sharded(self):
        """Sharded counterpart of :meth:`_gather_operands`: per expand step,
        per direction, the engine's cached dst-partitioned ``[D, ...]`` edge
        stacks (gather ids global, scatter ids localized, per-shard partial
        degree vectors) already placed shard-per-device."""
        eng = self.engine
        return tuple(
            tuple(eng.sharded_label_edges(step.label_id, rev, step.preds)
                  for rev in step.reverses)
            for step in self.steps if isinstance(step, ExpandStep))

    # -- execution ---------------------------------------------------------

    def default_sources(self) -> np.ndarray:
        """Source node ids selected by the plan's start constraints
        (label, primary key, predicates) on the *current* graph."""
        g = self.engine.g
        src_mask = g.node_mask(self.start_label_id, self.start_key)
        if self.start_preds:
            src_mask = src_mask & node_pred_mask(g, self.start_preds)
        return np.flatnonzero(np.asarray(src_mask)).astype(np.int32)

    def execute(self, sources: Optional[np.ndarray] = None) -> ReachResult:
        """Run the fused program over blocked sources; one metric sync.

        ``sources`` overrides start-node selection with an explicit id array
        (the :meth:`~repro.core.executor.PathExecutor.run_path` contract:
        caller-owned sources skip the start label/key/predicate filter)."""
        if sources is None:
            sources = self.default_sources()
        return self.execute_batch([np.asarray(sources, np.int32)])[0]

    def execute_batch(self, source_lists: Sequence[np.ndarray]
                      ) -> List[ReachResult]:
        """Run *many* same-plan queries as one stacked frontier batch.

        Each entry of ``source_lists`` is one logical query's source-id
        array; all rows are packed back-to-back into shared ``[blk, N]``
        frontier blocks (instead of padding every query to its own block)
        and the fused program runs once per *shared* block — the serving
        engine's cross-query batching.  Per-row DBHit/Rows vectors come back
        from the device, so each query's :class:`Metrics` is exactly what a
        solo :meth:`execute` would have reported: every kernel in the trace
        is row-local, and padding rows contribute zero to both counters.
        One host sync per batch.
        """
        return [rr.to_reach_result()
                for rr in self.execute_rows(source_lists)]

    def execute_rows(self, source_lists: Sequence[np.ndarray], *,
                     adaptive_blocks: bool = False) -> List[RowResult]:
        """:meth:`execute_batch` without the per-query metric folding:
        returns :class:`RowResult` s carrying the raw per-row DBHit/Rows
        vectors, so the serve engine can memoize executions across windows
        and answer row-subsumed bindings by gathering.  ``adaptive_blocks``
        enables the serve-path power-of-two block sizing (the per-query path
        keeps fixed ``src_block`` blocks — see :func:`block_sizes`)."""
        return self.launch_rows(source_lists,
                                adaptive_blocks=adaptive_blocks).collect()[0]

    def launch_rows(self, source_lists: Sequence[np.ndarray], *,
                    adaptive_blocks: bool = False) -> PendingRows:
        """The launch half of :meth:`execute_rows`: pad the sources, gather
        the operands and dispatch every block, with each block's copy to the
        host started; :meth:`PendingRows.collect` gives the results."""
        g = self.engine.g
        with span("mv4pg.plan.launch"):
            srcs = [np.asarray(s, np.int32) for s in source_lists]
            layout, off = [], 0
            for s in srcs:
                layout.append((0, off, s))
                off += int(s.shape[0])
            R = off
            sizes = block_sizes(R, self.cfg.src_block, adaptive_blocks)
            padded = np.full(sum(sizes), -1, np.int32)
            if R:
                padded[:R] = np.concatenate(srcs)
            if self.cfg.data_shards > 1:
                node_label, node_key, node_alive, nprops = \
                    self.engine.sharded_node_data(self._nprop_names)
                operands = self._gather_operands_sharded()
            else:
                node_label, node_key, node_alive = (g.node_label, g.node_key,
                                                    g.node_alive)
                nprops = tuple(g.node_prop_col(name)
                               for name in self._nprop_names)
                operands = self._gather_operands()
            first = value(BLOCKS_LAUNCHED)
            outs = []
            b0 = 0
            for blk in sizes:
                outs.append(_start_copies(self._fn(
                    jnp.asarray(padded[b0:b0 + blk]), node_label, node_key,
                    node_alive, nprops, operands)))
                b0 += blk
        return PendingRows(outs, first, sizes, R, g.node_cap, self.counting,
                           1, layout)

    # -- structural sharing ------------------------------------------------

    def structure_key(self) -> Optional[tuple]:
        """Structure-only fingerprint: the shape of the traced program with
        labels, keys and predicates demoted from compile-time constants to
        per-row operands.  Two plans with equal keys can execute through one
        :class:`SharedProgram`.  Only all-segment plans are eligible (dense/
        pallas hops would stack ``[M, N, N]`` adjacencies); direction is
        folded into the operands (src/dst pre-swapped), so an IN hop and an
        OUT hop share structure.  Returns ``None`` when ineligible."""
        sig: List[tuple] = []
        for s in self.steps:
            if isinstance(s, FilterStep):
                sig.append(("f",))
            else:
                if s.backend != "segment":
                    return None
                sig.append(("x", len(s.reverses), s.min_hops, s.max_hops))
        if not any(t[0] == "x" for t in sig):
            return None
        return (self.counting, self.cfg.collect_metrics,
                self.cfg.max_closure_iters, tuple(sig))

    def share_scales(self) -> Tuple[int, ...]:
        """log2-quantized edge-slice sizes per expand step.  Shared buckets
        partition on these so stacking members to a common padded edge count
        never inflates any member's per-row hop work by more than 2x (a
        4k-edge label must not pay a 32k-edge label's scatter width)."""
        out = []
        for s in self.steps:
            if isinstance(s, ExpandStep):
                esrc, _, _, _ = self.engine.label_edges(s.label_id, s.preds)
                out.append(max(int(esrc.shape[0]) - 1, 1).bit_length())
        return tuple(out)

    def _gather_shared_operands(self):
        """Operands for a :class:`SharedProgram` member: per-filter node
        masks (label/key/alive/predicates folded into one ``[N]`` bool — the
        exact mask the single-plan trace computes from its fused constants)
        and per-expand per-direction edge tuples with reverse pre-applied.
        Fetched fresh per execution, like :meth:`_gather_operands`."""
        eng = self.engine
        g = eng.g
        masks, expands = [], []
        for step in self.steps:
            if isinstance(step, FilterStep):
                m = g.node_mask(step.label_id, step.key)
                if step.preds:
                    m = m & node_pred_mask(g, step.preds)
                masks.append(m)
            else:
                per_dir = []
                for rev in step.reverses:
                    esrc, edst, ew, emask = eng.label_edges(step.label_id,
                                                            step.preds)
                    deg = eng.deg(step.label_id, rev, step.preds)
                    a, b = (edst, esrc) if rev else (esrc, edst)
                    per_dir.append((a, b, ew, emask, deg))
                expands.append(tuple(per_dir))
        return tuple(masks), tuple(expands)

    def _gather_shared_operands_sharded(self):
        """Sharded counterpart of :meth:`_gather_shared_operands`: host-side
        padded node masks (``[N_pad]``) and host-side dst-partitioned edge
        tuples (``[D, Ep]`` / deg ``[D, N_pad]``) per expand direction — the
        sharded :class:`SharedProgram` stacks members host-side, then ships
        each stack with its shard placement in one device_put."""
        eng = self.engine
        g = eng.g
        masks, expands = [], []
        for step in self.steps:
            if isinstance(step, FilterStep):
                m = g.node_mask(step.label_id, step.key)
                if step.preds:
                    m = m & node_pred_mask(g, step.preds)
                masks.append(eng.padded_node_mask(m))
            else:
                expands.append(tuple(
                    eng.sharded_label_edges(step.label_id, rev, step.preds,
                                            host=True)
                    for rev in step.reverses))
        return tuple(masks), tuple(expands)


# ---------------------------------------------------------------------------
# shared structural program
# ---------------------------------------------------------------------------

class SharedProgram:
    """One jitted fused program serving a plan-*structure* equivalence class
    (DESIGN.md §10).

    Where :class:`CompiledPlan` bakes its labels/keys/predicates into the
    trace as constants, a shared program takes them as *stacked operands*:
    per-filter node masks ``[M, N]`` and per-hop edge slices ``[M, E_max]``
    for the ``M`` member plans of a window bucket, with every frontier row
    carrying a member index that selects its row of each operand stack.  The
    trace therefore depends only on the structure signature (step kinds, hop
    bounds, direction counts) plus shapes — queries that differ only in
    labels, predicates and sources share one XLA executable instead of
    compiling per fingerprint.

    Exactness: the row kernels (``_hop_segment_rows`` / ``_hop_cost_rows``)
    are the homogeneous kernels with the operand broadcast made explicit, so
    a row whose member stack repeats one plan's operands computes bit-for-bit
    what that plan's own program computes — including the per-row DBHit/Rows
    vectors, since every kernel is row-local.  Members are padded to a
    power-of-two count with member 0's operands and padded rows carry id -1,
    contributing exactly zero everywhere.
    """

    def __init__(self, counting: bool, collect_metrics: bool,
                 max_closure_iters: int, steps_sig: Tuple[tuple, ...],
                 engine: Optional[ExecEngine] = None, data_shards: int = 1):
        self.counting = counting
        self.collect = collect_metrics
        self.max_closure_iters = max_closure_iters
        self.steps_sig = steps_sig
        self.engine = engine
        self.data_shards = data_shards
        if data_shards > 1:
            self._fn = self._make_sharded_fn()
        else:
            self._fn = jax.jit(self._program_shared)

    def _make_sharded_fn(self):
        """Sharded variant: masks column-shard over the data axis (members
        replicated), edge stacks carry a leading shard axis, ids/midx
        replicate; F returns column-assembled, metrics replicated.  Same
        mesh/spec scheme as :meth:`CompiledPlan._make_sharded_fn`."""
        from jax.sharding import PartitionSpec as P
        mesh = self.engine.mesh()
        in_specs = (P(None), P(None), P(None, "data"), P("data"))
        out_specs = (P(None, "data"), P(None), P(None), P())
        return jax.jit(jax.shard_map(
            self._program_sharded, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False))

    # -- traced program ----------------------------------------------------

    def _program_shared(self, ids, midx, masks, operands):
        """One source block: ``ids`` [blk] (-1 padding), ``midx`` [blk]
        member indices, ``masks`` a tuple of [M, N] bool stacks (one per
        filter step), ``operands`` a tuple (one per expand step) of
        per-direction (src, dst, ew, emask, deg) stacks.  Mirrors
        :meth:`CompiledPlan._program_plan` with member-selected operands."""
        counting, collect = self.counting, self.collect
        blk = ids.shape[0]
        N = masks[0].shape[1] if masks else operands[0][0][4].shape[1]
        valid = ids >= 0
        cols = jnp.where(valid, ids, 0)
        if counting:
            F = jnp.zeros((blk, N), jnp.int32).at[
                jnp.arange(blk), cols].add(valid.astype(jnp.int32))
        else:
            F = jnp.zeros((blk, N), bool).at[
                jnp.arange(blk), cols].max(valid)
        db = jnp.zeros(blk, jnp.int32)
        rows = jnp.zeros(blk, jnp.int32)
        ok = jnp.bool_(True)

        mi = oi = 0
        for sig in self.steps_sig:
            if sig[0] == "f":
                m = masks[mi][midx]           # [blk, N] per-row node mask
                mi += 1
                F = F & m if not counting else jnp.where(m, F, 0)
                continue
            _, ndirs, lo, hi = sig
            with jax.named_scope(_scope_name(hi, oi + 1)):
                # member-select each direction's operands once per step; the
                # hop closure (and the while_loop body) reuse the gathered rows
                step_rows = tuple(
                    tuple(arr[midx] for arr in operands[oi][d])
                    for d in range(ndirs))
                oi += 1

                def hop(Fc, db, rows, step_rows=step_rows, skip_db=False):
                    out = None
                    for (a, b, ew, emask, deg) in step_rows:
                        if collect and not skip_db:
                            db = db + _hop_cost_rows(Fc, deg)
                        nxt = _hop_segment_rows(Fc, a, b, emask, ew,
                                                counting=counting)
                        out = nxt if out is None else (
                            out + nxt if counting else out | nxt)
                    if collect:
                        rows = rows + _active_rows_per_source(out)
                    return out, db, rows

                if hi != INF_HOPS:
                    acc = F if lo == 0 else None
                    cur = F
                    for k in range(1, hi + 1):
                        cur, db, rows = hop(cur, db, rows)
                        if k >= lo:
                            acc = cur if acc is None else (
                                acc + cur if counting else acc | cur)
                    F = acc if acc is not None else jnp.zeros_like(F)
                    continue
                cur = F
                for _ in range(max(lo, 0)):
                    cur, db, rows = hop(cur, db, rows)

                def cond(c):
                    i, _reach, frontier, _db, _rows = c
                    return jnp.logical_and(i < self.max_closure_iters,
                                           jnp.any(frontier))

                def body(c):
                    i, reach, frontier, db, rows = c
                    nxt, db, rows = hop(frontier, db, rows, skip_db=True)
                    return (i + 1, reach | nxt, nxt & ~reach, db, rows)

                _, reach, frontier, db, rows = jax.lax.while_loop(
                    cond, body, (jnp.int32(0), cur, cur, db, rows))
                ok = ok & ~jnp.any(frontier)
                if collect:
                    # disjoint-frontier telescoping (see CompiledPlan._program_plan)
                    for (a, b, ew, emask, deg) in step_rows:
                        db = db + _hop_cost_rows(reach, deg)
                F = reach
        return F, db, rows, ok

    def _program_sharded(self, ids, midx, masks, operands):
        """Per-device body of the sharded shared program: masks arrive as
        the shard's ``[M, n_loc]`` column slice, edge stacks as the shard's
        partition ``[1, M, Ep]`` / deg ``[1, M, N_pad]`` (squeeze the shard
        axis), and rows scatter only into the local columns.  Metric
        partials and closure convergence follow
        :meth:`CompiledPlan._program_sharded` exactly: one end-of-program
        psum, psum'd global frontier counts in the while_loop carry."""
        counting, collect = self.counting, self.collect
        blk = ids.shape[0]
        # masks shard to local columns; deg stays full-width ([1, M, N_pad])
        n_loc = (masks[0].shape[1] if masks
                 else operands[0][0][4].shape[2] // jax.lax.axis_size("data"))
        offset = jax.lax.axis_index("data") * n_loc
        lcol = ids - offset
        mine = (ids >= 0) & (lcol >= 0) & (lcol < n_loc)
        lcol = jnp.clip(lcol, 0, n_loc - 1)
        if counting:
            F = jnp.zeros((blk, n_loc), jnp.int32).at[
                jnp.arange(blk), lcol].add(mine.astype(jnp.int32))
        else:
            F = jnp.zeros((blk, n_loc), bool).at[
                jnp.arange(blk), lcol].max(mine)
        db = jnp.zeros(blk, jnp.int32)
        rows = jnp.zeros(blk, jnp.int32)
        ok = jnp.bool_(True)

        mi = oi = 0
        for sig in self.steps_sig:
            if sig[0] == "f":
                m = masks[mi][midx]           # [blk, n_loc] local columns
                mi += 1
                F = F & m if not counting else jnp.where(m, F, 0)
                continue
            _, ndirs, lo, hi = sig
            step_rows = tuple(
                tuple(arr[0][midx] for arr in operands[oi][d])
                for d in range(ndirs))
            oi += 1

            def hop(Fc, db, rows, step_rows=step_rows, skip_db=False):
                F_full = jax.lax.all_gather(Fc, "data", axis=1, tiled=True)
                out = None
                for (a, b_local, ew, emask, deg) in step_rows:
                    if collect and not skip_db:
                        db = db + _hop_cost_rows(F_full, deg)
                    nxt = _hop_segment_rows_local(F_full, a, b_local, emask,
                                                  ew, counting=counting,
                                                  n_loc=n_loc)
                    out = nxt if out is None else (
                        out + nxt if counting else out | nxt)
                if collect:
                    rows = rows + _active_rows_per_source(out)
                return out, db, rows

            if hi != INF_HOPS:
                acc = F if lo == 0 else None
                cur = F
                for k in range(1, hi + 1):
                    cur, db, rows = hop(cur, db, rows)
                    if k >= lo:
                        acc = cur if acc is None else (
                            acc + cur if counting else acc | cur)
                F = acc if acc is not None else jnp.zeros_like(F)
                continue
            cur = F
            for _ in range(max(lo, 0)):
                cur, db, rows = hop(cur, db, rows)
            act = jax.lax.psum(jnp.sum(cur.astype(jnp.int32)), "data")

            def cond(c):
                i, _reach, _frontier, _db, _rows, act = c
                return jnp.logical_and(i < self.max_closure_iters, act > 0)

            def body(c):
                i, reach, frontier, db, rows, _act = c
                nxt, db, rows = hop(frontier, db, rows, skip_db=True)
                new = nxt & ~reach
                act = jax.lax.psum(jnp.sum(new.astype(jnp.int32)), "data")
                return (i + 1, reach | nxt, new, db, rows, act)

            _, reach, frontier, db, rows, act = jax.lax.while_loop(
                cond, body, (jnp.int32(0), cur, cur, db, rows, act))
            ok = ok & (act == 0)
            if collect:
                # disjoint-frontier telescoping (see CompiledPlan._program_plan)
                reach_full = jax.lax.all_gather(reach, "data", axis=1,
                                                tiled=True)
                for (a, b_local, ew, emask, deg) in step_rows:
                    db = db + _hop_cost_rows(reach_full, deg)
            F = reach
        met = jax.lax.psum(jnp.stack([db, rows]), "data")
        return F, met[0], met[1], ok

    # -- execution ---------------------------------------------------------

    def execute(self, plans: Sequence[CompiledPlan],
                spec_lists: Sequence[Sequence[np.ndarray]], *,
                adaptive_blocks: bool = True) -> List[List[RowResult]]:
        """Run several same-structure plans' bindings as one padded batch.

        ``spec_lists[m]`` holds plan ``m``'s unique source bindings; all rows
        of all members pack back-to-back into shared blocks, each row tagged
        with its member index.  Edge operands pad to the bucket's per-step
        maximum (padded edges are masked off → exact no-ops).  Returns
        per-plan lists of :class:`RowResult` matching ``spec_lists``."""
        return self.launch(plans, spec_lists,
                           adaptive_blocks=adaptive_blocks).collect()

    def launch(self, plans: Sequence[CompiledPlan],
               spec_lists: Sequence[Sequence[np.ndarray]], *,
               adaptive_blocks: bool = True) -> PendingRows:
        """The launch half of :meth:`execute`: stack the members' operands
        and dispatch every block, with each block's copy to the host
        started; :meth:`PendingRows.collect` gives the results."""
        with span("mv4pg.plan.launch"):
            cfg = plans[0].cfg
            eng = plans[0].engine
            M = len(plans)
            M_pad = 1 << max(M - 1, 1).bit_length()    # pow2 >= M, min 2
            sharded = self.data_shards > 1
            gathered = [p._gather_shared_operands_sharded() if sharded
                        else p._gather_shared_operands() for p in plans]

            n_filters = sum(1 for s in self.steps_sig if s[0] == "f")
            masks_st = []
            for fi in range(n_filters):
                ms = [gathered[m][0][fi] for m in range(M)]
                ms += [ms[0]] * (M_pad - M)
                if sharded:     # host stack → one column-sharded device_put
                    masks_st.append(eng.shard_put_mask_stack(np.stack(ms)))
                else:
                    masks_st.append(jnp.stack(ms))

            ops_st = []
            oi = 0
            for sig in self.steps_sig:
                if sig[0] != "x":
                    continue
                ndirs = sig[1]
                per_dir = []
                for d in range(ndirs):
                    cols = [gathered[m][1][oi][d] for m in range(M)]
                    # edge widths pad to the pow2 ceiling of the bucket max —
                    # recurring shapes then hit the same XLA executable across
                    # windows (the warm pool's compile skip); members share a
                    # log2 scale, so inflation stays within the bucket's 2x
                    # bound (padded edges are masked — exact no-ops)
                    ax = 1 if sharded else 0     # sharded leaves are [D, Ep]
                    E_max = max(int(c[0].shape[ax]) for c in cols)
                    E = 1 << max(E_max - 1, 1).bit_length()
                    stacked = []
                    for j in range(5):          # src, dst, ew, emask, deg
                        arrs = []
                        for c in cols:
                            a = c[j]
                            if j < 4 and int(a.shape[ax]) < E:
                                pad = (0, E - int(a.shape[ax]))
                                if sharded:
                                    a = np.pad(a, ((0, 0), pad))
                                else:
                                    a = jnp.pad(a, pad)
                            arrs.append(a)
                        arrs += [arrs[0]] * (M_pad - M)
                        if sharded:   # [D, M_pad, ...], shard axis leading
                            stacked.append(
                                eng.shard_put_edges(np.stack(arrs, axis=1)))
                        else:
                            stacked.append(jnp.stack(arrs))
                    per_dir.append(tuple(stacked))
                ops_st.append(tuple(per_dir))
                oi += 1
            masks_st = tuple(masks_st)
            ops_st = tuple(ops_st)

            layout: List[Tuple[int, int, np.ndarray]] = []
            src_parts, midx_parts = [], []
            off = 0
            for m, specs in enumerate(spec_lists):
                for s in specs:
                    arr = np.asarray(s, np.int32)
                    S = int(arr.shape[0])
                    layout.append((m, off, arr))
                    src_parts.append(arr)
                    midx_parts.append(np.full(S, m, np.int32))
                    off += S
            R = off
            sizes = block_sizes(R, cfg.src_block, adaptive_blocks)
            R_pad = sum(sizes)
            ids = np.full(R_pad, -1, np.int32)
            midx = np.zeros(R_pad, np.int32)
            if R:
                ids[:R] = np.concatenate(src_parts)
                midx[:R] = np.concatenate(midx_parts)

            first = value(BLOCKS_LAUNCHED)
            outs = []
            b0 = 0
            for blk in sizes:
                outs.append(_start_copies(self._fn(
                    jnp.asarray(ids[b0:b0 + blk]),
                    jnp.asarray(midx[b0:b0 + blk]), masks_st, ops_st)))
                b0 += blk
        return PendingRows(outs, first, sizes, R, eng.g.node_cap,
                           self.counting, M, layout)


# ---------------------------------------------------------------------------
# planner: the session plan cache
# ---------------------------------------------------------------------------

class QueryPlanner:
    """Session-lifetime owner of the rewrite cache and the plan cache.

    ``plan(q, views, view_gen)`` is the whole compile pipeline; both caches
    key off the query fingerprint, so repeated query *shapes* — regardless of
    variable spelling or RETURN clause — compile once.  ``plan_hits`` /
    ``plan_misses`` and ``rewrite_hits`` / ``rewrite_misses`` make the
    caching observable (tests and the workload driver read them);
    ``rewrite_seconds_total`` over ``plan_calls`` is the amortized rewrite
    cost the paper-protocol runs report.
    """

    def __init__(self, engine: ExecEngine, schema: GraphSchema,
                 cfg: Optional[ExecConfig] = None):
        self.engine = engine
        self.schema = schema
        self.cfg = cfg or engine.cfg
        self._plans: Dict[Tuple[QueryFingerprint, bool], CompiledPlan] = {}
        self._rewrites: Dict[Tuple[QueryFingerprint, int],
                             Tuple[PathPattern, bool]] = {}
        self._shared: Dict[tuple, SharedProgram] = {}
        self.plan_hits = 0
        self.plan_misses = 0
        self.rewrite_hits = 0
        self.rewrite_misses = 0
        self.plan_calls = 0
        self.rewrite_seconds_total = 0.0

    def plan(self, q: Query, views: Sequence, view_gen: int
             ) -> Tuple[CompiledPlan, float]:
        """Fingerprint → (memoized) rewrite → (cached) physical plan.

        Returns ``(plan, rewrite_seconds)`` where the second element is the
        rewrite time actually spent on *this* call (0.0 on a rewrite-cache
        hit — the number the workload driver watches go to ~0 on repeats).
        """
        self.plan_calls += 1
        fp = query_fingerprint(q, self.schema)
        use_views = bool(views)
        key = (fp, use_views)
        stale = self._plans.get(key)
        if stale is not None and stale.is_valid(view_gen):
            self.plan_hits += 1
            return stale, 0.0
        self.plan_misses += 1
        rewrite_s = 0.0
        if use_views:
            rw = self._rewrites.get((fp, view_gen))
            if rw is not None:
                self.rewrite_hits += 1
                path, force_bool = rw
            else:
                self.rewrite_misses += 1
                from repro.core.optimizer import optimize_query
                t0 = time.perf_counter()
                with span("mv4pg.plan.rewrite"):
                    q_rw = optimize_query(q, list(views))
                rewrite_s = time.perf_counter() - t0
                self.rewrite_seconds_total += rewrite_s
                path, force_bool = q_rw.path, q_rw.force_bool
                # superseded-generation entries are unreachable (the
                # generation only moves forward) — prune so catalog churn
                # cannot grow the cache without bound
                if any(k[1] != view_gen for k in self._rewrites):
                    self._rewrites = {k: v for k, v in self._rewrites.items()
                                      if k[1] == view_gen}
                self._rewrites[(fp, view_gen)] = (path, force_bool)
        else:
            path, force_bool = q.path, q.force_bool
        counting = (not force_bool
                    and not any(r.unbounded for r in path.rels))
        plan = CompiledPlan(self.engine, self.cfg, path, counting,
                            fingerprint=fp,
                            view_gen=view_gen if use_views else None,
                            reuse_from=stale)
        self._plans[key] = plan
        return plan, rewrite_s

    def shared_program(self, key: tuple) -> SharedProgram:
        """The session-lifetime :class:`SharedProgram` for a structure key
        (see :meth:`CompiledPlan.structure_key`).  Programs persist across
        windows and write fences: labels and predicates are operands, so
        epoch invalidation never stales the trace — only shapes respecialize.
        Sharded sessions get a sharded program (cached separately, so a cfg
        ``data_shards`` flip can't execute through a mismatched trace)."""
        shards = max(int(self.cfg.data_shards), 1)
        sp = self._shared.get((key, shards))
        if sp is None:
            counting, collect, max_iters, sig = key
            sp = SharedProgram(counting, collect, max_iters, sig,
                               engine=self.engine, data_shards=shards)
            self._shared[(key, shards)] = sp
        return sp
