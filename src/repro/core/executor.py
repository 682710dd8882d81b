"""Path-pattern executor: blocked multi-source reachability with metrics.

The GDBMS expand operator becomes array algebra:

* ``segment`` backend — one hop scatters frontier mass along alive edges:
  ``F' = scatter_add(F[:, src] * w, dst)`` (counting) or scatter-max (bool).
  This is the gather/scatter form that also serves tiny maintenance deltas.
* ``dense`` backend — label-masked adjacency is materialized as a dense
  ``[N, N]`` tile and a hop is ``F @ A`` on the MXU.  This is the semantics
  target of the Pallas ``block_spmm`` kernel (usable for moderate N / per
  block pair on TPU).

Hop-range algebra (paper §IV: ``e*n..m``):
  counting, finite m:   ``Σ_{k=n..m} F·A^k``            (exact walk counts)
  boolean, any m:       ``F·A^n`` then frontier closure  (reachability)

Metrics follow the paper's Definitions 2-3: ``DBHit`` counts storage touches
(1 per scanned node, 2 per expanded edge: the edge and its endpoint), ``Rows``
counts active bindings passed between operators.  Accumulation happens host-side
in Python ints, so counters never overflow device int32.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Iterable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (
    LabelEpochs, PropertyGraph, edge_pred_mask, gathered_pred_mask,
    node_pred_mask,
)
from repro.core.pattern import (
    Direction, PathPattern, PropPred, Query, RelPat, normalize_preds,
)
from repro.core.schema import GraphSchema, NO_LABEL
from repro.utils import INF_HOPS, round_up
from repro.utils.trace import count, span, to_host

# counter prefix of the graph session's device-to-host pulls outside the
# fused plans (executor slice rebuilds and reach rows, view maintenance);
# GraphSession.apply_writes counts those made in a fence as maint.to_host
SESSION_PULLS = "session.to_host"


@dataclass
class ExecConfig:
    backend: str = "segment"        # "segment" | "dense": unfused PathExecutor
    #                                 backend; "dense" also forces dense hops
    #                                 in compiled plans (legacy override)
    src_block: int = 256            # sources per frontier block
    max_closure_iters: int = 256    # safety bound for unbounded fixpoints
    use_pallas: bool = False        # route dense hops through the Pallas kernel
    interpret: bool = False         # Pallas interpret mode (CPU tests only)
    collect_metrics: bool = True    # DBHit/Rows accounting (host syncs/hop)
    # --- compiled-plan (core/plan.py) knobs ------------------------------
    plan_backend: str = "auto"      # "auto" = per-hop cost-based choice;
    #                                 "segment"/"dense"/"pallas" force one
    dense_node_limit: int = 4096    # never go dense above this node_cap
    dense_density: float = 0.05     # E_label / node_cap^2 threshold for dense
    data_shards: int = 1            # >1: compile plans as shard_map programs
    #                                 over a (data_shards x 1) device mesh
    #                                 (node columns + per-label edge slices
    #                                 dst-partitioned; DESIGN.md §12)


@dataclass
class Metrics:
    db_hits: int = 0
    rows: int = 0

    def __iadd__(self, other: "Metrics") -> "Metrics":
        self.db_hits += other.db_hits
        self.rows += other.rows
        return self

    def __add__(self, other: "Metrics") -> "Metrics":
        return Metrics(self.db_hits + other.db_hits, self.rows + other.rows)


class PairRows(NamedTuple):
    """Typed (src, dst, count) rows of a reachability result.

    A ``NamedTuple`` so the historical 3-tuple unpacking of
    :meth:`ReachResult.pairs` keeps working unchanged.
    """

    src: np.ndarray     # [P] source node ids
    dst: np.ndarray     # [P] int32 destination node ids
    count: np.ndarray   # [P] path counts (1s under set semantics)

    @property
    def n_pairs(self) -> int:
        return int(self.src.shape[0])


@dataclass
class ReachResult:
    """Reachability of one query: per-source rows over all node columns."""

    src_ids: np.ndarray             # [S] int32 source node ids
    reach: np.ndarray               # [S, N_cap] int32 counts (bool -> 0/1)
    counting: bool
    metrics: Metrics = field(default_factory=Metrics)

    def pairs(self) -> PairRows:
        """(src, dst, count) for every reachable pair."""
        rows, cols = np.nonzero(self.reach)
        return PairRows(self.src_ids[rows], cols.astype(np.int32),
                        self.reach[rows, cols])

    def num_results(self) -> int:
        """Bag cardinality (sum of path counts) — what RETURN n,m yields."""
        return int(self.reach.sum())

    def num_pairs(self) -> int:
        return int((self.reach > 0).sum())


# ---------------------------------------------------------------------------
# jitted single-hop steps
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("counting", "reverse"))
def _hop_segment(F, esrc, edst, emask, eweight, *, counting: bool, reverse: bool):
    """One expansion hop over the alive/label-masked edge set."""
    a, b = (edst, esrc) if reverse else (esrc, edst)
    if counting:
        msg = jnp.where(emask[None, :], F[:, a] * eweight[None, :], 0)
        return jnp.zeros_like(F).at[:, b].add(msg)
    msg = jnp.where(emask[None, :], F[:, a], False)
    return jnp.zeros_like(F).at[:, b].max(msg)


@partial(jax.jit, static_argnames=("counting", "n_loc"))
def _hop_segment_local(F_full, a, b_local, emask, eweight, *, counting: bool,
                       n_loc: int):
    """Device-local half of a sharded segment hop: gather from the
    all-gathered full frontier (``F_full`` [blk, N_pad]), scatter into the
    shard's **local** node-column range only (``[blk, n_loc]``).  Edges are
    pre-partitioned by scatter-side owner with ``b_local`` already localized
    (:func:`repro.graphops.distributed.partition_hop_edges`), so no
    cross-device scatter exists; direction is folded into the operands."""
    if counting:
        msg = jnp.where(emask[None, :], F_full[:, a] * eweight[None, :], 0)
        return jnp.zeros((F_full.shape[0], n_loc),
                         F_full.dtype).at[:, b_local].add(msg)
    msg = jnp.where(emask[None, :], F_full[:, a], False)
    return jnp.zeros((F_full.shape[0], n_loc), bool).at[:, b_local].max(msg)


@partial(jax.jit, static_argnames=("counting", "n_loc"))
def _hop_segment_rows_local(F_full, a, b_local, emask, eweight, *,
                            counting: bool, n_loc: int):
    """Row-parameterized :func:`_hop_segment_local` (per-row operand stacks —
    the sharded ``SharedProgram`` hop)."""
    rows = jnp.arange(F_full.shape[0])[:, None]
    if counting:
        msg = jnp.where(emask, jnp.take_along_axis(F_full, a, axis=1)
                        * eweight, 0)
        return jnp.zeros((F_full.shape[0], n_loc),
                         F_full.dtype).at[rows, b_local].add(msg)
    msg = jnp.where(emask, jnp.take_along_axis(F_full, a, axis=1), False)
    return jnp.zeros((F_full.shape[0], n_loc),
                     bool).at[rows, b_local].max(msg)


@partial(jax.jit, static_argnames=("counting",))
def _hop_dense(F, A, *, counting: bool):
    if counting:
        return F @ A
    return (F.astype(jnp.int32) @ A.astype(jnp.int32)) > 0


@partial(jax.jit, static_argnames=("counting",))
def _hop_segment_rows(F, esrc, edst, emask, eweight, *, counting: bool):
    """Row-parameterized segment hop: every frontier row carries its *own*
    edge operands (``[blk, E]`` instead of ``[E]``), so rows belonging to
    different plans of one structural equivalence class share a single trace
    (core/plan.py ``SharedProgram``).  Direction is folded into the operands
    (callers pre-swap src/dst for reverse hops).  For rows whose operand
    slices repeat one plan's arrays this computes exactly ``_hop_segment``:
    the gather/scatter targets and integer addends are identical per row."""
    rows = jnp.arange(F.shape[0])[:, None]
    if counting:
        msg = jnp.where(emask, jnp.take_along_axis(F, esrc, axis=1) * eweight,
                        0)
        return jnp.zeros_like(F).at[rows, edst].add(msg)
    msg = jnp.where(emask, jnp.take_along_axis(F, esrc, axis=1), False)
    return jnp.zeros_like(F).at[rows, edst].max(msg)


@jax.jit
def _hop_cost_rows(F, deg_rows):
    """Per-row DBHit vector with a per-row degree table (``[blk, N]``):
    ``_hop_cost_per_source`` for row-parameterized operands.  The elementwise
    multiply-sum reproduces the matvec exactly — int32 products summed in a
    different order are the same integers."""
    active = (F > 0).astype(jnp.int32) if F.dtype != jnp.bool_ \
        else F.astype(jnp.int32)
    return 2 * jnp.sum(active * deg_rows.astype(jnp.int32), axis=1)


@jax.jit
def _hop_cost(F, deg):
    """DBHits of expanding this frontier: 2 storage touches per expanded edge."""
    active = (F > 0).astype(jnp.int32) if F.dtype != jnp.bool_ else F.astype(jnp.int32)
    return 2 * jnp.sum(active @ deg.astype(jnp.int32))


@jax.jit
def _hop_cost_per_source(F, deg):
    """Per-frontier-row DBHit vector: ``_hop_cost`` split over the block.

    Rows of a serving batch belong to different queries, so the compiled
    plans accumulate a ``[blk]`` cost vector device-side and attribute it
    per query after the sync; summing the vector reproduces ``_hop_cost``
    exactly (same int32 dot products, summed in a different order)."""
    active = (F > 0).astype(jnp.int32) if F.dtype != jnp.bool_ else F.astype(jnp.int32)
    return 2 * (active @ deg.astype(jnp.int32))


@jax.jit
def _active_rows(F):
    active = F > 0 if F.dtype != jnp.bool_ else F
    return jnp.sum(active.astype(jnp.int32))


@jax.jit
def _active_rows_per_source(F):
    """Per-frontier-row Rows vector (`_active_rows` split over the block)."""
    active = F > 0 if F.dtype != jnp.bool_ else F
    return jnp.sum(active.astype(jnp.int32), axis=1)


def _dense_adjacency(g: PropertyGraph, m: jax.Array, counting: bool,
                     reverse: bool) -> jax.Array:
    """Dense [N, N] adjacency over the edges selected by mask ``m``."""
    a, b = (g.edge_dst, g.edge_src) if reverse else (g.edge_src, g.edge_dst)
    if counting:
        w = jnp.where(m, g.edge_weight, 0)
        return jnp.zeros((g.node_cap, g.node_cap), jnp.int32).at[a, b].add(w)
    return jnp.zeros((g.node_cap, g.node_cap), jnp.int32).at[a, b].max(
        m.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Engine: session-persistent cache owner
# ---------------------------------------------------------------------------

class ExecEngine:
    """Owns the executor state that outlives a single query or write.

    The seed rebuilt per-label compact edge slices, degree vectors, and dense
    adjacency tiles on *every* query (and twice per single-edge write, once
    per telescoping side).  The engine makes that state session-persistent:
    every cache entry records the :class:`LabelEpochs` epoch of its edge
    label at build time, and a mutation invalidates only the labels it
    touched — a write to ``replyOf`` leaves the ``hasTag`` slices warm.

    Wildcard (``NO_LABEL``) hops compile as the union over **base** edge
    labels only (:meth:`GraphSchema.base_edge_label_ids`): view labels are
    excluded so materialized views cannot leak phantom rows into unlabeled-rel
    queries.  The hop is backed by a cached compact all-base-edges index
    (host-side CSR-order sort; O(E_base) per hop instead of an O(E_arena)
    masked scan over the whole arena).  Wildcard entries key off the
    :class:`LabelEpochs` *base generation*, which moves only when a mutation
    touches a base label — view creation and view maintenance leave them
    warm.  ``hits`` / ``misses`` count cache lookups (the engine-layer tests
    assert reuse and per-label eviction through them).
    """

    def __init__(self, g: PropertyGraph, schema: GraphSchema,
                 cfg: Optional[ExecConfig] = None):
        self.g = g
        self.schema = schema
        self.cfg = cfg or ExecConfig()
        self.epochs = LabelEpochs()
        self._edge_cache: Dict[int, Tuple[int, Tuple]] = {}
        # predicate-filtered compact slices: (label_id, preds) -> masked slice
        self._edge_pred_cache: Dict[Tuple, Tuple[int, Tuple]] = {}
        self._deg_cache: Dict[Tuple, Tuple[int, jax.Array]] = {}
        self._adj_cache: Dict[Tuple, Tuple[int, jax.Array]] = {}
        self._base_mask_cache: Optional[Tuple[Tuple[int, int], np.ndarray]] = None
        self._count_cache: Dict[int, Tuple[Tuple[int, int], int]] = {}
        # sharded (dst-partitioned) hop operands: (label, preds, rev) ->
        # (validity, stacked arrays).  Validity is (label epoch,
        # reset_generation, node_cap): the partition layout depends on the
        # node capacity (owner = id // n_loc), so node-arena growth — which
        # bumps reset_generation *and* changes node_cap — must invalidate
        # every shard's cached slices even though per-label epochs also move
        # (the reset fence is the contract; epochs alone would miss an
        # external graph swap that keeps a label's epoch by rebuilding)
        self._shard_cache: Dict[Tuple, Tuple[Tuple, Tuple]] = {}
        self._shard_nodes_cache: Optional[Tuple] = None
        self._mesh = None
        # maintenance routing observability: owner shard -> delta sweeps
        # routed there (views.py records one per drained/maintained view)
        self.shard_sweeps: Dict[int, int] = {}
        self.hits = 0
        self.misses = 0

    # -- invalidation -----------------------------------------------------

    def set_graph(self, g: PropertyGraph,
                  touched_edge_labels: Optional[Iterable[int]] = None) -> None:
        """Swap in a mutated graph.

        ``touched_edge_labels`` lists the edge labels the mutation touched;
        only their entries are evicted — plus wildcard entries iff at least
        one touched label is a *base* label (wildcard state is independent of
        view-label churn).  ``None`` means the delta is unknown — evict
        everything (the conservative behavior external ``session.g = ...``
        assignments get).
        """
        if g is self.g:
            return
        self.g = g
        if touched_edge_labels is None:
            self.epochs.bump_all()
            self._edge_cache.clear()
            self._edge_pred_cache.clear()
            self._deg_cache.clear()
            self._adj_cache.clear()
            self._count_cache.clear()
            self._shard_cache.clear()
            self._shard_nodes_cache = None
            return
        touched = {int(lid) for lid in touched_edge_labels}
        touches_base = bool(touched - self.schema.view_edge_ids)
        self.epochs.bump(touched, touches_base=touches_base)

        def stale(lid: int) -> bool:
            return lid in touched or (lid == NO_LABEL and touches_base)

        for k in [k for k in self._edge_cache if stale(k)]:
            del self._edge_cache[k]
        for k in [k for k in self._edge_pred_cache if stale(k[0])]:
            del self._edge_pred_cache[k]
        for k in [k for k in self._deg_cache if stale(k[0])]:
            del self._deg_cache[k]
        for k in [k for k in self._adj_cache if stale(k[0])]:
            del self._adj_cache[k]
        for k in [k for k in self._shard_cache if stale(k[0])]:
            del self._shard_cache[k]
        self._shard_nodes_cache = None

    def snapshot(self, g: Optional[PropertyGraph] = None,
                 touched_edge_labels: Optional[Iterable[int]] = None
                 ) -> "ExecEngine":
        """Derived engine sharing every still-valid cache entry.

        Used for the old/mid-graph sides of telescoped maintenance deltas:
        those graphs differ from the engine's graph only by the labels a
        write touched, so the untouched labels' slices are reused instead of
        rebuilt (the copies are dict-shallow; no array work happens here).
        """
        eng = ExecEngine(self.g, self.schema, self.cfg)
        eng.epochs = self.epochs.snapshot()
        eng._edge_cache = dict(self._edge_cache)
        eng._edge_pred_cache = dict(self._edge_pred_cache)
        eng._deg_cache = dict(self._deg_cache)
        eng._adj_cache = dict(self._adj_cache)
        eng._base_mask_cache = self._base_mask_cache
        eng._count_cache = dict(self._count_cache)
        eng._shard_cache = dict(self._shard_cache)
        eng._mesh = self._mesh
        if g is not None:
            eng.set_graph(g, touched_edge_labels)
        return eng

    def cached_edge_labels(self) -> set:
        """Labels with a live compact-slice entry (engine-test introspection)."""
        return {lid for lid, (ep, _) in self._edge_cache.items()
                if ep == self.epochs.of(lid)}

    # -- epoch-checked lookup ---------------------------------------------

    def _lookup(self, cache: Dict, key, label_id: int, build):
        ep = self.epochs.of(label_id)
        ent = cache.get(key)
        if ent is not None and ent[0] == ep:
            self.hits += 1
            return ent[1]
        self.misses += 1
        val = build()
        cache[key] = (ep, val)
        return val

    def label_edges(self, label_id: int,
                    preds: Tuple[PropPred, ...] = ()):
        """Per-label edge index: compact (src, dst, weight, mask) arrays.

        A GDBMS scans only the label's adjacency; the mask-scan over the
        whole arena is O(E_total) per hop and — worse — view edges grow the
        arena and slow every *other* query down.  The compact slice makes a
        hop O(E_label) (measured 2-6x on the paper workloads; see
        EXPERIMENTS.md §Perf).  ``NO_LABEL`` returns the all-base-edges
        index: every alive edge whose label is base (never view edges),
        sorted into CSR order host-side.

        With ``preds`` (a normalized predicate conjunction) the returned mask
        is additionally filtered to edges satisfying every predicate — the
        predicate pushdown the compiled plans fuse into hop masks.  Pred
        entries are cached per (label, preds) under the same label epoch as
        the base slice, so a property write to the label rebuilds them."""
        ent = self._lookup(self._edge_cache, label_id, label_id,
                           lambda: self._build_label_edges(label_id))
        if not preds:
            return ent[:4]

        def build_pred():
            esrc, edst, ew, emask, eids = ent
            m = gathered_pred_mask(self.g.edge_props, preds, eids)
            pm = np.zeros(int(emask.shape[0]), bool)
            pm[:eids.shape[0]] = m
            return (esrc, edst, ew, emask & jnp.asarray(pm))

        return self._lookup(self._edge_pred_cache, (label_id, preds),
                            label_id, build_pred)

    @staticmethod
    def _pack_slices(src: np.ndarray, dst: np.ndarray, w: np.ndarray):
        """Pad compact host arrays to a 512 multiple and ship to device."""
        n = src.shape[0]
        cap = max(round_up(n, 512), 512)
        pad = np.zeros(cap, np.int32)
        src_p = pad.copy()
        dst_p = pad.copy()
        w_p = pad.copy()
        mask = np.zeros(cap, bool)
        src_p[:n] = src
        dst_p[:n] = dst
        w_p[:n] = w
        mask[:n] = True
        return (jnp.asarray(src_p), jnp.asarray(dst_p), jnp.asarray(w_p),
                jnp.asarray(mask))

    def _base_keep_mask(self) -> np.ndarray:
        """Host bool [E_cap]: alive edges carrying a *base* edge label.

        Memoized on (base_generation, edge_cap): several wildcard cache
        products (edge slice, 2 degree vectors, 4 adjacency variants) build
        from it after one invalidation, and only base-label mutations (which
        move the base generation) or arena growth (which changes the shape)
        can change its value — view-label writes only flip slots that are
        excluded either way."""
        key = (self.epochs.of(NO_LABEL), self.g.edge_cap)
        if self._base_mask_cache is not None \
                and self._base_mask_cache[0] == key:
            return self._base_mask_cache[1]
        with span("mv4pg.exec.slice_rebuild"):
            alive = to_host(self.g.edge_alive, SESSION_PULLS)
            if self.schema.view_edge_ids:
                base_ids = np.asarray(self.schema.base_edge_label_ids(),
                                      np.int32)
                mask = alive & np.isin(to_host(self.g.edge_label,
                                               SESSION_PULLS), base_ids)
            else:
                mask = alive
        self._base_mask_cache = (key, mask)
        return mask

    def _build_label_edges(self, label_id: int):
        """Compact slice + the arena edge ids behind it, in slice order (the
        ids align property columns with the slice for predicate masks)."""
        from repro.graphops.csr import compact_coo
        count("exec.slice_rebuilds")
        with span("mv4pg.exec.slice_rebuild"):
            g = self.g
            if label_id == NO_LABEL:
                keep = self._base_keep_mask()
            else:
                keep = (to_host(g.edge_alive, SESSION_PULLS)
                        & (to_host(g.edge_label, SESSION_PULLS) == label_id))
            src, dst, w, eids = compact_coo(
                to_host(g.edge_src, SESSION_PULLS),
                to_host(g.edge_dst, SESSION_PULLS),
                to_host(g.edge_weight, SESSION_PULLS), keep)
            return self._pack_slices(src, dst, w) + (eids,)

    def _edge_mask_for(self, label_id: int) -> jax.Array:
        """Arena-wide bool mask for ``label_id``; wildcard is base-only."""
        if label_id == NO_LABEL:
            return jnp.asarray(self._base_keep_mask())
        return self.g.edge_mask(label_id)

    def label_edge_count(self, label_id: int) -> int:
        """Number of alive edges carrying ``label_id`` (wildcard: base only).

        The planner's per-hop cost model (segment vs dense vs Pallas) reads
        this; it is cached per (label epoch, reset generation) with one host
        reduction per rebuild.  Deliberately outside the ``hits``/``misses``
        counters: cost-model probes are planner bookkeeping, not executor
        cache traffic."""
        key = (self.epochs.of(label_id), self.epochs.reset_generation)
        ent = self._count_cache.get(label_id)
        if ent is not None and ent[0] == key:
            return ent[1]
        if label_id == NO_LABEL:
            n = int(self._base_keep_mask().sum())
        else:
            n = int(np.sum(np.asarray(self.g.edge_alive)
                           & (np.asarray(self.g.edge_label) == label_id)))
        self._count_cache[label_id] = (key, n)
        return n

    def _pred_edge_mask(self, label_id: int,
                        preds: Tuple[PropPred, ...]) -> jax.Array:
        m = self._edge_mask_for(label_id)
        if preds:
            m = m & edge_pred_mask(self.g, preds)
        return m

    def deg(self, label_id: int, reverse: bool,
            preds: Tuple[PropPred, ...] = ()) -> jax.Array:
        def build():
            m = self._pred_edge_mask(label_id, preds).astype(jnp.int32)
            col = self.g.edge_dst if reverse else self.g.edge_src
            return jnp.zeros(self.g.node_cap, jnp.int32).at[col].add(m)
        return self._lookup(self._deg_cache, (label_id, reverse, preds),
                            label_id, build)

    def adj(self, label_id: int, counting: bool, reverse: bool,
            preds: Tuple[PropPred, ...] = ()) -> jax.Array:
        n = self.g.node_cap
        if n > self.cfg.dense_node_limit:
            raise ValueError(
                f"dense adjacency [{n}, {n}] int32 needs {4 * n * n} bytes; "
                f"node_cap exceeds dense_node_limit={self.cfg.dense_node_limit}"
                " — use segment hops (backend='segment', plan_backend='auto')")
        return self._lookup(
            self._adj_cache, (label_id, counting, reverse, preds), label_id,
            lambda: _dense_adjacency(self.g,
                                     self._pred_edge_mask(label_id, preds),
                                     counting, reverse))

    # -- sharded execution (DESIGN.md §12) --------------------------------

    @property
    def n_shards(self) -> int:
        return max(int(self.cfg.data_shards), 1)

    def mesh(self):
        """The (data_shards x 1) device mesh sharded plans execute on.
        Built lazily so single-device sessions never touch device state."""
        if self._mesh is None or self._mesh.shape["data"] != self.n_shards:
            from repro.launch.mesh import make_host_mesh
            self._mesh = make_host_mesh(n_data=self.n_shards)
        return self._mesh

    def node_pad(self) -> int:
        """Node-column capacity padded to a shard multiple; ``n_loc =
        node_pad // n_shards`` columns live on each shard.  Pad columns are
        unreachable (no edge scatters there, sources never select them)."""
        return max(round_up(self.g.node_cap, self.n_shards), self.n_shards)

    def _shard_validity(self, label_id: int) -> Tuple[int, int, int]:
        """Sharded entries revalidate on the label epoch AND the reset
        generation AND node_cap: the dst-partition layout is a function of
        node capacity, and reset fences (arena growth, external swaps) must
        invalidate every shard's cached slices (the PR-8 audit)."""
        return (self.epochs.of(label_id), self.epochs.reset_generation,
                self.g.node_cap)

    def shard_put_edges(self, arr: np.ndarray) -> jax.Array:
        """Ship a ``[D, ...]`` stacked per-shard array with row ``s`` resident
        on mesh device ``s`` (NamedSharding over the data axis)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P("data", *([None] * (arr.ndim - 1)))
        return jax.device_put(jnp.asarray(arr),
                              NamedSharding(self.mesh(), spec))

    def shard_put_cols(self, arr) -> jax.Array:
        """Ship a ``[N_pad, ...]`` node-column array column-sharded over the
        data axis (each shard holds its local ``n_loc`` slice)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P("data", *([None] * (np.ndim(arr) - 1)))
        return jax.device_put(jnp.asarray(arr),
                              NamedSharding(self.mesh(), spec))

    def sharded_label_edges(self, label_id: int, reverse: bool,
                            preds: Tuple[PropPred, ...] = (), *,
                            host: bool = False):
        """Dst-partitioned hop operands for one (label, preds, direction):
        ``(a, b_local, w, mask, deg)`` stacked ``[D, Ep]`` (deg ``[D, N_pad]``)
        with shard ``s``'s row resident on device ``s``.  Partitioned by the
        hop's scatter-side endpoint (dst, or src for reverse hops); ``deg`` is
        the per-shard partial degree vector whose psum reproduces
        :meth:`deg` exactly.  Cached per (label, preds, direction) under the
        sharded validity key (epoch, reset_generation, node_cap); both the
        host partition (``host=True`` — the sharded SharedProgram stacks
        members host-side before shipping) and its device placement live in
        the same entry."""
        from repro.graphops.distributed import partition_hop_edges
        key = (label_id, preds, reverse, self.n_shards)
        validity = self._shard_validity(label_id)
        ent = self._shard_cache.get(key)
        if ent is not None and ent[0] == validity:
            self.hits += 1
            return ent[1] if host else ent[2]
        self.misses += 1
        esrc, edst, ew, emask = self.label_edges(label_id, preds)
        keep = np.asarray(emask)
        src = np.asarray(esrc)[keep]
        dst = np.asarray(edst)[keep]
        w = np.asarray(ew)[keep]
        gather, scatter = (dst, src) if reverse else (src, dst)
        host_val = partition_hop_edges(
            gather, scatter, w, self.node_pad(), self.n_shards)
        dev_val = tuple(self.shard_put_edges(x) for x in host_val)
        self._shard_cache[key] = (validity, host_val, dev_val)
        return host_val if host else dev_val

    def sharded_node_data(self, nprop_names: Tuple[str, ...]):
        """Node columns padded to ``node_pad()`` and column-sharded:
        ``(label, key, alive, props)``.  Cached per graph object identity
        (every mutation swaps the graph pytree, so identity tracks
        freshness); pad columns are dead (alive=False) and unreachable."""
        n_pad = self.node_pad()
        cached = self._shard_nodes_cache
        if (cached is not None and cached[0] is self.g
                and cached[1] == nprop_names and cached[2] == n_pad):
            return cached[3]
        g = self.g
        pad = n_pad - g.node_cap

        def padded(col, fill=0):
            c = np.asarray(col)
            if pad:
                c = np.concatenate(
                    [c, np.full(pad, fill, c.dtype)])
            return self.shard_put_cols(c)

        val = (padded(g.node_label), padded(g.node_key),
               padded(g.node_alive, fill=False),
               tuple(padded(g.node_prop_col(n)) for n in nprop_names))
        self._shard_nodes_cache = (g, nprop_names, n_pad, val)
        return val

    def padded_node_mask(self, m) -> np.ndarray:
        """Pad a ``[node_cap]`` bool node mask to ``node_pad()`` with False —
        host-side; the sharded SharedProgram stacks member masks then ships
        the ``[M, N_pad]`` stack via :meth:`shard_put_mask_stack`."""
        m = np.asarray(m)
        pad = self.node_pad() - m.shape[0]
        if pad:
            m = np.concatenate([m, np.zeros(pad, bool)])
        return m

    def shard_put_mask_stack(self, arr) -> jax.Array:
        """Ship a ``[M, N_pad]`` member-mask stack column-sharded over the
        data axis (members replicated, node columns local)."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(jnp.asarray(arr),
                              NamedSharding(self.mesh(), P(None, "data")))

    def shard_owner_of(self, label_id: int) -> int:
        from repro.graphops.distributed import shard_owner
        return shard_owner(label_id, self.n_shards)

    def note_shard_sweep(self, label_id: int) -> None:
        """Record one maintenance delta sweep routed to a label's owner
        shard (views.py calls this per drained/maintained view when
        sharded — the routing counter benchmarks and tests observe)."""
        owner = self.shard_owner_of(label_id)
        self.shard_sweeps[owner] = self.shard_sweeps.get(owner, 0) + 1


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

class PathExecutor:
    """Evaluates :class:`PathPattern` s against a :class:`PropertyGraph`.

    Evaluation state (frontier blocking, metrics) lives here; cached derived
    state (label slices, degrees, adjacency) lives in the :class:`ExecEngine`.
    Constructing with ``engine=`` binds to a shared persistent engine; the
    legacy ``PathExecutor(g, schema, cfg)`` form creates a private one.
    """

    def __init__(self, g: Optional[PropertyGraph] = None,
                 schema: Optional[GraphSchema] = None,
                 cfg: Optional[ExecConfig] = None,
                 engine: Optional[ExecEngine] = None):
        if engine is None:
            if g is None or schema is None:
                raise ValueError("PathExecutor needs (g, schema) or engine=")
            engine = ExecEngine(g, schema, cfg)
        self.engine = engine
        self.schema = engine.schema if schema is None else schema
        self.cfg = cfg or engine.cfg

    @property
    def g(self) -> PropertyGraph:
        return self.engine.g

    # -- caches (delegated to the engine) ---------------------------------

    def invalidate(self, g: PropertyGraph):
        """Swap in a mutated graph (unknown delta: drops all caches)."""
        self.engine.set_graph(g, None)

    def _label_edges(self, label_id: int, preds=()):
        return self.engine.label_edges(label_id, preds)

    def _deg(self, label_id: int, reverse: bool, preds=()) -> jax.Array:
        return self.engine.deg(label_id, reverse, preds)

    def _adj(self, label_id: int, counting: bool, reverse: bool,
             preds=()) -> jax.Array:
        return self.engine.adj(label_id, counting, reverse, preds)

    # -- primitive hop ----------------------------------------------------

    def _hop(self, F, rel_label_id: int, direction: Direction, counting: bool,
             metrics: Metrics, preds: Tuple[PropPred, ...] = ()) -> jax.Array:
        dirs = ([False] if direction is Direction.OUT
                else [True] if direction is Direction.IN
                else [False, True])
        out = None
        for rev in dirs:
            if self.cfg.collect_metrics:
                metrics.db_hits += int(_hop_cost(
                    F, self._deg(rel_label_id, rev, preds)))
            if self.cfg.backend == "dense":
                A = self._adj(rel_label_id, counting, rev, preds)
                if self.cfg.use_pallas:
                    from repro.kernels import ops as kops
                    nxt = kops.block_spmm(
                        F.astype(jnp.int32) if counting else F.astype(jnp.int32),
                        A, counting=counting, interpret=self.cfg.interpret)
                    nxt = nxt if counting else nxt.astype(bool)
                else:
                    nxt = _hop_dense(F, A, counting=counting)
            else:
                esrc, edst, ew, emask = self._label_edges(rel_label_id, preds)
                nxt = _hop_segment(F, esrc, edst, emask, ew,
                                   counting=counting, reverse=rev)
            out = nxt if out is None else (out + nxt if counting else out | nxt)
        if self.cfg.collect_metrics:
            metrics.rows += int(_active_rows(out))
        return out

    def _node_filter(self, F, label_id: int, key: Optional[int],
                     preds: Tuple[PropPred, ...] = ()):
        mask = self.g.node_mask(label_id, key)
        if preds:
            mask = mask & node_pred_mask(self.g, preds)
        if F.dtype == jnp.bool_:
            return F & mask[None, :]
        return jnp.where(mask[None, :], F, 0)

    # -- hop-range expansion ----------------------------------------------

    def _expand_rel(self, F, rel: RelPat, counting: bool, metrics: Metrics):
        lid = self.schema.edge_label_id(rel.label)
        preds = normalize_preds(rel.preds)
        lo, hi = rel.min_hops, rel.max_hops
        if hi != INF_HOPS:
            # bounded: acc = sum/or over k in [lo, hi] (lo may be 0: identity)
            acc = F if lo == 0 else None
            cur = F
            for k in range(1, hi + 1):
                cur = self._hop(cur, lid, rel.direction, counting, metrics,
                                preds)
                if k >= lo:
                    if acc is None:
                        acc = cur
                    else:
                        acc = acc + cur if counting else acc | cur
                if not counting and bool(jnp.any(cur)) is False:
                    break
            return acc if acc is not None else jnp.zeros_like(F)
        # unbounded: boolean reach only (counting of infinite walk families
        # is undefined); the caller has already forced counting=False.
        assert not counting
        cur = F
        for _ in range(max(lo, 0)):
            cur = self._hop(cur, lid, rel.direction, False, metrics, preds)
        reach = cur
        frontier = cur
        for _ in range(self.cfg.max_closure_iters):
            if not bool(jnp.any(frontier)):
                break
            nxt = self._hop(frontier, lid, rel.direction, False, metrics,
                            preds)
            new = nxt & ~reach
            reach = reach | nxt
            frontier = new
        else:
            raise RuntimeError("closure did not converge within max_closure_iters")
        return reach

    # -- public API --------------------------------------------------------

    def source_ids(self, label_id: int, key: Optional[int],
                   preds: Tuple[PropPred, ...] = ()) -> np.ndarray:
        m = self.g.node_mask(label_id, key)
        if preds:
            m = m & node_pred_mask(self.g, preds)
        return np.flatnonzero(np.asarray(m)).astype(np.int32)

    def run_path(self, path: PathPattern, counting: Optional[bool] = None,
                 sources: Optional[np.ndarray] = None) -> ReachResult:
        """Evaluate a full path pattern; returns per-source reach + metrics."""
        if counting is None:
            counting = not any(r.unbounded for r in path.rels)
        if counting and any(r.unbounded for r in path.rels):
            counting = False  # set semantics for unbounded patterns

        start = path.start
        start_lid = self.schema.node_label_id(start.label)
        if sources is None:
            sources = self.source_ids(start_lid, start.key,
                                      normalize_preds(start.preds))
        sources = np.asarray(sources, np.int32)
        metrics = Metrics(db_hits=int(sources.shape[0]), rows=int(sources.shape[0]))

        S = sources.shape[0]
        N = self.g.node_cap
        blk = self.cfg.src_block
        S_pad = max(round_up(S, blk), blk)
        padded = np.full(S_pad, -1, np.int32)
        padded[:S] = sources

        out_rows = []
        for b0 in range(0, S_pad, blk):
            ids = jnp.asarray(padded[b0:b0 + blk])
            valid = ids >= 0
            cols = jnp.where(valid, ids, 0)
            if counting:
                F = jnp.zeros((blk, N), jnp.int32).at[
                    jnp.arange(blk), cols].add(valid.astype(jnp.int32))
            else:
                F = jnp.zeros((blk, N), bool).at[
                    jnp.arange(blk), cols].max(valid)
            # start-node constraints are implied by source selection; interior
            # and end node constraints interleave with rel expansion:
            for i, rel in enumerate(path.rels):
                F = self._expand_rel(F, rel, counting, metrics)
                nxt = path.nodes[i + 1]
                F = self._node_filter(
                    F, self.schema.node_label_id(nxt.label), nxt.key,
                    normalize_preds(nxt.preds))
            with span("mv4pg.exec.to_host"):
                out_rows.append(to_host(F, SESSION_PULLS))
        reach = np.concatenate(out_rows, axis=0)[:S].astype(np.int32)
        return ReachResult(src_ids=sources, reach=reach, counting=counting,
                           metrics=metrics)

    def run_query(self, query: Query) -> ReachResult:
        counting = False if query.force_bool else None
        return self.run_path(query.path, counting=counting)
