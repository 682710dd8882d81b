"""Shared workload driver mirroring the paper's evaluation protocol (§VI).

Per dataset: 7 read statements + 3 write statements (create edge / delete
edge / delete node, each followed by a recover statement restoring the
database), executed with and without materialized views.  Reads average over
``repeats`` runs (paper: 5); maintenance metrics come from the session.

``--serve`` replays the same mixed read/write workload as a *serving
stream* through :class:`~repro.serve.engine.ServeEngine` — many logical
clients per read statement, write fences between rounds — and reports
throughput (queries/s) plus group-occupancy stats::

    PYTHONPATH=src python -m benchmarks.workload_driver --serve \
        --dataset snb --small --clients 32 --rounds 3 --seed 0

``--freshness {exact,deferred,<N>}`` runs every view under the chosen
refresh policy (DESIGN.md §11); an integer selects ``REFRESH STALENESS N``.

``--devices N`` runs the workload sharded over ``N`` forced host devices
(DESIGN.md §12): sessions execute with ``ExecConfig(data_shards=N)`` on an
N-way data mesh.  XLA fixes the device count at first jax import, so the
flag is honored by scanning ``sys.argv`` *before* importing jax below —
``--devices`` therefore only works as a CLI flag of this module (callers
embedding :func:`run_serve_workload` must set XLA_FLAGS themselves).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple


def _early_devices() -> int:
    for i, a in enumerate(sys.argv):
        if a == "--devices" and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return 1


_N_DEVICES = _early_devices()
if (_N_DEVICES > 1 and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_N_DEVICES}").strip()

import jax  # noqa: E402  (XLA_FLAGS must be set above, before first import)
import numpy as np  # noqa: E402

from repro.configs.mv4pg import WorkloadConfig  # noqa: E402
from repro.core import ExecConfig, GraphSession  # noqa: E402
from repro.core import graph as G  # noqa: E402


@dataclass
class QueryResult:
    name: str
    ori_s: float
    opt_s: float
    rewrite_s: float
    speedup: float
    n_results_ori: int
    n_results_opt: int


@dataclass
class WorkloadReport:
    dataset: str
    view_creation_s: Dict[str, float]
    queries: List[QueryResult]
    w_ori: float = 0.0
    w_opt: float = 0.0
    mv_total: float = 0.0
    engine_hits: int = 0       # persistent-engine cache hits over the run
    engine_misses: int = 0
    plan_hits: int = 0         # compiled-plan cache hits over the run
    plan_misses: int = 0
    rewrite_total_s: float = 0.0    # Algorithm-3 rewrite time actually paid
    rewrite_amortized_s: float = 0.0  # rewrite_total_s / query executions:
    #                                   → ~0 as repeats hit the plan cache

    @property
    def workload_speedup(self) -> float:
        return self.w_ori / self.w_opt if self.w_opt else 0.0

    @property
    def workload_speedup_with_mv(self) -> float:
        return self.w_ori / (self.mv_total + self.w_opt) if self.w_opt else 0.0


def _time(fn, repeats: int) -> Tuple[float, object]:
    out = fn()  # warmup (compile caches)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn()
    return (time.perf_counter() - t0) / repeats, out


def _write_targets(sess: GraphSession, rng):
    """Pick a base edge to delete, endpoints for a new edge, and a node."""
    alive = np.flatnonzero(np.asarray(sess.g.edge_alive))
    # base edges only (exclude view labels)
    view_lids = {v.label_id for v in sess.views.values()}
    labels = np.asarray(sess.g.edge_label)[alive]
    base = alive[~np.isin(labels, list(view_lids))] if view_lids else alive
    eid = int(rng.choice(base))
    src = int(sess.g.edge_src[eid])
    dst = int(sess.g.edge_dst[eid])
    elabel = sess.schema.edge_labels.name_of(int(sess.g.edge_label[eid]))
    nodes = np.flatnonzero(np.asarray(sess.g.node_alive))
    nid = int(rng.choice(nodes))
    return eid, (src, dst, elabel), nid


def run_workload(g, schema, wl: WorkloadConfig, repeats: int = 3,
                 seed: int = 0, cfg: ExecConfig | None = None,
                 refresh: str = "", build: str = "unfused") -> WorkloadReport:
    """``refresh`` is an optional ``REFRESH ...`` clause suffix appended to
    every view definition (DESIGN.md §11), e.g. ``" REFRESH DEFERRED"``.
    ``build`` selects the view materialization path timed into Table III:
    ``"unfused"`` (the paper's per-source host-synced loop — the committed
    baseline) or ``"fused"`` (one compiled program per build,
    DESIGN.md §13)."""
    rng = np.random.default_rng(seed)
    sess = GraphSession(g, schema, cfg or ExecConfig())
    report = WorkloadReport(dataset=wl.name, view_creation_s={}, queries=[])

    # ---- reads without views -------------------------------------------
    ori_times = []
    ori_counts = []
    for q in wl.reads:
        t, res = _time(lambda q=q: sess.query(q, use_views=False), repeats)
        ori_times.append(t)
        ori_counts.append(res.num_results())

    # ---- create views (Table III) --------------------------------------
    for vtext in wl.views:
        view = sess.create_view(vtext + refresh, fused=(build == "fused"))
        report.view_creation_s[view.name] = view.creation_seconds
    report.mv_total = sum(report.view_creation_s.values())

    # ---- reads with views ----------------------------------------------
    for i, q in enumerate(wl.reads):
        t, res = _time(lambda q=q: sess.query(q, use_views=True), repeats)
        report.queries.append(QueryResult(
            name=f"Q{i+1}", ori_s=ori_times[i], opt_s=t,
            rewrite_s=sess.last_rewrite_seconds,
            speedup=ori_times[i] / t if t else 0.0,
            n_results_ori=ori_counts[i], n_results_opt=res.num_results()))

    # ---- writes: CE, DE, DV with recover (Q8-Q10) -----------------------
    eid, (src, dst, elabel), nid = _write_targets(sess, rng)

    def ce_with():
        slot = sess.create_edge(src, dst, elabel)   # maintained
        sess.delete_edge(slot)                      # recover
    def ce_without():
        # raw functional mutation on a local graph value: the create+delete
        # pair is a net no-op, so the session engine's caches stay warm
        g_tmp = sess.g
        slot = int(G.free_edge_slots(g_tmp, 1)[0])
        lid = sess.schema.edge_labels.intern(elabel)
        g_tmp = G.create_edge(g_tmp, slot, src, dst, lid)
        g_tmp = G.delete_edge(g_tmp, slot)
        jax.block_until_ready(g_tmp.edge_alive)

    cur_eid = [eid]

    def de_with():
        sess.delete_edge(cur_eid[0])
        cur_eid[0] = sess.create_edge(src, dst, elabel)  # recover (new slot)

    def de_without():
        g_tmp = G.delete_edge(sess.g, cur_eid[0])
        lid = sess.schema.edge_labels.intern(elabel)
        g_tmp = G.create_edge(g_tmp, cur_eid[0], src, dst, lid)
        jax.block_until_ready(g_tmp.edge_alive)

    # node delete: maintained delete+recover on the live session; the raw
    # (no-views) timing runs on a throwaway copy so views stay consistent
    def dv_pair():
        import jax
        inc = [(int(e), int(sess.g.edge_src[e]), int(sess.g.edge_dst[e]),
                int(sess.g.edge_label[e]))
               for e in np.flatnonzero(
                   (np.asarray(sess.g.edge_src) == nid)
                   | (np.asarray(sess.g.edge_dst) == nid))
               if bool(sess.g.edge_alive[e])]
        nlabel = int(sess.g.node_label[nid])
        nkey = int(sess.g.node_key[nid])
        t0 = time.perf_counter()
        sess.delete_node(nid)
        t_with = time.perf_counter() - t0
        # recover (maintained): re-create node, re-add base edges
        view_lids = {v.label_id for v in sess.views.values()}
        sess.g = G.create_node(sess.g, nid, nlabel, nkey)
        for e, s_, d_, l_ in inc:
            if l_ in view_lids:
                continue  # view edges re-derive via maintenance
            sess.create_edge(s_, d_, sess.schema.edge_labels.name_of(l_))
        # raw timing (functional update on a copy; session graph untouched)
        t0 = time.perf_counter()
        g_tmp = G.delete_node(sess.g, nid)
        jax.block_until_ready(g_tmp.edge_alive)
        t_without = time.perf_counter() - t0
        return t_with, t_without

    t_ce_w, _ = _time(ce_with, repeats)
    t_ce_o, _ = _time(ce_without, repeats)
    t_de_w, _ = _time(de_with, repeats)
    t_de_o, _ = _time(de_without, repeats)
    t_dv_w, t_dv_o = dv_pair()
    for name, tw, to in [("Q8(CE)", t_ce_w, t_ce_o),
                         ("Q9(DE)", t_de_w, t_de_o),
                         ("Q10(DV)", t_dv_w, t_dv_o)]:
        report.queries.append(QueryResult(
            name=name, ori_s=to, opt_s=tw, rewrite_s=0.0,
            speedup=to / tw if tw else 0.0,
            n_results_ori=0, n_results_opt=0))

    report.w_ori = sum(q.ori_s for q in report.queries)
    report.w_opt = sum(q.opt_s for q in report.queries)
    report.engine_hits = sess.engine.hits
    report.engine_misses = sess.engine.misses
    report.plan_hits = sess.planner.plan_hits
    report.plan_misses = sess.planner.plan_misses
    report.rewrite_total_s = sess.planner.rewrite_seconds_total
    report.rewrite_amortized_s = (
        sess.planner.rewrite_seconds_total / max(sess.planner.plan_calls, 1))
    # paper's consistency verification (§VI-C); non-exact views must be
    # drained first — stale-by-design queues fail the exactness check
    sess.drain_all()
    for vname in list(sess.views):
        assert sess.check_consistency(vname), f"{vname} inconsistent!"
    return report


# ---------------------------------------------------------------------------
# serving replay (--serve): the same workload as a many-client stream
# ---------------------------------------------------------------------------

@dataclass
class ServeReport:
    """Throughput + batching stats of one serving replay."""

    dataset: str
    queries: int               # read tickets served
    windows: int
    write_batches: int
    serve_s: float             # wall time of the batched serve run
    seq_s: float               # wall time of the per-query sequential replay
    qps: float                 # queries / serve_s
    speedup: float             # seq_s / serve_s (reads + writes)
    mean_group_size: float
    occupancy: float
    executions: int            # unique bindings evaluated (after dedup)
    mean_window_size: float = 0.0   # tickets per executed window
    deadline_misses: int = 0   # tickets admitted past their deadline
    share_rate: float = 0.0    # groups served via shared structural programs
    memo_hits: int = 0         # tickets answered from the cross-window memo
    gathers: int = 0           # tickets answered by row-subsumption gather
    hoisted: int = 0           # tickets served ahead of a pending fence

    def summary(self) -> str:
        return (f"{self.dataset}: {self.queries} queries in "
                f"{self.serve_s:.3f}s = {self.qps:.0f} q/s "
                f"({self.speedup:.2f}x vs sequential {self.seq_s:.3f}s); "
                f"windows={self.windows} writes={self.write_batches} "
                f"mean_group={self.mean_group_size:.1f} "
                f"mean_window={self.mean_window_size:.1f} "
                f"occupancy={self.occupancy:.2f} "
                f"executions={self.executions} memo={self.memo_hits} "
                f"gathers={self.gathers} hoisted={self.hoisted} "
                f"share_rate={self.share_rate:.2f} "
                f"deadline_misses={self.deadline_misses}")


def _serve_script(sess: GraphSession, wl: WorkloadConfig, clients: int,
                  rounds: int, rng) -> List[Tuple]:
    """Ordered op stream: per round, every read statement is issued once
    unbound plus once per client bound to a random start-label node; one
    write fence (delete + re-create a base edge) closes each round.  All
    targets are resolved against the *initial* graph, so the same script
    replays identically on a twin session."""
    from repro.core.parser import parse_query

    n_alive = np.flatnonzero(np.asarray(sess.g.node_alive))
    label_sources: Dict[str, np.ndarray] = {}
    for q in wl.reads:
        lbl = parse_query(q).path.start.label
        if lbl not in label_sources:
            lid = sess.schema.node_label_id(lbl)
            ids = np.flatnonzero(np.asarray(sess.g.node_mask(lid)))
            label_sources[lbl] = ids if ids.size else n_alive
    # fences target base edges only: view edges are maintained state
    alive_e = np.flatnonzero(np.asarray(sess.g.edge_alive))
    lab = np.asarray(sess.g.edge_label)[alive_e]
    view_lids = [v.label_id for v in sess.views.values()]
    base_e = alive_e[~np.isin(lab, view_lids)] if view_lids else alive_e
    fence_eids = rng.choice(base_e, size=rounds, replace=False)

    # pre-parse once: both replay paths receive Query objects, so the
    # serve-vs-sequential comparison times execution, not string parsing
    parsed = {q: parse_query(q) for q in wl.reads}
    ops: List[Tuple] = []
    for r in range(rounds):
        for q in wl.reads:
            ops.append(("read", parsed[q], None))
            pool = label_sources[parsed[q].path.start.label]
            for _ in range(clients):
                src = np.asarray([int(rng.choice(pool))], np.int32)
                ops.append(("read", parsed[q], src))
        eid = int(fence_eids[r])
        u = int(sess.g.edge_src[eid])
        v = int(sess.g.edge_dst[eid])
        lbl = sess.schema.edge_labels.name_of(int(sess.g.edge_label[eid]))
        # delete + logically re-create: the graph stays near its initial
        # state while every fence still triggers real view maintenance
        ops.append(("write", G.WriteBatch(edge_deletes=[eid])
                    .create_edge(u, v, lbl), None))
    return ops


def run_serve_workload(make_dataset: Callable[[], Tuple], wl: WorkloadConfig,
                       clients: int = 32, rounds: int = 3, seed: int = 0,
                       cfg: ExecConfig | None = None,
                       refresh: str = "",
                       sequential: bool = True) -> ServeReport:
    """Replay the workload through the serve engine and sequentially on a
    twin session; returns throughput and batching stats.

    ``make_dataset`` must build identical ``(graph, schema, ...)`` twins on
    every call (deterministic seed) — the sequential replay needs its own
    session so write fences land on equal state.  Row parity is spot-checked
    on result cardinality + DBHit/Rows per read (the exact row-for-row
    oracle lives in ``tests/test_serve.py``).  ``refresh`` appends a
    ``REFRESH ...`` clause to every view on both twins (DESIGN.md §11):
    fences then enqueue instead of maintaining, and both replay paths drain
    at the same first-conflicting-read points, so parity still holds.

    ``sequential=False`` skips the twin replay and its per-ticket parity
    check (``seq_s``/``speedup`` report 0) — used by the scaling curve,
    where only batched-serve qps matters and parity is covered by
    ``tests/test_sharded.py``.  Drain + view-consistency still run.
    """
    rng = np.random.default_rng(seed)
    ds = make_dataset()
    sess = GraphSession(ds[0], ds[1], cfg or ExecConfig())
    for vtext in wl.views:
        sess.create_view(vtext + refresh)
    ops = _serve_script(sess, wl, clients, rounds, rng)

    # ---- batched serve run (timer covers submission + drain, so the
    # two paths pay symmetric per-request overhead) ----------------------
    eng = sess.serve()
    tickets = []
    t0 = time.perf_counter()
    for kind, payload, src in ops:
        tickets.append(eng.submit(payload, sources=src) if kind == "read"
                       else eng.submit_writes(payload))
    stats = eng.run()
    serve_s = time.perf_counter() - t0

    # ---- sequential replay on the twin ---------------------------------
    seq_s = 0.0
    if sequential:
        ds2 = make_dataset()
        sess2 = GraphSession(ds2[0], ds2[1], cfg or ExecConfig())
        for vtext in wl.views:
            sess2.create_view(vtext + refresh)
        t0 = time.perf_counter()
        seq = []
        for kind, payload, src in ops:
            if kind == "read":
                r = sess2.query(payload, sources=src)
                seq.append((r.num_results(), r.metrics.db_hits,
                            r.metrics.rows))
            else:
                sess2.apply_writes(payload)
                seq.append(None)
        seq_s = time.perf_counter() - t0

        for t, want in zip(tickets, seq):
            if want is None:
                continue
            got = (t.result.num_results(), t.result.metrics.db_hits,
                   t.result.metrics.rows)
            assert got == want, (
                f"serve replay diverged from sequential on uid={t.uid}: "
                f"{got} != {want}")
    sess.drain_all()     # non-exact views: flush queues before the oracle
    for vname in list(sess.views):
        assert sess.check_consistency(vname), f"{vname} inconsistent!"

    return ServeReport(
        dataset=wl.name, queries=stats.queries, windows=stats.windows,
        write_batches=stats.write_batches, serve_s=serve_s, seq_s=seq_s,
        qps=stats.queries / serve_s if serve_s else 0.0,
        speedup=seq_s / serve_s if serve_s else 0.0,
        mean_group_size=stats.mean_group_size, occupancy=stats.occupancy,
        executions=stats.executions,
        mean_window_size=stats.mean_window_size,
        deadline_misses=stats.deadline_misses,
        share_rate=stats.share_rate, memo_hits=stats.memo_hits,
        gathers=stats.gathers, hoisted=stats.hoisted)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main() -> None:
    from repro.configs.mv4pg import WORKLOADS
    from repro.data.synthetic import finbench_like, snb_like

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--serve", action="store_true",
                    help="replay the workload through the ServeEngine")
    ap.add_argument("--dataset", default="snb", choices=("snb", "finbench"))
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--clients", type=int, default=32,
                    help="point clients per read statement per round")
    ap.add_argument("--rounds", type=int, default=3,
                    help="read windows (each closed by a write fence)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--freshness", default="exact",
                    help="view refresh policy: 'exact', 'deferred', or an "
                         "integer staleness bound (REFRESH STALENESS N)")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard execution over N forced host devices "
                         "(ExecConfig.data_shards=N; sets XLA_FLAGS before "
                         "jax import)")
    ap.add_argument("--no-sequential", action="store_true",
                    help="--serve only: skip the sequential twin replay "
                         "(faster; reports qps without speedup)")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.devices != _N_DEVICES:   # argparse and the early scan disagree
        raise SystemExit("--devices must be scannable from argv before "
                         "jax import; got inconsistent values")
    if args.devices > 1 and len(jax.devices()) < args.devices:
        raise SystemExit(
            f"--devices {args.devices} but only {len(jax.devices())} jax "
            "devices exist (XLA_FLAGS was set too late — is jax already "
            "imported via sitecustomize?)")
    cfg = (ExecConfig(data_shards=args.devices) if args.devices > 1
           else None)

    if args.freshness == "exact":
        refresh = ""
    elif args.freshness == "deferred":
        refresh = " REFRESH DEFERRED"
    else:
        refresh = f" REFRESH STALENESS {int(args.freshness)}"

    scale = 0.25 if args.small else 0.4
    if args.dataset == "snb":
        def make():
            return snb_like(seed=args.seed, n_person=int(2000 * scale),
                            n_post=int(1500 * scale),
                            n_comment=int(12000 * scale),
                            n_place=60, n_tag=300)
    else:
        def make():
            return finbench_like(seed=args.seed,
                                 n_account=int(4000 * scale),
                                 n_person=int(1500 * scale),
                                 n_company=int(500 * scale),
                                 n_loan=int(800 * scale))

    wl = WORKLOADS[args.dataset]
    if args.serve:
        rep = run_serve_workload(make, wl, clients=args.clients,
                                 rounds=args.rounds, seed=args.seed,
                                 cfg=cfg, refresh=refresh,
                                 sequential=not args.no_sequential)
        print(rep.summary())
        print(f"QPS {rep.qps:.3f}")   # machine-readable (scaling curve)
        return
    g, schema, _ = make()
    rep = run_workload(g, schema, wl, repeats=args.repeats, seed=args.seed,
                       cfg=cfg, refresh=refresh)
    for q in rep.queries:
        print(f"{q.name}: ori={q.ori_s*1e3:.2f}ms opt={q.opt_s*1e3:.2f}ms "
              f"speedup={q.speedup:.2f}")
    print(f"workload: W_ori/W_opt={rep.workload_speedup:.2f} "
          f"plan_hits={rep.plan_hits} plan_misses={rep.plan_misses}")


if __name__ == "__main__":
    main()
