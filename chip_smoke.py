#!/usr/bin/env python3
"""Drive the graph store's main path once on a TPU and check every answer.

    python3 chip_smoke.py [--seed 0] [--scale 1.0]   # one chip
    python3 chip_smoke.py --chips 4                  # sharded path only

One chip: an SNB-like graph generated from ``--seed`` (``snb_like`` default
sizes times ``--scale``) is loaded into a ``GraphSession``; the three SNB
views are created; the seven SNB reads run with and without views, and for
three seeded sources per read the rows are checked against a plain NumPy
walk-count / BFS reference; one write batch (edge creates, edge deletes,
a node delete) goes through ``apply_writes`` and every view must stay
consistent; a fenced serve replay must match sequential ``query`` calls
ticket for ticket.  FinBench repeats the load, views, reads and writes.

``--chips 4`` builds the SNB sessions with ``ExecConfig(data_shards=4)`` and
``data_shards=1`` in one process and requires identical rows and
DBHit/Rows for the reads, after a write batch, and through a serve replay.

Each phase prints a start line and an end line with its wall seconds and
the seconds JAX spent tracing, lowering and compiling in it (smoke
timings, not metrics).  Any failed check raises.  The last line of stdout
is ``{"ok": true, "device": {...}}``; without a TPU the script exits
non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N_CHECKED = 3          # seeded sources per read checked against the reference
SERVE_CLIENTS = 64     # point clients before the serve fence (two fingerprints)


# ---------------------------------------------------------------------------
# phase bookkeeping
# ---------------------------------------------------------------------------

class Phases:
    """Start/end lines per phase with wall and compile seconds."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.rows = []

        def listen(event, duration, **_):
            if event in self._EVENTS:
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(listen)

    @contextlib.contextmanager
    def __call__(self, name: str):
        print(f"[{name}] start", flush=True)
        t0, c0 = time.perf_counter(), self.compile_s
        yield
        wall, comp = time.perf_counter() - t0, self.compile_s - c0
        self.rows.append((name, wall, comp))
        print(f"[{name}] pass wall_s={wall:.2f} compile_s={comp:.2f} "
              f"peak_bytes_in_use={peak_bytes()}", flush=True)


def peak_bytes():
    """Largest ``peak_bytes_in_use`` over the devices (None if unreported)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def require(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# plain NumPy reference (nothing from repro.core.executor/plan/maintenance)
# ---------------------------------------------------------------------------

class Reference:
    """Walk counts (paths of bounded rels) or reachability (any unbounded
    rel) from one source, over host copies of the graph columns taken once
    at construction.  Base edges count once each."""

    def __init__(self, g, schema):
        self.schema = schema
        self.src = np.asarray(g.edge_src)
        self.dst = np.asarray(g.edge_dst)
        self.label = np.asarray(g.edge_label)
        self.alive = np.asarray(g.edge_alive)
        self.node_label = np.asarray(g.node_label)
        self.node_alive = np.asarray(g.node_alive)
        self.n = self.node_alive.shape[0]
        self._edges = {}

    def _label_edges(self, name: str):
        if name not in self._edges:
            m = self.alive & (self.label == self.schema.edge_label_id(name))
            self._edges[name] = (self.src[m], self.dst[m])
        return self._edges[name]

    def _hop(self, rel, vec: np.ndarray, counting: bool) -> np.ndarray:
        from repro.core.pattern import Direction
        src, dst = self._label_edges(rel.label)
        legs = {Direction.OUT: [(src, dst)], Direction.IN: [(dst, src)],
                Direction.BOTH: [(src, dst), (dst, src)]}[rel.direction]
        out = np.zeros(self.n, np.int64)
        for a, b in legs:
            out += np.bincount(b, weights=vec[a], minlength=self.n
                               ).astype(np.int64)
        return out if counting else (out > 0).astype(np.int64)

    def _expand(self, rel, vec: np.ndarray, counting: bool) -> np.ndarray:
        lo, hi = rel.min_hops, rel.max_hops
        if not rel.unbounded:
            acc = vec.copy() if lo == 0 else np.zeros(self.n, np.int64)
            cur = vec
            for k in range(1, hi + 1):
                cur = self._hop(rel, cur, counting)
                if k >= lo:
                    acc = acc + cur
            return acc if counting else (acc > 0).astype(np.int64)
        cur = vec
        for _ in range(lo):
            cur = self._hop(rel, cur, False)
        reach = cur > 0
        frontier = reach.copy()
        while frontier.any():
            nxt = self._hop(rel, frontier.astype(np.int64), False) > 0
            frontier = nxt & ~reach
            reach |= nxt
        return reach.astype(np.int64)

    def row(self, query, source: int) -> np.ndarray:
        path = query.path
        counting = not any(r.unbounded for r in path.rels)
        vec = np.zeros(self.n, np.int64)
        vec[source] = 1
        for rel, node in zip(path.rels, path.nodes[1:]):
            require(rel.label is not None and not rel.preds
                    and not node.preds and node.key is None,
                    f"reference covers labelled, predicate-free paths: "
                    f"{query.pretty()}")
            vec = self._expand(rel, vec, counting)
            keep = self.node_alive.copy()
            if node.label is not None:
                keep &= self.node_label == self.schema.node_label_id(
                    node.label)
            vec = np.where(keep, vec, 0)
        return vec


# ---------------------------------------------------------------------------
# datasets and write batches
# ---------------------------------------------------------------------------

def make_dataset(name: str, seed: int, scale: float):
    """``snb_like`` / ``finbench_like`` at their default sizes x ``scale``."""
    from repro.data.synthetic import finbench_like, snb_like
    s = lambda n: max(int(round(n * scale)), 1)  # noqa: E731
    if name == "snb":
        return snb_like(seed=seed, n_person=s(2000), n_post=s(1500),
                        n_comment=s(12000), n_place=s(60), n_tag=s(300))
    return finbench_like(seed=seed, n_account=s(4000), n_person=s(1500),
                         n_company=s(500), n_loan=s(800))


# (label, src kind, dst kind) of the edges each write batch creates, the
# labels of the edges it deletes, and the kind of the node it deletes: the
# paper's Q8 (create edge), Q9 (delete edge) and Q10 (delete node) shapes
WRITES = {
    "snb": dict(creates=[("replyOf", "comments", "posts"),
                         ("knows", "persons", "persons"),
                         ("hasTag", "posts", "tags")],
                deletes=["replyOf", "knows", "hasTag"],
                node="comments"),
    "finbench": dict(creates=[("transfer", "accounts", "accounts"),
                              ("transfer", "accounts", "accounts"),
                              ("apply", "persons", "loans")],
                     deletes=["transfer", "deposit", "own"],
                     node="accounts"),
}


def write_batch(name: str, sess, ids: dict, rng):
    """One seeded WriteBatch: edge creates, edge deletes, one node delete."""
    from repro.core import WriteBatch
    spec = WRITES[name]
    batch = WriteBatch()
    for label, a, b in spec["creates"]:
        u, v = rng.choice(ids[a]), rng.choice(ids[b])
        if u == v:
            v = ids[b][(ids[b].index(v) + 1) % len(ids[b])]
        batch.create_edge(int(u), int(v), label)
    alive = np.asarray(sess.g.edge_alive)
    labels = np.asarray(sess.g.edge_label)
    for label in spec["deletes"]:
        pool = np.flatnonzero(alive & (labels == sess.schema.edge_label_id(
            label)))
        batch.delete_edge(int(rng.choice(pool)))
    batch.delete_node(int(rng.choice(ids[spec["node"]])))
    return batch


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def same_result(got, want, ctx: str) -> None:
    """Rows and DBHit/Rows identical."""
    require(np.array_equal(got.src_ids, want.src_ids), f"sources differ {ctx}")
    require(got.reach.shape == want.reach.shape
            and np.array_equal(got.reach, want.reach), f"rows differ {ctx}")
    require(got.metrics.db_hits == want.metrics.db_hits,
            f"DBHit {got.metrics.db_hits} != {want.metrics.db_hits} {ctx}")
    require(got.metrics.rows == want.metrics.rows,
            f"Rows {got.metrics.rows} != {want.metrics.rows} {ctx}")


def check_reads(sess, reads, rng, tag: str) -> None:
    """Each read with and without views: equal pairs, and rows of seeded
    sources equal to the NumPy reference."""
    from repro.core.parser import parse_query
    ref = Reference(sess.g, sess.schema)
    for i, text in enumerate(reads):
        q = parse_query(text)
        base = sess.query(q, use_views=False)
        view = sess.query(q, use_views=True)
        require(np.array_equal(base.src_ids, view.src_ids),
                f"{tag} Q{i+1}: sources differ with views")
        bs, bd, bc = base.pairs()
        vs, vd, vc = view.pairs()
        require(np.array_equal(bs, vs) and np.array_equal(bd, vd),
                f"{tag} Q{i+1}: pair sets differ with views")
        if base.counting and view.counting:
            require(np.array_equal(bc, vc),
                    f"{tag} Q{i+1}: path counts differ with views")
        nonempty = base.src_ids[base.reach.any(axis=1)]
        pool = nonempty if nonempty.size >= N_CHECKED else base.src_ids
        picked = rng.choice(pool, size=min(N_CHECKED, pool.size),
                            replace=False)
        for s in picked:
            want = ref.row(q, int(s))
            for res, how in ((base, "base"), (view, "views")):
                got = res.reach[int(np.searchsorted(res.src_ids, s))]
                if res.counting:
                    ok = np.array_equal(got, want)
                else:
                    ok = np.array_equal(got > 0, want > 0)
                require(ok, f"{tag} Q{i+1} ({how}) source {s}: rows differ "
                            f"from the reference")
        print(f"  {tag} Q{i+1}: sources={base.src_ids.size} "
              f"pairs={bs.size} counting={base.counting} "
              f"dbhit base/views={base.metrics.db_hits}/"
              f"{view.metrics.db_hits} rows_bytes_host={base.reach.nbytes}",
              flush=True)
        del base, view


def load(name: str, args, cfg=None):
    from repro.core import GraphSession
    import jax
    g, schema, ids = make_dataset(name, args.seed, args.scale)
    sess = GraphSession(g, schema, cfg)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(sess.g))
    print(f"  {name}: node_cap={g.node_cap} edge_cap={g.edge_cap} "
          f"nodes={int(g.num_nodes())} edges={int(g.num_edges())} "
          f"graph_bytes_on_device={nbytes} peak_bytes_in_use={peak_bytes()}",
          flush=True)
    return sess, ids


def create_views(sess, views, tag: str) -> None:
    for text in views:
        v = sess.create_view(text)
        print(f"  {tag} view {v.name}: e_vl={v.stats().e_vl} "
              f"build_s={v.creation_seconds:.2f}", flush=True)


def check_consistency(sess, tag: str) -> None:
    for name in sess.views:
        require(sess.check_consistency(name),
                f"{tag}: view {name} inconsistent with its definition")


def serve_script(sess, ids, rng):
    """Reads from point clients over two SNB fingerprints, a fence, then
    more reads: ``[(kind, payload, sources)]``."""
    from repro.configs.mv4pg import SNB_WORKLOAD
    from repro.core import WriteBatch
    fps = [(SNB_WORKLOAD.reads[2], "persons"),    # knows -> knows
           (SNB_WORKLOAD.reads[4], "comments")]   # replyOf*1..2 -> hasTag
    ops = []

    def reads(n):
        for k in range(n):
            text, kind = fps[k % 2]
            ops.append(("read", text,
                        np.asarray([int(rng.choice(ids[kind]))], np.int32)))

    reads(SERVE_CLIENTS)
    p = ids["persons"]
    alive = np.asarray(sess.g.edge_alive)
    knows = np.flatnonzero(alive & (np.asarray(sess.g.edge_label)
                                    == sess.schema.edge_label_id("knows")))
    fence = (WriteBatch()
             .create_edge(int(p[0]), int(p[1]), "knows")
             .create_edge(int(rng.choice(ids["comments"])),
                          int(rng.choice(ids["posts"])), "replyOf")
             .delete_edge(int(rng.choice(knows))))
    ops.append(("write", fence, None))
    reads(SERVE_CLIENTS // 2)
    return ops


def run_serve(sess, ops):
    """Submit ``ops`` to ``sess.serve()`` and run; returns (tickets, stats)."""
    eng = sess.serve()
    tickets = [eng.submit(p, sources=s) if k == "read"
               else eng.submit_writes(p) for k, p, s in ops]
    return tickets, eng.run()


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_one_chip(args, phase) -> None:
    from repro.configs.mv4pg import WORKLOADS
    rng = np.random.default_rng(args.seed)
    for name in ("snb", "finbench"):
        wl = WORKLOADS[name]
        with phase(f"{name}.load"):
            sess, ids = load(name, args)
        with phase(f"{name}.views"):
            create_views(sess, wl.views, name)
        with phase(f"{name}.reads"):
            check_reads(sess, wl.reads, rng, name)
        with phase(f"{name}.writes"):
            sess.apply_writes(write_batch(name, sess, ids, rng))
            check_consistency(sess, name)
            check_reads(sess, wl.reads, rng, f"{name}+writes")
        if name == "snb":
            with phase("snb.serve"):
                serve_one_chip(sess, ids, rng)
        del sess


def serve_one_chip(sess, ids, rng) -> None:
    """Serve replay vs sequential ``query(..., sources=)`` at the same
    snapshot: reads before the fence are answered up front, reads after it
    once the engine has applied the fence."""
    ops = serve_script(sess, ids, rng)
    fence_at = next(i for i, (k, _, _) in enumerate(ops) if k == "write")
    before = [sess.query(p, sources=s) for _, p, s in ops[:fence_at]]
    tickets, stats = run_serve(sess, ops)
    after = [sess.query(p, sources=s) for _, p, s in ops[fence_at + 1:]]
    for t, want in zip(tickets[:fence_at] + tickets[fence_at + 1:],
                       before + after):
        same_result(t.result, want, f"serve ticket {t.uid} ({t.via})")
    check_consistency(sess, "snb+serve")
    print(f"  serve: {stats.summary()}", flush=True)


# ---------------------------------------------------------------------------
# four chips: sharded vs single-device in one process
# ---------------------------------------------------------------------------

def run_sharded(args, phase, shards: int) -> None:
    import jax
    from repro.configs.mv4pg import SNB_WORKLOAD as wl
    from repro.core import ExecConfig
    require(len(jax.devices()) >= shards,
            f"--chips {shards} needs {shards} devices, found "
            f"{len(jax.devices())}")
    with phase("sharded.load"):
        one, ids = load("snb", args)
        many, _ = load("snb", args, ExecConfig(data_shards=shards))
        mesh = many.engine.mesh()
        devs = list(mesh.devices.flat)
        require(len(set(devs)) == shards
                and set(devs) == set(jax.devices()[:shards]),
                f"mesh spans {devs}, not {shards} distinct chips")
    with phase("sharded.views"):
        create_views(one, wl.views, "1-chip")
        create_views(many, wl.views, f"{shards}-chip")
        lid = many.schema.edge_label_id("replyOf")
        placed = many.engine.sharded_label_edges(lid, False)[0]
        on = {sh.device for sh in placed.addressable_shards}
        require(len(on) == shards,
                f"sharded replyOf slice lives on {len(on)} device(s)")
        print(f"  mesh devices: {[str(d) for d in devs]}; replyOf slice "
              f"on {len(on)} devices", flush=True)

    def parity(tag):
        for i, text in enumerate(wl.reads):
            same_result(many.query(text), one.query(text),
                        f"{tag} Q{i+1} {shards}-chip vs 1-chip")
        print(f"  {tag}: 7 reads identical (rows, DBHit, Rows)", flush=True)

    with phase("sharded.reads"):
        parity("sharded")
    with phase("sharded.writes"):
        rng_a = np.random.default_rng(args.seed)
        rng_b = np.random.default_rng(args.seed)
        one.apply_writes(write_batch("snb", one, ids, rng_a))
        many.apply_writes(write_batch("snb", many, ids, rng_b))
        check_consistency(one, "1-chip+writes")
        check_consistency(many, f"{shards}-chip+writes")
        parity("sharded+writes")
    with phase("sharded.serve"):
        ops_a = serve_script(one, ids, np.random.default_rng(args.seed + 1))
        ops_b = serve_script(many, ids, np.random.default_rng(args.seed + 1))
        ta, sa = run_serve(one, ops_a)
        tb, sb = run_serve(many, ops_b)
        for a, b in zip(ta, tb):
            if a.kind == "read":
                same_result(b.result, a.result, f"serve ticket {a.uid}")
        print(f"  serve 1-chip: {sa.summary()}", flush=True)
        print(f"  serve {shards}-chip: {sb.summary()}", flush=True)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="multiplies every node count of both generators")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded path and its 1-chip twin")
    args = ap.parse_args(argv)

    import jax
    t0 = time.perf_counter()
    print("[device] start", flush=True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    print(f"[device] pass wall_s={time.perf_counter() - t0:.2f} "
          f"platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)

    from repro.utils.compile_cache import enable_compile_cache
    print(f"  compile cache: {enable_compile_cache()}", flush=True)
    phase = Phases()
    if args.chips == 1:
        run_one_chip(args, phase)
    else:
        run_sharded(args, phase, args.chips)
    print(f"phases: {len(phase.rows)} passed, "
          f"wall_s={sum(r[1] for r in phase.rows):.1f} "
          f"compile_s={sum(r[2] for r in phase.rows):.1f} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
