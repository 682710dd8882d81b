"""One run of one cell: set-up, warm-up, the timed window, the comparison
with the reference, and the numbers the metric readers read.

The window drives ``GraphSession.serve()`` with the load a traffic file
states (:class:`Loop`): closed-loop readers or an open loop of arrivals,
sources uniform or Zipf with a drifting hot set, bound or unbound reads,
and at most one writer, closed-loop or paced by read windows.  Every
input is drawn from the seed.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.lib import graphgen, reference as ref_mod, trace as trace_mod
from bench.lib.writes import KINDS, Writer, draw_pool, relabel_pool, replay

STALE_POLICY = "REFRESH STALENESS 1000000000"


@dataclass
class Run:
    """What the metric readers read."""

    cell: str
    setup_s: float
    window_s: float
    read_lat_s: np.ndarray
    writes_acked: int
    steps: Dict[str, Tuple[float, int]]      # step kind -> (seconds, count)
    read_tickets: int
    read_windows: int
    fences: int
    compiles_in_window: int
    views_build_s: float
    warm_s: float
    trace: Optional[trace_mod.Summary] = None


class Compiles:
    """Counts JAX tracing and backend-compile events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = 0

        def listen(event, duration, **_):
            if event in self.EVENTS:
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


class Schedule:
    """Per-stream request sequences: each stream takes the reads in shuffled
    rounds (every read once per round), each bound to one source from the
    read's start nodes, drawn by the traffic's ``sources`` policy
    (:func:`draw_sources`); a share ``unbound_share`` of the requests is
    sent unbound instead (every start node)."""

    def __init__(self, rng, readers: int, length: int, n_reads: int,
                 starts: List[np.ndarray], check_share: float,
                 sources: Optional[dict] = None, unbound_share: float = 0.0):
        rounds = -(-length // n_reads)
        q = np.stack([np.concatenate([rng.permutation(n_reads)
                                      for _ in range(rounds)])[:length]
                      for _ in range(readers)]) if readers else \
            np.zeros((0, length), np.int64)
        src = np.zeros_like(q)
        for qi in range(n_reads):
            m = q == qi
            src[m] = draw_sources(rng, starts[qi], np.nonzero(m)[1],
                                  sources or {"dist": "uniform"})
        self.q, self.src = q, src
        self.check = rng.random(q.shape) < check_share
        self.unbound = (rng.random(q.shape) < unbound_share
                        if unbound_share else np.zeros(q.shape, bool))
        self.length = length


def draw_sources(rng, nodes: np.ndarray, pos: np.ndarray, policy: dict
                 ) -> np.ndarray:
    """One source per request at schedule positions ``pos``.  ``uniform``;
    or ``zipf``: rank ``r`` drawn with weight ``r ** -a`` over a seeded
    ranking of ``nodes``, and with ``drift_every`` the ranking turns by a
    seeded step every that many requests, so the hot set moves."""
    if policy["dist"] == "uniform":
        return rng.choice(nodes, pos.shape[0])
    if policy["dist"] != "zipf":
        raise ValueError(f"unknown source policy {policy['dist']!r}")
    n = nodes.shape[0]
    w = np.arange(1, n + 1, dtype=np.float64) ** -policy["a"]
    rank = rng.choice(n, pos.shape[0], p=w / w.sum())
    ranking = rng.permutation(n)
    every = policy.get("drift_every") or 0
    turn = int(rng.integers(1, n)) if n > 1 else 0
    epoch = pos // every if every else np.zeros_like(pos)
    return nodes[ranking[(rank + epoch * turn) % n]]


class Arrivals:
    """Open-loop arrival times: a seeded Poisson process at ``rate`` per
    second, ``burst_factor`` times faster for the first ``burst_s`` of
    every ``burst_every_s`` (drawn by thinning, so a seed fixes them)."""

    def __init__(self, rng, rate: float, burst_every_s: float = 0.0,
                 burst_s: float = 0.0, burst_factor: float = 1.0):
        self.rng = rng
        self.rate = rate
        self.every, self.burst, self.factor = (burst_every_s, burst_s,
                                               burst_factor)
        self.peak = rate * max(burst_factor, 1.0)
        self.cand: Optional[Tuple[float, float]] = None
        self.t = 0.0

    def rate_at(self, t: float) -> float:
        if self.every and (t % self.every) < self.burst:
            return self.rate * self.factor
        return self.rate

    def due(self, until: float) -> List[float]:
        """The arrival times up to ``until`` seconds not given out yet."""
        out = []
        while True:
            if self.cand is None:
                self.cand = (self.t + self.rng.exponential(1.0 / self.peak),
                             self.rng.random())
            t, u = self.cand
            if t > until:
                return out
            self.t, self.cand = t, None
            if u * self.peak < self.rate_at(t):
                out.append(t)


class Loop:
    """The load over one serve engine.

    Readers are closed-loop clients, one request in flight each and no
    think time, or with ``arrival`` an open loop that submits each request
    of one stream at its arrival time.  Latency runs from submission (in
    an open loop, from arrival) to the end of the ``step()`` that answered
    the ticket.  The writer, if any, submits its next batch as soon as the
    last is acknowledged, or with ``write_every`` once that many read
    windows have run since."""

    def __init__(self, eng, queries, sched: Schedule,
                 writer: Optional[Writer], spans: "Spans", check_cap: int,
                 arrival: Optional[Arrivals] = None,
                 write_every: Optional[int] = None, rng=None):
        self.eng = eng
        self.queries = queries
        self.sched = sched
        self.writer = writer
        self.spans = spans
        self.check_cap = check_cap
        self.arrival = arrival
        self.write_every = write_every
        self.rng = rng if rng is not None else np.random.default_rng(0)
        self.pos = np.zeros(sched.q.shape[0], np.int64)
        # in flight: [ticket, stream, (qi, src, check, unbound), t, epoch]
        self.flight: List = []
        self.t_origin = 0.0
        self.wt = None
        self.win_at_ack = 0
        self.recording = False
        self.lat: List[float] = []
        self.samples: List[tuple] = []
        self.stale = 0
        self.acks = 0
        self.snap_at: set = set()
        self.snapshots: List[tuple] = []     # (epoch, graph)
        self.steps: Dict[str, List[float]] = {}

    def _read(self, i: int, t_sub: Optional[float] = None) -> list:
        s = self.sched
        k = int(self.pos[i] % s.length)
        self.pos[i] += 1
        qi, src = int(s.q[i, k]), int(s.src[i, k])
        unbound = bool(s.unbound[i, k])
        epoch = self.eng.epoch
        t = time.perf_counter() if t_sub is None else t_sub
        ticket = self.eng.submit(
            self.queries[qi],
            sources=None if unbound else np.array([src], np.int32))
        return [ticket, i, (qi, src, bool(s.check[i, k]), unbound), t, epoch]

    def _submit_write(self) -> None:
        kind, batch = self.writer.batch()
        self.wt = (kind, batch, self.eng.submit_writes(batch))

    def prime(self) -> None:
        self.t_origin = time.perf_counter()
        if self.arrival is None:
            self.flight = [self._read(i) for i in range(self.pos.shape[0])]
        if self.writer is not None:
            self._submit_write()

    def in_flight(self) -> int:
        return sum(1 for f in self.flight if not f[0].done) + (
            1 if self.wt is not None and not self.wt[2].done else 0)

    def finish_write(self) -> bool:
        kind, batch, t = self.wt
        if not t.done:
            return False
        self.writer.ack(kind, batch, t.write_result)
        self.wt = None
        self.win_at_ack = self.eng.stats.windows
        if self.recording:
            self.acks += 1
            if self.acks in self.snap_at:
                self.snapshots.append((self.eng.epoch, self.eng.sess.g))
        return True

    def _record(self, f: list, now: float) -> None:
        t, _, (qi, src, check, unbound), t_sub, epoch = f
        self.lat.append(now - t_sub)
        if t.window < epoch:
            self.stale += 1
        if check and len(self.samples) < self.check_cap:
            r = t.result
            row = int(self.rng.integers(0, r.reach.shape[0])) if unbound \
                else 0
            self.samples.append((qi, int(r.src_ids[row]) if unbound else src,
                                 np.array(r.reach[row]), t.window,
                                 r.reach.shape[0] if unbound else -1))

    def step(self) -> None:
        st = self.eng.stats
        w0, f0 = st.windows, st.write_batches
        t0 = time.perf_counter()
        with self.spans("bench.step"):
            self.eng.step()
        now = time.perf_counter()
        kind = ("read" if st.windows > w0 else
                "fence" if st.write_batches > f0 else "other")
        acc = self.steps.setdefault(kind, [0.0, 0])
        acc[0] += now - t0
        acc[1] += 1
        with self.spans("bench.clients"):
            for j, f in enumerate(self.flight):
                if not f[0].done:
                    continue
                if self.recording:
                    self._record(f, now)
                self.flight[j] = (self._read(f[1]) if self.arrival is None
                                  else None)
            if self.arrival is not None:
                self.flight = [f for f in self.flight if f is not None] + [
                    self._read(0, self.t_origin + a)
                    for a in self.arrival.due(now - self.t_origin)]
            if self.writer is not None:
                if self.wt is not None:
                    self.finish_write()
                if self.wt is None and (
                        self.write_every is None
                        or st.windows - self.win_at_ack >= self.write_every):
                    self._submit_write()


class Spans:
    """Harness spans: ``TraceAnnotation`` in traced runs, nothing else."""

    def __init__(self, annotate: bool):
        self.annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _load(base: graphgen.BaseGraph, slack: float, order: graphgen.BaseGraph):
    """Load ``base`` into the program, with label ids interned in the order
    labels first appear in ``order``: compiled plans carry label ids as
    constants, so every relabelled copy then compiles to the same programs
    (and finds them in the persistent cache)."""
    from repro.core import GraphBuilder, GraphSchema
    schema = GraphSchema()
    for lab in dict.fromkeys(order.node_label):
        schema.node_labels.intern(lab)
    for lab in dict.fromkeys(order.label):
        schema.edge_labels.intern(lab)
    b = GraphBuilder(schema)
    for lab in base.node_label:
        b.add_node(lab)
    for s, d, lab in zip(base.src.tolist(), base.dst.tolist(), base.label):
        b.add_edge(s, d, lab)
    return b.finalize(slack=slack), schema


def _designed_windows(sess, serve_cfg: dict, queries, starts, rows_list,
                      rng) -> None:
    """Windows of fixed composition that launch every block height the
    cell's windows can reach: ``r`` rows of every read together, then
    ``r`` rows of each read alone, for each ``r`` in ``rows_list``."""
    from repro.serve.engine import ServeConfig
    n = len(queries)
    for r in rows_list:
        srcs = [rng.choice(starts[qi], min(r, starts[qi].shape[0]),
                           replace=False) for qi in range(n)]
        for group in [list(range(n))] + [[qi] for qi in range(n)]:
            total = sum(srcs[qi].shape[0] for qi in group)
            eng = sess.serve(ServeConfig(**serve_cfg, window_init=total,
                                         window_min=total, window_max=total))
            for qi in group:
                for s in srcs[qi]:
                    eng.submit(queries[qi], sources=np.array([s], np.int32))
            eng.run()


def _peak_bytes() -> Optional[int]:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# comparison with the reference
# ---------------------------------------------------------------------------

def _graph_host(g) -> dict:
    return {k: np.asarray(getattr(g, k)) for k in
            ("edge_src", "edge_dst", "edge_label", "edge_alive",
             "edge_weight", "node_label", "node_alive")}


def _n_slots(h: dict, model) -> int:
    """The node id space of both sides: every id either can hold."""
    return max(h["node_label"].shape[0], model.n)


def _view_pairs_stored(h: dict, label_id: int, counting: bool, n_slots: int
                       ) -> Tuple[np.ndarray, np.ndarray, int]:
    """The view's alive arena edges as sorted ``pair_keys`` and counts
    (the weight of counting views, 1 otherwise), and how many pairs are
    stored more than once.  Of a pair stored twice the entry in the later
    slot stands, as the last one written into a dict would."""
    m = h["edge_alive"] & (h["edge_label"] == label_id)
    keys = ref_mod.pair_keys(h["edge_src"][m], h["edge_dst"][m], n_slots)
    counts = (h["edge_weight"][m].astype(np.int64) if counting
              else np.ones(keys.shape[0], np.int64))
    order = np.argsort(keys, kind="stable")
    keys, counts = keys[order], counts[order]
    new = keys[1:] != keys[:-1]
    first, last = np.ones((2, keys.shape[0]), bool)
    first[1:] = last[:-1] = new
    return keys[last], counts[last], int(np.count_nonzero(first & ~last))


def _key_diff(a: Tuple[np.ndarray, np.ndarray],
              b: Tuple[np.ndarray, np.ndarray]) -> int:
    """How many keys of the union have a different count on the two sides,
    a key on one side alone counting; each side is (sorted distinct int64
    keys, counts)."""
    (ka, ca), (kb, cb) = a, b
    at = np.searchsorted(kb, ka)
    hit = at < kb.shape[0]
    hit[hit] = kb[at[hit]] == ka[hit]
    same = int(np.count_nonzero(ca[hit] == cb[at[hit]]))
    return ka.shape[0] + kb.shape[0] - int(np.count_nonzero(hit)) - same


def _label_ids(registry, names: np.ndarray) -> Tuple[np.ndarray, int]:
    """The program's ids of label ``names`` (an object array), each name
    looked up once; a name the program never interned gets an id of its own
    past the program's.  Returns the ids and the size of the id space."""
    uniq, inv = np.unique(names, return_inverse=True)
    ids = np.empty(uniq.shape[0], np.int64)
    n = len(registry)
    for i, name in enumerate(uniq.tolist()):
        if name in registry:
            ids[i] = registry.id_of(name)
        else:
            ids[i], n = n, n + 1
    return ids[inv.reshape(-1)], n


def _base_diff(h: dict, schema, view_names, model) -> int:
    """Live base edges, as a multiset of (src, dst, label), and live nodes
    with their labels that differ between the program and the model."""
    n_slots = _n_slots(h, model)
    view_ids = [schema.edge_label_id(v) for v in view_names]
    m = h["edge_alive"] & ~np.isin(h["edge_label"], view_ids)
    src, dst, names = model.edge_arrays()
    lab, n_lab = _label_ids(schema.edge_labels, names)
    lab_got = h["edge_label"][m].astype(np.int64)
    if lab_got.size and (lab_got.min() < 0 or lab_got.max() >= n_lab):
        raise ValueError(f"an edge label id lies outside [0, {n_lab})")
    if n_slots ** 2 * n_lab > 2 ** 63:
        raise ValueError("too many node slots and labels to key edges")
    got = np.unique(ref_mod.pair_keys(h["edge_src"][m], h["edge_dst"][m],
                                      n_slots) * n_lab + lab_got,
                    return_counts=True)
    want = np.unique(ref_mod.pair_keys(src, dst, n_slots) * n_lab + lab,
                     return_counts=True)
    alive_got = np.flatnonzero(h["node_alive"])
    alive_want = np.flatnonzero(np.asarray(model.node_alive, bool))
    nodes_want = _label_ids(schema.node_labels, np.asarray(
        [model.node_label[i] for i in alive_want.tolist()], object))[0]
    return (_key_diff(got, want)
            + _key_diff((alive_got, h["node_label"][alive_got]
                         .astype(np.int64)), (alive_want, nodes_want)))


def _row_wrong(got: np.ndarray, want: np.ndarray, counting: bool) -> bool:
    n = want.shape[0]
    if got.shape[0] < n or np.any(got[n:] != 0):
        return True
    if counting:
        return not np.array_equal(got[:n].astype(np.int64), want)
    return not np.array_equal(got[:n] > 0, want > 0)


def compare(model0, log, samples, snapshots, paths, views, schema,
            reads_used) -> Dict[str, int]:
    """Replay the acknowledged writes over the reference model and compare
    every sampled read at the state it ran against, and every snapshot's
    base graph and view contents."""
    model = model0.copy()
    version = 0
    by_epoch: Dict[int, List[tuple]] = {}
    for s in samples:
        by_epoch.setdefault(s[3], []).append(s)
    snaps = {}
    for epoch, h in snapshots:
        snaps.setdefault(epoch, []).append(h)
    rows_wrong = views_wrong = views_duplicated = base_wrong = 0
    for epoch in sorted(set(by_epoch) | set(snaps)):
        while version < epoch:
            replay(model, log[version][1])
            version += 1
        ref = ref_mod.Reference(model)
        groups: Dict[int, List[tuple]] = {}
        for s in by_epoch.get(epoch, []):
            groups.setdefault(s[0], []).append(s)
        for qi, ss in groups.items():
            want = ref.rows(paths[qi], [s[1] for s in ss]).toarray()
            n_start = model.label_nodes(paths[qi].nodes[0][1]).shape[0]
            for s, w in zip(ss, want):
                rows_wrong += _row_wrong(s[2], w, paths[qi].counting)
                # an unbound read answers one row per live start node
                rows_wrong += int(s[4] >= 0 and s[4] != n_start)
        for h in snaps.get(epoch, []):
            base_wrong += _base_diff(h, schema, [v.name for v in views],
                                     model)
            n_slots = _n_slots(h, model)
            for v in views:
                *stored, dup = _view_pairs_stored(
                    h, schema.edge_label_id(v.name), v.path.counting, n_slots)
                views_wrong += _key_diff(stored, ref.view_pairs(v, n_slots))
                views_duplicated += dup
    seen = {s[0] for s in samples}
    return {"read_rows_wrong": rows_wrong,
            "view_pairs_wrong": views_wrong,
            "view_pairs_duplicated": views_duplicated,
            "base_items_wrong": base_wrong,
            "reads_unchecked": sum(1 for qi in reads_used if qi not in seen)}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run_cell(name: str, cfg: dict, tr: dict, seed: int, seconds: float,
             trace: bool, t_start: float, control: Optional[str] = None,
             save_events: Optional[str] = None) -> Tuple[Run, dict]:
    """Run one cell; returns the metric readers' :class:`Run` and the
    result fields (``correct``, ``attempted``, ``failed``, ``device``,
    ``checks``, optional ``breakdown``)."""
    import jax
    from repro.core import GraphSession, parse_query
    from repro.serve.engine import ServeConfig

    ss = np.random.SeedSequence(seed % 2 ** 63)
    rng_ids, rng_reads, rng_pool, rng_warm, rng_snap, rng_arrive, \
        rng_rows = [np.random.default_rng(c) for c in ss.spawn(7)]
    compiles = Compiles()
    spans = Spans(annotate=trace)
    t_phase = time.perf_counter()

    def phase(name: str) -> None:
        nonlocal t_phase
        now = time.perf_counter()
        _log(f"set-up: {name} {now - t_phase:.2f} s")
        t_phase = now

    dataset = graphgen.generate(cfg["generator"], cfg["dataset_seed"], cfg)
    base, perm = graphgen.relabel(dataset, rng_ids)

    def pool(k: int):
        m = ref_mod.GraphModel(dataset.node_label, dataset.src, dataset.dst,
                               dataset.label)
        fixed = draw_pool(m, cfg["writes"], k,
                          np.random.default_rng(cfg["dataset_seed"]))
        return relabel_pool(fixed, perm, rng_pool)

    model = ref_mod.GraphModel(base.node_label, base.src, base.dst,
                               base.label)
    model0 = model.copy()
    phase("generate")
    g, schema = _load(base, cfg["slack"], dataset)
    sess = GraphSession(g, schema)
    del base, g
    phase("load")

    view_texts = cfg["views"]
    if control == "stale_views":
        view_texts = [v.replace("REFRESH EXACT", STALE_POLICY)
                      for v in view_texts]
    elif control is not None:
        raise ValueError(f"unknown control {control!r}")
    t_views = time.perf_counter()
    for text in view_texts:
        sess.create_view(text)
    views_build_s = time.perf_counter() - t_views
    phase("views")
    views = [ref_mod.parse_view(v) for v in cfg["views"]]

    reads_used = (list(range(len(cfg["reads"]))) if tr["reads"] == "all"
                  else list(tr["reads"]))
    texts = [cfg["reads"][i] for i in reads_used]
    queries = [parse_query(t) for t in texts]
    paths = [ref_mod.parse_query(t) for t in texts]
    starts = [model.label_nodes(p.nodes[0][1]) for p in paths]
    arr = tr.get("arrival", {"kind": "closed"})
    if arr["kind"] not in ("closed", "open"):
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    sched = Schedule(rng_reads, tr["readers"] if arr["kind"] == "closed"
                     else min(tr["readers"], 1), tr["schedule_len"],
                     len(queries), starts, tr["check_share"],
                     tr.get("sources"), tr.get("unbound_share", 0.0))

    writer = None
    if tr["writers"]:
        writer = Writer(model, cfg["writes"], pool(tr["pool"]))
    eng = sess.serve(ServeConfig(**cfg["serve"]))
    if control == "stale_views" and writer is None:
        # a read-only cell has no write for stale views to miss: apply one
        # CE, DE and DV first, so the views lag the base graph
        writer = Writer(model, cfg["writes"], pool(1),
                        kinds=("CE", "DE", "DV"))
        for _ in range(3):
            kind, b = writer.batch()
            writer.ack(kind, b, eng.result(eng.submit_writes(b)))
        pre_log, writer = writer.log, None
    else:
        pre_log = []

    # -- warm-up: designed windows, then the cell's own loop until quiet
    t_warm = time.perf_counter()
    phase("traffic")
    c_warm = compiles.n
    _designed_windows(sess, cfg["serve"], queries, starts, tr["warm_rows"],
                      rng_warm)
    phase("warm.designed")
    arrival = None
    if arr["kind"] == "open":
        arrival = Arrivals(rng_arrive, arr["rate"],
                           arr.get("burst_every_s", 0), arr.get("burst_s", 0),
                           arr.get("burst_factor", 1))
    loop = Loop(eng, queries, sched, writer, spans, tr["check_cap"],
                arrival=arrival, write_every=tr.get("write_every_windows"),
                rng=rng_rows)
    loop.prime()
    w = tr["warm"]
    quiet = n_steps = 0
    t_note = time.perf_counter()
    while True:
        c0 = compiles.n
        loop.step()
        n_steps += 1
        quiet = quiet + 1 if compiles.n == c0 else 0
        # (under the control a fence may wait for ever: see writes.py)
        enough = (n_steps >= w["min_steps"] and quiet >= w["quiet_steps"]
                  and (writer is None or control is not None
                       or writer.passes >= 1))
        now = time.perf_counter()
        if enough or now - t_warm > w["max_s"]:
            break
        if now - t_note >= 10:
            # so that a run stopped inside a long warm-up says where it was
            t_note = now
            _log(f"warm-up so far: {n_steps} steps, {now - t_warm:.1f} s, "
                 f"quiet {quiet}, compile events {compiles.n - c_warm}")
    warm_s = time.perf_counter() - t_warm
    phase("warm.loop")
    _log(f"warm-up: {n_steps} steps, {warm_s:.2f} s, quiet {quiet}, "
         f"compile events {compiles.n - c_warm} (set-up {compiles.n})")

    # -- the window
    if writer is not None:
        a = int(rng_snap.integers(0, len(KINDS)))
        loop.snap_at = set(range(a + 1, a + 1 + len(KINDS)))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    st = eng.stats
    s0 = (st.windows, st.write_batches, st.queries)
    c0 = compiles.n
    loop.steps = {}
    with spans("bench.window"):
        t0 = time.perf_counter()
        loop.recording = True
        while True:
            loop.step()
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        loop.recording = False
    if trace:
        jax.profiler.stop_trace()
    compiles_in_window = compiles.n - c0
    d_windows, d_fences, d_queries = (st.windows - s0[0],
                                      st.write_batches - s0[1],
                                      st.queries - s0[2])
    in_flight = loop.in_flight()
    eng.run()
    if loop.wt is not None:
        loop.finish_write()
    failed = loop.in_flight()
    peak = _peak_bytes()

    summary = None
    if trace:
        events = trace_mod.events_from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if save_events:
            trace_mod.save_events(events, save_events)
        summary = trace_mod.reduce(events)
        del events

    run = Run(cell=name, setup_s=t0 - t_start, window_s=t1 - t0,
              read_lat_s=np.asarray(loop.lat), writes_acked=loop.acks,
              steps={k: (v[0], v[1]) for k, v in loop.steps.items()},
              read_tickets=d_queries, read_windows=d_windows,
              fences=d_fences, compiles_in_window=compiles_in_window,
              views_build_s=views_build_s, warm_s=warm_s, trace=summary)

    _log(f"window: {t1 - t0:.3f} s, reads {len(loop.lat)}, "
         f"windows {d_windows}, fences {d_fences}, compile events "
         f"{compiles_in_window}, samples {len(loop.samples)}, "
         f"states to compare {len(loop.snapshots) + 1}")

    # -- the comparison, with the program's state freed first
    t_ref = time.perf_counter()
    log = pre_log + (writer.log if writer is not None else [])
    snapshots = [(e, _graph_host(gr)) for e, gr in loop.snapshots]
    snapshots.append((eng.epoch, _graph_host(sess.g)))
    epochs_wrong = int(eng.epoch != len(log))
    samples = loop.samples
    kinds_seen = {log[e - 1][0] for e, _ in snapshots if e >= 1}
    stale, lat = loop.stale, np.asarray(loop.lat)
    del loop, eng, sess
    gc.collect()
    checks = compare(model0, log, samples, snapshots, paths, views, schema,
                     range(len(queries)) if tr["readers"] else [])
    checks["stale_reads"] = stale
    checks["bad_acks"] = model.bad_acks
    checks["epochs_wrong"] = epochs_wrong
    if writer is not None:
        checks["write_kinds_unchecked"] = len(set(KINDS) - kinds_seen)
    ref_s = time.perf_counter() - t_ref

    _log(f"reference {ref_s:.2f} s")
    if writer is not None and lat.size:
        _log(f"readers (not a metric): p50 {np.median(lat) * 1e3:.3f} ms, "
             f"p95 {np.percentile(lat, 95) * 1e3:.3f} ms")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    out = {"attempted": int(lat.size + run.writes_acked + in_flight),
           "failed": int(failed), "device": device,
           "checks": {k: {"value": int(v), "limit": 0}
                      for k, v in checks.items()}}
    out["correct"] = all(c["value"] <= c["limit"]
                         for c in out["checks"].values())
    if summary is not None:
        out["breakdown"] = summary.breakdown()
    return run, out
