"""The plain reference: path queries and views over a host model of the
base graph, with nothing taken from the program under test.

It parses the restricted Cypher the configurations use (labelled nodes,
labelled relationships with an optional hop range, no predicates) and
evaluates a path from a block of sources with SciPy sparse products:

* a path with no unbounded relationship counts walks (each base edge once,
  parallel edges separately);
* a path with an unbounded relationship gives reachability;
* after every relationship the row keeps only live nodes of the next
  node's label (intermediate hops of one ranged relationship are not
  filtered).

Explicit sources are taken as given, as the program's ``sources=`` binding
does.  View contents are the path's rows from every live node of the start
label, oriented as the view's CONSTRUCT says.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np
import scipy.sparse as sp

INF = None   # max_hops of an unbounded relationship


@dataclass(frozen=True)
class Rel:
    label: str
    direction: str          # "out" | "in" | "both"
    lo: int
    hi: Optional[int]       # None: unbounded


@dataclass(frozen=True)
class Path:
    nodes: Tuple[Tuple[str, Optional[str]], ...]     # (var, label)
    rels: Tuple[Rel, ...]

    @property
    def counting(self) -> bool:
        return all(r.hi is not INF for r in self.rels)


@dataclass(frozen=True)
class View:
    name: str
    forward: bool           # CONSTRUCT (start)->(end)
    path: Path


_NODE = re.compile(r"\(\s*(\w*)\s*(?::\s*(\w+))?\s*\)")
_REL = re.compile(r"(<)?-\[\s*\w*\s*(?::\s*(\w+))?\s*(\*\s*(\d*)\s*(\.\.)?"
                  r"\s*(\d*))?\s*\]-(>)?")


def parse_path(text: str) -> Path:
    text = text.strip()
    nodes, rels = [], []
    m = _NODE.match(text)
    if not m:
        raise ValueError(f"path must start with a node: {text!r}")
    nodes.append((m.group(1), m.group(2)))
    pos = m.end()
    while pos < len(text):
        r = _REL.match(text, pos)
        if not r:
            raise ValueError(f"cannot parse relationship at {text[pos:]!r}")
        left, label, star, a, dots, b, right = r.groups()
        if label is None:
            raise ValueError("the reference covers labelled relationships")
        if star is None:
            lo, hi = 1, 1
        elif dots is None:
            lo = hi = int(a) if a else None
            if lo is None:
                lo, hi = 1, INF
        else:
            lo = int(a) if a else 1
            hi = int(b) if b else INF
        direction = ("both" if bool(left) == bool(right)
                     else "in" if left else "out")
        rels.append(Rel(label, direction, lo, hi))
        n = _NODE.match(text, r.end())
        if not n:
            raise ValueError(f"relationship must end at a node: {text!r}")
        nodes.append((n.group(1), n.group(2)))
        pos = n.end()
    return Path(tuple(nodes), tuple(rels))


def parse_query(text: str) -> Path:
    m = re.match(r"\s*MATCH\s+(.*?)\s+RETURN\b", text, re.S)
    if not m:
        raise ValueError(f"not a MATCH ... RETURN query: {text!r}")
    return parse_path(m.group(1))


def parse_view(text: str) -> View:
    m = re.match(r"\s*CREATE\s+VIEW\s+(\w+)\s+AS\s*\(\s*CONSTRUCT\s*"
                 r"\(\s*(\w+)\s*\)\s*-\[[^\]]*\]->\s*\(\s*(\w+)\s*\)\s*"
                 r"MATCH\s+(.*)\)\s*(REFRESH\b.*)?$", text, re.S)
    if not m:
        raise ValueError(f"not a CREATE VIEW statement: {text!r}")
    name, a, b, body = m.group(1), m.group(2), m.group(3), m.group(4)
    path = parse_path(body)
    start, end = path.nodes[0][0], path.nodes[-1][0]
    if (a, b) not in ((start, end), (end, start)):
        raise ValueError(f"CONSTRUCT endpoints of {name} are not the path's")
    return View(name, (a, b) == (start, end), path)


class GraphModel:
    """Host model of the base graph as acknowledged writes leave it.

    Edges are keyed by the arena slot the program acknowledged for them,
    so a delete by slot removes what that slot held in the model.  An
    acknowledgement that cannot be true (a slot handed out while it holds a
    live edge, a node created while alive, a delete of an empty slot) is
    counted in ``bad_acks`` and the model goes on as the write said."""

    def __init__(self, node_label: List[str], src, dst, label: List[str]):
        self.node_label = list(node_label)
        self.node_alive = [True] * len(node_label)
        self.edges: Dict[int, Tuple[int, int, str]] = {
            i: (int(s), int(d), lab)
            for i, (s, d, lab) in enumerate(zip(src, dst, label))}
        self._incident: Dict[int, set] = {}
        self._by_triple: Dict[Tuple[int, int, str], set] = {}
        for slot, e in self.edges.items():
            self._index(slot, e)
        self.bad_acks = 0

    def copy(self) -> "GraphModel":
        m = GraphModel.__new__(GraphModel)
        m.node_label = list(self.node_label)
        m.node_alive = list(self.node_alive)
        m.edges = dict(self.edges)
        m._incident = {k: set(v) for k, v in self._incident.items()}
        m._by_triple = {k: set(v) for k, v in self._by_triple.items()}
        m.bad_acks = self.bad_acks
        return m

    def _index(self, slot, e):
        self._incident.setdefault(e[0], set()).add(slot)
        self._incident.setdefault(e[1], set()).add(slot)
        self._by_triple.setdefault(e, set()).add(slot)

    # -- writes, as acknowledged -------------------------------------------

    def create_edge(self, slot: int, s: int, d: int, label: str) -> None:
        if slot in self.edges:
            self.bad_acks += 1
            slot = -1 - len(self.edges)      # keep both edges in the model
        e = (int(s), int(d), label)
        self.edges[slot] = e
        self._index(slot, e)

    def delete_edge(self, slot: int) -> Optional[Tuple[int, int, str]]:
        if slot not in self.edges:
            self.bad_acks += 1
            return None
        e = self.edges.pop(slot)
        self._incident[e[0]].discard(slot)
        self._incident[e[1]].discard(slot)
        self._by_triple[e].discard(slot)
        return e

    def delete_node(self, n: int) -> List[Tuple[int, int, str]]:
        """Delete a node and its incident edges; returns those edges."""
        gone = [self.delete_edge(s) for s in sorted(self._incident.get(n, ()))]
        self.node_alive[n] = False
        return gone

    def create_node(self, n: int, label: str) -> None:
        while len(self.node_label) <= n:
            self.node_label.append(None)
            self.node_alive.append(False)
        if self.node_alive[n]:
            self.bad_acks += 1
        self.node_label[n] = label
        self.node_alive[n] = True

    def slot_of(self, triple: Tuple[int, int, str]) -> int:
        return min(self._by_triple[triple])

    # -- reads ---------------------------------------------------------------

    @property
    def n(self) -> int:
        return len(self.node_label)

    def label_nodes(self, label: str) -> np.ndarray:
        return np.asarray([i for i, (lab, a) in enumerate(
            zip(self.node_label, self.node_alive)) if a and lab == label],
            np.int64)

    def edge_multiset(self) -> Dict[Tuple[int, int, str], int]:
        out: Dict[Tuple[int, int, str], int] = {}
        for e in self.edges.values():
            out[e] = out.get(e, 0) + 1
        return out

    def edge_arrays(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Every live edge as ``src`` and ``dst`` (int64) and ``label``
        (label names, an object array), in one order."""
        es = list(self.edges.values())
        return (np.fromiter((e[0] for e in es), np.int64, len(es)),
                np.fromiter((e[1] for e in es), np.int64, len(es)),
                np.asarray([e[2] for e in es], object))


@dataclass
class Reference:
    """Evaluates paths over one fixed state of a :class:`GraphModel`."""

    model: GraphModel
    _adj: Dict[Tuple[str, str], sp.csr_matrix] = field(default_factory=dict)

    def __post_init__(self):
        m = self.model
        self.n = m.n
        alive = np.asarray(m.node_alive, bool)
        labels = np.asarray([lab or "" for lab in m.node_label], object)
        self._keep = {lab: alive & (labels == lab) for lab in set(labels)}
        self._alive = alive
        self._src, self._dst, self._lab = m.edge_arrays()

    def adj(self, label: str, direction: str) -> sp.csr_matrix:
        key = (label, direction)
        if key not in self._adj:
            m = self._lab == label
            s, d = self._src[m], self._dst[m]
            if direction == "in":
                s, d = d, s
            elif direction == "both":
                s, d = np.concatenate([s, d]), np.concatenate([d, s])
            self._adj[key] = sp.csr_matrix(
                (np.ones(s.shape[0], np.int64), (s, d)), shape=(self.n, self.n))
        return self._adj[key]

    def _keep_diag(self, label: Optional[str]) -> sp.dia_matrix:
        keep = self._alive if label is None else self._keep.get(
            label, np.zeros(self.n, bool))
        return sp.diags(keep.astype(np.int64), dtype=np.int64)

    @staticmethod
    def _bool(x: sp.csr_matrix) -> sp.csr_matrix:
        x = x.tocsr()
        x.data = (x.data > 0).astype(np.int64)
        x.eliminate_zeros()
        return x

    def _expand(self, rel: Rel, X: sp.csr_matrix, counting: bool):
        A = self.adj(rel.label, rel.direction)
        hop = (lambda Y: (Y @ A).tocsr()) if counting else \
            (lambda Y: self._bool(Y @ A))
        if rel.hi is not INF:
            acc = X.copy() if rel.lo == 0 else sp.csr_matrix(X.shape, dtype=np.int64)
            cur = X
            for k in range(1, rel.hi + 1):
                cur = hop(cur)
                if k >= rel.lo:
                    acc = acc + cur
            return acc.tocsr() if counting else self._bool(acc)
        cur = self._bool(X)
        for _ in range(rel.lo):
            cur = self._bool(cur @ A)
        reach = cur
        frontier = cur
        while frontier.nnz:
            nxt = self._bool(frontier @ A)
            frontier = self._bool(nxt - nxt.multiply(reach))
            reach = self._bool(reach + frontier)
        return reach

    def rows(self, path: Path, sources) -> sp.csr_matrix:
        """[S, n] rows: walk counts (counting paths) or 0/1 reachability."""
        sources = np.asarray(sources, np.int64)
        X = sp.csr_matrix((np.ones(sources.shape[0], np.int64),
                           (np.arange(sources.shape[0]), sources)),
                          shape=(sources.shape[0], self.n))
        counting = path.counting
        for rel, (_, label) in zip(path.rels, path.nodes[1:]):
            X = self._expand(rel, X, counting)
            X = (X @ self._keep_diag(label)).tocsr()
            X.eliminate_zeros()
        return X

    def view_pairs(self, view: View, n_slots: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """The view's edges as sorted ``pair_keys`` and their counts (1 for
        set views), both int64."""
        start = self.model.label_nodes(view.path.nodes[0][1])
        R = self.rows(view.path, start).tocoo()
        s = start[R.row]
        d = R.col.astype(np.int64)
        c = (R.data.astype(np.int64) if view.path.counting
             else np.ones(R.data.shape[0], np.int64))
        if not view.forward:
            s, d = d, s
        keys = pair_keys(s, d, n_slots)
        order = np.argsort(keys, kind="stable")
        return keys[order], c[order]


def pair_keys(src, dst, n_slots: int) -> np.ndarray:
    """One int64 key per (src, dst) pair, ``src * n_slots + dst``: no two
    pairs share a key while every id lies in ``[0, n_slots)``, which is
    checked."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if n_slots ** 2 > 2 ** 63:
        raise ValueError(f"{n_slots} node slots are too many to key pairs")
    for ids in (src, dst):
        if ids.size and (ids.min() < 0 or ids.max() >= n_slots):
            raise ValueError(f"a node id lies outside [0, {n_slots})")
    return src * n_slots + dst
