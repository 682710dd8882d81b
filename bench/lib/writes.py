"""The paper's write protocol (MV4PG §VI): create edge (CE), delete edge
(DE) and delete node (DV), each followed by a statement that recovers it,
so the graph is the same at the end of every cycle.

A DV is recovered in two batches, the node and then its base edges,
because a ``WriteBatch`` applies edge creates before node creates.  The
node's batch also carries the cycle's DE: the serve engine applies a fence
only when no queued read may run before it, and a node create alone
conflicts with no point read, so under closed-loop readers it would wait
for ever.  A cycle is six batches:

    CE | CE.recover | DV | DE + the DV node back | DV edges back | DE.recover

Targets come from a pool used in turn.  The pool spreads its sizes evenly:
candidates are ranked by how much of the graph a write to them can reach,
and the pool takes them at fixed quantiles of that ranking.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np
import scipy.sparse as sp

from bench.lib.reference import GraphModel, Reference

KINDS = ("CE", "CE.recover", "DV", "DE.node", "DV.recover", "DE.recover")


@dataclass
class Target:
    ce: Tuple[int, int]                 # (u, v) of the edge to create
    de: Tuple[int, int, str]            # base edge to delete, as a triple
    dv: int                             # node to delete


def _reach_within(A: sp.csr_matrix, hops: int) -> np.ndarray:
    """Per node, how many nodes it reaches within ``hops`` (bool walks)."""
    R = A.copy()
    R.data[:] = 1
    cur = R
    for _ in range(hops - 1):
        cur = cur @ A
        cur.data[:] = 1
        R = R + cur
        R.data[:] = 1
    return np.diff(R.tocsr().indptr)


def _quantile_pick(sizes: np.ndarray, k: int) -> np.ndarray:
    order = np.argsort(sizes, kind="stable")
    at = ((np.arange(k) + 0.5) / k * order.shape[0]).astype(int)
    return order[at]


def draw_pool(model: GraphModel, spec: dict, k: int,
              rng: np.random.Generator) -> List[Target]:
    """``k`` targets for the config's write ``spec`` (edge label, its
    endpoint labels, node label to delete)."""
    ref = Reference(model)
    label = spec["edge_label"]
    down = _reach_within(ref.adj(label, "out"), spec["reach_hops"])
    up = _reach_within(ref.adj(label, "in"), spec["reach_hops"])

    src_nodes = model.label_nodes(spec["src_label"])
    dst_nodes = model.label_nodes(spec["dst_label"])
    us = rng.choice(src_nodes, 8 * k)
    vs = rng.choice(dst_nodes, 8 * k)
    ok = us != vs
    us, vs = us[ok], vs[ok]
    ce_i = _quantile_pick((1 + up[us]) * (1 + down[vs]), k)

    edges = [(slot, e) for slot, e in sorted(model.edges.items())
             if e[2] == label]
    cand = rng.choice(len(edges), min(8 * k, len(edges)), replace=False)
    es = [edges[i][1] for i in cand]
    de_sizes = np.asarray([(1 + up[s]) * (1 + down[d]) for s, d, _ in es])
    de_i = _quantile_pick(de_sizes, k)

    # a DV target must not touch a DE target: the node's delete would take
    # the edge with it
    touched = {x for i in de_i for x in es[i][:2]}
    pool = np.asarray([n for n in model.label_nodes(spec["node_label"])
                       if n not in touched])
    nodes = rng.choice(pool, min(8 * k, pool.shape[0]), replace=False)
    dv_i = _quantile_pick(up[nodes] + down[nodes], k)

    return [Target((int(us[a]), int(vs[a])), es[b], int(nodes[c]))
            for a, b, c in zip(ce_i, de_i, dv_i)]


def relabel_pool(pool: List[Target], perm: np.ndarray,
                 rng: np.random.Generator) -> List[Target]:
    """The pool's targets in a relabelled copy of its graph, in a seeded
    order."""
    p = perm.tolist()
    mapped = [Target((p[t.ce[0]], p[t.ce[1]]),
                     (p[t.de[0]], p[t.de[1]], t.de[2]), p[t.dv])
              for t in pool]
    return [mapped[i] for i in rng.permutation(len(mapped))]


class Writer:
    """One closed-loop writer cycling through :data:`KINDS` over a pool.

    The model is updated on every acknowledgement with the slots the
    program acknowledged; ``log`` keeps each applied write for replay."""

    def __init__(self, model: GraphModel, spec: dict, pool: List[Target],
                 kinds=KINDS):
        self.model = model
        self.spec = spec
        self.pool = pool
        self.kinds = tuple(kinds)
        self.i = 0                      # statements issued
        self.log: List[Tuple[str, tuple]] = []
        self._ce_slot: Optional[int] = None
        self._de_triple: Optional[Tuple[int, int, str]] = None
        self._dv: Optional[Tuple[int, List[Tuple[int, int, str]]]] = None
        self._dv_new: Optional[int] = None

    @property
    def target(self) -> Target:
        return self.pool[(self.i // len(self.kinds)) % len(self.pool)]

    @property
    def kind(self) -> str:
        return self.kinds[self.i % len(self.kinds)]

    @property
    def passes(self) -> int:
        """Complete passes over the pool."""
        return self.i // (len(self.kinds) * len(self.pool))

    def batch(self):
        from repro.core import WriteBatch
        t, kind, label = self.target, self.kind, self.spec["edge_label"]
        b = WriteBatch()
        if kind == "CE":
            b.create_edge(t.ce[0], t.ce[1], label)
        elif kind == "CE.recover":
            b.delete_edge(self._ce_slot)
        elif kind == "DE":
            b.delete_edge(self.model.slot_of(t.de))
        elif kind == "DE.recover":
            b.create_edge(*self._de_triple)
        elif kind == "DV":
            b.delete_node(t.dv)
        elif kind == "DE.node":
            b.delete_edge(self.model.slot_of(t.de))
            b.create_node(self.model.node_label[t.dv], key=t.dv)
        else:
            n, gone = self._dv
            for s, d, lab in gone:
                b.create_edge(self._dv_new if s == n else s,
                              self._dv_new if d == n else d, lab)
        return kind, b

    def ack(self, kind: str, batch, result) -> None:
        """Apply an acknowledged batch to the model."""
        m = self.model
        if kind in ("CE", "DE.recover", "DV.recover"):
            ops = []
            for slot, (s, d, lab) in zip(result.edge_slots,
                                         batch.edge_creates):
                m.create_edge(int(slot), s, d, lab)
                ops.append(("ce", int(slot), s, d, lab))
            if kind == "CE":
                self._ce_slot = int(result.edge_slots[0])
            self.log.append((kind, tuple(ops)))
        elif kind in ("CE.recover", "DE"):
            slot = int(batch.edge_deletes[0])
            e = m.delete_edge(slot)
            if kind == "DE":
                self._de_triple = e or self.target.de
            self.log.append((kind, (("de", slot),)))
        elif kind == "DV":
            n = int(batch.node_deletes[0])
            self._dv = (n, m.delete_node(n))
            self.log.append((kind, (("dv", n),)))
        else:     # DE.node: edge deletes apply before node creates
            slot = int(batch.edge_deletes[0])
            self._de_triple = m.delete_edge(slot) or self.target.de
            label = batch.node_creates[0][0]
            n = int(result.node_slots[0])
            m.create_node(n, label)
            self._dv_new = n
            self.log.append((kind, (("de", slot), ("cn", n, label))))
        self.i += 1


def replay(model: GraphModel, ops) -> None:
    """Apply one logged write to a model."""
    for op in ops:
        if op[0] == "ce":
            model.create_edge(op[1], op[2], op[3], op[4])
        elif op[0] == "de":
            model.delete_edge(op[1])
        elif op[0] == "dv":
            model.delete_node(op[1])
        else:
            model.create_node(op[1], op[2])
