"""Seeded base graphs for the benchmark's deployments.

Each schema's generator is a module of its own under
``bench/generators/``, found by the name a configuration gives; every
per-label node and edge count follows from the configuration's sizes.  A
configuration's dataset is drawn once from its ``dataset_seed``, as
LDBC's data generator fixes its seed per scale factor; a run's seed then
draws an isomorphic copy (:func:`relabel`: node ids and
edge order), so every run holds the same sizes, and the programs over it
the same shapes, with its own ids.  The graph comes back as plain host
arrays: the benchmark loads it into the program through ``GraphBuilder``
and keeps its own copy for the reference.  Node ``i`` and edge ``i`` are
the ``i``-th ``add_node`` / ``add_edge`` call, which is the id the program
gives them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import numpy as np


@dataclass
class BaseGraph:
    node_label: List[str]            # label name of node i
    src: np.ndarray                  # [E] int64
    dst: np.ndarray                  # [E] int64
    label: List[str]                 # label name of edge i


class Builder:
    def __init__(self):
        self.node_label: List[str] = []
        self.parts: List[tuple] = []

    def nodes(self, label: str, n: int) -> np.ndarray:
        start = len(self.node_label)
        self.node_label.extend([label] * n)
        return np.arange(start, start + n, dtype=np.int64)

    def edges(self, s, d, label: str) -> None:
        s, d = np.broadcast_arrays(np.asarray(s, np.int64),
                                   np.asarray(d, np.int64))
        self.parts.append((s, d, label))

    def done(self) -> BaseGraph:
        src = np.concatenate([p[0] for p in self.parts])
        dst = np.concatenate([p[1] for p in self.parts])
        label = [p[2] for p in self.parts for _ in range(p[0].shape[0])]
        return BaseGraph(self.node_label, src, dst, label)


def ring_offsets(rng, base: int, n_edges: int, n_nodes: int, a: float):
    """Zipf(``a``) offsets around a ring of ``n_nodes``, never 0 mod n."""
    off = (base + rng.zipf(a, n_edges)) % n_nodes
    off[off == 0] = 1
    return off


def exact_subset(rng, n: int, share: float) -> np.ndarray:
    """A seeded subset of ``range(n)`` of exactly ``round(share * n)``."""
    return np.sort(rng.permutation(n)[:int(round(share * n))])


def generate(name: str, seed: int, cfg: dict) -> BaseGraph:
    """The graph of a configuration, from ``bench/generators/<name>.py``
    (a module with ``generate(seed, **sizes) -> BaseGraph``); its sizes
    are the configuration's top-level keys."""
    from bench.lib import registry
    return registry.generator(name)(seed, **cfg)


def relabel(g: BaseGraph, rng: np.random.Generator
            ) -> Tuple[BaseGraph, np.ndarray]:
    """An isomorphic copy with node ids and edge order drawn from ``rng``;
    returns it with ``perm`` (old node id -> new)."""
    n = len(g.node_label)
    perm = rng.permutation(n)
    node_label = [None] * n
    for old, new in enumerate(perm.tolist()):
        node_label[new] = g.node_label[old]
    order = rng.permutation(len(g.label))
    return BaseGraph(node_label, perm[g.src[order]], perm[g.dst[order]],
                     [g.label[i] for i in order.tolist()]), perm
