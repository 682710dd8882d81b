"""LDBC SNB Interactive-shaped social graph.

Person knows Person, Person livesIn Place, Post and Comment created by a
Person, Comment replyOf a Post or an earlier Comment (so every reply tree
is rooted at a Post), Post and Comment hasTag Tag.  Node counts are the
configuration's; every per-label edge count follows from them:
``round(n_person * knows_deg)`` knows edges, one creator and one reply
parent per message, one place per person, one tag per post and one on a
``comment_tag_share`` of the comments.

Knows edges are distinct pairs.  Each person's targets lie at ring
offsets drawn without replacement with weight ``offset ** -knows_zipf``:
LDBC's generator links persons that lie close in a sorted order (a
sliding window over correlated attributes), so near neighbours dominate.
"""
from __future__ import annotations

import numpy as np

from bench.lib.graphgen import BaseGraph, Builder, exact_subset


def _knows(rng, n: int, n_edges: int, a: float):
    """``n_edges`` distinct (src, dst) pairs, src != dst, spread as evenly
    over the sources as the count allows."""
    deg = np.full(n, n_edges // n, np.int64)
    deg[rng.permutation(n)[:n_edges % n]] += 1
    deg = np.minimum(deg, n - 1)
    off = np.arange(1, n, dtype=np.float64)
    p = off ** -a
    p /= p.sum()
    src, dst = [], []
    for i in range(n):
        pick = rng.choice(n - 1, int(deg[i]), replace=False, p=p) + 1
        src.append(np.full(pick.shape[0], i, np.int64))
        dst.append((i + pick) % n)
    return np.concatenate(src), np.concatenate(dst)


def generate(seed: int, n_person: int, n_post: int, n_comment: int,
             n_place: int, n_tag: int, knows_deg: float, knows_zipf: float,
             reply_to_post: float, comment_tag_share: float, **_
             ) -> BaseGraph:
    rng = np.random.default_rng(seed)
    b = Builder()
    persons = b.nodes("Person", n_person)
    places = b.nodes("Place", n_place)
    posts = b.nodes("Post", n_post)
    tags = b.nodes("Tag", n_tag)
    comments = b.nodes("Comment", n_comment)
    src, dst = _knows(rng, n_person, int(round(n_person * knows_deg)),
                      knows_zipf)
    b.edges(persons[src], persons[dst], "knows")
    b.edges(persons, places[rng.integers(0, n_place, n_person)], "livesIn")
    b.edges(posts, tags[rng.integers(0, n_tag, n_post)], "hasTag")
    b.edges(persons[rng.integers(0, n_person, n_post)], posts, "created")
    to_post = np.zeros(n_comment, bool)
    to_post[exact_subset(rng, n_comment, reply_to_post)] = True
    to_post[0] = True
    parent = (rng.random(n_comment) * np.arange(n_comment)).astype(np.int64)
    b.edges(comments, np.where(to_post,
                               posts[rng.integers(0, n_post, n_comment)],
                               comments[parent]), "replyOf")
    b.edges(persons[rng.integers(0, n_person, n_comment)], comments,
            "created")
    tagged = exact_subset(rng, n_comment, comment_tag_share)
    b.edges(comments[tagged], tags[rng.integers(0, n_tag, tagged.shape[0])],
            "hasTag")
    return b.done()
