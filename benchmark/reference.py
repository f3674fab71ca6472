"""The plain reference of SHEEP's partition, written from the algorithm.

It imports nothing of the program. Pipeline, as the SHEEP paper
(PVLDB 8(12), 2015) defines it:

1. degrees (each endpoint counts; a self-loop counts twice);
2. elimination order: by degree ascending, ties by vertex id;
3. elimination forest (Liu): walk the vertices in that order; each
   earlier neighbour's component root becomes a child of the vertex;
4. greedy split into ``k`` parts (bags of subtrees of at most
   ``total / k`` vertices, each given to the least-loaded part);
5. edge cut and total: non-self-loop edges, and those whose endpoints
   lie in different parts.

Step 3 runs on a minimum spanning forest of the graph under the weight
"position of the later endpoint" (scipy's Kruskal): such a forest
connects exactly what the graph connects below every threshold, and the
elimination forest depends on nothing else, so the union-find loop runs
over fewer than V edges instead of E.
"""

from __future__ import annotations

import heapq

import numpy as np
from scipy.sparse import coo_matrix
from scipy.sparse.csgraph import minimum_spanning_tree


def degrees(edges: np.ndarray, n: int) -> np.ndarray:
    return np.bincount(edges.reshape(-1), minlength=n).astype(np.int64)


def elimination_order(deg: np.ndarray) -> np.ndarray:
    """pos[v]: rank of v by (degree, id)."""
    order = np.argsort(deg, kind="stable")
    pos = np.empty(len(deg), np.int64)
    pos[order] = np.arange(len(deg), dtype=np.int64)
    return pos


def spanning_forest(edges: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """Minimum spanning forest, weight = position of the later endpoint;
    ``(m, 2)`` rows oriented (earlier, later)."""
    n = len(pos)
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    swap = pos[e[:, 0]] > pos[e[:, 1]]
    lo = np.where(swap, e[:, 1], e[:, 0])
    hi = np.where(swap, e[:, 0], e[:, 1])
    keep = lo != hi
    key = np.unique(lo[keep] * n + hi[keep])  # duplicates would sum
    lo, hi = key // n, key % n
    g = coo_matrix((pos[hi].astype(np.float64) + 1.0, (lo, hi)),
                   shape=(n, n)).tocsr()
    t = minimum_spanning_tree(g).tocoo()
    a, b = t.row.astype(np.int64), t.col.astype(np.int64)
    swap = pos[a] > pos[b]
    return np.stack([np.where(swap, b, a), np.where(swap, a, b)], axis=1)


def forest_parent(span: np.ndarray, pos: np.ndarray) -> np.ndarray:
    """Liu's elimination forest from (earlier, later) rows: parent[v],
    -1 for a root."""
    n = len(pos)
    rows = span[np.argsort(pos[span[:, 1]], kind="stable")]
    parent = [-1] * n
    root = list(range(n))
    for u, v in rows.tolist():
        r = u
        while root[r] != r:
            root[r] = root[root[r]]
            r = root[r]
        if r != v:  # v is still its own root: links only go upward
            parent[r] = v
            root[r] = v
    return np.asarray(parent, np.int64)


def split(parent: np.ndarray, pos: np.ndarray, k: int) -> np.ndarray:
    """Greedy bag packing of the forest into k parts, unit weights.

    Walk the vertices by position (children before parents), adding up
    each vertex's subtree weight not yet given away. Where that reaches
    the capacity ``total / k``, or at a root, the vertex's child
    subtrees, heaviest first (ties in the order the children were
    seen), are packed first-fit into bags of at most the capacity; each
    full bag goes to the least-loaded part (ties to the lower part id).
    The last bag goes with the vertex itself if that fills it or the
    vertex is a root; otherwise it stays attached and its weight moves
    up. A vertex is then labelled with the part of its nearest ancestor
    (itself included) that was given away."""
    n = len(parent)
    cap = max(float(n) / k, 1.0)
    order = np.argsort(pos, kind="stable").tolist()
    par = parent.tolist()
    rem = [1] * n
    kids = {}
    given = [-1] * n
    loads = [(0, p) for p in range(k)]

    def give(bag, weight):
        load, p = heapq.heappop(loads)
        for x in bag:
            given[x] = p
        heapq.heappush(loads, (load + weight, p))

    for v in order:
        ch = kids.pop(v, ())
        tot = 1 + sum(rem[c] for c in ch)
        root = par[v] < 0
        if tot < cap and not root:
            rem[v] = tot
            kids.setdefault(par[v], []).append(v)
            continue
        bag, w = [], 0
        for c in sorted(ch, key=lambda c: -rem[c]):
            if bag and w + rem[c] > cap:
                give(bag, w)
                bag, w = [], 0
            bag.append(c)
            w += rem[c]
        if root or w + 1 >= cap:
            bag.append(v)
            give(bag, w + 1)
        else:
            rem[v] = w + 1
            kids.setdefault(par[v], []).append(v)
    part = [0] * n
    for v in reversed(order):
        part[v] = given[v] if given[v] >= 0 else part[par[v]]
    return np.asarray(part, np.int32)


def cut_and_total(edges: np.ndarray, part: np.ndarray) -> tuple:
    e = np.asarray(edges).reshape(-1, 2)
    real = e[:, 0] != e[:, 1]
    cut = np.count_nonzero(real & (part[e[:, 0]] != part[e[:, 1]]))
    return int(cut), int(np.count_nonzero(real))


class Partition:
    """The reference's answer for one graph: forest, parts, cut, total."""

    def __init__(self, parent, part, cut, total):
        self.parent, self.part, self.cut, self.total = parent, part, cut, total


def partition(edges: np.ndarray, n: int, k: int) -> Partition:
    pos = elimination_order(degrees(edges, n))
    parent = forest_parent(spanning_forest(edges, pos), pos)
    part = split(parent, pos, k)
    return Partition(parent, part, *cut_and_total(edges, part))
