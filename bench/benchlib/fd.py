"""Stream building blocks, kept with the benchmark so that no program change
can move the yardstick.

* ``fully_dynamic`` is the paper's Sect. 4.1 construction (MoSSo, KDD 2020):
  the insertions in random order, and each inserted edge deleted with
  probability ``p`` at a uniformly random later position.  Without a
  ``horizon`` it draws from the rng exactly as
  ``repro.graph.streams.edges_to_fully_dynamic_stream`` does and returns
  the same stream, in O(n log n) instead of one list insert per deletion.
* ``ba_grow`` is Barabasi-Albert preferential attachment as in
  ``repro.graph.streams.barabasi_albert_edges``.
* ``validate_stream`` is the Sect. 2.1 soundness check.
"""
from __future__ import annotations

import random
from typing import Iterable, List, Optional, Sequence, Set, Tuple

Change = Tuple[int, int, bool]          # (u, v, is_insert)


def fully_dynamic(edges: Sequence[Tuple[int, int]], p: float,
                  rng: random.Random, horizon: Optional[int] = None,
                  ) -> List[Change]:
    """Sect. 4.1: shuffle the insertions, then delete each inserted edge
    with probability ``p`` just before the insertion at a uniformly drawn
    later index (``len(edges)`` = at the end), at most ``horizon``
    insertions later where one is given.  Deletions aimed at one index
    land in reverse draw order, as repeated ``list.insert`` leaves them."""
    order = list(edges)
    rng.shuffle(order)
    n = len(order)
    keyed = [((i, 0, 0), (u, v, True)) for i, (u, v) in enumerate(order)]
    for i, (u, v) in enumerate(order):
        if rng.random() < p:
            last = n if horizon is None else min(n, i + horizon)
            keyed.append(((rng.randint(i + 1, last), -1, -i), (u, v, False)))
    keyed.sort(key=lambda kc: kc[0])
    return [c for _, c in keyed]


def ba_grow(n_nodes: int, m: int, rng: random.Random,
            ) -> List[Tuple[int, int]]:
    """All Barabasi-Albert edges on ``n_nodes`` nodes, sorted: node ``u``
    links to ``m`` distinct earlier nodes drawn from the endpoint multiset,
    exactly as ``repro.graph.streams.barabasi_albert_edges`` draws them."""
    edges: Set[Tuple[int, int]] = set()
    rep: List[int] = list(range(m + 1))
    for u in range(m + 1, n_nodes):
        chosen: Set[int] = set()
        while len(chosen) < m:
            chosen.add(rng.choice(rep))
        for v in chosen:
            edges.add((min(u, v), max(u, v)))
            rep.extend((u, v))
    return sorted(edges)


def validate_stream(stream: Iterable[Change]) -> bool:
    """Sect. 2.1 soundness: insert only absent non-loop edges, delete only
    live ones."""
    live: Set[Tuple[int, int]] = set()
    for (u, v, ins) in stream:
        e = (min(u, v), max(u, v))
        if ins:
            if e in live or u == v:
                return False
            live.add(e)
        else:
            if e not in live:
                return False
            live.remove(e)
    return True


class Stream:
    """A finite stream of changes, handed out in whole chunks."""

    def __init__(self, changes: List[Change]) -> None:
        self.changes = changes
        self.pos = 0

    def take(self, n: int) -> Optional[List[Change]]:
        """The next ``n`` changes, or None once fewer than ``n`` are left."""
        if self.pos + n > len(self.changes):
            return None
        out = self.changes[self.pos:self.pos + n]
        self.pos += n
        return out
