"""The plain reference: host replays of the stream, independent of the program.

Nothing here imports the system under test.  The reference replays the
exact changes a run handed in and holds the program's output to it:

* every shard's decoded edge set equals the replayed live edges that the
  placement rule assigns to that shard (lossless, and routed to its owner);
* the program's ``phi`` equals the optimal encoding cost (MoSSo Sect. 3.1,
  ``min(e, t - e + 1)`` per supernode pair) of its own supernode partition
  over the replayed edges: the refold;
* each sampled read equals the replay of its own view's epoch.

The placement rule is the one the system documents as fixed forever: an
edge belongs to shard ``min(h(u), h(v)) % n_shards``, with ``h`` the
splitmix64 finalizer of an int label folded to 62 bits.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Set, Tuple

Pair = Tuple[object, object]
MASK31 = 0x7FFFFFFF
MASK62 = (1 << 62) - 1
MASK64 = (1 << 64) - 1


def label_hash(label: int) -> int:
    """62-bit placement hash of an int label (splitmix64, folded)."""
    if isinstance(label, bool) or not isinstance(label, int):
        raise TypeError(f"the reference places int labels only: {label!r}")
    z = (label + 0x9E3779B97F4A7C15) & MASK64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK64
    z ^= z >> 31
    return ((z >> 33) << 31 | (z & MASK31)) & MASK62


def owner_shard(u: int, v: int, n_shards: int) -> int:
    return min(label_hash(u), label_hash(v)) % n_shards


def canon(u, v) -> Pair:
    return (u, v) if u <= v else (v, u)


def replay(changes: Iterable[Tuple[object, object, bool]],
           live: Set[Pair] | None = None) -> Set[Pair]:
    """Live edge set after applying ``changes`` in order."""
    live = set() if live is None else live
    for u, v, ins in changes:
        (live.add if ins else live.discard)(canon(u, v))
    return live


class AdjacencyReplay:
    """Adjacency of the stream prefix, advanced chunk by chunk."""

    def __init__(self, chunks: Sequence[Sequence]) -> None:
        self.chunks = chunks
        self.applied = 0
        self.adj: Dict[object, Set[object]] = {}

    def advance(self, n_chunks: int) -> None:
        if n_chunks < self.applied:
            raise ValueError("the replay only moves forward")
        adj = self.adj
        while self.applied < n_chunks:
            for u, v, ins in self.chunks[self.applied]:
                if ins:
                    adj.setdefault(u, set()).add(v)
                    adj.setdefault(v, set()).add(u)
                else:
                    adj[u].discard(v)
                    adj[v].discard(u)
            self.applied += 1

    def neighbors(self, u) -> Set[object]:
        return self.adj.get(u, set())


def decode(supernodes: Dict[int, Set[object]], superedges: Iterable,
           c_plus: Iterable, c_minus: Iterable) -> Set[Pair]:
    """E = (expanded superedges + C+) - C- (Sect. 2.1)."""
    members = {sid: sorted(mem) for sid, mem in supernodes.items()}
    edges: Set[Pair] = set()
    for a, b in superedges:
        if a == b:
            mem = members[a]
            for i, u in enumerate(mem):
                for v in mem[i + 1:]:
                    edges.add(canon(u, v))
        else:
            for u in members[a]:
                for v in members[b]:
                    edges.add(canon(u, v))
    edges |= {canon(u, v) for u, v in c_plus}
    edges -= {canon(u, v) for u, v in c_minus}
    return edges


def encoding_cost(supernodes: Dict[int, Set[object]],
                  edges: Iterable[Pair]) -> int:
    """Optimal encoding cost of a partition over an edge set: per supernode
    pair, ``min(e, t - e + 1)`` with ``t`` the pair's possible edges."""
    sid_of = {u: sid for sid, mem in supernodes.items() for u in mem}
    size = {sid: len(mem) for sid, mem in supernodes.items()}
    count: Dict[Tuple[int, int], int] = {}
    for u, v in edges:
        a, b = sid_of[u], sid_of[v]
        key = (a, b) if a <= b else (b, a)
        count[key] = count.get(key, 0) + 1
    cost = 0
    for (a, b), e in count.items():
        t = size[a] * (size[a] - 1) // 2 if a == b else size[a] * size[b]
        cost += min(e, t - e + 1)
    return cost


def check_summary(parts: List[tuple], phi: int, changes, n_shards: int,
                  ) -> Tuple[Dict[str, int], Dict[str, int]]:
    """Hold the final epoch to the replay of every change it holds.

    ``parts`` is one ``(supernodes, superedges, c_plus, c_minus)`` per shard
    in caller labels.  Returns the counts compared, each to be 0: replayed
    edges missing from their owner shard's decode, decoded edges the replay
    does not place there, and the gap between ``phi`` and the refold;
    then the refold itself, the replay's live edge count and the fullest
    shard's nodes and edges."""
    if len(parts) != n_shards:
        raise ValueError(f"{len(parts)} shard parts for {n_shards} shards")
    own: List[Set[Pair]] = [set() for _ in range(n_shards)]
    live = replay(changes)
    for u, v in live:
        own[owner_shard(u, v, n_shards)].add((u, v))
    missing = extra = 0
    refold = 0
    nodes = []
    for s, (supernodes, superedges, c_plus, c_minus) in enumerate(parts):
        dec = decode(supernodes, superedges, c_plus, c_minus)
        missing += len(own[s] - dec)
        extra += len(dec - own[s])
        # nodes a shard never saw cannot carry its edges: count them extra
        seen = {u for mem in supernodes.values() for u in mem}
        nodes.append(len(seen))
        unplaced = {e for e in own[s] if e[0] not in seen or e[1] not in seen}
        refold += encoding_cost(supernodes, own[s] - unplaced)
    return {"edges_missing": missing, "edges_extra": extra,
            "phi_gap": abs(int(phi) - refold)}, {
                "refold_phi": refold, "live_edges": len(live),
                "max_shard_nodes": max(nodes),
                "max_shard_edges": max(len(o) for o in own)}


def check_reads(samples: Sequence[tuple], chunks: Sequence[Sequence],
                ) -> Dict[str, int]:
    """Compare sampled reads with the replay of their own view's epoch.

    A sample is ``(epoch, kind, keys, answers)``; the view at epoch ``e``
    holds ``chunks[:e]``.  Kinds: ``neighbors`` (set), ``degree`` (int),
    ``has_edge`` (bool, keys are pairs)."""
    rep = AdjacencyReplay(chunks)
    wrong = checked = 0
    for epoch, kind, keys, answers in sorted(samples, key=lambda s: s[0]):
        rep.advance(epoch)
        for key, got in zip(keys, answers):
            if kind == "neighbors":
                want = rep.neighbors(key)
            elif kind == "degree":
                want = len(rep.neighbors(key))
            elif kind == "has_edge":
                want = key[1] in rep.neighbors(key[0])
            else:
                raise ValueError(f"unknown read kind {kind!r}")
            wrong += got != want
            checked += 1
    return {"reads_wrong": wrong, "reads_checked": checked}
