"""Online query engine over the live compressed summary (no decompression).

The paper's headline property — the summary graph plus corrections *is*
the graph — is served here as a read path: ``neighbors(u)``, ``degree(u)``
and ``has_edge(u, v)`` are answered directly from :class:`EngineState`
arrays, never by ``decode_edges()``.  Every answer walks the encoding the
way Lemma 1 prescribes:

1. **membership lookup** — ``n2s[u]`` resolves u's supernode A (unseen
   nodes are the caller-facing ``LookupError`` contract);
2. **superedge scan** — A's supernode adjacency (``snadj``/``eab``/
   ``ssize``) is scanned under the optimal-encoding rule ``2e > t + 1``,
   yielding the candidate neighbors covered by superedges of A;
3. **correction patch-up** — u's correction store is consulted: pairs in
   C+ mode add their listed edges, pairs in superedge mode subtract the
   C- holes.

The corrections are a *derived* view on device (the engine never
materializes C+/C- arrays — ``adj``/``epos`` is the correction store), so
step 3 reads u's adjacency slot list and classifies each listed edge by
its pair's encoding mode.  The composed answer
``(superedge-candidates ∩ listed) ∪ C+-listed`` therefore cross-checks
``n2s``/``ssize``/``eab``/``snadj`` against ``adj``/``deg`` on every
query — which is exactly what lets tests hold the read path to a
query-vs-decode differential bar: any drift between the summary encoding
and the edge store shows up as a wrong answer, not a hidden invariant.

Everything compiles to batched jit kernels: the per-query scans are
``O(sndeg(A) + deg(u))`` dynamic-trip loops vmapped over the query batch,
and the point probes (``eab``/``epos``) lower through
``ht_lookup_batch``/``ht_find_batch`` under the active trial backend, so
``REPRO_TRIAL_BACKEND=pallas`` serves reads through the same fused probe
kernel the write path uses.

Two host-facing views wrap the kernels:

* :class:`SummaryQuery` — snapshot view over a ``BatchedSummarizer``.
* :class:`ShardedSummaryQuery` — snapshot view over a
  ``ShardedSummarizer``: queries are hash-placed (``labelhash``) and
  fanned out to every shard inside one ``shard_map`` kernel (edge
  partitioning is a vertex cut, so a node's neighborhood may span all
  shards); per-shard answers merge by union (neighbors), sum (degree) or
  any (has_edge — only the ``shard_key`` owner of a pair can hold it).

**Snapshot semantics.**  A view pins the state references that are live
when ``query()`` is called.  Engine dispatch replaces state pytrees
functionally (never in place), so a snapshot is always SOME flushed
epoch's state — on the pipelined sharded path the snapshot intentionally
lags the write head by the one routed-but-not-dispatched chunk, which is
what lets reads run concurrent with an in-flight write chunk without ever
observing a torn intermediate.  ``view.epoch`` records which flush epoch
the answers correspond to.  On buffer-donating backends (non-CPU) the
NEXT engine dispatch invalidates a held snapshot; pass ``copy=True`` or
consume the view before resuming writes (docs/KNOWN_ISSUES.md).

**Policy independence (PR 8).**  The read path takes no
``proposal``/``objective``/``commit`` branches, because per pair the
composed answer algebraically reduces to the LISTED edge set whichever
mode rule classifies the pair (superedge mode: candidates minus the
derived C- holes == candidates ∩ listed; C+ mode: the listed edges
verbatim) — and every policy maintains ``adj``/``epos`` as the exact
live edge set.  The weighted objective's different mode threshold
(``2W > TW + 1`` over weighted masses instead of ``2e > t + 1`` over
counts) therefore cannot change an answer.  This module needs no
per-policy code; the contract is pinned by
``tests/test_differential.py::test_query_vs_decode_under_nondefault_policies``.
"""
from __future__ import annotations

from functools import lru_cache
from typing import List, NamedTuple, Sequence, Set, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.engine.hashtable import (ht_find_batch, ht_lookup,
                                         ht_lookup_batch,
                                         resolve_trial_backend,
                                         trial_backend_scope)
from repro.core.engine.ops import t_of
from repro.core.engine.state import EngineState


# --------------------------------------------------------------------------- #
# engine-id query cores (single EngineState, jit/vmap-compatible)
# --------------------------------------------------------------------------- #


def _neighbors_one(st: EngineState, u: jax.Array,
                   ) -> Tuple[jax.Array, jax.Array]:
    """Lemma-1 neighborhood of one engine-id node as a bool[n_cap] mask.

    ``u < 0`` or unseen (``n2s[u] < 0``) lanes answer an all-False mask
    with ``ok=False``.  The scan bounds are the true ``sndeg(A)`` /
    ``deg(u)``, so per-query work matches the paper's retrieval cost; the
    two masks compose as ``(superedge-candidates ∩ listed) ∪ C+-listed``
    which equals N(u) exactly when the summary encoding is consistent
    with the edge store (the query-vs-decode differential bar).
    """
    n_cap = st.n2s.shape[0]
    ok = u >= 0
    uu = jnp.where(ok, u, 0)
    a = st.n2s[uu]
    ok = ok & (a >= 0)
    a0 = jnp.where(ok, a, 0)
    sz_a = st.ssize[a0]

    def pair_is_superedge(b0):
        ca, cb = jnp.minimum(a0, b0), jnp.maximum(a0, b0)
        e = ht_lookup(st.eab, ca, cb)
        t = t_of(sz_a, st.ssize[b0], a0 == b0)
        return 2 * e > t + 1

    # step 2: superedge scan over SN(A) -> candidate supernodes
    def sn_body(i, m):
        b0 = jnp.clip(ht_lookup(st.snadj, a0, i), 0)
        return m.at[b0].set(m[b0] | pair_is_superedge(b0))

    se_sid = jax.lax.fori_loop(0, jnp.where(ok, st.sndeg[a0], 0), sn_body,
                               jnp.zeros((n_cap,), jnp.bool_))
    cand = se_sid[jnp.clip(st.n2s, 0)] & (st.n2s >= 0)

    # step 3: correction patch-up from u's slot list (the derived C store):
    # a listed edge whose pair is in C+ mode is a C+ entry; a candidate
    # pair NOT listed is a C- hole (it drops out of cand & listed)
    def adj_body(i, carry):
        listed, cplus = carry
        w0 = jnp.clip(ht_lookup(st.adj, uu, i), 0)
        se = pair_is_superedge(st.n2s[w0])
        return (listed.at[w0].set(True),
                cplus.at[w0].set(cplus[w0] | ~se))

    listed, cplus = jax.lax.fori_loop(
        0, jnp.where(ok, st.deg[uu], 0), adj_body,
        (jnp.zeros((n_cap,), jnp.bool_), jnp.zeros((n_cap,), jnp.bool_)))

    return ((cand & listed) | cplus) & ok, ok


def _degree_core(st: EngineState, u: jax.Array,
                 ) -> Tuple[jax.Array, jax.Array]:
    """(degree, ok) per query id; 0 / False for invalid or unseen lanes."""
    ok = u >= 0
    uu = jnp.where(ok, u, 0)
    ok = ok & (st.n2s[uu] >= 0)
    return jnp.where(ok, st.deg[uu], 0), ok


def _has_edge_core(st: EngineState, u: jax.Array, v: jax.Array,
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(present, via_superedge, ok) per query pair, batched probes.

    Membership -> one batched ``eab`` probe decides the pair's encoding
    mode -> one batched ``epos`` probe consults the correction store: in
    C+ mode the edge is present iff listed; in superedge mode it is
    present iff NOT a C- hole — both reduce to the same listed-edge
    probe, so ``via_superedge`` reports which arm answered (the per-query
    cost accounting the utility-variant papers motivate).
    """
    ok = (u >= 0) & (v >= 0) & (u != v)
    uu = jnp.where(ok, u, 0)
    vv = jnp.where(ok, v, 0)
    a, b = st.n2s[uu], st.n2s[vv]
    ok = ok & (a >= 0) & (b >= 0)
    a0 = jnp.where(ok, a, 0)
    b0 = jnp.where(ok, b, 0)
    ca, cb = jnp.minimum(a0, b0), jnp.maximum(a0, b0)
    e = ht_lookup_batch(st.eab, ca, cb)
    t = t_of(st.ssize[a0], st.ssize[b0], a0 == b0)
    se = (2 * e > t + 1) & ok
    _, listed = ht_find_batch(st.epos, uu, vv)
    return listed & ok, se, ok


class QueryKernels(NamedTuple):
    neighbors: object   # (state, u[Q]) -> (mask[Q, n_cap], ok[Q])
    degree: object      # (state, u[Q]) -> (deg[Q], ok[Q])
    has_edge: object    # (state, u[Q], v[Q]) -> (present, via_se, ok)[Q]


@lru_cache(maxsize=None)
def _query_kernels(trial_backend: str) -> QueryKernels:
    def neighbors(st, u):
        with trial_backend_scope(trial_backend):
            return jax.vmap(lambda x: _neighbors_one(st, x))(u)

    def degree(st, u):
        with trial_backend_scope(trial_backend):
            return _degree_core(st, u)

    def has_edge(st, u, v):
        with trial_backend_scope(trial_backend):
            return _has_edge_core(st, u, v)

    # read-only kernels: nothing is donated, so a snapshot can be queried
    # repeatedly without consuming its buffers
    return QueryKernels(neighbors=jax.jit(neighbors),
                        degree=jax.jit(degree),
                        has_edge=jax.jit(has_edge))


def make_query_kernels(trial_backend: str | None = None) -> QueryKernels:
    """Jitted single-engine query kernels under the given probe backend.

    Memoized on the resolved backend; jit handles shape polymorphism, so
    one kernel set serves every config and (padded) query-batch size.
    """
    return _query_kernels(resolve_trial_backend(trial_backend))


# --------------------------------------------------------------------------- #
# sharded fan-out kernels (stacked EngineState + InternState)
# --------------------------------------------------------------------------- #

_SHARDED_CACHE: dict = {}


def _intern_resolve(ist, hi: jax.Array, lo: jax.Array,
                    ) -> Tuple[jax.Array, jax.Array]:
    """Hash words -> (local nid, found) against one shard's intern table.

    The intern keys are full-entropy label hashes, so the batch probe is
    ``prehashed`` (same layout contract as the router's pre-lookup);
    ``hi < 0`` marks padded query lanes.
    """
    valid = hi >= 0
    h1 = jnp.where(valid, hi, 0)
    h2 = jnp.where(valid, lo, 0)
    slot, found = ht_find_batch(ist.h2l, h1, h2, prehashed=True)
    found = found & valid
    return jnp.where(found, ist.h2l.val[slot], -1), found


def make_sharded_query_kernels(cfg, mesh, trial_backend: str | None = None,
                               ) -> QueryKernels:
    """shard_map query kernels over the stacked per-shard states.

    Queries arrive as replicated hash-word arrays; every shard resolves
    them against its own intern table and answers for the nodes it knows
    (vertex-cut fan-out).  Outputs keep the leading shard axis — the host
    view merges them (union / sum / any) — plus per-shard ``found`` flags
    whose across-shard disjunction is the seen-label contract.  Memoized
    on ``(cfg, mesh, trial_backend)`` like the router steps.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.router import _state_specs

    trial_backend = resolve_trial_backend(trial_backend)
    key = ("query", cfg, mesh, trial_backend)
    if key in _SHARDED_CACHE:
        return _SHARDED_CACHE[key]
    axis = mesh.axis_names[0]
    est_specs, ist_specs = _state_specs(cfg, axis)

    def nbrs_local(est, ist, hi, lo):
        with trial_backend_scope(trial_backend):
            def per_shard(st, it):
                with jax.named_scope("query/resolve"):
                    nid, found = _intern_resolve(it, hi, lo)
                with jax.named_scope("query/scan"):
                    mask, _ = jax.vmap(lambda x: _neighbors_one(st, x))(nid)
                return mask, found
            return jax.vmap(per_shard)(est, ist)

    def deg_local(est, ist, hi, lo):
        with trial_backend_scope(trial_backend):
            def per_shard(st, it):
                with jax.named_scope("query/resolve"):
                    nid, found = _intern_resolve(it, hi, lo)
                with jax.named_scope("query/scan"):
                    d, _ = _degree_core(st, nid)
                return d, found
            return jax.vmap(per_shard)(est, ist)

    def he_local(est, ist, uhi, ulo, vhi, vlo):
        with trial_backend_scope(trial_backend):
            def per_shard(st, it):
                with jax.named_scope("query/resolve"):
                    nu, fu = _intern_resolve(it, uhi, ulo)
                    nv, fv = _intern_resolve(it, vhi, vlo)
                with jax.named_scope("query/scan"):
                    present, se, _ = _has_edge_core(st, nu, nv)
                return present, se, fu, fv
            return jax.vmap(per_shard)(est, ist)

    def wrap(fn, n_q_args, n_out):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=(est_specs, ist_specs) + (P(),) * n_q_args,
            out_specs=(P(axis),) * n_out, check_vma=False))

    kernels = QueryKernels(neighbors=wrap(nbrs_local, 2, 2),
                           degree=wrap(deg_local, 2, 2),
                           has_edge=wrap(he_local, 4, 4))
    _SHARDED_CACHE[key] = kernels
    return kernels


# --------------------------------------------------------------------------- #
# host-facing snapshot views
# --------------------------------------------------------------------------- #


def _pad_pow2(a: np.ndarray, fill) -> np.ndarray:
    """Pad a 1-D query array to the next power of two (min 8) so jit
    retraces O(log Q) shapes instead of one per batch size."""
    n = max(8, 1 << (max(len(a), 1) - 1).bit_length())
    if len(a) == n:
        return a
    return np.concatenate([a, np.full(n - len(a), fill, a.dtype)])


class SummaryQuery:
    """Read view over one ``BatchedSummarizer`` snapshot (caller labels).

    Pins the engine state and the interned-label horizon at construction:
    labels streamed after ``query()`` raise ``LookupError`` here even
    though the summarizer has since seen them, and answers keep matching
    the pinned epoch on non-donating backends.
    """

    def __init__(self, summarizer) -> None:
        self._state = summarizer.state
        self._ids = summarizer._ids          # live dict; horizon pins reads
        self._rev = summarizer._rev
        self._n_seen = len(summarizer._rev)
        self._k = make_query_kernels(summarizer.trial_backend)
        self.epoch = summarizer.flush_epoch
        self._summ = summarizer
        self._inc = summarizer._incarnation  # restore fences this view

    # ------------------------------------------------------------- id space
    def _check_pin(self) -> None:
        """A checkpoint ``restore()`` rewinds the summarizer to a different
        epoch lineage and replaces its label maps; a view pinned before the
        restore would resolve labels against state it was never snapshotted
        from.  Fail loudly instead — take a fresh ``query()`` view."""
        if self._summ._incarnation != self._inc:
            raise RuntimeError(
                f"query view pinned at epoch {self.epoch} predates a "
                f"checkpoint restore on this summarizer; take a fresh "
                f"view with .query()")

    def seen_labels(self) -> List[object]:
        """Labels interned at snapshot time, in encounter order."""
        self._check_pin()
        return list(self._rev[:self._n_seen])

    def _nids(self, labels: Sequence[object]) -> np.ndarray:
        self._check_pin()
        out = np.empty(len(labels), np.int32)
        for i, lab in enumerate(labels):
            nid = self._ids.get(lab)
            if nid is None or nid >= self._n_seen:
                raise LookupError(
                    f"query: label {lab!r} has not been streamed "
                    f"(as of epoch {self.epoch})")
            out[i] = nid
        return out

    # -------------------------------------------------------------- queries
    def neighbors_batch(self, labels: Sequence[object]) -> List[Set[object]]:
        u = _pad_pow2(self._nids(labels), -1)
        mask = np.asarray(self._k.neighbors(self._state, u)[0])
        return [{self._rev[w] for w in np.flatnonzero(mask[i])}
                for i in range(len(labels))]

    def neighbors(self, label: object) -> Set[object]:
        return self.neighbors_batch([label])[0]

    def degree_batch(self, labels: Sequence[object]) -> List[int]:
        u = _pad_pow2(self._nids(labels), -1)
        d = np.asarray(self._k.degree(self._state, u)[0])
        return [int(x) for x in d[:len(labels)]]

    def degree(self, label: object) -> int:
        return self.degree_batch([label])[0]

    def has_edge_batch(self, pairs: Sequence[Tuple[object, object]],
                       ) -> List[bool]:
        u = _pad_pow2(self._nids([p[0] for p in pairs]), -1)
        v = _pad_pow2(self._nids([p[1] for p in pairs]), -1)
        present = np.asarray(self._k.has_edge(self._state, u, v)[0])
        return [bool(x) for x in present[:len(pairs)]]

    def has_edge(self, u: object, v: object) -> bool:
        return self.has_edge_batch([(u, v)])[0]


class ShardedSummaryQuery:
    """Read view over one ``ShardedSummarizer`` flush-epoch snapshot.

    Construction performs NO device fetch and does not flush the dispatch
    pipeline: on the pipelined router the snapshot is the last state an
    engine stage produced (``epoch`` chunks applied), so reads proceed
    while the routed-but-undispatched chunk — and any in-flight engine
    work — stays in flight.  The snapshot's own ``n_dropped`` counters
    are checked on the first materialized answer (capacity overflows must
    not serve silently-lossy reads).
    """

    def __init__(self, summarizer, copy: bool = False) -> None:
        est, ist = summarizer.state, summarizer.intern
        if copy:   # survive buffer donation by later writes (non-CPU)
            est = jax.tree.map(jnp.copy, est)
            ist = jax.tree.map(jnp.copy, ist)
        self._est, self._ist = est, ist
        self._summ = summarizer
        self._k = make_sharded_query_kernels(
            summarizer.cfg, summarizer.mesh, summarizer.trial_backend)
        self._rev_cache: dict = {}
        self._intern_host = None
        self.epoch = summarizer.flush_epoch
        self.n_shards = summarizer.n_shards
        self._inc = summarizer._incarnation  # restore fences this view
        self._obs = summarizer.obs
        self._batches = 0           # read batches served: span request ids

    # ------------------------------------------------------------- id space
    def _check_pin(self) -> None:
        """Restore fence (see :meth:`SummaryQuery._check_pin`): this view
        resolves nids through the summarizer's live hash -> label map, so a
        checkpoint restore — which replaces that map with a different
        lineage's — must invalidate it loudly."""
        if self._summ._incarnation != self._inc:
            raise RuntimeError(
                f"query view pinned at epoch {self.epoch} predates a "
                f"checkpoint restore on this summarizer; take a fresh "
                f"view with .query()")

    def _hash_words(self, labels: Sequence[object]):
        self._check_pin()
        from repro.dist import labelhash
        hi, lo = labelhash.hash_words(list(labels))
        return _pad_pow2(hi, -1), _pad_pow2(lo, -1)

    def _require_seen(self, labels, found: np.ndarray) -> None:
        seen = found.any(axis=0)
        for i, lab in enumerate(labels):
            if not seen[i]:
                raise LookupError(
                    f"query: label {lab!r} has not been streamed "
                    f"(as of epoch {self.epoch})")

    def _batch_span(self, name: str):
        """Root span of one read batch, request id ``(epoch, batch)``."""
        self._batches += 1
        return self._obs.span(name, (self.epoch, self._batches))

    def _snapshot_intern(self):
        """Host copy of the snapshot's reverse maps (one fetch, memoized);
        also the capacity tripwire for every answer this view serves."""
        if self._intern_host is None:
            l2h, n_nodes, n_dropped = jax.device_get(
                (self._ist.l2h, self._ist.n_nodes, self._ist.n_dropped))
            self._summ._raise_if_dropped(int(np.sum(n_dropped)))
            self._intern_host = (np.asarray(l2h), np.asarray(n_nodes))
        return self._intern_host

    def _rev(self, shard: int) -> List[object]:
        """nid -> caller label for one shard, from the SNAPSHOT intern."""
        if shard not in self._rev_cache:
            from repro.dist import labelhash
            self._check_pin()
            l2h, n_nodes = self._snapshot_intern()
            rows = l2h[shard][:int(n_nodes[shard])]
            self._summ._fold_labels()   # append-only superset map: safe
            h2l = self._summ._h2label
            self._rev_cache[shard] = [
                h2l[int(h)] for h in labelhash.combine(rows[:, 0],
                                                       rows[:, 1])]
        return self._rev_cache[shard]

    def seen_labels(self) -> List[object]:
        """Distinct labels interned in any shard at snapshot time."""
        out, seen = [], set()
        for s in range(self.n_shards):
            for lab in self._rev(s):
                if lab not in seen:
                    seen.add(lab)
                    out.append(lab)
        return out

    # -------------------------------------------------------------- queries
    def neighbors_batch(self, labels: Sequence[object]) -> List[Set[object]]:
        obs = self._obs
        with self._batch_span("query.neighbors"):
            with obs.span("query.hash"):
                hi, lo = self._hash_words(labels)
            with obs.span("query.dispatch"):
                mask, found = self._k.neighbors(self._est, self._ist, hi, lo)
            with obs.span("query.wait"):        # blocked on the device
                mask, found = np.asarray(mask), np.asarray(found)
                self._snapshot_intern()
            with obs.span("query.decode"):
                self._require_seen(labels, found)
                out: List[Set[object]] = []
                for q in range(len(labels)):
                    acc: Set[object] = set()
                    for s in range(self.n_shards):
                        hits = np.flatnonzero(mask[s, q])
                        if hits.size:
                            rev = self._rev(s)
                            acc.update(rev[int(w)] for w in hits)
                    out.append(acc)
        return out

    def neighbors(self, label: object) -> Set[object]:
        return self.neighbors_batch([label])[0]

    def degree_batch(self, labels: Sequence[object]) -> List[int]:
        obs = self._obs
        with self._batch_span("query.degree"):
            with obs.span("query.hash"):
                hi, lo = self._hash_words(labels)
            with obs.span("query.dispatch"):
                d, found = self._k.degree(self._est, self._ist, hi, lo)
            with obs.span("query.wait"):
                d, found = np.asarray(d), np.asarray(found)
                self._snapshot_intern()
            with obs.span("query.decode"):
                self._require_seen(labels, found)
                # per-shard edge partitions are disjoint, so degrees add
                # exactly
                return [int(x) for x in d.sum(axis=0)[:len(labels)]]

    def degree(self, label: object) -> int:
        return self.degree_batch([label])[0]

    def has_edge_by_shard(self, pairs: Sequence[Tuple[object, object]],
                          ) -> np.ndarray:
        """bool[n_shards, len(pairs)]: which shard holds each edge.  At
        most one True per column — the pair's ``shard_key`` owner."""
        with self._batch_span("query.has_edge"):
            return self._has_edge_by_shard(pairs)

    def _has_edge_by_shard(self, pairs) -> np.ndarray:
        obs = self._obs
        with obs.span("query.hash"):
            uh, ul = self._hash_words([p[0] for p in pairs])
            vh, vl = self._hash_words([p[1] for p in pairs])
        with obs.span("query.dispatch"):
            present, _, fu, fv = self._k.has_edge(
                self._est, self._ist, uh, ul, vh, vl)
        with obs.span("query.wait"):
            present, fu, fv = (np.asarray(x) for x in (present, fu, fv))
            self._snapshot_intern()
        with obs.span("query.decode"):
            self._require_seen([p[0] for p in pairs], fu)
            self._require_seen([p[1] for p in pairs], fv)
            return present[:, :len(pairs)]

    def has_edge_batch(self, pairs: Sequence[Tuple[object, object]],
                       ) -> List[bool]:
        with self._batch_span("query.has_edge"):
            present = self._has_edge_by_shard(pairs)
            return [bool(x) for x in present.any(axis=0)]

    def has_edge(self, u: object, v: object) -> bool:
        return self.has_edge_batch([(u, v)])[0]
