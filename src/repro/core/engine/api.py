"""Host-side wrappers around the batched engine (Tier B public API).

Two front-ends share the jitted step:

* :class:`BatchedSummarizer` — one engine on one device.
* :class:`ShardedSummarizer` — an edge-partitioned fleet of engines laid out
  over a 1-D device mesh via ``shard_map`` (one ``EngineState`` replica per
  partition, several replicas per device when ``n_shards`` exceeds the device
  count), merged into a :class:`ShardedSummaryOutput` on the host.  This is
  how the MoSSo engine scales past a single device's ``n_cap``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.engine.state import EngineConfig, EngineState, new_state
from repro.core.engine.trial import make_step
from repro.core.summary import (ShardedSummaryOutput, SummaryOutput,
                                encoding_cost, is_superedge, pair_key)
from repro.obs import SpanRecorder

Change = Tuple[int, int, bool]


# --------------------------------------------------------------------------- #
# state-level exports (shared by both front-ends; engine-id space)
# --------------------------------------------------------------------------- #


def state_live_edges(state: EngineState) -> Set[Tuple[int, int]]:
    """Export the live edge set from the slot-position table."""
    k1 = np.asarray(state.epos.k1)
    k2 = np.asarray(state.epos.k2)
    live = k1 >= 0
    return {(int(a), int(b)) for a, b in zip(k1[live], k2[live]) if a < b}


def state_materialize(state: EngineState,
                      cfg: EngineConfig | None = None) -> SummaryOutput:
    """Derive (G*, P, C+, C-) from counts + membership (optimal encoding).

    Decoding is lossless under EVERY objective — the encoding always
    reproduces exactly the live edge set.  The objective only decides
    which side of the per-pair superedge/corrections rule is cheaper:
    pass ``cfg`` so a weighted-objective state picks modes by
    ``is_superedge(W, TW)`` (the rule its ``phi`` was accounted under)
    instead of the unweighted counts.
    """
    weighted = cfg is not None and cfg.objective == "weighted"
    n2s = np.asarray(state.n2s)
    ssize = np.asarray(state.ssize)
    seen = n2s >= 0
    members: Dict[int, Set[int]] = {}
    for u in np.nonzero(seen)[0]:
        members.setdefault(int(n2s[u]), set()).add(int(u))
    for sid, mem in members.items():
        assert len(mem) == ssize[sid], f"ssize drift at sid {sid}"

    k1 = np.asarray(state.eab.k1)
    k2 = np.asarray(state.eab.k2)
    val = np.asarray(state.eab.val)
    live = k1 >= 0
    edges = state_live_edges(state)

    if weighted:
        from repro.core.reference.weights import host_node_weight
        wmap = {}
        wk1 = np.asarray(state.weab.k1)
        wlive = wk1 >= 0
        for a, b, w in zip(wk1[wlive], np.asarray(state.weab.k2)[wlive],
                           np.asarray(state.weab.val)[wlive]):
            wmap[(int(a), int(b))] = int(w)

        def w_of(u: int) -> int:
            return host_node_weight(u, cfg.weight_levels)

    superedges: Set[Tuple[int, int]] = set()
    c_plus: Set[Tuple[int, int]] = set()
    c_minus: Set[Tuple[int, int]] = set()
    for a, b, e in zip(k1[live], k2[live], val[live]):
        a, b, e = int(a), int(b), int(e)
        sa, sb = len(members[a]), len(members[b])
        t = sa * (sa - 1) // 2 if a == b else sa * sb
        pair_edges = [pq for pq in _pairs(members[a], members[b], a == b)]
        actual = [pq for pq in pair_edges if pq in edges]
        assert len(actual) == e, f"eab drift at pair {(a, b)}: {len(actual)} != {e}"
        if weighted:
            wab = wmap.get((a, b), 0)
            w_actual = sum(w_of(p) * w_of(q) for (p, q) in actual)
            assert w_actual == wab, \
                f"weab drift at pair {(a, b)}: {w_actual} != {wab}"
            tw = sum(w_of(p) * w_of(q) for (p, q) in pair_edges)
            mode_super = is_superedge(wab, tw)
        else:
            mode_super = is_superedge(e, t)
        if mode_super:
            superedges.add(pair_key(a, b))
            c_minus.update(pq for pq in pair_edges if pq not in edges)
        else:
            c_plus.update(actual)
    return SummaryOutput(supernodes=members, superedges=superedges,
                         c_plus=c_plus, c_minus=c_minus)


def state_phi_recomputed(state: EngineState,
                         cfg: EngineConfig | None = None) -> int:
    """Refold phi from the live pair table (weighted fold when ``cfg``
    selects the weighted objective)."""
    if cfg is not None and cfg.objective == "weighted":
        k1 = np.asarray(state.weab.k1)
        k2 = np.asarray(state.weab.k2)
        val = np.asarray(state.weab.val)
        wsum = np.asarray(state.wsum)
        wsq = np.asarray(state.wsq)
        live = k1 >= 0
        tot = 0
        for a, b, w in zip(k1[live], k2[live], val[live]):
            a, b = int(a), int(b)
            if a == b:
                tw = (int(wsum[a]) ** 2 - int(wsq[a])) // 2
            else:
                tw = int(wsum[a]) * int(wsum[b])
            tot += encoding_cost(int(w), tw)
        return tot
    k1 = np.asarray(state.eab.k1)
    k2 = np.asarray(state.eab.k2)
    val = np.asarray(state.eab.val)
    ssize = np.asarray(state.ssize)
    live = k1 >= 0
    tot = 0
    for a, b, e in zip(k1[live], k2[live], val[live]):
        a, b = int(a), int(b)
        sa, sb = int(ssize[a]), int(ssize[b])
        t = sa * (sa - 1) // 2 if a == b else sa * sb
        tot += encoding_cost(int(e), t)
    return tot


def _pairs(ma: Set[int], mb: Set[int], same: bool):
    if same:
        mem = sorted(ma)
        for i, u in enumerate(mem):
            for v in mem[i + 1:]:
                yield (u, v)
    else:
        for u in sorted(ma):
            for v in sorted(mb):
                yield (u, v) if u < v else (v, u)


def _relabel_output(out: SummaryOutput, rev: Sequence[object],
                    sid_offset: int) -> SummaryOutput:
    """Map a shard's engine-id output back to caller labels, with supernode
    ids offset into a globally unique range."""
    return SummaryOutput(
        supernodes={sid_offset + sid: {rev[u] for u in mem}
                    for sid, mem in out.supernodes.items()},
        superedges={(sid_offset + a, sid_offset + b)
                    for (a, b) in out.superedges},
        c_plus={pair_key(rev[a], rev[b]) for (a, b) in out.c_plus},
        c_minus={pair_key(rev[a], rev[b]) for (a, b) in out.c_minus},
    )


# --------------------------------------------------------------------------- #
# crash consistency (shared by both front-ends)
# --------------------------------------------------------------------------- #


class _CrashConsistency:
    """Epoch checkpoints + write-ahead chunk journal for a summarizer.

    Both front-ends dispatch the stream in fixed-size chunks
    (``dispatch_chunk``), and chunk boundaries fully determine padding
    and the engine-round/PRNG schedule — so a run is reconstructible
    bitwise from (checkpoint at epoch E) + (the exact chunk slices
    dispatched after E).  This mixin supplies that contract:

    * with ``checkpoint_dir`` set, every chunk is durably journaled
      (:class:`repro.checkpoint.journal.ChunkJournal`) **before** it is
      dispatched;
    * ``save()`` writes the full recovery closure at a flushed epoch and
      compacts the journal; ``restore()`` loads the newest checkpoint
      that passes its checksums (refusing config mismatches);
    * ``recover()`` = restore + deterministic journal-tail replay, the
      crash path proven bitwise by ``tests/test_recovery.py``.

    ``stream_cursor`` counts stream changes applied so far — a driver
    resumes feeding from there after ``recover()``.  ``_incarnation``
    bumps on every restore so pinned query views fail loudly instead of
    resolving labels against a state they were not snapshotted from.
    """

    def _init_crash_consistency(self, checkpoint_dir: Optional[str]) -> None:
        self._ckpt_dir = checkpoint_dir
        self._journal = None        # lazily opened ChunkJournal
        self._journal_seq = 0       # chunks dispatched (journal record seq)
        self._cursor = 0            # stream changes applied
        self._replaying = False     # recovery replay: don't re-journal
        self._recovered = False     # this instance resumed an old directory
        self.stream_retries = 0     # recoveries performed by a retry driver
        self._incarnation = 0       # bumps per restore; query views pin it

    @property
    def stream_cursor(self) -> int:
        """Stream changes applied (journaled-and-dispatched) so far."""
        return self._cursor

    def _journal_chunk(self, chunk) -> None:
        """WAL append for one dispatch chunk; seq advances regardless of
        whether journaling is enabled so save/restore counters line up."""
        seq = self._journal_seq
        self._journal_seq += 1
        if self._ckpt_dir is None or self._replaying:
            return
        if self._journal is None:
            from repro.checkpoint.journal import ChunkJournal
            from repro.checkpoint.summary import journal_path
            self._journal = ChunkJournal(journal_path(self._ckpt_dir))
            if seq == 0 and not self._recovered:
                self._journal.reset()   # fresh stream into an old directory
        self._journal.append(seq, chunk)

    def _replay_chunk(self, changes) -> None:
        """Re-dispatch one journaled chunk during recovery (no re-append).
        Each journal record is one original dispatch slice (≤ the chunk
        size), so replaying it as its own ``process`` call reproduces the
        original padding and engine-round schedule exactly."""
        self._replaying = True
        try:
            self.process(changes)
        finally:
            self._replaying = False

    def _require_ckpt_dir(self, ckpt_dir: Optional[str]) -> str:
        d = ckpt_dir or self._ckpt_dir
        if d is None:
            raise ValueError(
                "no checkpoint directory: pass one explicitly or construct "
                "the summarizer with checkpoint_dir=...")
        return d

    def _ckpt_shardings(self):
        """Placement of the restored tree's leaves (``None``: default
        device)."""
        return None

    def save(self, ckpt_dir: Optional[str] = None) -> str:
        """Checkpoint the full recovery closure at a flushed epoch."""
        from repro.checkpoint import summary as ckpt
        return ckpt.save_summarizer(self, self._require_ckpt_dir(ckpt_dir))

    def restore(self, ckpt_dir: Optional[str] = None,
                step: Optional[int] = None) -> dict:
        """Load the newest verifiable checkpoint (or ``step``) into this
        summarizer; raises on config mismatch, falls back across corrupt
        epochs."""
        from repro.checkpoint import summary as ckpt
        return ckpt.restore_summarizer(self, self._require_ckpt_dir(ckpt_dir),
                                       step=step)

    def recover(self, ckpt_dir: Optional[str] = None) -> dict:
        """Crash recovery: restore last valid epoch + replay journal tail."""
        from repro.checkpoint import summary as ckpt
        return ckpt.recover_summarizer(self, self._require_ckpt_dir(ckpt_dir))


# --------------------------------------------------------------------------- #
# single-engine front-end
# --------------------------------------------------------------------------- #


class BatchedSummarizer(_CrashConsistency):
    """Feed a fully dynamic graph stream through the jitted engine step.

    **Id space.** ``process``/``run`` accept arbitrary hashable caller
    labels and intern them (host-side, encounter order) into the engine's
    dense ``[0, n_cap)`` id space.  Outputs stay in ENGINE ids:
    ``live_edges``/``materialize``/``phi_recomputed`` report engine-id
    pairs; map engine ids back to labels through ``self._rev`` (or map a
    label-space ground truth into engine ids through ``self._ids``) when
    comparing — the sharded front-end, by contrast, reports caller labels.

    **Capacity.** One engine, one device: at most ``n_cap`` distinct
    labels ever seen (asserted at interning time) and ``m_cap`` live edges
    (a table-sizing contract, unchecked — see :class:`EngineConfig`).
    Scale past either with :class:`ShardedSummarizer`.

    **Probe backend.** ``trial_backend`` selects how the step's batched
    hash-table probes lower: ``"xla"`` (vmapped while loops, the
    differential reference) or ``"pallas"`` (one fused kernel launch per
    probe batch, ``repro.kernels.ht_probe``; interpret mode off-TPU).
    ``None`` defers to the ``REPRO_TRIAL_BACKEND`` env default.  Both
    backends are leaf-bitwise state-identical on identical streams.
    """

    def __init__(self, cfg: EngineConfig | None = None, *,
                 trial_backend: str | None = None,
                 checkpoint_dir: Optional[str] = None, **overrides) -> None:
        from repro.core.engine.hashtable import resolve_trial_backend
        if cfg is None:
            cfg = EngineConfig(**overrides)
        elif overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        self.cfg = cfg
        self.trial_backend = resolve_trial_backend(trial_backend)
        self.state: EngineState = new_state(cfg)
        self._step = make_step(cfg, trial_backend=self.trial_backend)
        self._ids: Dict[object, int] = {}
        self._rev: List[object] = []
        self._epoch = 0             # engine-step dispatches applied so far
        self._init_crash_consistency(checkpoint_dir)

    # ------------------------------------------------------------------ ids
    def _nid(self, label: object) -> int:
        i = self._ids.get(label)
        if i is None:
            i = len(self._rev)
            assert i < self.cfg.n_cap, "node capacity exceeded"
            self._ids[label] = i
            self._rev.append(label)
        return i

    # --------------------------------------------------------------- stream
    @property
    def dispatch_chunk(self) -> int:
        """Stream slice size per journaled dispatch (= ``cfg.batch``)."""
        return self.cfg.batch

    def process(self, changes: Sequence[Change]) -> None:
        b = self.cfg.batch
        changes = list(changes)
        # slice BEFORE interning: each batch slice is journaled (WAL) and
        # then interned+dispatched on its own, so a journal-tail replay of
        # the same slices reproduces _ids encounter order, padding and the
        # engine-round/PRNG schedule exactly (interning is stream-ordered
        # either way, so per-slice interning is bitwise identical to the
        # old whole-call interning)
        for off in range(0, len(changes), b):
            sl = changes[off:off + b]
            self._journal_chunk(sl)
            buf = [(self._nid(u), self._nid(v), ins) for (u, v, ins) in sl]
            pad = b - len(buf)
            u = np.array([c[0] for c in buf] + [-1] * pad, np.int32)
            v = np.array([c[1] for c in buf] + [-1] * pad, np.int32)
            ins = np.array([c[2] for c in buf] + [False] * pad, bool)
            self.state = self._step(self.state, u, v, ins)
            self._epoch += 1
            self._cursor += len(sl)

    def run(self, stream: Iterable[Change]) -> "BatchedSummarizer":
        self.process(list(stream))
        return self

    def flush(self) -> None:
        """No-op barrier (dispatch is synchronous here); API symmetry with
        the sharded tier so checkpoint code can flush either."""

    # ---------------------------------------------------------------- reads
    @property
    def flush_epoch(self) -> int:
        """Engine-step dispatches applied to ``state`` so far.  The state
        pytree is replaced functionally per dispatch, so a reference
        captured between ``process`` calls is exactly this epoch's state."""
        return self._epoch

    def query(self):
        """Snapshot read view answering ``neighbors``/``degree``/
        ``has_edge`` in caller-label space directly from the compressed
        engine state — no decompression (:mod:`repro.serve.query`).
        Labels streamed after this call raise ``LookupError`` on the view.
        """
        from repro.serve.query import SummaryQuery
        return SummaryQuery(self)

    # ------------------------------------------------------------ maintenance
    def table_pressure(self) -> Dict[str, float]:
        """live+tombstone slot fraction per table (probe-chain health)."""
        from repro.core.engine.hashtable import TOMB
        out = {}
        tables = ("adj", "epos", "eab", "snadj", "snpos")
        if self.cfg.objective == "weighted":
            tables += ("weab",)
        for name in tables:
            t = getattr(self.state, name)
            k1 = np.asarray(t.k1)
            out[name] = float(((k1 >= 0) | (k1 == int(TOMB))).mean())
        return out

    def maybe_compact(self, threshold: float = 0.7) -> bool:
        """Rebuild tables whose occupied fraction (live + tombstones) crosses
        ``threshold``.  Long fully-dynamic streams accumulate tombstones that
        stretch linear-probe chains; production deployments call this between
        steps (it is pure state -> state, so it composes with checkpoints).
        """
        from repro.core.engine.hashtable import ht_rebuild
        pressure = self.table_pressure()
        dirty = {n: p for n, p in pressure.items() if p > threshold}
        if not dirty:
            return False
        self.state = self.state._replace(
            **{n: ht_rebuild(getattr(self.state, n)) for n in dirty})
        return True

    # ---------------------------------------------------------------- stats
    @property
    def phi(self) -> int:
        return int(self.state.phi)

    @property
    def num_edges(self) -> int:
        return int(self.state.num_edges)

    def compression_ratio(self) -> float:
        e = self.num_edges
        return float(self.phi) / e if e else 0.0

    def stats(self) -> dict:
        s = self.state
        return dict(phi=int(s.phi), num_edges=int(s.num_edges),
                    trials=int(s.n_trials), accepted=int(s.n_accept),
                    skipped=int(s.n_skipped),
                    stream_retries=self.stream_retries)

    # ----------------------------------------------------- recovery closure
    def _ckpt_tree(self) -> dict:
        return {"est": self.state._asdict()}

    def _ckpt_host(self) -> dict:
        return {"ids": dict(self._ids), "rev": list(self._rev)}

    def _ckpt_manifest(self) -> dict:
        return {"tier": "batched", "config": self.cfg.manifest(),
                "trial_backend": self.trial_backend}

    @staticmethod
    def _ckpt_pins() -> tuple:
        # trial_backend is a bitwise-identical execution variant (standing
        # differential bar) — recorded, not pinned
        return ("tier", "config")

    def _ckpt_apply(self, tree: dict, host: dict, extra: dict) -> None:
        self.state = EngineState(**tree["est"])
        self._ids = dict(host["ids"])
        self._rev = list(host["rev"])
        self._epoch = int(extra["epoch"])
        self._journal_seq = int(extra["journal_seq"])
        self._cursor = int(extra["cursor"])
        self._recovered = True
        self._incarnation += 1

    # ------------------------------------------------------------ materialize
    def live_edges(self) -> Set[Tuple[int, int]]:
        return state_live_edges(self.state)

    def materialize(self) -> SummaryOutput:
        return state_materialize(self.state, self.cfg)

    def phi_recomputed(self) -> int:
        return state_phi_recomputed(self.state, self.cfg)


# --------------------------------------------------------------------------- #
# sharded front-end
# --------------------------------------------------------------------------- #


class ShardedSummarizer(_CrashConsistency):
    """Edge-partitioned summarization across mesh devices.

    Every stream change is routed to the shard owning its canonical pair
    (``min(h(u), h(v)) % n_shards`` over the stable 62-bit label hash
    ``h``, :mod:`repro.dist.labelhash`), so each engine replica sees a
    deterministic, disjoint edge partition and summarizes it losslessly on
    its own ``n_cap``-bounded id space.  Aggregate capacity therefore grows
    linearly with the shard count.  The merged output is the union-of-parts
    encoding (:class:`ShardedSummaryOutput`); ``phi`` is the sum of shard
    phis since per-pair encodings never span shards.

    **Id spaces.** Three layers, all host-recoverable:

    * caller labels — any hashable (streaming) / mutually orderable
      (``live_edges``/``materialize``) values;
    * 62-bit label hashes — a pure stable function of the label
      (splitmix64 for ints, blake2b-8 otherwise), carried on device as two
      31-bit words; the routing key is computed on hashes, so placement
      needs no host dict and no encounter-order state;
    * per-shard local nids — dense ``[0, n_cap)`` ids the engine state is
      indexed by, assigned ON DEVICE in delivery order by the intern tables
      of :mod:`repro.dist.router` (both routing modes assign identically).

    The hash -> label reverse map needed by ``decode``/``materialize``/
    ``shard_of`` is folded lazily at sync points from a per-chunk label
    buffer — never on the dispatch path.  A (astronomically unlikely)
    62-bit hash collision is detected at the fold and raises rather than
    silently merging two nodes.

    **Routing modes** (``routing=``):

    * ``"device"`` (default) — changes stream through the two-stage
      jit-compiled router: the **route** stage (shard keys + a
      capacity-bounded ``all_to_all`` lane exchange, run as a bounded
      on-device drain loop when a (source, shard) lane exceeds
      ``lane_cap``) depends only on the chunk, and the **engine** stage
      (on-device interning + pmax-agreed engine rounds) carries the state.
      With the default ``max_drain_rounds`` delivery of a full chunk is
      statically guaranteed, so dispatch is **sync-free**, and the two
      stages form a software pipeline: chunk k+1 is hashed, packed and
      routed (drain rounds included) while chunk k runs its engine rounds
      (``pipeline=False`` forces serial per-chunk dispatch, bit-identical
      results).  Only an explicitly lowered ``max_drain_rounds`` (or
      ``chunk_sync=True``) reinstates the per-chunk watermark fetch; a
      suffix left undelivered when the round budget runs out falls back to
      the host path below and ``router_overflows`` counts the spilled
      changes.
    * ``"host"`` — the differential reference: the host buckets hashed
      changes per shard (vectorized numpy, stream order preserved) and
      feeds padded ``[n_shards, batch]`` rounds.  Given identical
      ``process`` call boundaries (calls no longer than ``router_chunk``),
      both modes produce bit-identical engine states — including through
      multi-round drains — as long as no host fallback ran (the fallback
      legitimately shifts the PRNG schedule).

    **Replica execution** (``replica_exec=``): how the shard replicas
    stacked on one device (``n_shards > n_devices``, the production
    layout) are laid out inside the compiled step:

    * ``"vmap"`` — one batched program over the stacked replica axis.
      The trial engine is cond-free predicated data flow, so vmap pays
      no both-branches penalty and the engine stage becomes one
      replica-parallel step.
    * ``"map"`` — ``lax.map`` over replicas, serializing them per
      device.  Also the differential reference, like ``routing="host"``:
      both modes are leaf-bitwise state-identical on identical inputs.

    The default (``repro.dist.router.default_replica_exec()``, resolved
    here rather than at import) is backend-aware: vmap on accelerators,
    map on the XLA CPU backend, where batched control flow carries a
    measured fixed dispatch tax (see docs/KNOWN_ISSUES.md).
    ``REPRO_REPLICA_EXEC`` overrides.

    **Probe backend** (``trial_backend=``): how the engine's batched
    hash-table probes (trial lookups + the router's intern pre-lookup)
    lower — ``"xla"`` (vmapped while loops; the default and the
    differential reference) or ``"pallas"`` (one fused
    ``repro.kernels.ht_probe`` launch per batch; interpret mode off-TPU).
    ``REPRO_TRIAL_BACKEND`` sets the process default; both backends are
    leaf-bitwise state-identical.

    **Routing telemetry.** ``router_syncs`` counts per-chunk watermark
    fetches (0 when ``sync_free``), ``router_host_dict_ops`` counts
    label-map mutations performed inside dispatch (0 on the hash-routed
    steady state — the reverse map folds lazily at sync points),
    ``router_overflows`` counts changes replayed through the host path,
    and ``stats()['router_drain_rounds']`` counts extra drain rounds
    beyond the first (carried in the engine stage's device-side state —
    the route stage's round count rides into the engine step, which
    accumulates it on device; fetched only at sync points, with zero
    host-side buffering of per-chunk counts); beside it
    ``stats()['engine_rounds']`` counts the engine rounds run, each
    paying ``n_shards x batch`` slots whether filled or not.

    **Spans.** ``obs`` (:class:`repro.obs.SpanRecorder`) records where the
    host's time goes: ``summarizer.process`` per call with its
    ``journal``/``pack``/``route``/``engine``/``compact_labels`` children
    (request id: the chunk's journal sequence number), ``summarizer.sync``
    at sync points, and the query views' ``query.*`` spans.  Recording
    fetches nothing from the device; ``obs.enabled = False`` turns it off.

    **Capacity semantics.** Edge partitioning is a vertex cut: a node
    touching edges in several partitions occupies a local id in each, so
    per-shard ``n_cap`` must budget the replication factor (see
    ``src/repro/dist/README.md``).  The host path and the device path both
    intern on device; exceeding ``n_cap`` increments a per-shard
    ``n_dropped`` counter and skips the change, and the next host-side
    sync point (``phi``/``stats``/``materialize``/...) raises
    ``RuntimeError`` — a dropped change would otherwise silently break
    losslessness.
    """

    def __init__(self, cfg: EngineConfig | None = None, *,
                 mesh=None, n_shards: Optional[int] = None,
                 routing: str = "device", router_chunk: int = 1024,
                 lane_cap: Optional[int] = None,
                 max_drain_rounds: Optional[int] = None,
                 chunk_sync: bool = False,
                 pipeline: bool = True,
                 replica_exec: Optional[str] = None,
                 trial_backend: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None,
                 **overrides) -> None:
        import math

        import jax

        from repro.dist import router as dist_router

        if cfg is None:
            cfg = EngineConfig(**overrides)
        elif overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        self.cfg = cfg
        if replica_exec is None:
            replica_exec = dist_router.default_replica_exec()
        if replica_exec not in dist_router.REPLICA_EXEC_MODES:
            raise ValueError(
                f"replica_exec must be one of "
                f"{dist_router.REPLICA_EXEC_MODES}: {replica_exec}")
        self.replica_exec = replica_exec
        from repro.core.engine.hashtable import resolve_trial_backend
        self.trial_backend = resolve_trial_backend(trial_backend)
        if mesh is None:
            from repro.launch.mesh import make_engine_mesh
            if n_shards is None:
                mesh = make_engine_mesh()
            else:
                # fit the mesh to the shard count: n_shards replicas spread
                # over the largest local device subset that divides them
                mesh = make_engine_mesh(
                    math.gcd(int(n_shards), len(jax.devices())))
        self.mesh = mesh
        n_dev = int(mesh.devices.size)
        self.n_shards = n_dev if n_shards is None else int(n_shards)
        if self.n_shards % n_dev != 0:
            raise ValueError(
                f"n_shards={self.n_shards} must be a multiple of the mesh "
                f"device count {n_dev}")
        if self.n_shards >= dist_router.MAX_SHARDS:
            raise ValueError(
                f"n_shards={self.n_shards} must be < "
                f"{dist_router.MAX_SHARDS} (device shard keys compose "
                f"31-bit hash words over uint32 residues)")
        if routing not in ("device", "host"):
            raise ValueError(f"routing must be 'device' or 'host': {routing}")
        self.routing = routing
        # round the chunk up so it splits evenly over the devices
        self.router_chunk = -(-int(router_chunk) // n_dev) * n_dev
        self.lane_cap = (dist_router.default_lane_cap(
            self.router_chunk, n_dev, self.n_shards, cfg.batch)
            if lane_cap is None
            else min(int(lane_cap), self.router_chunk // n_dev))
        self.router_overflows = 0   # changes spilled to the host path
        self.router_syncs = 0       # per-chunk watermark fetches performed
        self.chunk_sync = bool(chunk_sync)
        # drain- and engine-round telemetry lives IN the engine stage's
        # carried state (int32[n_dev, 2], accumulated on device, fetched
        # only at sync points); host-path rounds are counted on the host
        self._drain_rounds = dist_router.drain_telemetry_new(n_dev)
        self._host_engine_rounds = 0
        self.obs = SpanRecorder()   # in-program spans (repro.obs)
        self._bucketed = dist_router.make_bucketed_step(
            cfg, mesh, replica_exec, self.trial_backend)
        if routing == "device":
            self._route, self.router_geometry = dist_router.make_route_step(
                mesh, self.n_shards, self.router_chunk, self.lane_cap,
                max_drain_rounds)
            self._engine = dist_router.make_engine_step(
                cfg, mesh, self.n_shards, self.router_geometry.acc_cap,
                replica_exec, self.trial_backend)
            self.lane_cap = self.router_geometry.lane_cap
            self.max_drain_rounds = self.router_geometry.max_drain_rounds
            # delivery statically guaranteed -> the overflow watermark never
            # gates anything and dispatch needs no per-chunk host round-trip
            self.sync_free = (self.router_geometry.drain_guaranteed
                              and not self.chunk_sync)
        else:
            self._route = self._engine = None
            self.router_geometry = None
            self.max_drain_rounds = None
            self.sync_free = False
        # the memoized jitted programs per stage, for stats()'s program
        # counts (the attributes above may be wrapped by a caller)
        self._stage_fns = {
            "route": () if self._route is None else (self._route,),
            "engine": (self._bucketed if self._engine is None
                       else self._engine,)}
        # the route stage has no state dependencies, so on the sync-free
        # path chunk k+1's routing is dispatched while chunk k's engine
        # rounds execute (one routed chunk in flight, flushed at sync)
        self.pipeline = bool(pipeline) and self.sync_free
        self._pending = None        # (seq, routed buckets) awaiting engine
        self._epoch = 0             # engine dispatches applied to self.state
        self._init_crash_consistency(checkpoint_dir)

        self.state, self.intern = dist_router.new_stacked_states(
            cfg, mesh, self.n_shards)

        self._h2label: Dict[int, object] = {}  # 62-bit hash -> caller label
        self._label_buf: List = []   # (labels, hi, lo) pending lazy fold
        self._label_head = None      # compacted (labels, hashes), hash-sorted
        self._host_dict_ops = 0      # label-map mutations inside dispatch
        self._in_dispatch = False
        self._host_cache = None

    # ------------------------------------------------------------------ ids
    def _pack_chunk(self, chunk: Sequence[Change], pad_to: int = 0):
        """Hash one chunk of labeled changes into device words.

        One vectorized numpy pass for integer labels (no per-change Python
        object work), a pure per-element hash otherwise — either way zero
        dict mutations; the labels are buffered for the lazy reverse-map
        fold at the next sync point.
        """
        from repro.dist import labelhash

        m = len(chunk)
        us = [c[0] for c in chunk]
        vs = [c[1] for c in chunk]
        uh, ul = labelhash.hash_words(us)
        vh, vl = labelhash.hash_words(vs)
        fl = np.fromiter((c[2] for c in chunk), np.int32, m)
        self._label_buf.append((us, uh, ul))
        self._label_buf.append((vs, vh, vl))
        if pad_to > m:
            def pad(a, fill):
                return np.concatenate(
                    [a, np.full(pad_to - m, fill, a.dtype)])
            uh, ul, vh, vl = (pad(a, -1) for a in (uh, ul, vh, vl))
            fl = pad(fl, 0)
        return uh, ul, vh, vl, fl

    @staticmethod
    def _collision(a, b, h) -> "RuntimeError":
        return RuntimeError(
            f"62-bit label-hash collision: {a!r} and {b!r} both hash to "
            f"{int(h):#x}; rename one label (collision odds are ~n^2/2^63 "
            f"— this is loud instead of silently merging the two nodes)")

    def _compact_label_buf(self) -> None:
        """Dedup the pending label buffer by hash — numpy only, no dict.

        Without this a long zero-sync run would buffer every label
        OCCURRENCE (two per change) until the next fold.  Compaction
        dedups the un-compacted tail (object-array work proportional to
        the tail only) and merges it into a hash-sorted compacted head
        with pure int64 numpy ops, so the buffer is bounded at O(unique
        labels) and per-cycle Python-object work at O(compaction window).
        Dropped duplicates are equality-checked against the kept first
        occurrence (vectorized object compare), so a hash collision still
        raises loudly here rather than being silently compacted away."""
        from repro.dist import labelhash

        buf = self._label_buf
        if not buf:
            return
        labels = [x for (ls, _, _) in buf for x in ls]
        arr = np.array(labels, dtype=object)
        if arr.ndim != 1:           # e.g. equal-length tuple labels
            arr = np.empty(len(labels), object)
            for i, x in enumerate(labels):
                arr[i] = x
        comb = np.concatenate([labelhash.combine(hi, lo)
                               for (_, hi, lo) in buf])
        uniq, first, inv = np.unique(comb, return_index=True,
                                     return_inverse=True)
        # identity escape mirrors _fold_labels' `prev is not label`: a
        # non-reflexive label (NaN) must not read as a self-collision
        same = arr == arr[first[inv]]
        for i in np.flatnonzero(~np.asarray(same, bool)):
            j = int(first[inv[int(i)]])
            if arr[int(i)] is not arr[j]:
                raise self._collision(arr[j], arr[int(i)], comb[int(i)])
        keep = arr[first]
        if self._label_head is None:
            self._label_head = (keep, uniq)
        else:
            h_lab, h_hash = self._label_head
            pos = np.searchsorted(h_hash, uniq)
            posc = np.minimum(pos, len(h_hash) - 1)
            known = (pos < len(h_hash)) & (h_hash[posc] == uniq)
            if bool(np.any(known)):
                same2 = keep[known] == h_lab[posc[known]]
                kidx = np.flatnonzero(known)
                for k in np.flatnonzero(~np.asarray(same2, bool)):
                    i = int(kidx[int(k)])
                    if keep[i] is not h_lab[int(posc[i])]:
                        raise self._collision(h_lab[int(posc[i])], keep[i],
                                              uniq[i])
            fresh = ~known
            m_hash = np.concatenate([h_hash, uniq[fresh]])
            order = np.argsort(m_hash)       # disjoint hashes: total order
            self._label_head = (
                np.concatenate([h_lab, keep[fresh]])[order], m_hash[order])
        buf.clear()

    def _fold_labels(self) -> None:
        """Fold buffered/compacted labels into the hash -> label map.

        Runs at sync points (``materialize``/``shard_of``/``stats``/...),
        never on the steady-state dispatch path: no dispatch code calls
        this by construction, and ``router_host_dict_ops`` is the runtime
        tripwire proving it — any future code path that folds (mutates
        the label map) while ``process()`` is dispatching gets counted,
        and the `== 0` assertions in tests/benchmarks/example go red.
        Raises on a 62-bit hash collision between distinct labels:
        placement and interning key on the hash, so a collision would
        silently merge two nodes — loud failure is the contract.
        """
        head, buf = self._label_head, self._label_buf
        if head is None and not buf:
            return
        from repro.dist import labelhash

        if self._in_dispatch:
            self._host_dict_ops += (
                (len(head[0]) if head is not None else 0)
                + sum(len(e[0]) for e in buf))
        h2l = self._h2label
        entries = ([] if head is None
                   else [(head[0].tolist(), head[1])])
        entries += [(labels, labelhash.combine(hi, lo))
                    for (labels, hi, lo) in buf]
        for labels, comb in entries:
            for label, h in zip(labels, comb.tolist()):
                prev = h2l.setdefault(h, label)
                if prev is not label and prev != label:
                    raise self._collision(prev, label, h)
        self._label_head = None
        buf.clear()

    def host_label_map(self) -> Dict[int, object]:
        """The folded 62-bit hash -> caller label map (host side).

        A sync point: drains the dispatch pipeline and folds any buffered
        chunk labels first, so this plus ``state``/``intern`` really is
        everything a checkpoint needs to resume decoding.  The returned
        dict is live state — treat it as read-only."""
        self._flush_dispatch()
        self._fold_labels()
        return self._h2label

    def shard_of(self, u: object, v: object) -> int:
        """Deterministic owner shard of a STREAMED edge {u, v}.

        Placement is a pure function of the label hashes, so the answer
        never depends on stream order; the method still raises
        ``LookupError`` for labels this summarizer has not seen, keeping
        "has this node been streamed" queryable (and typos loud).
        Read-only: consults the lazily-folded reverse map, assigns
        nothing.
        """
        from repro.dist import labelhash

        self._fold_labels()
        hu, hv = labelhash.hash_label(u), labelhash.hash_label(v)
        for label, h in ((u, hu), (v, hv)):
            if h not in self._h2label:
                raise LookupError(
                    f"shard_of: label {label!r} has not been streamed")
        return min(hu, hv) % self.n_shards

    # --------------------------------------------------------------- stream
    def process(self, changes: Sequence[Change]) -> None:
        """Apply a sequence of changes, ``router_chunk`` at a time.

        Both routing modes consume the same chunk boundaries, so a host- and
        a device-routed run fed identical calls stay comparable change for
        change.  On the sync-free device path the last chunk's engine stage
        may still be in flight when this returns (jax async dispatch +
        the route/engine pipeline); every state accessor flushes first.
        """
        changes = list(changes)
        obs = self.obs
        self._in_dispatch = True
        try:
            with obs.span("summarizer.process", self._journal_seq):
                for off in range(0, len(changes), self.router_chunk):
                    chunk = changes[off:off + self.router_chunk]
                    seq = self._journal_seq
                    with obs.span("summarizer.journal", seq):
                        self._journal_chunk(chunk)  # durable BEFORE dispatch
                    if self.routing == "device":
                        self._process_chunk_device(chunk, seq)
                    else:
                        self._process_chunk_host(chunk, seq)
                    self._cursor += len(chunk)
        finally:
            self._in_dispatch = False

    @property
    def dispatch_chunk(self) -> int:
        """Stream slice size per journaled dispatch (= ``router_chunk``)."""
        return self.router_chunk

    def _process_chunk_host(self, chunk: Sequence[Change],
                            seq: Optional[int] = None) -> None:
        """Host routing: bucket hashed changes per shard, feed padded
        rounds.  Vectorized (stable ``flatnonzero`` order == stream
        order); shares the packing/hashing path with the device router so
        the two modes see identical keys."""
        from repro.dist import labelhash

        self._flush_dispatch()
        obs = self.obs
        n, b = self.n_shards, self.cfg.batch
        with obs.span("summarizer.pack", seq):
            uh, ul, vh, vl, fl = self._pack_chunk(chunk)
            dest = np.minimum(labelhash.combine(uh, ul),
                              labelhash.combine(vh, vl)) % n
            idxs = [np.flatnonzero(dest == s) for s in range(n)]
        rounds = (max((len(i) for i in idxs), default=0) + b - 1) // b
        with obs.span("summarizer.engine", seq):
            for r in range(rounds):
                buh = np.full((n, b), -1, np.int32)
                bul = np.full((n, b), -1, np.int32)
                bvh = np.full((n, b), -1, np.int32)
                bvl = np.full((n, b), -1, np.int32)
                bfl = np.zeros((n, b), np.int32)
                for s, idx in enumerate(idxs):
                    sel = idx[r * b:(r + 1) * b]
                    k = len(sel)
                    if k:
                        buh[s, :k], bul[s, :k] = uh[sel], ul[sel]
                        bvh[s, :k], bvl[s, :k] = vh[sel], vl[sel]
                        bfl[s, :k] = fl[sel]
                self.state, self.intern = self._bucketed(
                    self.state, self.intern, buh, bul, bvh, bvl, bfl)
        self._host_engine_rounds += rounds
        self._epoch += 1
        self._host_cache = None
        if len(self._label_buf) >= 128:
            with obs.span("summarizer.compact_labels", seq):
                self._compact_label_buf()

    def _process_chunk_device(self, chunk: Sequence[Change],
                              seq: Optional[int] = None) -> None:
        """Device routing: route stage + engine stage, software-pipelined.

        In the default (``sync_free``) configuration this method performs
        ZERO device-to-host transfers and ZERO host dict operations: the
        chunk is hashed in one vectorized pass, the route dispatch returns
        immediately (jax async dispatch), and the engine stage for the
        PREVIOUS chunk is dispatched after it — so chunk k+1's routing
        (drain rounds included) overlaps chunk k's engine rounds, with the
        routed buckets as donated double buffers.  Drain-round telemetry
        accumulates as a lazy device scalar fetched only at sync points.
        Only when the drain budget is explicitly bounded
        (``max_drain_rounds`` below the delivery guarantee) or
        ``chunk_sync=True`` does the watermark get fetched per chunk,
        gating the host-path replay of an undelivered suffix so stream
        order — and therefore losslessness — is preserved (serial
        dispatch: the pipeline needs the delivery guarantee)."""
        obs = self.obs
        with obs.span("summarizer.pack", seq):
            packed = self._pack_chunk(chunk, pad_to=self.router_chunk)
        with obs.span("summarizer.route", seq):
            *buckets, counts, delivered, rounds = self._route(*packed)
        # the route stage's round count rides into the engine stage, which
        # folds it into the carried device-side telemetry — no host-side
        # buffering of per-chunk drain counts at all
        routed = (*buckets, counts, rounds)
        self._host_cache = None
        # the label buffer compacts to unique hashes every 128 entries
        # (numpy only: no device fetch, no host dict ops)
        if len(self._label_buf) >= 128:
            with obs.span("summarizer.compact_labels", seq):
                self._compact_label_buf()
        if self.pipeline:
            prev, self._pending = self._pending, (seq, routed)
            if prev is not None:
                self._dispatch_engine(*prev)
            return
        self._dispatch_engine(seq, routed)
        if self.sync_free:
            return                           # statically fully delivered
        self.router_syncs += 1
        i0 = int(np.asarray(delivered).min())  # per-chunk sync (fallback gate)
        if i0 < len(chunk):
            self.router_overflows += len(chunk) - i0
            self._process_chunk_host(chunk[i0:], seq)

    def _flush_dispatch(self) -> None:
        """Dispatch the engine stage for a still-pending routed chunk.

        Device-side only — never fetches — so the sync-free contract
        holds; sync points call this before reading any state."""
        if self._pending is not None:
            prev, self._pending = self._pending, None
            self._dispatch_engine(*prev)

    def _dispatch_engine(self, seq: Optional[int], routed: tuple) -> None:
        """Dispatch the engine stage for one routed chunk (no fetch)."""
        with self.obs.span("summarizer.engine", seq):
            self.state, self.intern, self._drain_rounds = self._engine(
                self.state, self.intern, self._drain_rounds, *routed)
        self._epoch += 1

    def flush(self) -> None:
        """Public barrier: drain the dispatch pipeline (device-side only).

        After this, ``state``/``intern`` reflect every processed change;
        useful before checkpointing the raw device state."""
        self._flush_dispatch()

    def run(self, stream: Iterable[Change]) -> "ShardedSummarizer":
        self.process(list(stream))
        return self

    # ---------------------------------------------------------------- reads
    @property
    def flush_epoch(self) -> int:
        """Engine dispatches applied to ``state``/``intern`` so far — the
        flushed-epoch counter query snapshots pin.  On the pipelined path
        this trails the chunks handed to ``process`` by the one routed
        chunk still awaiting its engine stage."""
        return self._epoch

    def query(self, copy: bool = False):
        """Snapshot read view answering ``neighbors``/``degree``/
        ``has_edge`` in caller-label space from the live per-shard states
        — hash-placed fan-out, answers merged across shards, NO pipeline
        flush and NO decompression (:mod:`repro.serve.query`).  The view
        is pinned to ``flush_epoch``; on buffer-donating backends pass
        ``copy=True`` to keep it valid past the next ``process`` call
        (docs/KNOWN_ISSUES.md)."""
        from repro.serve.query import ShardedSummaryQuery
        return ShardedSummaryQuery(self, copy=copy)

    # ---------------------------------------------------------------- stats
    def host_states(self) -> List[EngineState]:
        """All shard engine states as host arrays: one device transfer,
        memoized until the next ``process`` call mutates the device state.
        Engine states index nodes by per-shard local nid."""
        return self._host_fetch()[0]

    def host_interns(self) -> List["object"]:
        """Per-shard intern states (hash <-> local nid maps) on the host."""
        return self._host_fetch()[1]

    def _host_fetch(self):
        self._flush_dispatch()
        if self._host_cache is None:
            import jax
            with self.obs.span("summarizer.sync"):
                est, ist = jax.device_get((self.state, self.intern))
            self._host_cache = (
                [jax.tree.map(lambda x: x[s], est)
                 for s in range(self.n_shards)],
                [jax.tree.map(lambda x: x[s], ist)
                 for s in range(self.n_shards)])
        self._check_capacity()
        return self._host_cache

    def _check_capacity(self) -> None:
        self._flush_dispatch()
        if self._host_cache is not None:   # free: counters already fetched
            dropped = sum(int(i.n_dropped) for i in self._host_cache[1])
        else:
            dropped = int(np.asarray(self.intern.n_dropped).sum())
        self._raise_if_dropped(dropped)

    def _raise_if_dropped(self, dropped: int) -> None:
        if dropped:
            raise RuntimeError(
                f"node capacity exceeded: {dropped} endpoint interns dropped "
                f"(per-shard n_cap={self.cfg.n_cap}; raise n_cap or n_shards "
                f"— losslessness does not hold for the dropped changes)")

    def _shard_rev(self, shard: int) -> List[object]:
        """nid -> caller label for one shard: the device intern table's
        ``l2h`` rows through the lazily-folded hash -> label map."""
        from repro.dist import labelhash

        self._fold_labels()
        ist = self.host_interns()[shard]
        n = int(ist.n_nodes)
        l2h = np.asarray(ist.l2h)[:n]
        return [self._h2label[int(h)]
                for h in labelhash.combine(l2h[:, 0], l2h[:, 1])]

    def shard_state(self, shard: int) -> EngineState:
        return self.host_states()[shard]

    def shard_phis(self) -> List[int]:
        self._check_capacity()
        return [int(x) for x in np.asarray(self.state.phi)]

    @property
    def phi(self) -> int:
        """Global objective: sum of shard phis (per-pair encodings never
        span shards, so the union-of-parts cost is exactly additive)."""
        return sum(self.shard_phis())

    @property
    def num_edges(self) -> int:
        self._check_capacity()
        return int(np.asarray(self.state.num_edges).sum())

    def compression_ratio(self) -> float:
        e = self.num_edges
        return float(self.phi) / e if e else 0.0

    def stats(self) -> dict:
        """Aggregate engine counters plus routing telemetry:
        ``router_overflows`` counts changes that spilled from the device
        router back to the host path (only possible with an explicitly
        bounded ``max_drain_rounds``; always 0 in ``routing="host"`` mode),
        ``router_drain_rounds`` counts extra on-device exchange rounds
        beyond the first (key-skew indicator), ``router_syncs`` counts
        per-chunk watermark fetches (0 when ``sync_free``), and
        ``router_host_dict_ops`` counts label-map mutations inside
        dispatch (0 on the hash-routed path).  ``engine_rounds`` counts
        the engine rounds run (each ``n_shards x batch`` slots, filled or
        not), ``trial_passes`` the speculative trial passes summed over
        shards (``core/engine/trial.py``; about one per live trial group
        plus one per commit; ``None`` in the ``map`` layout, which runs
        the serial trial loop), and ``stage_programs`` the compiled
        programs each jitted stage holds (``{"route", "engine",
        "query"}``; ``None`` where the JAX version cannot tell).  One device transfer (counters only) —
        this is a sync point."""
        import jax

        from repro.dist.router import TELEM_DRAIN, TELEM_ENGINE
        self._flush_dispatch()
        self._fold_labels()
        s = self.state
        with self.obs.span("summarizer.sync"):
            phi, ne, tr, ac, sk, tpass, dr, telem = jax.device_get(
                (s.phi, s.num_edges, s.n_trials, s.n_accept, s.n_skipped,
                 s.n_passes, self.intern.n_dropped, self._drain_rounds))
        self._raise_if_dropped(int(np.sum(dr)))
        tot = lambda x: int(np.sum(x))  # noqa: E731
        return dict(phi=tot(phi), num_edges=tot(ne),
                    trials=tot(tr), accepted=tot(ac),
                    skipped=tot(sk),
                    trial_passes=(tot(tpass) if self.replica_exec == "vmap"
                                  else None),
                    n_shards=self.n_shards,
                    routing=self.routing,
                    router_overflows=self.router_overflows,
                    # engine-stage carried telemetry: every device carries
                    # the same accumulated counts (the drain loop is
                    # pmin-agreed, the engine rounds pmax-agreed), so max
                    # == the per-run total
                    router_drain_rounds=int(np.max(telem[:, TELEM_DRAIN])),
                    engine_rounds=(int(np.max(telem[:, TELEM_ENGINE]))
                                   + self._host_engine_rounds),
                    stage_programs=self._stage_programs(),
                    router_syncs=self.router_syncs,
                    router_host_dict_ops=self._host_dict_ops,
                    router_sync_free=self.sync_free,
                    router_pipelined=self.pipeline,
                    # recoveries performed by a retry driver on this live
                    # object; deliberately NOT part of the checkpoint
                    # closure or the bitwise-recovery bar (it counts the
                    # recoveries themselves)
                    stream_retries=self.stream_retries)

    def _stage_programs(self) -> dict:
        """Compiled programs held per jitted stage (``None`` per stage
        where the JAX version does not expose a jit's cache size)."""
        from repro.serve.query import make_sharded_query_kernels
        query = make_sharded_query_kernels(self.cfg, self.mesh,
                                           self.trial_backend)
        fns = dict(self._stage_fns, query=tuple(query))
        out = {}
        for stage, stage_fns in fns.items():
            try:
                out[stage] = sum(int(f._cache_size()) for f in stage_fns)
            except AttributeError:
                out[stage] = None
        return out

    # ----------------------------------------------------- recovery closure
    def _ckpt_tree(self) -> dict:
        return {"est": self.state._asdict(), "ist": self.intern._asdict()}

    def _ckpt_shardings(self) -> dict:
        # every restored leaf goes straight to its shard's device
        from repro.dist import router as dist_router
        est, ist = dist_router.state_shardings(self.cfg, self.mesh)
        return {"est": est._asdict(), "ist": ist._asdict()}

    def _ckpt_host(self) -> dict:
        # host_label_map() is the sync point: drains the pipeline and folds
        # the lazy label buffer, so the map alone carries label recovery
        return {"h2label": dict(self.host_label_map()),
                "drain_rounds": np.asarray(self._drain_rounds),
                "host_engine_rounds": self._host_engine_rounds,
                "router_overflows": self.router_overflows,
                "router_syncs": self.router_syncs,
                "host_dict_ops": self._host_dict_ops}

    def _ckpt_manifest(self) -> dict:
        # drain geometry only shapes the PRNG schedule when delivery is NOT
        # statically guaranteed (host-fallback replays shift it); pin the
        # exact geometry only in that regime so the default config stays
        # freely restorable across meshes (lane_cap derives from n_dev)
        guaranteed = bool(self.router_geometry.drain_guaranteed) \
            if self.router_geometry is not None else True
        return {"tier": "sharded", "config": self.cfg.manifest(),
                "n_shards": self.n_shards,
                "router_chunk": self.router_chunk,
                "drain_geometry": (None if guaranteed else
                                   [self.lane_cap, self.max_drain_rounds]),
                "routing": self.routing,
                "replica_exec": self.replica_exec,
                "trial_backend": self.trial_backend,
                "n_devices": int(self.mesh.devices.size)}

    @staticmethod
    def _ckpt_pins() -> tuple:
        # routing / replica_exec / trial_backend / n_devices are
        # bitwise-identical execution variants (standing differential bar)
        # — recorded, not pinned; config, shard placement, chunk boundaries
        # and an unguaranteed drain geometry all shape the replayed bits
        return ("tier", "config", "n_shards", "router_chunk",
                "drain_geometry")

    def _ckpt_apply(self, tree: dict, host: dict, extra: dict) -> None:
        from repro.dist import router as dist_router
        self.state = EngineState(**tree["est"])
        self.intern = dist_router.InternState(**tree["ist"])
        self._drain_rounds = dist_router.drain_telemetry_restore(
            host["drain_rounds"], int(self.mesh.devices.size))
        self._host_engine_rounds = int(host.get("host_engine_rounds", 0))
        self._h2label = dict(host["h2label"])
        self._label_buf = []
        self._label_head = None
        self.router_overflows = int(host["router_overflows"])
        self.router_syncs = int(host["router_syncs"])
        self._host_dict_ops = int(host["host_dict_ops"])
        self._pending = None
        self._host_cache = None
        self._epoch = int(extra["epoch"])
        self._journal_seq = int(extra["journal_seq"])
        self._cursor = int(extra["cursor"])
        self._recovered = True
        self._incarnation += 1

    # ------------------------------------------------------------ materialize
    def live_edges(self) -> Set[Tuple[object, object]]:
        """Union of per-shard live edges, mapped back to caller labels."""
        out: Set[Tuple[object, object]] = set()
        for s, st in enumerate(self.host_states()):
            rev = self._shard_rev(s)
            for (a, b) in state_live_edges(st):
                out.add(pair_key(rev[a], rev[b]))
        return out

    def materialize(self) -> ShardedSummaryOutput:
        """Merged host-side output: per-shard lossless summaries in caller
        label space, supernode ids offset into disjoint per-shard ranges
        (``shard * n_cap``).  The relabeling reads the device intern maps,
        so it is exact under router-batched delivery: whatever order the
        all_to_all delivered changes in, ``l2h`` records the resulting nid
        assignment."""
        shards = []
        for s, st in enumerate(self.host_states()):
            out = state_materialize(st, self.cfg)
            shards.append(
                _relabel_output(out, self._shard_rev(s), s * self.cfg.n_cap))
        return ShardedSummaryOutput(shards=shards)

    def phi_recomputed(self) -> int:
        return sum(state_phi_recomputed(st, self.cfg)
                   for st in self.host_states())
