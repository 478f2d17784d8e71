"""Device-side stream router for edge-partitioned summarization.

:class:`~repro.core.engine.api.ShardedSummarizer` partitions the edge stream
over a fleet of engine replicas by canonical-pair key
``min(h(u), h(v)) % n_shards``, where ``h`` is a stable 62-bit label hash
(:mod:`repro.dist.labelhash`).  Until PR 4 the key was computed on dense
gids a host-side Python dict assigned in encounter order — a per-change
host tax and the last centralized step in dispatch.  The router now
consumes raw hashed labels and runs the whole dispatch path on device, as
a two-stage software pipeline:

**Stage 1 — route** (:func:`make_route_step`, no state dependencies):

1. The host hands the router one flat chunk of hashed changes (four
   ``int32`` hash words + a flag per change, ``-1``-padded to a fixed
   ``chunk`` length, split contiguously over the mesh so device ``d``
   holds stream positions ``[d*n_in, (d+1)*n_in)``).
2. Each source device computes shard keys and scatters its changes into a
   capacity-bounded send buffer of ``lane_cap`` slots per (source device,
   destination shard) lane.
3. One ``lax.all_to_all`` inside the ``shard_map`` region delivers every
   lane to the device owning its destination shard; the receiver compacts
   the lanes source-major, which reconstructs global stream order.
4. If some lane overflowed, steps 2-3 repeat as a bounded on-device
   **drain loop** (``lax.while_loop``): each round routes the pending
   stream prefix up to the first still-overflowing position (agreed with
   ``lax.pmin``) and appends the deliveries to the per-shard buckets, so
   multi-round delivery is lossless and order-preserving without any host
   round-trip.

**Stage 2 — engine** (:func:`make_engine_step`, consumes stage-1 buckets):

5. Each shard interns the received hash words into its dense local id
   space (:class:`InternState`, first-come-first-served — the same order
   host bucketing would produce): a vectorized batch pre-lookup resolves
   already-known nodes in parallel, and a sequential scan probes only for
   chunk-novel keys, preserving exact assignment order.
6. The shard runs ``ceil(max_count / batch)`` engine rounds, the round
   count agreed across shards with ``lax.pmax`` so every replica advances
   its PRNG stream identically.  The replicas stacked on one device are
   laid out per ``replica_exec`` — one ``jax.vmap``-batched program over
   the replica axis (possible because the trial engine is cond-free
   predicated data flow) or a serializing ``lax.map`` — and the route
   stage's drain-round count is folded into the stage's carried
   telemetry on device (``telem += rounds - 1``), next to the count of
   engine rounds run.

Because stage 1 depends only on the chunk (never on engine or intern
state), ``ShardedSummarizer`` dispatches chunk k+1's routing — drain
rounds included — while chunk k's engine rounds are still executing: the
steady state is a two-deep pipeline with zero per-chunk host fetches and
zero per-chunk host dict operations.

**Overflow contract.** A lane holds at most ``lane_cap`` changes per drain
round.  Rather than dropping or reordering on overflow, each round routes
only the pending stream prefix before the first overflowing *position*
(``lax.pmin`` across devices) and the next round re-ranks the remainder —
per round at least ``lane_cap`` changes are delivered, so
``ceil(chunk / lane_cap)`` rounds always drain a full chunk
(:func:`router_geometry` computes this bound as ``full_drain_rounds``).
With the default ``max_drain_rounds`` (the full bound) delivery is
statically guaranteed and the caller never has to look at the watermark;
only an explicitly lowered ``max_drain_rounds`` can leave a suffix, which
the caller then feeds through the host-routed path
(:func:`make_bucketed_step`, shared intern state, counted in
``ShardedSummarizer.router_overflows``) — losslessness and stream order
are preserved either way; only the PRNG schedule differs from the
no-overflow trajectory when the host path runs.

**Why both paths intern on device.** Trial randomness depends on local node
ids (they seed the min-hash clustering), so host- and device-routed runs are
bit-identical only if both assign ids in the same per-shard order.  Keeping
the hash -> local-id map in device memory (a
:mod:`~repro.core.engine.hashtable` open-addressing table per shard) gives
both paths one source of truth and makes the host path a true differential
reference for the router.

SPMD hazard audit (docs/KNOWN_ISSUES.md): all gather/scatter here happens
*inside* ``shard_map`` on per-device local arrays, so the GSPMD
concat-of-aligned-slices pattern that miscompiled ``apply_rope`` cannot
arise — the partitioner never sees these concatenations.  The two-stage
split adds no new exposure: the stage boundary passes ``P(axis)``-sharded
bucket arrays between two ``shard_map`` regions without host contact, and
every drain round's scatter/exchange/append runs on per-device locals
inside the ``lax.while_loop`` body.

**Policy threading (PR 8).**  The router is policy-agnostic: routing
keys on label hashes only, and the proposal/objective/commit triple
reaches the engine rounds as static fields on the ``EngineConfig`` the
step factories close over.  ``_STEP_CACHE`` keys on the whole (hashable)
config, so two summarizers with different policy triples — or the same
triple under different ``commit_margin``/``weight_levels`` — never share
a compiled step.  No routing or intern code inspects the triple; the CI
router-stress matrix re-runs this module's suites under a non-default
triple (``REPRO_PROPOSAL``/``REPRO_OBJECTIVE``) to keep it that way.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine.hashtable import (HashTable, ht_find, ht_find_batch,
                                         ht_new, ht_set,
                                         resolve_trial_backend,
                                         trial_backend_scope)
from repro.core.engine.state import EngineConfig, new_state
from repro.core.engine.trial import pwhen, step_fn

INVALID = np.int32(-1)   # numpy: importing the router starts no backend

# the device shard key is (h_hi * 2**31 + h_lo) % n_shards computed in
# uint32 residues; (n-1)**2 + (n-1) must stay below 2**31
MAX_SHARDS = 1 << 15

# How engine/intern work is laid out over the shard replicas stacked on one
# device (the n_shards > n_devices production path):
#
# * ``"vmap"`` — one batched program over the stacked replica axis.  The
#   trial engine is cond-free predicated data flow
#   (``core/engine/trial.py``), so vmap pays no both-branches penalty:
#   its predicated regions are phased to carry scalars, not state.
# * ``"map"`` — ``lax.map`` over replicas, serializing them per device
#   but letting each replica's predicated regions short-circuit at
#   runtime.  Also the differential reference (like ``routing="host"``):
#   identical math, independent lowering, bit-identical states.
#
# The default is backend-aware: ``"vmap"`` on accelerator backends — the
# deployment target, where replica lanes vectorize in hardware and a
# future Pallas trial kernel slots in — and ``"map"`` on the XLA *CPU*
# backend, where measurement (docs/KNOWN_ISSUES.md) shows every batched
# ``while`` pays a fixed ~8us dispatch tax (vs <1us unbatched), taxing
# the engine's probe loops and predicated regions ~3-5x over the mapped
# lowering.  Both modes are leaf-bitwise state-identical, so the choice
# is pure performance; REPRO_REPLICA_EXEC overrides (the CI router-stress
# job uses it to cover both).
REPLICA_EXEC_MODES = ("vmap", "map")


def default_replica_exec() -> str:
    """The replica layout for the running backend, resolved at call time
    (never at import, so importing ``repro`` initialises no backend)."""
    return os.environ.get(
        "REPRO_REPLICA_EXEC",
        "map" if jax.default_backend() == "cpu" else "vmap")


def _replica_apply(fn, replica_exec: str, *stacked):
    """Run ``fn`` across the leading (stacked-replica) axis of ``stacked``."""
    if replica_exec == "vmap":
        return jax.vmap(fn)(*stacked)
    return jax.lax.map(lambda args: fn(*args), stacked)


# --------------------------------------------------------------------------- #
# device-resident (h_hi, h_lo) -> local-nid interning
# --------------------------------------------------------------------------- #


class InternState(NamedTuple):
    """Per-shard device-resident node intern table.

    Maps 62-bit label hashes — carried as two non-negative ``int32`` words
    ``(hi, lo)``, the native key shape of :class:`HashTable` — to the
    shard's dense local id space ``[0, n_cap)`` that the engine state
    arrays are indexed by.  Ids are assigned first-come-first-served in
    delivery order, which both routing modes reproduce identically.
    ``l2h`` is the reverse map used by ``materialize``/``live_edges`` to
    translate local nids back to label hashes (and, through the host's
    lazily-folded hash -> label map, to caller labels).
    """

    h2l: HashTable      # (h_hi, h_lo) -> local nid
    l2h: jax.Array      # int32[n_cap, 2]: local nid -> (h_hi, h_lo), -1 unset
    n_nodes: jax.Array  # int32: next fresh nid == number interned
    n_dropped: jax.Array  # int32: endpoint interns dropped at full capacity


def intern_new(cfg: EngineConfig) -> InternState:
    cap = 1
    while cap < 4 * cfg.n_cap:   # ~25% max load keeps probes O(1)
        cap <<= 1
    return InternState(
        h2l=ht_new(cap),
        l2h=jnp.full((cfg.n_cap, 2), -1, jnp.int32),
        n_nodes=jnp.int32(0),
        n_dropped=jnp.int32(0),
    )


# columns of the engine stage's carried telemetry
TELEM_DRAIN = 0     # extra exchange rounds beyond the first, per chunk
TELEM_ENGINE = 1    # engine rounds run (pmax-agreed ceil(max_count / batch))


def drain_telemetry_new(n_dev: int) -> jax.Array:
    """Fresh engine-stage telemetry carry (``int32[n_dev, 2]``): per
    device the drain-round count (column ``TELEM_DRAIN``) and the
    engine-round count (column ``TELEM_ENGINE``).

    Crash-consistency note (``repro.checkpoint.summary``): the route stage
    is a pure function of the chunk — it has no state to checkpoint.  The
    recovery closure is exactly the engine stage's carried operands: the
    stacked ``EngineState`` + :class:`InternState` and this telemetry
    array.  The drain loop is pmin-agreed and the engine rounds
    pmax-agreed, so every row is the same by construction; a checkpoint
    can therefore restore it onto a mesh with a *different* device count
    by broadcasting the per-run counts (``max`` per column) — the basis of
    the elastic-restore leg.
    """
    return jnp.zeros((n_dev, 2), jnp.int32)


def drain_telemetry_restore(saved, n_dev: int) -> jax.Array:
    """Re-broadcast saved (mesh-uniform) telemetry onto a mesh of ``n_dev``
    devices; bitwise-identical when the topology matches.  A checkpoint
    written before the engine-round column (``int32[n_dev]``) restores
    with that count at 0."""
    saved = np.asarray(saved, np.int32)
    if saved.size == 0:
        counts = np.zeros(2, np.int32)
    elif saved.ndim == 1:
        counts = np.array([saved.max(), 0], np.int32)
    else:
        counts = saved.max(axis=0)
    return jnp.asarray(np.tile(counts, (n_dev, 1)))


def _intern_probe(ist: InternState, hi: jax.Array, lo: jax.Array,
                  valid: jax.Array, n_cap: int,
                  ) -> Tuple[InternState, jax.Array]:
    """Sequential-path intern: probe, then insert if fresh (dense FCFS nid).

    Returns ``-1`` when invalid or dropped at capacity.  The intern table
    keys are full-entropy label hashes, so probes start at the prehashed
    position (no re-mix — see ``hashtable.ht_find``).  Cond-free: the
    insert is a masked write under ``take``, so the op vmaps over stacked
    replicas without a both-branches (whole-table-select) penalty.
    """
    h1 = jnp.where(valid, hi, 0)
    h2 = jnp.where(valid, lo, 0)
    slot, found = ht_find(ist.h2l, h1, h2, prehashed=True)
    existing = ist.h2l.val[slot]
    fresh = valid & ~found
    room = ist.n_nodes < n_cap
    take = fresh & room
    nid_new = ist.n_nodes
    nid_w = jnp.minimum(nid_new, n_cap - 1)   # in-bounds slot for the write
    ist = ist._replace(
        h2l=ht_set(ist.h2l, h1, h2, nid_new, prehashed=True, ok=take),
        l2h=ist.l2h.at[nid_w].set(
            jnp.where(take, jnp.stack([h1, h2]), ist.l2h[nid_w])),
        n_nodes=ist.n_nodes + take.astype(jnp.int32),
        n_dropped=ist.n_dropped + (fresh & ~room).astype(jnp.int32))
    nid = jnp.where(found, existing, jnp.where(take, nid_new, INVALID))
    return ist, jnp.where(valid, nid, INVALID)


def _intern_one(ist: InternState, hi: jax.Array, lo: jax.Array,
                valid: jax.Array, pre_found: jax.Array, pre_slot: jax.Array,
                n_cap: int, dense: bool) -> Tuple[InternState, jax.Array]:
    """One intern with a vectorized pre-lookup hint.

    ``pre_found``/``pre_slot`` come from a batch ``ht_find`` against the
    table state at chunk entry.  Linear-probe insertions only ever fill
    EMPTY/TOMB slots — they never relocate existing entries — so a
    pre-found slot stays valid through the scan and the hit path is a
    single gather.  Only chunk-novel keys (or repeats of one) take the
    predicated probe-and-insert region — masked data flow when ``dense``
    (the vmapped-replica lowering), a zero-cost ``pwhen`` short-circuit
    on the all-hits steady state otherwise; never a ``lax.cond``.
    """
    need = valid & ~pre_found

    def miss(carry):
        ist, _ = carry
        return _intern_probe(ist, hi, lo, need, n_cap)

    if dense:
        ist, nid_miss = miss((ist, INVALID))
    else:
        ist, nid_miss = pwhen(need, miss, (ist, INVALID))
    nid = jnp.where(pre_found & valid, ist.h2l.val[pre_slot], nid_miss)
    return ist, jnp.where(valid, nid, INVALID)


def intern_changes(ist: InternState,
                   uh: jax.Array, ul: jax.Array,
                   vh: jax.Array, vl: jax.Array,
                   n_cap: int, dense: bool = False,
                   ) -> Tuple[InternState, jax.Array, jax.Array]:
    """Intern a hashed change sequence in order: ``(ist, u_nid, v_nid)``.

    A change with a dropped endpoint (shard node capacity hit) maps to
    ``(-1, -1)`` — the engine skips it and ``n_dropped`` records the event
    for the host to surface.  The assignment order (hence every nid) is
    identical to a purely sequential intern: the vectorized pre-lookup
    only short-circuits probes for keys already in the table at entry.
    """
    valid = (uh >= 0) & (vh >= 0)

    def batch_find(hi, lo):
        # masked lanes probe key (0, 0) — the garbage-key side of the
        # predication contract; under the pallas backend the whole
        # pre-lookup is one fused probe launch (kernels/ht_probe.py)
        h1 = jnp.where(valid, hi, 0)
        h2 = jnp.where(valid, lo, 0)
        return ht_find_batch(ist.h2l, h1, h2, prehashed=True)

    psu, pfu = batch_find(uh, ul)
    psv, pfv = batch_find(vh, vl)

    def body(ist, ch):
        uh_i, ul_i, vh_i, vl_i, v_i, pfu_i, psu_i, pfv_i, psv_i = ch
        ist, nu = _intern_one(ist, uh_i, ul_i, v_i, pfu_i, psu_i, n_cap,
                              dense)
        ist, nv = _intern_one(ist, vh_i, vl_i, v_i, pfv_i, psv_i, n_cap,
                              dense)
        ok = (nu >= 0) & (nv >= 0)
        return ist, (jnp.where(ok, nu, INVALID), jnp.where(ok, nv, INVALID))

    ist, (u, v) = jax.lax.scan(
        body, ist, (uh, ul, vh, vl, valid, pfu, psu, pfv, psv))
    return ist, u, v


# --------------------------------------------------------------------------- #
# shard keys from hash words
# --------------------------------------------------------------------------- #


def shard_key(uh: jax.Array, ul: jax.Array, vh: jax.Array, vl: jax.Array,
              n_shards: int) -> jax.Array:
    """Canonical-pair shard key ``min(h(u), h(v)) % n_shards`` on device.

    The 62-bit hashes live as two 31-bit words, so the min is
    lexicographic and the modulus composes over uint32 residues:
    ``(hi * 2**31 + lo) % n == ((hi % n) * (2**31 % n) + lo % n) % n``.
    All intermediates stay below ``2**31`` because ``n < MAX_SHARDS``.
    """
    u_le = (uh < vh) | ((uh == vh) & (ul <= vl))
    mh = jnp.where(u_le, uh, vh).astype(jnp.uint32)
    ml = jnp.where(u_le, ul, vl).astype(jnp.uint32)
    m = jnp.uint32(n_shards)
    two31 = jnp.uint32((1 << 31) % n_shards)
    return (((mh % m) * two31 + ml % m) % m).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# host-routed (bucketed) step — the differential reference + overflow path
# --------------------------------------------------------------------------- #


def _state_specs(cfg: EngineConfig, axis: str):
    est_sds = jax.eval_shape(lambda: new_state(cfg))
    ist_sds = jax.eval_shape(lambda: intern_new(cfg))
    return (jax.tree.map(lambda _: P(axis), est_sds),
            jax.tree.map(lambda _: P(axis), ist_sds))


def state_shardings(cfg: EngineConfig, mesh):
    """``(EngineState, InternState)`` trees of ``NamedSharding``: each
    stacked leaf split over the mesh's shard axis, as the steps expect."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        _state_specs(cfg, mesh.axis_names[0]),
                        is_leaf=lambda x: isinstance(x, P))


def new_stacked_states(cfg: EngineConfig, mesh, n_shards: int):
    """Fresh stacked ``(EngineState, InternState)`` for ``n_shards`` shards,
    built in place over ``mesh``: every device materializes only its own
    shards (no full stack on one device before the first step reshards)."""
    def build():
        stack = lambda l: jnp.broadcast_to(l[None], (n_shards,) + l.shape)  # noqa: E731
        est = jax.tree.map(stack, new_state(cfg))
        # decorrelate the per-shard trial PRNG streams
        est = est._replace(
            step_no=jnp.uint32(cfg.seed)
            + jnp.arange(n_shards, dtype=jnp.uint32) * jnp.uint32(2654435761))
        return est, jax.tree.map(stack, intern_new(cfg))

    return jax.jit(build, out_shardings=state_shardings(cfg, mesh))()


def _donate_argnums(mesh, *argnums: int) -> tuple:
    """Donate the given buffers where the mesh's platform supports it.

    Donation lets XLA update the (large) stacked engine states — and the
    pipeline's double-buffered routing buckets — in place, so the host can
    stage chunk k+1 while chunk k computes without doubling device memory.
    The CPU backend ignores donation (and warns), so gate on the platform
    of the devices the step is compiled for.
    """
    return () if mesh.devices.flat[0].platform == "cpu" else argnums


# compiled-step memo: ShardedSummarizer constructions with identical
# geometry share one compiled program (EngineConfig is a frozen dataclass
# and Mesh hashes by device assignment, so the key captures everything
# that affects compilation).  Without this, every summarizer pair in a
# differential test recompiles the full shard_map from scratch.
_STEP_CACHE: dict = {}


def make_bucketed_step(cfg: EngineConfig, mesh,
                       replica_exec: Optional[str] = None,
                       trial_backend: Optional[str] = None):
    """jit(shard_map) step consuming host-bucketed ``[n_shards, batch]``
    hash-word rounds.  Bucketing/packing happens on the host; interning and
    the engine step run on device, the per-device shard replicas laid out
    by ``replica_exec`` — one vmapped program over the stacked replica axis
    (default; the predicated engine pays no both-branches cost), or a
    serializing ``lax.map`` (the differential reference).  Batched probes
    lower per ``trial_backend`` (resolved against the
    ``REPRO_TRIAL_BACKEND`` default).  Memoized on
    ``(cfg, mesh, replica_exec, trial_backend)``."""
    replica_exec = replica_exec or default_replica_exec()
    trial_backend = resolve_trial_backend(trial_backend)
    key = ("bucketed", cfg, mesh, replica_exec, trial_backend)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    axis = mesh.axis_names[0]
    est_specs, ist_specs = _state_specs(cfg, axis)
    dense = replica_exec == "vmap"   # vmap lanes want pure data flow

    def one(est, ist, uh, ul, vh, vl, ins):
        ist, u, v = intern_changes(ist, uh, ul, vh, vl, cfg.n_cap, dense)
        return step_fn(est, u, v, ins != 0, cfg, dense), ist

    def local(est, ist, uh, ul, vh, vl, ins):
        # scope entered inside the traced body: the probe call sites bake
        # in the backend while this function traces under jit
        with trial_backend_scope(trial_backend):
            return _replica_apply(one, replica_exec,
                                  est, ist, uh, ul, vh, vl, ins)

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(est_specs, ist_specs) + (P(axis),) * 5,
        out_specs=(est_specs, ist_specs), check_vma=False),
        donate_argnums=_donate_argnums(mesh, 0, 1))
    _STEP_CACHE[key] = fn
    return fn


# --------------------------------------------------------------------------- #
# stage 1: route — shard keys + all_to_all drain rounds (state-independent)
# --------------------------------------------------------------------------- #


class RouterGeometry(NamedTuple):
    """Resolved static geometry of one compiled router program.

    ``static_no_overflow`` proves a single exchange round always suffices
    (``lane_cap == n_in``: a lane can never receive more than its source
    slice), in which case the compiled program carries no overflow watermark
    at all.  ``drain_guaranteed`` is the weaker — and default — proof that
    ``max_drain_rounds`` rounds always deliver the whole chunk (each
    non-final round delivers at least ``lane_cap`` changes, so
    ``full_drain_rounds = ceil(chunk / lane_cap)`` is a delivery
    guarantee); when it holds the caller never needs to inspect the
    watermark, which is what lets ``ShardedSummarizer`` elide the per-chunk
    host sync — and, since the route stage depends on nothing but the
    chunk, pipeline chunk k+1's routing under chunk k's engine rounds.
    """

    n_dev: int                 # mesh devices
    n_loc: int                 # shard replicas per device
    n_in: int                  # stream positions per source device
    lane_cap: int              # slots per (source, shard) lane per round
    max_drain_rounds: int      # compiled bound on exchange rounds
    full_drain_rounds: int     # rounds that provably deliver a full chunk
    acc_cap: int               # per-shard receive-bucket capacity
    static_no_overflow: bool   # lane_cap == n_in: one round, no watermark
    drain_guaranteed: bool     # max_drain_rounds >= full_drain_rounds


def router_geometry(mesh, n_shards: int, chunk: int, lane_cap: int,
                    max_drain_rounds: Optional[int] = None) -> RouterGeometry:
    """Resolve the router's static knobs for a fixed (mesh, chunk) geometry."""
    n_dev = int(mesh.devices.size)
    if chunk % n_dev != 0:
        raise ValueError(f"chunk={chunk} must be divisible by n_dev={n_dev}")
    if n_shards % n_dev != 0:
        raise ValueError(
            f"n_shards={n_shards} must be a multiple of n_dev={n_dev}")
    if n_shards >= MAX_SHARDS:
        raise ValueError(
            f"n_shards={n_shards} must be < {MAX_SHARDS} (the device shard "
            f"key composes 31-bit hash words over uint32 residues)")
    n_loc = n_shards // n_dev
    n_in = chunk // n_dev            # stream positions per source device
    lane_cap = min(int(lane_cap), n_in)  # a lane can't exceed its source slice
    if lane_cap < 1:
        raise ValueError(f"lane_cap must be >= 1, got {lane_cap}")
    static_no_overflow = lane_cap == n_in
    # each non-final drain round delivers >= lane_cap changes (the blocking
    # lane sends a full lane), so this many rounds always drain the chunk
    full_drain = 1 if static_no_overflow else -(-chunk // lane_cap)
    if max_drain_rounds is None:
        max_drain_rounds = full_drain
    max_drain_rounds = max(1, min(int(max_drain_rounds), full_drain))
    r_cap = n_dev * lane_cap         # max deliverable per shard per round
    acc_cap = min(chunk, max_drain_rounds * r_cap)
    return RouterGeometry(
        n_dev=n_dev, n_loc=n_loc, n_in=n_in, lane_cap=lane_cap,
        max_drain_rounds=max_drain_rounds, full_drain_rounds=full_drain,
        acc_cap=acc_cap, static_no_overflow=static_no_overflow,
        drain_guaranteed=max_drain_rounds >= full_drain)


def make_route_step(mesh, n_shards: int, chunk: int, lane_cap: int,
                    max_drain_rounds: Optional[int] = None):
    """Compile the state-independent routing stage for a fixed geometry.

    Returns ``(route, geometry)`` where ``route`` is a jitted
    ``(uh, ul, vh, vl, ins) -> (buckets, counts, delivered, rounds)``: the
    inputs are flat ``[chunk]`` hash-word change arrays (``-1`` padded);
    ``buckets`` is the 5-tuple of per-shard ``[n_shards, acc_cap]`` bucket
    arrays in delivery (== stream) order; ``counts`` is ``[n_shards]``
    delivered-change counts; ``delivered`` is, per device, the first
    stream position NOT routed when ``max_drain_rounds`` ran out
    (``chunk`` when everything was delivered — always, when
    ``geometry.drain_guaranteed``); ``rounds`` is the number of exchange
    rounds the drain loop ran (1 = no overflow anywhere).

    The stage reads no engine or intern state, so its dispatch for chunk
    k+1 can overlap chunk k's engine stage.  Memoized on the geometry key.
    """
    key = ("route", mesh, n_shards, chunk, lane_cap, max_drain_rounds)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    axis = mesh.axis_names[0]
    geom = router_geometry(mesh, n_shards, chunk, lane_cap, max_drain_rounds)
    n_dev, n_loc, n_in = geom.n_dev, geom.n_loc, geom.n_in
    lane_cap, acc_cap = geom.lane_cap, geom.acc_cap
    r_cap = n_dev * lane_cap

    def local(uh, ul, vh, vl, ins):
        # uh/ul/vh/vl/ins local [n_in]
        with jax.named_scope("route/keys"):
            me = jax.lax.axis_index(axis)
            valid = (uh >= 0) & (vh >= 0)
            dest = jnp.where(valid, shard_key(uh, ul, vh, vl, n_shards),
                             n_shards)
            pos = me * n_in + jnp.arange(n_in, dtype=jnp.int32)
            payload = jnp.stack(
                [uh, ul, vh, vl, ins.astype(jnp.int32)], axis=-1)
        rows = jnp.arange(n_loc, dtype=jnp.int32)[:, None]
        sid = jnp.arange(n_shards, dtype=jnp.int32)[None]

        def drain_round(carry):
            r, delivered, acc, counts = carry
            pending = valid & (pos >= delivered)

            # rank of each pending change within its (source, dest) lane;
            # order-stable (monotone in stream position)
            onehot = (dest[:, None] == sid) & pending[:, None]
            cum = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
            rank = jnp.take_along_axis(
                cum, jnp.clip(dest, 0, n_shards - 1)[:, None],
                axis=1)[:, 0] - 1

            # capacity bound: route only the pending stream prefix before
            # the first overflowing position, so the delivered set is always
            # a stream prefix and per-shard order survives multi-round drain
            if geom.static_no_overflow:
                first = jnp.int32(chunk)   # provably no overflow: no pmin
            else:
                over = pending & (rank >= lane_cap)
                my_first = jnp.min(jnp.where(over, pos, jnp.int32(chunk)))
                first = jax.lax.pmin(my_first, axis)
            keep = pending & (rank < lane_cap) & (pos < first)

            # scatter kept changes into the [n_dev, n_loc, lane_cap] lanes
            dd = jnp.where(keep, dest // n_loc, n_dev)  # OOB index -> drop
            dl = jnp.where(keep, dest % n_loc, 0)
            rk = jnp.where(keep, rank, 0)
            send = jnp.full((n_dev, n_loc, lane_cap, 5), -1, jnp.int32)
            send = send.at[dd, dl, rk].set(payload, mode="drop")

            # exchange: recv[j, l] = source j's lane for my local shard l
            recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            # source-major flatten per shard == global stream order
            recv = jnp.swapaxes(recv, 0, 1).reshape(n_loc, r_cap, 5)

            # stable compaction, appended at each shard's bucket watermark
            rvalid = recv[..., 0] >= 0
            cpos = jnp.cumsum(rvalid.astype(jnp.int32), axis=1) - 1
            idx = jnp.where(rvalid, counts[:, None] + cpos, acc_cap)
            acc = acc.at[rows, idx].set(recv, mode="drop")
            counts = counts + rvalid.sum(axis=1).astype(jnp.int32)
            return r + 1, first, acc, counts

        # drain until the whole chunk is delivered or the round budget is
        # spent; the loop condition is pmin-agreed, hence mesh-uniform
        init = (jnp.int32(0), jnp.int32(0),
                jnp.full((n_loc, acc_cap, 5), -1, jnp.int32),
                jnp.zeros((n_loc,), jnp.int32))
        with jax.named_scope("route/drain"):
            rounds, delivered, acc, counts = jax.lax.while_loop(
                lambda c: (c[1] < chunk) & (c[0] < geom.max_drain_rounds),
                drain_round, init)
        return (acc[..., 0], acc[..., 1], acc[..., 2], acc[..., 3],
                acc[..., 4], counts, delivered[None], rounds[None])

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis),) * 5,
        out_specs=(P(axis),) * 8, check_vma=False))
    _STEP_CACHE[key] = (fn, geom)
    return fn, geom


# --------------------------------------------------------------------------- #
# stage 2: engine — intern the routed buckets, run pmax-agreed engine rounds
# --------------------------------------------------------------------------- #


def make_engine_step(cfg: EngineConfig, mesh, n_shards: int, acc_cap: int,
                     replica_exec: Optional[str] = None,
                     trial_backend: Optional[str] = None):
    """Compile the state-carrying engine stage for routed buckets.

    ``(est, ist, telem, a_uh, a_ul, a_vh, a_vl, a_ins, counts, rounds)
    -> (est, ist, telem)``: interns each shard's ``[n_shards, acc_cap]``
    bucket (delivery order == stream order) and runs
    ``pmax(ceil(max_count / batch))`` engine rounds so every replica's
    PRNG advances in lockstep.  The shard replicas stacked on one device
    are laid out by ``replica_exec``: one vmapped program over the replica
    axis (default), or a serializing ``lax.map`` (the differential
    reference).

    ``telem`` is the carried telemetry (``int32[n_dev, 2]``, equal across
    devices; :func:`drain_telemetry_new`): the stage folds the route
    stage's drain-round count ``rounds`` into it on device
    (``+= rounds - 1``) and, beside it, the engine rounds it ran, so the
    host never buffers per-chunk round counts.  The engine/intern/telemetry
    states AND the bucket buffers are donated on non-CPU backends — the
    buckets are the pipeline's double buffer, consumed exactly once.

    Memoized on ``(cfg, mesh, n_shards, acc_cap, replica_exec,
    trial_backend)``.
    """
    replica_exec = replica_exec or default_replica_exec()
    trial_backend = resolve_trial_backend(trial_backend)
    key = ("engine", cfg, mesh, n_shards, acc_cap, replica_exec,
           trial_backend)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    axis = mesh.axis_names[0]
    n_dev = int(mesh.devices.size)
    n_loc = n_shards // n_dev
    b = cfg.batch
    est_specs, ist_specs = _state_specs(cfg, axis)
    dense = replica_exec == "vmap"   # vmap lanes want pure data flow

    def local(est, ist, telem, *bucket_args):
        # probe backend baked in at trace time (same idiom as the
        # bucketed step)
        with trial_backend_scope(trial_backend):
            return _local(est, ist, telem, *bucket_args)

    def _local(est, ist, telem, a_uh, a_ul, a_vh, a_vl, a_ins, counts,
               rounds):
        # est/ist stacked [n_loc, ...]; buckets [n_loc, acc_cap];
        # telem [1, 2], rounds [1] (device-local slices of the [n_dev]
        # arrays)
        # intern each shard's whole bucket up front — the same order host
        # bucketing interns in, so both paths assign identical local ids
        def int_one(ist_l, uh_l, ul_l, vh_l, vl_l):
            return intern_changes(ist_l, uh_l, ul_l, vh_l, vl_l,
                                  cfg.n_cap, dense)

        with jax.named_scope("engine/intern"):
            ist, u_all, v_all = _replica_apply(
                int_one, replica_exec, ist, a_uh, a_ul, a_vh, a_vl)

        # one spare round of padding so dynamic_slice never clamps
        u_all = jnp.concatenate(
            [u_all, jnp.full((n_loc, b), -1, jnp.int32)], axis=1)
        v_all = jnp.concatenate(
            [v_all, jnp.full((n_loc, b), -1, jnp.int32)], axis=1)
        i_all = jnp.concatenate(
            [a_ins, jnp.zeros((n_loc, b), jnp.int32)], axis=1)

        # every shard steps the same number of rounds (uniform PRNG advance,
        # matching the host path's ceil(max_bucket / batch) schedule)
        erounds = jax.lax.pmax(jnp.max((counts + b - 1) // b), axis)

        def round_body(carry):
            r, est = carry

            def one(est_l, u_l, v_l, i_l):
                us = jax.lax.dynamic_slice(u_l, (r * b,), (b,))
                vs = jax.lax.dynamic_slice(v_l, (r * b,), (b,))
                fs = jax.lax.dynamic_slice(i_l, (r * b,), (b,)) != 0
                return step_fn(est_l, us, vs, fs, cfg, dense)

            return r + 1, _replica_apply(one, replica_exec,
                                         est, u_all, v_all, i_all)

        with jax.named_scope("engine/round"):
            _, est = jax.lax.while_loop(
                lambda c: c[0] < erounds, round_body, (jnp.int32(0), est))
        # telemetry, accumulated device-side: extra exchange rounds beyond
        # the first, and the engine rounds run (both mesh-uniform by
        # construction)
        return est, ist, telem + jnp.concatenate([rounds - 1,
                                                  erounds[None]])[None]

    fn = jax.jit(jax.shard_map(
        local, mesh=mesh,
        in_specs=(est_specs, ist_specs) + (P(axis),) * 8,
        out_specs=(est_specs, ist_specs, P(axis)), check_vma=False),
        donate_argnums=_donate_argnums(mesh, 0, 1, 2, 3, 4, 5, 6, 7))
    _STEP_CACHE[key] = fn
    return fn


def default_lane_cap(chunk: int, n_dev: int, n_shards: int,
                     batch: int) -> int:
    """4x-headroom lane size over the balanced expectation, floored at one
    engine batch and capped at the source slice (beyond which a lane cannot
    fill) — with the default drain bound the router then delivers any chunk
    fully on device, and a key-skewed chunk costs extra drain rounds rather
    than a host replay."""
    balanced = -(-chunk // (n_dev * n_shards))   # ceil
    return min(max(batch, 4 * balanced), chunk // n_dev)
