"""Fixed-shape engine state for the batched TPU-native MoSSo (Tier B).

Everything lives in preallocated arrays/hash tables so a summarization step
is a pure jitted function ``(state, change_batch, seed) -> state``.

Capacity model (host-validated): ``n_cap`` nodes, ``m_cap`` live undirected
edges, movable-node degree bound ``d_cap``, supernode-adjacency bound
``sn_cap``.  Hash tables are sized at ~4x their worst-case live entries so
linear probing stays O(1) (see `hashtable.py`).
"""
from __future__ import annotations

import dataclasses
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine.hashtable import HashTable, ht_new

NO_CLUSTER = np.int32(0x7FFFFFFF)   # numpy: no backend at import

# Canonical policy names.  The implementations live in
# ``repro.core.engine.policies`` (which imports this module, so only the
# name tuples can live here); a test pins the registry keys to these
# tuples so they cannot drift.
PROPOSALS = ("minhash", "magsdm")
OBJECTIVES = ("exact", "weighted")
COMMIT_RULES = ("saving", "threshold")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Capacity and search parameters of one engine instance.

    **Id space.** The engine is oblivious to caller labels: it consumes
    dense node ids in ``[0, n_cap)`` (every state array below is indexed by
    them).  Front-ends own the translation — ``BatchedSummarizer`` interns
    labels on the host; ``ShardedSummarizer`` interns per shard on device
    (``repro/dist/router.py``), so under sharding ``n_cap`` is a PER-SHARD
    budget and, edge partitioning being a vertex cut, must cover the node
    replication factor, not just ``|V| / n_shards``.

    **Capacity semantics.** ``n_cap`` is hard: interning past it fails
    fast (host assert in ``BatchedSummarizer``, or a device drop counter
    that raises at the next sync under sharding).  ``m_cap`` is a sizing
    contract, not a checked bound: it fixes the hash-table capacities
    (``table_caps``) at ~4x their worst-case live entries, so streaming
    more than ``m_cap`` live edges degrades probe chains instead of
    erroring — monitor ``table_pressure()``/``maybe_compact()`` on long
    streams.  ``d_cap``/``sn_cap`` are soft trial bounds: trials that
    would exceed them are skipped — never corrupted — and counted in
    ``n_skipped`` (DESIGN deviation #1).

    **Policy triple.** ``proposal`` / ``objective`` / ``commit`` select the
    Alg.-1 policies (candidate generation, move scoring, accept rule) as
    STATIC fields: policy dispatch happens at trace time (plain Python
    dict lookup in ``trial.py`` / ``policies.py``, never a ``lax.cond``),
    and because the config is frozen/hashable, every compile cache —
    ``make_step``'s ``lru_cache`` and the router's ``_STEP_CACHE`` — keys
    on the resolved triple automatically.  Defaults come from
    ``REPRO_PROPOSAL`` / ``REPRO_OBJECTIVE`` (the ``REPRO_TRIAL_BACKEND``
    pattern) so the CI matrix can flip them for a whole suite.
    ``weight_levels`` parameterizes the ``weighted`` objective's node
    weights ``w(u) = 1 + (hash(u) % weight_levels)``; ``0``/``1`` mean
    uniform weights, under which the weighted objective is bit-identical
    to ``exact``.  Keep it small: per-supernode ``SW**2`` must stay below
    2**31 (int32 TW products).
    """

    n_cap: int = 1 << 14          # max distinct nodes (per engine/shard)
    m_cap: int = 1 << 17          # max live undirected edges
    d_cap: int = 64               # movable-node degree bound (deviation #1)
    sn_cap: int = 32              # supernode-adjacency bound for moves
    c: int = 20                   # samples per input node (paper's c)
    escape: float = 0.3           # corrective-escape probability (paper's e)
    batch: int = 32               # changes per jitted step
    seed: int = 0
    # policy triple (static: part of every compile-cache key)
    proposal: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_PROPOSAL", "minhash"))
    objective: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_OBJECTIVE", "exact"))
    commit: str = "saving"
    commit_margin: int = 0        # accept iff dphi <= margin ("threshold")
    weight_levels: int = 0        # 0/1 = uniform node weights ("weighted")

    def __post_init__(self):
        if self.proposal not in PROPOSALS:
            raise ValueError(f"unknown proposal {self.proposal!r}; "
                             f"expected one of {PROPOSALS}")
        if self.objective not in OBJECTIVES:
            raise ValueError(f"unknown objective {self.objective!r}; "
                             f"expected one of {OBJECTIVES}")
        if self.commit not in COMMIT_RULES:
            raise ValueError(f"unknown commit rule {self.commit!r}; "
                             f"expected one of {COMMIT_RULES}")

    def manifest(self) -> dict:
        """JSON-able identity of this config for checkpoint manifests.

        Every field participates: the table capacities, the policy triple
        and the PRNG seed all shape the engine state arrays and the trial
        schedule, so a checkpoint taken under one config is only bitwise
        replayable under an equal config (``repro.checkpoint.summary``
        refuses a mismatched restore instead of silently corrupting).
        """
        return dataclasses.asdict(self)

    def table_caps(self) -> dict:
        def pow2(x: int) -> int:
            c = 1
            while c < x:
                c <<= 1
            return c
        return dict(
            adj=pow2(4 * self.m_cap),      # (u, slot) -> v, two directions
            epos=pow2(4 * self.m_cap),     # (u, v) -> slot, two directions
            eab=pow2(2 * self.m_cap),      # canonical pair -> |E_AB|
            snadj=pow2(2 * self.m_cap),    # (sid, slot) -> sid
            snpos=pow2(2 * self.m_cap),    # (sid, sid) -> slot
            # canonical pair -> W_AB, live iff the eab entry is (positive
            # weights), kept at the same capacity so probe chains match;
            # a 8-slot dummy when the objective doesn't maintain weights
            weab=(pow2(2 * self.m_cap)
                  if self.objective == "weighted" else 8),
        )


class EngineState(NamedTuple):
    # per node
    n2s: jax.Array      # int32[n_cap], -1 = unseen node
    deg: jax.Array      # int32[n_cap]
    minh: jax.Array     # int32[n_cap], min-hash cluster id (NO_CLUSTER if none)
    # per supernode (sid space == node space)
    ssize: jax.Array    # int32[n_cap]
    sndeg: jax.Array    # int32[n_cap], |SN(sid)| (supernodes with E>0)
    free: jax.Array     # int32[n_cap], free sid stack
    free_top: jax.Array  # int32 scalar, #free sids
    # weighted-objective view (dummy 1/8-sized leaves under "exact" so the
    # pytree structure is config-static and the default jaxpr untouched)
    wsum: jax.Array     # int32[n_cap] SW(sid) = sum of member weights
    wsq: jax.Array      # int32[n_cap] SQ(sid) = sum of squared weights
    # tables
    adj: HashTable
    epos: HashTable
    eab: HashTable
    snadj: HashTable
    snpos: HashTable
    weab: HashTable     # canonical pair -> W_AB (weighted objective only)
    # scalars
    phi: jax.Array        # int32
    num_edges: jax.Array  # int32
    step_no: jax.Array    # uint32, PRNG stream position
    # counters for stats
    n_trials: jax.Array
    n_accept: jax.Array
    n_skipped: jax.Array  # trials skipped by capacity guards (deviation audit)
    n_passes: jax.Array   # speculative trial passes run (trial.py, dense
    #                       lowering only; a checkpoint without it restores 0)


def new_state(cfg: EngineConfig) -> EngineState:
    caps = cfg.table_caps()
    n = cfg.n_cap
    nw = n if cfg.objective == "weighted" else 1
    return EngineState(
        n2s=jnp.full((n,), -1, jnp.int32),
        deg=jnp.zeros((n,), jnp.int32),
        minh=jnp.full((n,), NO_CLUSTER, jnp.int32),
        ssize=jnp.zeros((n,), jnp.int32),
        sndeg=jnp.zeros((n,), jnp.int32),
        free=jnp.arange(n - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.int32(n),
        wsum=jnp.zeros((nw,), jnp.int32),
        wsq=jnp.zeros((nw,), jnp.int32),
        adj=ht_new(caps["adj"]),
        epos=ht_new(caps["epos"]),
        eab=ht_new(caps["eab"]),
        snadj=ht_new(caps["snadj"]),
        snpos=ht_new(caps["snpos"]),
        weab=ht_new(caps["weab"]),
        phi=jnp.int32(0),
        num_edges=jnp.int32(0),
        step_no=jnp.uint32(cfg.seed),
        n_trials=jnp.int32(0),
        n_accept=jnp.int32(0),
        n_skipped=jnp.int32(0),
        n_passes=jnp.int32(0),
    )
