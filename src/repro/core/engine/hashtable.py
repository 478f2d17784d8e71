"""Open-addressing hash tables in fixed-shape JAX arrays (Tier B substrate).

The paper assumes "the neighborhood in C+, C- and P of each node is stored in
a hash table" (Thm. 3).  On TPU we realize that assumption with preallocated
HBM-resident open-addressing tables: `int32` key pairs, linear probing,
tombstone deletion.  All operations are pure functions `table -> table` and
compile into bounded `lax.while_loop` probes (expected O(1) probes at the
load factors we configure).

Keys are pairs ``(k1, k2)`` of non-negative int32 so that node-pair and
(node, slot) keys never need 64-bit arithmetic.  ``k1 == EMPTY`` marks a free
slot and ``k1 == TOMB`` a deleted one.

**Predicated writes.**  Every mutating op takes an ``ok`` predicate; a
masked call (``ok=False``) probes as usual but writes the slot's existing
contents back, so it is a structural no-op of constant cost — the
predication contract the branch-free trial engine (``trial.py``) builds on.
Masked calls may receive garbage keys (padding, untaken arms): probe loops
always terminate (a chain ends at EMPTY or wraps after ``cap`` steps) and
nothing is committed.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# numpy scalars: a jnp constant here would start a backend at import
EMPTY = np.int32(-1)
TOMB = np.int32(-2)

# ---------------------------------------------------------------------- #
# batched-probe backend switch
# ---------------------------------------------------------------------- #
#
# Batched probes — the trial engine's lookups and the router's intern
# pre-lookup — lower in one of two ways:
#
# * ``"xla"`` (default) — ``jax.vmap`` over the scalar probe loops below:
#   one batched ``lax.while_loop`` per call site.  The differential
#   reference, and the only compiled path on CPU.
# * ``"pallas"`` — one fused kernel launch per batch
#   (``repro.kernels.ht_probe``), bit-identical by contract; on the CPU
#   backend it runs in Pallas interpret mode (inlined into the XLA
#   program), so CI can exercise the kernel path end to end.
#
# The backend is resolved at TRACE time: callers that compile a step enter
# :func:`trial_backend_scope` inside the to-be-jitted function body (see
# ``trial.make_step`` / ``dist.router``), so the scope is active while the
# probe call sites trace and each compiled program bakes in exactly one
# backend.  ``REPRO_TRIAL_BACKEND`` sets the process-wide default.
TRIAL_BACKENDS = ("xla", "pallas")
_BACKEND_STACK: List[str] = []


def resolve_trial_backend(backend: str | None = None) -> str:
    """The effective probe backend: explicit arg > active scope > env."""
    if backend is None:
        backend = (_BACKEND_STACK[-1] if _BACKEND_STACK
                   else os.environ.get("REPRO_TRIAL_BACKEND", "xla"))
    if backend not in TRIAL_BACKENDS:
        raise ValueError(
            f"trial backend must be one of {TRIAL_BACKENDS}: {backend!r}")
    return backend


@contextmanager
def trial_backend_scope(backend: str | None):
    """Pin the batched-probe backend for call sites traced in this scope."""
    _BACKEND_STACK.append(resolve_trial_backend(backend))
    try:
        yield _BACKEND_STACK[-1]
    finally:
        _BACKEND_STACK.pop()


class HashTable(NamedTuple):
    k1: jax.Array  # int32[cap]
    k2: jax.Array  # int32[cap]
    val: jax.Array  # int32[cap]

    @property
    def capacity(self) -> int:
        return self.k1.shape[0]


def ht_new(capacity: int) -> HashTable:
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return HashTable(
        k1=jnp.full((capacity,), EMPTY, jnp.int32),
        k2=jnp.full((capacity,), EMPTY, jnp.int32),
        val=jnp.zeros((capacity,), jnp.int32),
    )


def _hash(k1: jax.Array, k2: jax.Array, cap: int) -> jax.Array:
    """Two-word integer mix (fmix32-style) onto [0, cap)."""
    h = k1.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h + k2.astype(jnp.uint32) * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x27D4EB2F)
    h = h ^ (h >> 15)
    return (h & jnp.uint32(cap - 1)).astype(jnp.int32)


def _probe_start(k1: jax.Array, k2: jax.Array, cap: int,
                 prehashed: bool) -> jax.Array:
    """First probe slot for a key.

    ``prehashed=True`` skips the fmix re-mix and folds the words directly
    onto the table — for tables whose keys are already full-entropy hashes
    (the router's label-intern tables, keyed by 62-bit splitmix64/blake2b
    words).  A table must be accessed with one consistent setting: the
    probe sequence IS the on-device layout.
    """
    if prehashed:
        h = (k1.astype(jnp.uint32) ^ k2.astype(jnp.uint32))
        return (h & jnp.uint32(cap - 1)).astype(jnp.int32)
    return _hash(k1, k2, cap)


def ht_find(ht: HashTable, k1, k2,
            prehashed: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Return (slot, found). Probes until the key or an EMPTY slot is hit."""
    cap = ht.capacity
    k1 = jnp.asarray(k1, jnp.int32)
    k2 = jnp.asarray(k2, jnp.int32)
    start = _probe_start(k1, k2, cap, prehashed)

    def cond(carry):
        i, _ = carry
        slot = (start + i) & (cap - 1)
        hit = (ht.k1[slot] == k1) & (ht.k2[slot] == k2)
        return (~hit) & (ht.k1[slot] != EMPTY) & (i < cap)

    def body(carry):
        i, _ = carry
        return (i + 1, jnp.int32(0))

    i, _ = jax.lax.while_loop(cond, body, (jnp.int32(0), jnp.int32(0)))
    slot = (start + i) & (cap - 1)
    found = (ht.k1[slot] == k1) & (ht.k2[slot] == k2)
    return slot, found


def ht_lookup(ht: HashTable, k1, k2, default=0) -> jax.Array:
    slot, found = ht_find(ht, k1, k2)
    return jnp.where(found, ht.val[slot], jnp.int32(default))


def _probe_batch(ht: HashTable, k1: jax.Array, k2: jax.Array,
                 prehashed: bool, backend: str | None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Backend dispatch for a batch of find-probes: (slot, found, val).

    ``val`` is the value at the key's chain-end slot — garbage when
    ``~found``; callers select against their own default.  Both backends
    are leaf-bitwise identical (tests/test_kernels.py sweeps this).
    """
    k1 = jnp.asarray(k1, jnp.int32)
    k2 = jnp.asarray(k2, jnp.int32)
    if resolve_trial_backend(backend) == "pallas":
        # lazy import: the kernels layer imports this module for the
        # probe-sequence constants, so the dependency cannot be top-level
        from repro.kernels import ops as _kops
        return _kops.ht_probe(ht.k1, ht.k2, ht.val, k1, k2,
                              prehashed=prehashed, mode="find")
    slot, found = jax.vmap(
        lambda a, b: ht_find(ht, a, b, prehashed=prehashed))(k1, k2)
    return slot, found, ht.val[slot]


def ht_find_batch(ht: HashTable, k1: jax.Array, k2: jax.Array,
                  prehashed: bool = False, backend: str | None = None,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Batched :func:`ht_find`: (slot, found) per query, one fused probe
    pass under the active trial backend."""
    slot, found, _ = _probe_batch(ht, k1, k2, prehashed, backend)
    return slot, found


def ht_lookup_batch(ht: HashTable, k1: jax.Array, k2: jax.Array,
                    default=0, backend: str | None = None) -> jax.Array:
    """Vectorized read-only lookups under the active trial backend."""
    _, found, val = _probe_batch(ht, k1, k2, False, backend)
    return jnp.where(found, val, jnp.int32(default))


def _find_insert_slot(ht: HashTable, k1, k2,
                      prehashed: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Slot for an upsert: the key's slot if present, else first free slot."""
    cap = ht.capacity
    start = _probe_start(k1, k2, cap, prehashed)

    # pass 1: find the key or the end of its probe chain (EMPTY).
    def cond1(i):
        slot = (start + i) & (cap - 1)
        hit = (ht.k1[slot] == k1) & (ht.k2[slot] == k2)
        return (~hit) & (ht.k1[slot] != EMPTY) & (i < cap)

    i1 = jax.lax.while_loop(cond1, lambda i: i + 1, jnp.int32(0))
    slot1 = (start + i1) & (cap - 1)
    found = (ht.k1[slot1] == k1) & (ht.k2[slot1] == k2)

    # pass 2 (only matters when not found): first EMPTY or TOMB slot.
    def cond2(i):
        slot = (start + i) & (cap - 1)
        free = (ht.k1[slot] == EMPTY) | (ht.k1[slot] == TOMB)
        return (~free) & (i < cap)

    i2 = jax.lax.while_loop(cond2, lambda i: i + 1, jnp.int32(0))
    slot2 = (start + i2) & (cap - 1)
    return jnp.where(found, slot1, slot2), found


def ht_set(ht: HashTable, k1, k2, v, prehashed: bool = False,
           ok=True) -> HashTable:
    """Upsert key -> v (masked write-back of the slot when ``~ok``)."""
    k1 = jnp.asarray(k1, jnp.int32)
    k2 = jnp.asarray(k2, jnp.int32)
    slot, _ = _find_insert_slot(ht, k1, k2, prehashed)
    return HashTable(
        k1=ht.k1.at[slot].set(jnp.where(ok, k1, ht.k1[slot])),
        k2=ht.k2.at[slot].set(jnp.where(ok, k2, ht.k2[slot])),
        val=ht.val.at[slot].set(
            jnp.where(ok, jnp.asarray(v, jnp.int32), ht.val[slot])),
    )


def ht_add(ht: HashTable, k1, k2, delta, remove_if_zero: bool = False,
           ok=True) -> Tuple[HashTable, jax.Array]:
    """val[key] += delta (inserting at 0 if absent); returns (table, new val).

    With ``remove_if_zero`` the entry is tombstoned when it reaches 0 —
    used by the E_AB count table so that `SN` adjacency mirrors E>0 pairs.
    ``new`` is the would-be value either way; the table is only mutated
    under ``ok``.
    """
    k1 = jnp.asarray(k1, jnp.int32)
    k2 = jnp.asarray(k2, jnp.int32)
    slot, found = _find_insert_slot(ht, k1, k2)
    old = jnp.where(found, ht.val[slot], jnp.int32(0))
    new = old + jnp.asarray(delta, jnp.int32)
    dead = remove_if_zero & (new == 0)
    return HashTable(
        k1=ht.k1.at[slot].set(
            jnp.where(ok, jnp.where(dead, TOMB, k1), ht.k1[slot])),
        k2=ht.k2.at[slot].set(
            jnp.where(ok, jnp.where(dead, TOMB, k2), ht.k2[slot])),
        val=ht.val.at[slot].set(
            jnp.where(ok, jnp.where(dead, 0, new), ht.val[slot])),
    ), new


def ht_delete(ht: HashTable, k1, k2, ok=True) -> HashTable:
    """Tombstone the key if present (no-op otherwise or when ``~ok``)."""
    k1 = jnp.asarray(k1, jnp.int32)
    k2 = jnp.asarray(k2, jnp.int32)
    slot, found = ht_find(ht, k1, k2)
    found = found & ok
    return HashTable(
        k1=ht.k1.at[slot].set(jnp.where(found, TOMB, ht.k1[slot])),
        k2=ht.k2.at[slot].set(jnp.where(found, TOMB, ht.k2[slot])),
        val=ht.val.at[slot].set(jnp.where(found, 0, ht.val[slot])),
    )


def ht_contains(ht: HashTable, k1, k2) -> jax.Array:
    _, found = ht_find(ht, k1, k2)
    return found


def ht_live_mask(ht: HashTable) -> jax.Array:
    return ht.k1 >= 0


def ht_load(ht: HashTable) -> jax.Array:
    """Fraction of live slots (host-side maintenance signal)."""
    return jnp.mean(ht_live_mask(ht).astype(jnp.float32))


def ht_rebuild(ht: HashTable, prehashed: bool = False) -> HashTable:
    """Host-callable compaction: rehash live entries into a fresh table.

    Long fully-dynamic streams accumulate tombstones that stretch probe
    chains; production deployments call this between steps when
    ``ht_load + tombstone fraction`` crosses a threshold.

    ``prehashed`` MUST match how the table is probed (see
    ``_probe_start``): rebuilding a prehashed table with the default mix
    would relocate every entry off its probe chain.  (The router's intern
    tables are prehashed but never tombstone, so they never need this.)
    """
    fresh = ht_new(ht.capacity)

    def body(i, t):
        live = ht.k1[i] >= 0
        return jax.lax.cond(
            live,
            lambda t: ht_set(t, ht.k1[i], ht.k2[i], ht.val[i],
                             prehashed=prehashed),
            lambda t: t, t)

    return jax.lax.fori_loop(0, ht.capacity, body, fresh)
