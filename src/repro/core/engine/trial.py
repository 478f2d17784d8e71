"""Batched trial engine: the jitted MoSSo step (Tier B).

One ``step(state, batch)`` applies B stream changes and then runs, for every
input node, the paper's trial loop (Alg. 1) in fixed shape:

  1. TP(u): ``c`` uniform neighbor samples — O(1) each via the slot-indexed
     adjacency (the TPU-native replacement of GetRandomNeighbor, Thm. 1-3).
  2. TN filter: keep testing node w with probability 1/deg(w).
  3. Corrective escape with probability ``e`` -> fresh singleton.
  4. Otherwise a candidate destination from the PROPOSAL policy (default:
     CP(y) = TP(u) ∩ R(y) via min-hash equality; uniform candidate).
  5. Score with the OBJECTIVE policy (default: exact closed-form dphi) and
     accept per the COMMIT policy (default: dphi <= 0, Move if Saved).

Steps 4-5 dispatch through ``repro.core.engine.policies`` on the static
``EngineConfig`` policy triple — resolved at trace time, so every
registered combination compiles cond-free and the default triple is
bit-identical to the historical hard-coded engine.

Capacity guards (deg <= d_cap, |SN| <= sn_cap) skip — never corrupt — trials
that exceed the fixed shapes; skips are counted in ``n_skipped``.

**Cond-free invariant.**  The step contains ZERO ``lax.cond``: Alg. 1 is
lowered as *predicated data flow*.  Every trial computes its arms as
masked data flow (candidate selection, closed-form dphi, the masked move)
and commits through the ``ok`` predicates of the ops layer
(:mod:`repro.core.engine.ops`), so a rejected/skipped/filtered trial is a
bit-exact structural no-op.  The PRNG is counter-based and stateless, so
masked lanes drawing (and discarding) randomness cannot shift any other
lane's stream — the predicated step is bit-identical to the historical
``lax.cond`` lowering on identical inputs.

**Two lowerings, one semantics.**  The step compiles in one of two
modes, selected by the static ``dense`` flag of :func:`step_fn` /
:func:`make_step` — both bit-identical, because every write is masked
either way:

* ``dense=True`` — the change-application ops (:func:`_apply_change`)
  execute unconditionally and commit under their predicates.  This is
  the lowering the ``jax.vmap``-over-replicas layout uses
  (``repro/dist/router.py``): a batched 0/1-trip while region pays a
  per-lane select over its whole carry on every fire, which for a
  state-carrying region that fires once per change costs more than the
  masked ops themselves.
* ``dense=False`` — those regions short-circuit through :func:`pwhen`
  (never a ``lax.cond``), the fast lowering for serial execution where a
  dead region costs one trip-count check.

The trial loop itself needs no mode split: it is phased (see
:func:`_one_trial`) so its frequent predicated regions are *pure* and
carry only scalars — cheap under both lowerings — and engine state is
carried only by the commit tail, which fires at the move-acceptance
rate.  Only the per-node ``lax.scan`` — stream-order semantics — stays
sequential in both modes.
"""
from __future__ import annotations

from functools import lru_cache
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.engine import policies
from repro.core.engine.hashtable import (ht_lookup_batch,
                                         resolve_trial_backend,
                                         trial_backend_scope)
from repro.core.engine.ops import (alloc_sid, apply_move, delete_edge,
                                   insert_edge, rnd_below, rnd_u01, rnd_u32)
from repro.core.engine.state import EngineConfig, EngineState


def pwhen(pred: jax.Array, fn, carry):
    """Uniformly-predicated region: apply ``fn`` to ``carry`` iff ``pred``.

    Lowers to a 0/1-trip ``lax.while_loop``, NOT a ``lax.cond``: a dead
    predicate costs one trip check, and under ``jax.vmap`` the body runs
    (batched, at most once) iff any lane is live — SIMT predication.  ``fn``
    must itself commit through masked writes, because with mixed live/dead
    lanes it executes for all of them; the loop's per-lane carry select is
    the second, redundant layer of protection.  ``carry`` may be any
    pytree (the router predicates its intern path with it too).
    """
    done = jax.lax.while_loop(
        lambda c: c[0],
        lambda c: (jnp.zeros_like(c[0]), fn(c[1])),
        (pred, carry))
    return done[1]


def _pregion(pred: jax.Array, fn, carry, dense: bool):
    """One predicated region, lowered per the step's ``dense`` mode.

    ``dense=True`` executes ``fn`` unconditionally — correct because every
    write inside commits under its own mask; this is what the vmapped
    replica layout compiles, where a batched :func:`pwhen` would pay
    full-carry selects per fire.  ``dense=False`` short-circuits through
    :func:`pwhen`."""
    if dense:
        return fn(carry)
    return pwhen(pred, fn, carry)


def _one_trial(st: EngineState, y: jax.Array, tp: jax.Array,
               tp_minh: jax.Array, seed: jax.Array, cfg: EngineConfig,
               pred: jax.Array, dense: bool) -> EngineState:
    """Steps 3-5 of Alg. 1 for one testing node y, committed under ``pred``.

    ``pred`` folds the group-validity and TN-filter gates.  The trial is
    phased so every :func:`pwhen` carries as little as possible — that is
    what makes the SAME lowering optimal serial AND vmapped (a batched
    while loop selects its *carry* per lane on every fire; closed-over
    loop inputs like ``st`` in the pure phases are free):

    1. ``plan`` (under ``pred``) — candidate selection: pure reads, the
       carry is a handful of scalars.
    2. ``eval_phi`` (under ``ok``) — the closed-form dphi: pure reads,
       the carry is the ``d_cap`` neighbor slots.
    3. the commit tail (under ``commit``) — the only phase that carries
       engine state, firing at the (rare) move-acceptance rate.
    4. trial counters — masked scalar adds, always.

    The phases are SIBLINGS, never nested: a ``pwhen`` inside a batched
    ``pwhen`` body promotes the inner region's closed-over state into the
    outer loop's carry, reintroducing exactly the full-state copies the
    small carries avoid.

    **Policy dispatch.**  The candidate scheme, the dphi objective, and
    the accept rule are resolved HERE, at trace time, from the static
    config fields (``repro.core.engine.policies``) — plain Python lookups,
    so a compiled step bakes in exactly one policy triple and the
    cond-free invariant holds for every registered combination.  The
    default triple reproduces the pre-policy-layer expressions (and PRNG
    counters) exactly, keeping it bit-identical to the historical engine.
    """
    d_cap = cfg.d_cap
    propose = policies.PROPOSALS[cfg.proposal]
    objective = policies.OBJECTIVES[cfg.objective]
    accept = policies.COMMIT_RULES[cfg.commit]

    def plan(carry):
        a = st.n2s[y]
        esc = rnd_u01(seed, jnp.uint32(3)) <= cfg.escape

        # candidate selection (proposal policy); counters 4.. are reserved
        # for the proposal's own draws
        cand_target, cand_ok = propose(st, y, tp, tp_minh, seed, cfg)

        fresh_sid = st.free[jnp.maximum(st.free_top - 1, 0)]
        target = jnp.where(esc, fresh_sid, cand_target)

        cap_ok = ((st.deg[y] <= cfg.d_cap)
                  & (st.sndeg[a] <= cfg.sn_cap)
                  & (esc | (st.sndeg[cand_target] <= cfg.sn_cap))
                  & ((~esc) | (st.free_top > 0)))
        sem_ok = jnp.where(esc, st.ssize[a] > 1, cand_ok)
        ok = pred & cap_ok & sem_ok
        return esc, a, target, ok, cap_ok

    f = jnp.zeros((), bool)
    z32 = jnp.int32(0)
    with jax.named_scope("plan"):
        esc, a, target, ok, cap_ok = _pregion(pred, plan,
                                              (f, z32, z32, f, f), dense)

    def eval_phi(c):
        # masked data flow: dphi of the candidate move (a -> a when the
        # trial is masked, so every gather stays in bounds)
        tgt_s = jnp.clip(jnp.where(ok, target, a), 0)
        return objective(st, y, tgt_s, esc, cfg)

    c2 = (z32, jnp.full((d_cap,), -1, jnp.int32), jnp.zeros((d_cap,), bool))
    with jax.named_scope("eval_phi"):
        dphi, nbrs, nvalid = pwhen(ok, eval_phi, c2)

    def commit_tail(st: EngineState) -> EngineState:
        st = alloc_sid(st, ok=commit & esc)[0]
        st = apply_move(st, y, target, dphi, nbrs, nvalid, cfg, ok=commit)
        return st._replace(
            n_accept=st.n_accept + jnp.where(commit, 1, 0).astype(jnp.int32))

    with jax.named_scope("commit"):
        commit = ok & accept(dphi, cfg)
        st = pwhen(commit, commit_tail, st)
    return st._replace(
        n_trials=st.n_trials + jnp.where(pred, 1, 0).astype(jnp.int32),
        n_skipped=st.n_skipped
        + jnp.where(pred & ~cap_ok, 1, 0).astype(jnp.int32))


def _trial_group(st: EngineState, u: jax.Array, seed: jax.Array,
                 cfg: EngineConfig, dense: bool) -> EngineState:
    """Steps 1-5 of Alg. 1 for one input node u (predicated, cond-free).

    The TP-sampling preamble is pure and cheap, so it runs unmasked for
    every lane (including padding, with a clipped index); ``valid`` rides
    into each trial's predicate instead.
    """
    u_s = jnp.clip(u, 0)
    valid = (u >= 0) & (st.n2s[u_s] >= 0) & (st.deg[u_s] > 0)

    du = st.deg[u_s]
    ks = jnp.arange(cfg.c, dtype=jnp.uint32)
    ridx = jax.vmap(lambda k: rnd_below(seed, k * 8 + 1, du))(ks)
    tp = ht_lookup_batch(st.adj, jnp.full((cfg.c,), u_s, jnp.int32),
                         ridx, default=0)
    tp_minh = st.minh[tp]

    def body(k, st):
        y = tp[k]
        tseed = rnd_u32(seed, jnp.uint32(100) + k.astype(jnp.uint32))
        # TN filter: testing prob 1/deg(w)  (Careful Selection (1))
        keep = (rnd_u01(tseed, jnp.uint32(2))
                * st.deg[y].astype(jnp.float32) <= 1.0)
        return _one_trial(st, y, tp, tp_minh, tseed, cfg,
                          pred=valid & keep, dense=dense)

    return jax.lax.fori_loop(0, cfg.c, body, st)


def _apply_change(st: EngineState, u: jax.Array, v: jax.Array,
                  ins: jax.Array, cfg: EngineConfig, dense: bool,
                  ) -> EngineState:
    valid = u >= 0
    do_ins = valid & ins
    do_del = valid & ~ins
    st = _pregion(do_ins,
                  lambda s: insert_edge(s, u, v, cfg, ok=do_ins),
                  st, dense)
    st = _pregion(do_del,
                  lambda s: delete_edge(s, u, v, cfg, ok=do_del),
                  st, dense)
    return st


def step_fn(st: EngineState, u: jax.Array, v: jax.Array, ins: jax.Array,
            cfg: EngineConfig, dense: bool = False) -> EngineState:
    """One jitted engine step over a padded batch of changes.

    Batch semantics (DESIGN.md deviation #3): all changes apply first, then
    trial groups run for every endpoint in stream order.
    """

    def ap(st, ch):
        return _apply_change(st, ch[0], ch[1], ch[2] != 0, cfg,
                             dense), None

    changes = jnp.stack([u, v, ins.astype(jnp.int32)], axis=1)
    with jax.named_scope("apply"):
        st, _ = jax.lax.scan(ap, st, changes)

    nodes = jnp.stack([u, v], axis=1).reshape(-1)  # u0,v0,u1,v1,...

    def tg(st, xs):
        node, idx = xs
        seed = rnd_u32(st.step_no, idx.astype(jnp.uint32) * jnp.uint32(2654435761))
        return _trial_group(st, node, seed, cfg, dense), None

    with jax.named_scope("trial_group"):
        st, _ = jax.lax.scan(
            tg, st, (nodes, jnp.arange(nodes.shape[0], dtype=jnp.int32)))
    return st._replace(step_no=st.step_no + jnp.uint32(1))


@lru_cache(maxsize=None)
def _make_step(cfg: EngineConfig, dense: bool, trial_backend: str):
    # the backend scope is entered INSIDE the jitted body: jit traces
    # lazily at first call, and the scope must be active while the
    # batched-probe call sites trace so the compiled program bakes in
    # exactly the requested backend
    def stepped(st, u, v, ins):
        with trial_backend_scope(trial_backend):
            return step_fn(st, u, v, ins, cfg, dense)

    return jax.jit(stepped)


def make_step(cfg: EngineConfig, dense: bool = False,
              trial_backend: str | None = None):
    """Compile the engine step for a fixed config (and lowering mode).

    Memoized on the (hashable) config — plus the lowering mode and the
    resolved batched-probe backend (``trial_backend``: explicit arg >
    active scope > ``REPRO_TRIAL_BACKEND`` env > ``"xla"``) — so
    same-config summarizers, e.g. the two sides of a differential test,
    share one compiled program per backend.
    """
    return _make_step(cfg, dense, resolve_trial_backend(trial_backend))
