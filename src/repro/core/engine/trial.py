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
:func:`make_step` — both bit-identical in every state leaf and counter
but ``n_passes``, which counts the passes only the first runs:

* ``dense=True`` — the lowering the ``jax.vmap``-over-replicas layout
  uses (``repro/dist/router.py``).  The change-application ops
  (:func:`_apply_change`) execute unconditionally and commit under their
  predicates: a batched 0/1-trip while region pays a per-lane select
  over its whole carry on every fire, which for a state-carrying region
  that fires once per change costs more than the masked ops themselves.
  A trial group runs as *speculative passes*
  (:func:`_trial_group_passes`): each pass plans all ``c`` trials at
  once against the current state, scores the first ``PASS_WIDTH`` live
  plans, commits the first that accepts, and resumes after it — about
  ``1 + commits`` passes per group instead of ``c`` serial trials.
* ``dense=False`` — those regions short-circuit through :func:`pwhen`
  (never a ``lax.cond``), and a trial group is a ``fori_loop`` over its
  ``c`` trials (:func:`_trial_group`), each phased (see
  :func:`_one_trial`) so its frequent predicated regions are pure and
  carry only scalars.  It is the lowering of the ``lax.map`` replica
  layout (the CPU default) and the differential reference the passes
  are held to.

The passes are exact, not an approximation.  A trial that does not
commit changes no engine state — its candidate selection and dphi are
pure reads, its only effect the ``n_trials`` / ``n_skipped`` counters —
and a trial's randomness is ``rnd_u32(seed, 100 + k)``, a function of
its index alone (the PRNG is counter-based and stateless).  So every
trial up to and including a group's first commit sees exactly the state
at the start of the group, scores exactly as it would serially, and the
pass that finds the first commit has computed what the serial loop
computes up to it; the next pass starts from the committed state at the
following trial.  A trial whose plan is not ``ok`` cannot commit, so a
pass that leaves its objective out (as the serial loop does) loses
nothing, and a pass that scored ``PASS_WIDTH`` live plans without a
commit has decided every trial up to the last of them.  Only the per-node ``lax.scan`` over groups — stream-
order semantics — stays sequential in both modes.
"""
from __future__ import annotations

from functools import lru_cache
import jax
import jax.numpy as jnp

from repro.core.engine import policies
from repro.core.engine.hashtable import (ht_lookup_batch,
                                         resolve_trial_backend,
                                         trial_backend_scope)
from repro.core.engine.ops import (alloc_sid, apply_move, delete_edge,
                                   insert_edge, rnd_below, rnd_u01, rnd_u32)
from repro.core.engine.state import EngineConfig, EngineState


# Trials a speculative pass scores with the objective: the first this many
# whose plan is ``ok``.  Most plans are not (the TN filter, a candidate in
# the node's own supernode, an escape from a singleton), so one pass
# usually covers a whole group; a pass that scores this many without a
# commit ends at the last of them, and the next resumes after it.  A pass
# costs a fixed part plus a part per scored trial, so a narrow pass wins
# while live plans are rare (measurements: PERF.md).
PASS_WIDTH = 8


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


def _trial_seed(seed: jax.Array, k: jax.Array) -> jax.Array:
    """Trial ``k``'s PRNG seed: a function of the group seed and ``k``
    alone, never of the engine state — what makes the speculative passes
    exact."""
    return rnd_u32(seed, jnp.uint32(100) + k.astype(jnp.uint32))


def _tn_keep(st: EngineState, y: jax.Array, tseed: jax.Array) -> jax.Array:
    """TN filter: test node y with probability 1/deg(y) (Careful
    Selection (1))."""
    return (rnd_u01(tseed, jnp.uint32(2))
            * st.deg[y].astype(jnp.float32) <= 1.0)


def _plan(st: EngineState, y: jax.Array, tp: jax.Array, tp_minh: jax.Array,
          seed: jax.Array, cfg: EngineConfig, pred: jax.Array):
    """Candidate selection for testing node y (pure reads):
    ``(esc, a, target, ok, cap_ok)``; ``ok`` folds ``pred`` with the
    capacity and semantic guards."""
    propose = policies.PROPOSALS[cfg.proposal]
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


def _eval_phi(st: EngineState, y: jax.Array, a: jax.Array, target: jax.Array,
              esc: jax.Array, ok: jax.Array, cfg: EngineConfig):
    """``(dphi, nbrs, nvalid)`` of the candidate move under the objective
    policy — masked data flow: the move a -> a when the trial is masked,
    so every gather stays in bounds."""
    tgt_s = jnp.clip(jnp.where(ok, target, a), 0)
    return policies.OBJECTIVES[cfg.objective](st, y, tgt_s, esc, cfg)


def _commit_tail(st: EngineState, y: jax.Array, target: jax.Array,
                 esc: jax.Array, dphi: jax.Array, nbrs: jax.Array,
                 nvalid: jax.Array, commit: jax.Array,
                 cfg: EngineConfig) -> EngineState:
    """Apply an accepted move under ``commit`` (a masked no-op otherwise):
    allocate the fresh sid on escape, move y, count the acceptance."""
    st = alloc_sid(st, ok=commit & esc)[0]
    st = apply_move(st, y, target, dphi, nbrs, nvalid, cfg, ok=commit)
    return st._replace(
        n_accept=st.n_accept + jnp.where(commit, 1, 0).astype(jnp.int32))


def _one_trial(st: EngineState, y: jax.Array, tp: jax.Array,
               tp_minh: jax.Array, seed: jax.Array, cfg: EngineConfig,
               pred: jax.Array) -> EngineState:
    """Steps 3-5 of Alg. 1 for one testing node y, committed under ``pred``.

    ``pred`` folds the group-validity and TN-filter gates.  The trial is
    phased so every :func:`pwhen` carries as little as possible (a batched
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
    the accept rule are resolved at trace time from the static config
    fields (``repro.core.engine.policies``) — plain Python lookups, so a
    compiled step bakes in exactly one policy triple and the cond-free
    invariant holds for every registered combination.  The default
    triple reproduces the pre-policy-layer expressions (and PRNG
    counters) exactly, keeping it bit-identical to the historical engine.
    """
    accept = policies.COMMIT_RULES[cfg.commit]
    f = jnp.zeros((), bool)
    z32 = jnp.int32(0)
    with jax.named_scope("plan"):
        esc, a, target, ok, cap_ok = pwhen(
            pred, lambda c: _plan(st, y, tp, tp_minh, seed, cfg, pred),
            (f, z32, z32, f, f))

    c2 = (z32, jnp.full((cfg.d_cap,), -1, jnp.int32),
          jnp.zeros((cfg.d_cap,), bool))
    with jax.named_scope("eval_phi"):
        dphi, nbrs, nvalid = pwhen(
            ok, lambda c: _eval_phi(st, y, a, target, esc, ok, cfg), c2)

    with jax.named_scope("commit"):
        commit = ok & accept(dphi, cfg)
        st = pwhen(commit,
                   lambda s: _commit_tail(s, y, target, esc, dphi, nbrs,
                                          nvalid, commit, cfg), st)
    return st._replace(
        n_trials=st.n_trials + jnp.where(pred, 1, 0).astype(jnp.int32),
        n_skipped=st.n_skipped
        + jnp.where(pred & ~cap_ok, 1, 0).astype(jnp.int32))


def _sample_tp(st: EngineState, u: jax.Array, seed: jax.Array,
               cfg: EngineConfig):
    """Step 1 of Alg. 1: ``(valid, tp, tp_minh)`` for input node u.

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
    return valid, tp, st.minh[tp]


def _trial_group(st: EngineState, u: jax.Array, seed: jax.Array,
                 cfg: EngineConfig) -> EngineState:
    """Steps 1-5 of Alg. 1 for one input node u, trial after trial: a
    ``fori_loop`` over the ``c`` trials (predicated, cond-free).

    The serial lowering (``dense=False``) and the reference the
    speculative passes are held to.  It runs no pass, so ``n_passes``
    stays as it was.
    """
    valid, tp, tp_minh = _sample_tp(st, u, seed, cfg)

    def body(k, st):
        y = tp[k]
        tseed = _trial_seed(seed, k)
        return _one_trial(st, y, tp, tp_minh, tseed, cfg,
                          pred=valid & _tn_keep(st, y, tseed))

    return jax.lax.fori_loop(0, cfg.c, body, st)


def _trial_group_passes(st: EngineState, u: jax.Array, seed: jax.Array,
                        cfg: EngineConfig) -> EngineState:
    """Steps 1-5 of Alg. 1 for one input node u as speculative passes.

    Each pass, from the carried trial index ``k0``, plans every trial
    ``k >= k0`` at once against the current state (the same TN-filter
    draw and plan as :func:`_one_trial`, vmapped over ``k``) and scores
    with the objective the first ``PASS_WIDTH`` of them whose plan is
    ``ok`` — the rest cannot commit, and the serial loop skips their
    objective too.  The pass ends at the first of those that commits
    (its move is applied), else at the last one scored if the pass
    scored ``PASS_WIDTH``, else at the group's end; it counts the trials
    up to there from its own predicates, and the next pass resumes after
    it.  A padding slot runs none.  Exact because a trial that does not
    commit is pure and its randomness depends on ``k`` alone (module
    docstring).  One ``lax.while_loop`` (no ``lax.cond``); ``n_passes``
    counts its iterations.
    """
    valid, tp, tp_minh = _sample_tp(st, u, seed, cfg)
    accept = policies.COMMIT_RULES[cfg.commit]
    width = min(PASS_WIDTH, cfg.c)
    ks = jnp.arange(cfg.c, dtype=jnp.int32)

    def plan(st, k, live):
        y = tp[k]
        tseed = _trial_seed(seed, k)
        pred = live & _tn_keep(st, y, tseed)
        return (pred,) + _plan(st, y, tp, tp_minh, tseed, cfg, pred)

    def one_pass(carry):
        st, k0, _ = carry
        with jax.named_scope("plan"):
            pred, esc, a, target, ok, cap_ok = jax.vmap(
                lambda k: plan(st, k, valid & (k >= k0)))(ks)
        # the first `width` trials with a live plan, in trial order
        sel = jnp.nonzero(ok, size=width, fill_value=0)[0]
        n_ok = jnp.sum(ok, dtype=jnp.int32)
        scored = jnp.arange(width) < n_ok
        with jax.named_scope("eval_phi"):
            dphi, nbrs, nvalid = jax.vmap(
                lambda k, live: _eval_phi(st, tp[k], a[k], target[k],
                                          esc[k], live, cfg))(sel, scored)
        commit = scored & accept(dphi, cfg)
        found = jnp.any(commit)
        j = jnp.argmax(commit)                         # first commit, or 0
        last = jnp.where(n_ok >= width, sel[width - 1], cfg.c - 1)
        end = jnp.where(found, sel[j], last)
        ran = pred & (ks <= end)
        st = st._replace(
            n_trials=st.n_trials + jnp.sum(ran, dtype=jnp.int32),
            n_skipped=st.n_skipped + jnp.sum(ran & ~cap_ok, dtype=jnp.int32),
            n_passes=st.n_passes + 1)
        kc = sel[j]
        with jax.named_scope("commit"):
            st = _commit_tail(st, tp[kc], target[kc], esc[kc], dphi[j],
                              nbrs[j], nvalid[j], found, cfg)
        return st, end + 1, end + 1 < cfg.c

    st, _, _ = jax.lax.while_loop(lambda c: c[2], one_pass,
                                  (st, jnp.int32(0), valid))
    return st


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
    group = _trial_group_passes if dense else _trial_group

    def tg(st, xs):
        node, idx = xs
        seed = rnd_u32(st.step_no, idx.astype(jnp.uint32) * jnp.uint32(2654435761))
        return group(st, node, seed, cfg), None

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
