"""Speculative trial passes against the serial trial loop.

``dense=True`` lowers each trial group as speculative passes (plan every
trial against the current state, score the first ``PASS_WIDTH`` live
plans, commit the first that accepts, resume after it); ``dense=False``
runs the ``c`` trials one after another.  The
two must leave every state leaf and counter bitwise equal, over whole
streams and for every policy triple — all but ``n_passes``, the passes
only the speculative lowering runs (the serial loop leaves it at 0).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.core.engine.ops import insert_edge
from repro.core.engine.state import (COMMIT_RULES, OBJECTIVES, PROPOSALS,
                                     new_state)
from repro.core.engine.trial import (PASS_WIDTH, _trial_group,
                                     _trial_group_passes, make_step)
from repro.graph.streams import edges_to_fully_dynamic_stream, sbm_edges


def _cfg(**kw):
    base = dict(n_cap=128, m_cap=1024, d_cap=32, sn_cap=24, c=8, batch=16,
                escape=0.3)
    base.update(kw)
    return EngineConfig(**base)


def _replay(cfg, stream, dense):
    """The stream through ``make_step(cfg, dense)``, batch by batch, labels
    interned in encounter order and padded with -1 as
    ``BatchedSummarizer.process`` does."""
    step = make_step(cfg, dense=dense)
    st = new_state(cfg)
    ids = {}
    b = cfg.batch
    for off in range(0, len(stream), b):
        sl = stream[off:off + b]
        u = np.full(b, -1, np.int32)
        v = np.full(b, -1, np.int32)
        ins = np.zeros(b, bool)
        for i, (x, y, f) in enumerate(sl):
            u[i] = ids.setdefault(x, len(ids))
            v[i] = ids.setdefault(y, len(ids))
            ins[i] = f
        st = step(st, u, v, ins)
    return st


def _assert_leaves_equal(a, b):
    """Every leaf of the serial state ``a`` equals the speculative state
    ``b``'s, but the pass count, which only ``b`` advances."""
    np.testing.assert_array_equal(np.asarray(a.n_passes), 0)
    a = a._replace(n_passes=b.n_passes)
    for name in a._fields:
        for x, y in zip(jax.tree.leaves(getattr(a, name)),
                        jax.tree.leaves(getattr(b, name))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)


def _star(cfg, n_leaves):
    """A fresh state holding the star 0 - 1..n_leaves (no trials run)."""
    leaves = jnp.arange(1, n_leaves + 1, dtype=jnp.int32)
    return jax.jit(lambda st: jax.lax.fori_loop(
        0, n_leaves,
        lambda i, st: insert_edge(st, jnp.int32(0), leaves[i], cfg),
        st))(new_state(cfg))


def _sbm_stream(seed, p_in=0.5):
    edges = sbm_edges(30, 3, p_in, 0.06, seed=seed)
    return edges_to_fully_dynamic_stream(edges, delete_prob=0.2,
                                         seed=seed + 1)


_TRIPLES = [dict(proposal=p, objective=o, commit=c, commit_margin=1,
                 weight_levels=3)
            for p, o, c in itertools.product(PROPOSALS, OBJECTIVES,
                                             COMMIT_RULES)]
_CASES = ([(f"{t['proposal']}-{t['objective']}-{t['commit']}", t, 17)
           for t in _TRIPLES]
          # most trials escape to a fresh singleton (alloc_sid on commit)
          + [("escapes", dict(escape=0.6, commit="threshold",
                              commit_margin=2), 23)]
          # overlapping dense blocks and a tolerant accept rule: many
          # commits per group, so most groups take several passes
          + [("many-commits", dict(commit="threshold", commit_margin=4), 29)]
          # more trials per group than a pass scores
          + [("c-above-pass-width", dict(c=40, commit="threshold",
                                         commit_margin=2), 31)])


@pytest.mark.parametrize("case,kw,seed", _CASES, ids=[c[0] for c in _CASES])
def test_passes_equal_serial_loop_bitwise(case, kw, seed):
    cfg = _cfg(**kw)
    stream = _sbm_stream(seed, p_in=0.8 if case == "many-commits" else 0.5)
    serial = _replay(cfg, stream, dense=False)
    passes = _replay(cfg, stream, dense=True)
    _assert_leaves_equal(serial, passes)
    assert int(passes.n_accept) > 0, case
    n_groups = 2 * len(stream)          # an upper bound on live groups
    assert n_groups // 2 <= int(passes.n_passes), case
    if case == "many-commits":
        # more passes than groups: the multi-pass path really ran
        assert int(passes.n_passes) > n_groups


def test_group_whose_every_trial_commits():
    """A star's hub: every sampled leaf has degree 1 (the TN filter keeps
    it), no escapes, the modal proposal always finds another supernode
    among the samples, and the accept rule takes any move — so each of
    the ``c`` trials commits, and the speculative lowering runs exactly
    ``c`` passes, ending on the group's last trial."""
    cfg = _cfg(c=4, escape=0.0, proposal="magsdm", commit="threshold",
               commit_margin=1 << 20)
    st = _star(cfg, 40)
    seed = jnp.uint32(12345)
    hub = jnp.int32(0)
    serial = jax.jit(lambda s: _trial_group(s, hub, seed, cfg))(st)
    passes = jax.jit(lambda s: _trial_group_passes(s, hub, seed, cfg))(st)
    _assert_leaves_equal(serial, passes)
    assert int(passes.n_accept) == cfg.c
    assert int(passes.n_trials) == cfg.c
    assert int(passes.n_passes) == cfg.c


def test_pass_scores_at_most_pass_width_live_plans():
    """Every plan live and none accepted: the group's ``c`` trials take
    ``ceil(c / PASS_WIDTH)`` passes, each ending at the last trial it
    scored, and all of them count as trials."""
    cfg = _cfg(c=2 * PASS_WIDTH + 8, escape=0.0, proposal="magsdm",
               commit="threshold", commit_margin=-(1 << 20))
    st = _star(cfg, 40)
    seed = jnp.uint32(12345)
    hub = jnp.int32(0)
    serial = jax.jit(lambda s: _trial_group(s, hub, seed, cfg))(st)
    passes = jax.jit(lambda s: _trial_group_passes(s, hub, seed, cfg))(st)
    _assert_leaves_equal(serial, passes)
    assert int(passes.n_accept) == 0
    assert int(passes.n_trials) == cfg.c
    assert int(passes.n_passes) == 3


def test_padding_group_runs_no_pass():
    cfg = _cfg(c=4)
    st = _star(cfg, 1)
    group = jax.jit(lambda s, u: _trial_group_passes(s, u, jnp.uint32(7),
                                                     cfg))
    for u in (-1, 5):                   # padding, and a node never seen
        out = group(st, jnp.int32(u))
        assert int(out.n_passes) == 0
        _assert_leaves_equal(st, out)
