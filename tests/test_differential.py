"""Differential engine-vs-reference tests.

The same synthetic FD stream (graph/streams.py) drives the Tier-A
DynamicSummary and the Tier-B BatchedSummarizer side by side; after every
engine batch both must (a) satisfy the phi == |P| + |C+| + |C-| invariant
and (b) decode losslessly back to the exact live edge set.  This is the
standing verification bar for engine changes (ROADMAP open items).
"""
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # container has no hypothesis; deterministic shim
    from repro.testing.proptest import given, settings, strategies as st

from repro.core.engine import BatchedSummarizer, EngineConfig, ShardedSummarizer
from repro.core.reference.dynamic_summary import DynamicSummary
from repro.core.summary import pair_key
from repro.graph.streams import edges_to_fully_dynamic_stream, sbm_edges

from conftest import ground_truth_edges


def _cfg(**kw):
    base = dict(n_cap=256, m_cap=2048, d_cap=48, sn_cap=32, c=8, batch=16,
                escape=0.3)
    base.update(kw)
    return EngineConfig(**base)


@pytest.mark.parametrize("seed", [0, 1])
def test_differential_tier_a_vs_tier_b_batchwise(seed):
    edges = sbm_edges(40, 4, 0.55, 0.04, seed=seed)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.15,
                                           seed=seed + 1)
    cfg = _cfg()
    bs = BatchedSummarizer(cfg)
    ref = DynamicSummary()
    live = set()

    for off in range(0, len(stream), cfg.batch):
        chunk = stream[off:off + cfg.batch]
        bs.process(chunk)
        for (u, v, ins) in chunk:
            e = (min(u, v), max(u, v))
            if ins:
                ref.insert(*e)
                live.add(e)
            else:
                ref.delete(*e)
                live.discard(e)
        tag = f"seed={seed} off={off}"
        # (a) phi invariant in BOTH tiers, after every batch
        ref_mat = ref.materialize()
        assert ref.phi == ref_mat.phi == ref.phi_recomputed(), tag
        eng_mat = bs.materialize()      # also asserts eab vs live edges
        assert bs.phi == eng_mat.phi == bs.phi_recomputed(), tag
        # (b) both decode losslessly to the exact live edge set
        assert ref_mat.decode_edges() == live, tag
        eng_live = {pair_key(bs._ids[u], bs._ids[v]) for (u, v) in live}
        assert eng_mat.decode_edges() == eng_live, tag

    assert live == ground_truth_edges(stream)
    # both tiers end bounded by |E| (phi <= |E| under the optimal encoding)
    assert ref.phi <= len(live)
    assert bs.phi <= len(live)


def test_differential_final_phi_within_band():
    """Tier-B phi lands in a band around Tier-A on the same stream: both are
    randomized greedy searches over the same objective."""
    edges = sbm_edges(48, 4, 0.6, 0.03, seed=5)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.1, seed=6)
    bs = BatchedSummarizer(_cfg(c=12)).run(stream)
    ref = DynamicSummary()
    for (u, v, ins) in stream:
        (ref.insert if ins else ref.delete)(u, v)
    n_live = len(ground_truth_edges(stream))
    assert 0 < bs.phi <= n_live
    assert ref.phi == n_live    # no moves: reference stays at trivial encoding
    assert bs.phi <= ref.phi    # the trial engine may only improve on trivial


@settings(max_examples=5, deadline=None)
@given(st.integers(0, 9999), st.integers(2, 4))
def test_predicated_step_matches_reference_batchwise_property(seed, deg):
    """Property (PR 5): for any stream seed/density, the PREDICATED trial
    engine — Alg. 1 as cond-free masked data flow — satisfies the Tier-A
    reference contract batchwise: the phi invariant holds in both tiers
    after every batch and both decode losslessly to the exact live edge
    set.  One fixed config, so every example reuses one compiled step."""
    edges = sbm_edges(28, deg, 0.5, 0.06, seed=seed)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2,
                                           seed=seed + 1)
    cfg = _cfg(n_cap=128, m_cap=1024, batch=8, c=6)
    bs = BatchedSummarizer(cfg)
    ref = DynamicSummary()
    live = set()
    for off in range(0, len(stream), cfg.batch):
        chunk = stream[off:off + cfg.batch]
        bs.process(chunk)
        for (u, v, ins) in chunk:
            e = (min(u, v), max(u, v))
            if ins:
                ref.insert(*e)
                live.add(e)
            else:
                ref.delete(*e)
                live.discard(e)
        tag = f"seed={seed} off={off}"
        ref_mat = ref.materialize()
        assert ref.phi == ref_mat.phi == ref.phi_recomputed(), tag
        eng_mat = bs.materialize()      # also asserts eab vs live edges
        assert bs.phi == eng_mat.phi == bs.phi_recomputed(), tag
        assert ref_mat.decode_edges() == live, tag
        eng_live = {pair_key(bs._ids[u], bs._ids[v]) for (u, v) in live}
        assert eng_mat.decode_edges() == eng_live, tag
    assert live == ground_truth_edges(stream)


def _count_primitives(jaxpr, name: str) -> int:
    """Occurrences of a primitive at any nesting depth (incl. inside
    pallas_call kernel jaxprs, which live in eqn params)."""
    import jax.extend.core as jc

    def subjaxprs(val):
        if isinstance(val, jc.ClosedJaxpr):
            return [val.jaxpr]
        if isinstance(val, jc.Jaxpr):
            return [val]
        if isinstance(val, (list, tuple)):
            return [s for v in val for s in subjaxprs(v)]
        return []

    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for val in eqn.params.values():
            for sub in subjaxprs(val):
                n += _count_primitives(sub, name)
    return n


@pytest.mark.parametrize("trial_backend", ["xla", "pallas"])
def test_trial_engine_compiles_cond_free(trial_backend):
    """Acceptance tripwire (PR 5, extended to the probe-kernel backend in
    PR 6): the lowered engine step must contain no ``cond`` primitive at
    any nesting depth — predication (masked writes + 0/1-trip while
    regions) is the only control flow besides scan/while, under BOTH
    probe backends and both ``dense`` lowerings.  The pallas path must
    actually contain probe-kernel launches; the xla path must contain
    none."""
    import numpy as np

    import jax
    from repro.core.engine.hashtable import trial_backend_scope
    from repro.core.engine.state import new_state
    from repro.core.engine.trial import step_fn

    cfg = _cfg(n_cap=64, m_cap=256, d_cap=8, sn_cap=8, c=3, batch=4)

    u = np.zeros(4, np.int32)
    for dense in (False, True):
        with trial_backend_scope(trial_backend):
            closed = jax.make_jaxpr(
                lambda s, a, b, c: step_fn(s, a, b, c, cfg, dense))(
                    new_state(cfg), u, u + 1, u > 0)
        tag = f"backend={trial_backend} dense={dense}"
        assert _count_primitives(closed.jaxpr, "cond") == 0, \
            f"cond found ({tag})"
        n_pallas = _count_primitives(closed.jaxpr, "pallas_call")
        if trial_backend == "pallas":
            assert n_pallas > 0, f"no probe kernel launch traced ({tag})"
        else:
            assert n_pallas == 0, f"unexpected pallas_call ({tag})"


def test_policy_matrix_compiles_cond_free():
    """Acceptance tripwire (PR 8): EVERY proposal x objective x commit
    triple lowers with zero ``cond`` primitives at any nesting depth, under
    both ``dense`` lowerings — policy dispatch is trace-time Python, so no
    variant may smuggle data-dependent control flow into the step."""
    import itertools

    import numpy as np

    import jax
    from repro.core.engine.state import (COMMIT_RULES, OBJECTIVES, PROPOSALS,
                                         new_state)
    from repro.core.engine.trial import step_fn

    u = np.zeros(4, np.int32)
    for prop, obj, com in itertools.product(PROPOSALS, OBJECTIVES,
                                            COMMIT_RULES):
        cfg = _cfg(n_cap=64, m_cap=256, d_cap=8, sn_cap=8, c=3, batch=4,
                   proposal=prop, objective=obj, commit=com,
                   commit_margin=1, weight_levels=3)
        for dense in (False, True):
            closed = jax.make_jaxpr(
                lambda s, a, b, c: step_fn(s, a, b, c, cfg, dense))(
                    new_state(cfg), u, u + 1, u > 0)
            tag = f"triple=({prop},{obj},{com}) dense={dense}"
            assert _count_primitives(closed.jaxpr, "cond") == 0, \
                f"cond found ({tag})"


def test_magsdm_engine_matches_reference_batchwise():
    """PR 8 variant bar, proposal="magsdm": the engine's modal-supernode
    candidate scheme runs against its own host reference (MoSSoMags, WITH
    trials) on an identical FD stream; after every batch both tiers satisfy
    the phi invariant and decode losslessly to the exact live edge set."""
    from repro.core.reference import MoSSoMags

    edges = sbm_edges(40, 4, 0.55, 0.04, seed=31)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.15, seed=32)
    cfg = _cfg(proposal="magsdm")
    bs = BatchedSummarizer(cfg)
    algo = MoSSoMags(seed=0, c=24)
    live = set()

    for off in range(0, len(stream), cfg.batch):
        chunk = stream[off:off + cfg.batch]
        bs.process(chunk)
        for (u, v, ins) in chunk:
            algo.process(u, v, ins)
            e = (min(u, v), max(u, v))
            (live.add if ins else live.discard)(e)
        tag = f"off={off}"
        ref_mat = algo.s.materialize()
        assert algo.s.phi == ref_mat.phi == algo.s.phi_recomputed(), tag
        eng_mat = bs.materialize()      # also asserts eab vs live edges
        assert bs.phi == eng_mat.phi == bs.phi_recomputed(), tag
        assert ref_mat.decode_edges() == live, tag
        eng_live = {pair_key(bs._ids[u], bs._ids[v]) for (u, v) in live}
        assert eng_mat.decode_edges() == eng_live, tag

    assert live == ground_truth_edges(stream)
    assert bs.phi <= len(live) and algo.s.phi <= len(live)
    assert int(bs.state.n_accept) > 0      # the variant actually moved nodes


def test_weighted_engine_matches_reference_batchwise():
    """PR 8 variant bar, objective="weighted": the engine (hashed weights
    over DENSE interned ids) runs against its own host reference — a
    WeightedDynamicSummary weighing caller labels through the intern map,
    so both tiers price the same node identically.  After every batch both
    satisfy the weighted phi invariant (live phi == materialized
    ``phi_weighted`` == refolded pair table) and decode losslessly: weights
    move encoding choices, never the edge set."""
    from repro.core.reference import WeightedDynamicSummary, host_node_weight

    levels = 3
    edges = sbm_edges(40, 4, 0.55, 0.04, seed=33)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.15, seed=34)
    # the engine interns labels in first-appearance order; replaying the
    # stream reproduces the dense-id map before the engine exists
    interned = {}
    for (u, v, _) in stream:
        for x in (u, v):
            interned.setdefault(x, len(interned))
    w_label = lambda lab: host_node_weight(interned[lab], levels)
    w_dense = lambda d: host_node_weight(d, levels)

    cfg = _cfg(objective="weighted", weight_levels=levels)
    bs = BatchedSummarizer(cfg)
    ref = WeightedDynamicSummary(weight_levels=levels, node_weight=w_label)
    live = set()

    for off in range(0, len(stream), cfg.batch):
        chunk = stream[off:off + cfg.batch]
        bs.process(chunk)
        for (u, v, ins) in chunk:
            e = (min(u, v), max(u, v))
            if ins:
                ref.insert(*e)
                live.add(e)
            else:
                ref.delete(*e)
                live.discard(e)
        tag = f"off={off}"
        ref_mat = ref.materialize()
        assert ref.phi == ref_mat.phi_weighted(ref._w) \
            == ref.phi_recomputed(), tag
        eng_mat = bs.materialize()  # asserts eab vs live edges + weab drift
        assert bs.phi == eng_mat.phi_weighted(w_dense) \
            == bs.phi_recomputed(), tag
        assert ref_mat.decode_edges() == live, tag
        eng_live = {pair_key(bs._ids[u], bs._ids[v]) for (u, v) in live}
        assert eng_mat.decode_edges() == eng_live, tag

    assert live == ground_truth_edges(stream)
    # the precomputed intern replay really is the engine's dense-id map —
    # the premise that made w_label and w_dense price nodes identically
    assert interned == bs._ids


def test_query_vs_decode_under_nondefault_policies():
    """The query path is policy-INDEPENDENT by construction: answers always
    equal the listed edge set, whatever produced it.  Pin that under the
    fully non-default triple — after every batch, neighbors/degree/has_edge
    from the compressed state equal the decode oracle."""
    import itertools

    cfg = _cfg(n_cap=128, m_cap=1024, batch=16, c=6, proposal="magsdm",
               objective="weighted", weight_levels=3, commit="threshold",
               commit_margin=0)
    edges = sbm_edges(36, 4, 0.55, 0.05, seed=35)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=36)
    bs = BatchedSummarizer(cfg)

    for off in range(0, len(stream), cfg.batch):
        bs.process(stream[off:off + cfg.batch])
        tag = f"off={off}"
        q = bs.query()
        dec = {pair_key(bs._rev[a], bs._rev[b])
               for (a, b) in bs.materialize().decode_edges()}
        adj = _adj_from_edges(dec)
        labs = q.seen_labels()
        for lab, nb, dg in zip(labs, q.neighbors_batch(labs),
                               q.degree_batch(labs)):
            want = adj.get(lab, set())
            assert nb == want, f"neighbors({lab}) {tag}"
            assert dg == len(want), f"degree({lab}) {tag}"
        pairs = list(itertools.combinations(labs[:12], 2))
        for (u, v), got in zip(pairs, q.has_edge_batch(pairs)):
            assert got == (pair_key(u, v) in dec), f"has_edge({u},{v}) {tag}"


def test_pallas_step_bitwise_equals_xla_step():
    """The probe-kernel backend is not 'close': on an identical stream the
    pallas- and xla-backed engines must end in leaf-bitwise IDENTICAL
    states — the probe sequence is the on-device table layout, so any
    divergence is corruption, not noise."""
    import jax
    import numpy as np

    edges = sbm_edges(30, 3, 0.5, 0.06, seed=21)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=22)
    cfg = _cfg(n_cap=128, m_cap=1024, batch=8, c=6)
    bx = BatchedSummarizer(cfg, trial_backend="xla").run(stream)
    bp = BatchedSummarizer(cfg, trial_backend="pallas").run(stream)
    assert bx.phi == bp.phi
    for lx, lp in zip(jax.tree.leaves(bx.state), jax.tree.leaves(bp.state)):
        np.testing.assert_array_equal(np.asarray(lx), np.asarray(lp))


@settings(max_examples=3, deadline=None)
@given(st.integers(0, 9999), st.integers(2, 4))
def test_pallas_step_matches_reference_batchwise_property(seed, deg):
    """Property (PR 6): the PALLAS-backed trial engine — batched probes
    fused into ``kernels/ht_probe.py`` launches, interpret mode on CPU —
    satisfies the same Tier-A reference contract batchwise as the
    predicated XLA engine: the phi invariant holds in both tiers after
    every batch and both decode losslessly to the exact live edge set.
    One fixed config, so every example reuses one compiled step."""
    edges = sbm_edges(28, deg, 0.5, 0.06, seed=seed)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2,
                                           seed=seed + 1)
    cfg = _cfg(n_cap=128, m_cap=1024, batch=8, c=6)
    bs = BatchedSummarizer(cfg, trial_backend="pallas")
    ref = DynamicSummary()
    live = set()
    for off in range(0, len(stream), cfg.batch):
        chunk = stream[off:off + cfg.batch]
        bs.process(chunk)
        for (u, v, ins) in chunk:
            e = (min(u, v), max(u, v))
            if ins:
                ref.insert(*e)
                live.add(e)
            else:
                ref.delete(*e)
                live.discard(e)
        tag = f"seed={seed} off={off}"
        ref_mat = ref.materialize()
        assert ref.phi == ref_mat.phi == ref.phi_recomputed(), tag
        eng_mat = bs.materialize()      # also asserts eab vs live edges
        assert bs.phi == eng_mat.phi == bs.phi_recomputed(), tag
        assert ref_mat.decode_edges() == live, tag
        eng_live = {pair_key(bs._ids[u], bs._ids[v]) for (u, v) in live}
        assert eng_mat.decode_edges() == eng_live, tag
    assert live == ground_truth_edges(stream)


def _adj_from_edges(edge_set):
    adj = {}
    for (u, v) in edge_set:
        adj.setdefault(u, set()).add(v)
        adj.setdefault(v, set()).add(u)
    return adj


@pytest.mark.parametrize("trial_backend", ["xla", "pallas"])
def test_query_vs_decode_differential_batched(trial_backend):
    """Standing-bar extension (PR 7): on an FD stream, after EVERY batch,
    neighbors/degree/has_edge answered from the compressed engine state —
    membership -> superedge scan -> correction patch-up, no decompression
    — must exactly equal answers computed from ``decode_edges()``, and a
    third, independent host walk of the materialized output (the
    :class:`SummaryQueryOracle`) must agree with both; under both probe
    backends."""
    import itertools

    from repro.core.reference import SummaryQueryOracle

    edges = sbm_edges(36, 4, 0.55, 0.05, seed=3)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=4)
    cfg = _cfg(n_cap=128, m_cap=1024, batch=16, c=6)
    bs = BatchedSummarizer(cfg, trial_backend=trial_backend)

    for off in range(0, len(stream), cfg.batch):
        bs.process(stream[off:off + cfg.batch])
        tag = f"backend={trial_backend} off={off}"
        q = bs.query()
        mat = bs.materialize()
        # the decode oracle, mapped back to caller labels
        dec = {pair_key(bs._rev[a], bs._rev[b])
               for (a, b) in mat.decode_edges()}
        adj = _adj_from_edges(dec)
        oracle = SummaryQueryOracle(mat)       # host Lemma-1 walk, eng ids
        labs = q.seen_labels()
        for lab, nb, dg in zip(labs, q.neighbors_batch(labs),
                               q.degree_batch(labs)):
            want = adj.get(lab, set())
            assert nb == want, f"neighbors({lab}) {tag}"
            assert dg == len(want), f"degree({lab}) {tag}"
            assert oracle.neighbors(bs._ids[lab]) == \
                {bs._ids[w] for w in want}, f"oracle({lab}) {tag}"
        pairs = list(itertools.combinations(labs[:12], 2))
        for (u, v), got in zip(pairs, q.has_edge_batch(pairs)):
            want = pair_key(u, v) in dec
            assert got == want, f"has_edge({u},{v}) {tag}"
            assert oracle.has_edge(bs._ids[u], bs._ids[v]) == want, tag


def test_query_vs_decode_differential_sharded():
    """Standing-bar extension (PR 7), sharded tier: after every routed
    chunk the flushed snapshot's query answers must exactly equal the
    union-of-parts ``decode_edges()`` (both in caller-label space), and
    the host oracle over the merged output must agree.  ``replica_exec``
    and the probe backend come from the environment, so the CI
    router-stress matrix runs this under all four combinations."""
    import itertools

    from repro.core.reference import SummaryQueryOracle

    edges = sbm_edges(40, 4, 0.5, 0.05, seed=13)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=14)
    cfg = _cfg(n_cap=128, m_cap=1024, batch=8)
    ss = ShardedSummarizer(cfg, n_shards=2, router_chunk=64)

    for off in range(0, len(stream), ss.router_chunk):
        ss.process(stream[off:off + ss.router_chunk])
        tag = f"off={off}"
        mat = ss.materialize()     # sync point: flushes the pipeline
        q = ss.query()             # snapshot == the flushed epoch
        assert q.epoch == ss.flush_epoch
        dec = mat.decode_edges()   # caller-label pairs (union of parts)
        adj = _adj_from_edges(dec)
        oracle = SummaryQueryOracle(mat)
        labs = q.seen_labels()
        for lab, nb, dg in zip(labs, q.neighbors_batch(labs),
                               q.degree_batch(labs)):
            want = adj.get(lab, set())
            assert nb == want, f"neighbors({lab}) {tag}"
            assert dg == len(want), f"degree({lab}) {tag}"
            assert oracle.neighbors(lab) == want, f"oracle({lab}) {tag}"
        pairs = list(itertools.combinations(labs[:12], 2))
        for (u, v), got in zip(pairs, q.has_edge_batch(pairs)):
            want = pair_key(u, v) in dec
            assert got == want, f"has_edge({u},{v}) {tag}"
            assert oracle.has_edge(u, v) == want, tag


def test_sharded_summarizer_matches_ground_truth_single_device():
    """ShardedSummarizer with 2 logical partitions on however many devices
    the test process has (1 in tier-1 runs): lossless union decode, phi
    additivity, and agreement of the invariants per shard."""
    edges = sbm_edges(44, 4, 0.5, 0.05, seed=11)
    stream = edges_to_fully_dynamic_stream(edges, delete_prob=0.2, seed=12)
    cfg = _cfg(n_cap=128, m_cap=1024, batch=8)
    ss = ShardedSummarizer(cfg, n_shards=2)
    assert ss.n_shards == 2
    ss.run(stream)

    truth = ground_truth_edges(stream)
    assert ss.live_edges() == truth
    out = ss.materialize()
    assert len(out.shards) == 2
    assert out.decode_edges() == truth
    assert out.phi == ss.phi == sum(ss.shard_phis()) == ss.phi_recomputed()
    assert ss.num_edges == len(truth)
    assert 0 < ss.phi <= len(truth)
    # both partitions actually carried load
    assert all(int(n) > 0 for n in
               __import__("numpy").asarray(ss.state.num_edges))
