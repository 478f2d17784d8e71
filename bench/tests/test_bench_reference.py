"""The benchmark's plain reference agrees with the program's faithful host
state machine, and its checks notice what they are for."""
import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchlib import fd, reference  # noqa: E402


@pytest.fixture(scope="module")
def summarized():
    """A small fully dynamic BA stream through the faithful MoSSo (Tier A),
    so supernodes really merge."""
    from repro.core.reference import ALGORITHMS
    stream = fd.fully_dynamic(fd.ba_grow(120, 4, random.Random(5)), 0.2,
                              random.Random(5))
    algo = ALGORITHMS["mosso"](seed=0)
    algo.run(stream)
    return stream, algo.s


def test_replay_matches_dynamic_summary(summarized):
    stream, ds = summarized
    out = ds.materialize()
    live = reference.replay(stream)
    assert reference.decode(out.supernodes, out.superedges, out.c_plus,
                            out.c_minus) == live == out.decode_edges()
    assert any(len(m) > 1 for m in out.supernodes.values())
    # the refold of the summary's own partition is its phi
    assert reference.encoding_cost(out.supernodes, live) == ds.phi
    rep = reference.AdjacencyReplay([stream])
    rep.advance(1)
    for u in {x for e in live for x in e}:
        assert rep.neighbors(u) == ds.neighbors(u)


def test_label_hash_is_the_placement_rule():
    from repro.dist import labelhash
    for x in (0, 1, -1, 12345, (1 << 63) - 1, -(1 << 63), 2 ** 40 + 7):
        assert reference.label_hash(x) == labelhash.hash_label(x)
    with pytest.raises(TypeError):
        reference.label_hash("a")


def _parts(out):
    return [(out.supernodes, out.superedges, out.c_plus, out.c_minus)]


def test_check_summary_counts(summarized):
    stream, ds = summarized
    out = ds.materialize()
    got, ref = reference.check_summary(_parts(out), ds.phi, stream, 1)
    assert got == {"edges_missing": 0, "edges_extra": 0, "phi_gap": 0}
    assert ref["refold_phi"] == ds.phi
    assert ref["live_edges"] == ref["max_shard_edges"] == len(
        reference.replay(stream))
    # a dropped deletion, a phi off by one
    extra = [c for c in stream if c[2]]
    got, _ = reference.check_summary(_parts(out), ds.phi + 1, extra, 1)
    assert got["edges_missing"] > 0 and got["phi_gap"] > 0
    # edges on the wrong shard count as missing and extra
    empty = ({}, set(), set(), set())
    got, _ = reference.check_summary(_parts(out) + [empty], ds.phi,
                                     stream, 2)
    assert got["edges_missing"] > 0 and got["edges_extra"] > 0


def test_check_reads():
    chunks = [[(1, 2, True), (2, 3, True)], [(1, 2, False)]]
    ok = [(1, "neighbors", [2], [{1, 3}]), (2, "degree", [1, 2], [0, 1]),
          (2, "has_edge", [(2, 3), (1, 2)], [True, False])]
    assert reference.check_reads(ok, chunks) == {"reads_wrong": 0,
                                                 "reads_checked": 5}
    bad = [(2, "neighbors", [2], [{1, 3}])]      # answered at epoch 1
    assert reference.check_reads(bad, chunks)["reads_wrong"] == 1
