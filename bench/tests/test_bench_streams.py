"""The benchmark's stream generators: faithful to the program's own
constructions, sound, and a pure function of the seed."""
import json
import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchlib import fd, registry  # noqa: E402


SEEDS = [0, 7, 2 ** 31 + 11]


@pytest.mark.parametrize("seed", SEEDS)
def test_fully_dynamic_matches_the_program_construction(seed):
    from repro.graph.streams import (barabasi_albert_edges,
                                     edges_to_fully_dynamic_stream)
    edges = barabasi_albert_edges(300, 4, seed)
    assert fd.ba_grow(300, 4, random.Random(seed)) == edges
    for p in (0.1, 0.5):
        want = edges_to_fully_dynamic_stream(edges, p, seed)
        assert fd.fully_dynamic(edges, p, random.Random(seed)) == want


def _gen(name, **over):
    stream = json.loads((registry.BENCH / "configs" / "ba_fd_1chip.json")
                        .read_text())["stream"]
    return registry.load_module("generators", name), {**stream, **over}


@pytest.mark.parametrize("name,over", [
    ("ba_fd", {"n_nodes": 1000}),
])
@pytest.mark.parametrize("seed", SEEDS)
def test_generator_sound_and_deterministic(name, over, seed):
    mod, params = _gen(name, **over)
    a, b = mod.Stream(params, seed), mod.Stream(params, seed)
    # taken in uneven pieces, to the end
    got = a.take(700) + a.take(2500) + a.take(3)
    assert got == b.take(len(got))
    rest = len(a.changes) - len(got)
    assert a.take(rest + 1) is None
    got += a.take(rest)
    assert a.take(1) is None
    assert fd.validate_stream(got)
    # the whole graph, every edge inserted once (m per node after the
    # first m + 1), and the Sect. 4.1 share of them deleted
    inserts = sum(1 for c in got if c[2])
    assert inserts == (params["n_nodes"] - params["m"] - 1) * params["m"]
    assert 0.05 < (len(got) - inserts) / inserts < 0.15
    other = mod.Stream(params, seed + 1).take(len(got))
    assert other != got
    for u, v, _ in got:
        assert isinstance(u, int) and isinstance(v, int)
        assert 0 <= u < params["n_nodes"] and 0 <= v < params["n_nodes"]
    # every deletion within ``delete_horizon`` insertions of its insertion
    seen, inserted_at = 0, {}
    for u, v, ins in got:
        if ins:
            inserted_at[(u, v)] = seen
            seen += 1
        else:
            assert seen - inserted_at[(u, v)] <= params["delete_horizon"]
