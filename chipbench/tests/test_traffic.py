"""The traffic generator: seeded, clipped, and the same work on every
seed."""
import pytest

import harness
import traffic

CELLS = [w["name"] for w in harness.load_manifest()["workloads"]
         if "traffic" in harness.load_json(
             harness.HERE / "cells" / f"{w['name']}.json")]


def spec(cell):
    return harness.load_json(harness.HERE / "cells" / f"{cell}.json")[
        "traffic"]


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_job(cell):
    tr = dict(spec(cell), requests=64)
    assert traffic.job(tr, 2**33 + 5, 1000) == traffic.job(tr, 2**33 + 5,
                                                           1000)
    assert traffic.job(tr, 1, 1000) != traffic.job(tr, 2, 1000)


@pytest.mark.parametrize("cell", CELLS)
def test_lengths_inside_clips_and_rounded(cell):
    tr = spec(cell)
    for part in ("prompt", "output"):
        s = tr[part]
        ls = traffic.lengths(s, tr["requests"])
        assert min(ls) >= s["min"] and max(ls) <= s["max"]
        assert all(x % s.get("round", 1) == 0 for x in ls)
    job = traffic.job(dict(tr, requests=32), 9, 50)
    assert all(0 <= t < 50 for p, _ in job for t in p)


@pytest.mark.parametrize("cell", CELLS)
def test_every_seed_same_sizes(cell):
    tr = dict(spec(cell), requests=128)
    a = traffic.job(tr, 3, 100)
    b = traffic.job(tr, 2**31 + 77, 100)
    assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]


def test_medians_follow_the_spec():
    tr = spec("granite-3-8b-pp2.chat")
    ls = sorted(traffic.lengths(tr["prompt"], 1001))
    assert ls[500] == 384                 # median 384, a multiple of 64
    os_ = sorted(traffic.lengths(tr["output"], 1001))
    assert os_[500] == 256
