"""The traffic generators: a seed gives its trace exactly, sizes do not
depend on the seed, and lengths keep to each mix file's clips."""
from __future__ import annotations

import glob
import json
import os

import numpy as np
import pytest

import cells

MIXES = sorted(glob.glob(os.path.join(cells.HERE, "mixes", "*.json")))


def _gen(mix):
    return cells.load_module(os.path.join(cells.HERE, "generators",
                                          f"{mix['generator']}.py"),
                             f"gen_{mix['generator']}")


def _requests(plan):
    if plan["kind"] == "open":
        return plan["requests"]
    return [r for c in plan["clients"] for r in c]


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_seed_reproduces_trace(path):
    mix = json.load(open(path))
    a = _gen(mix).make(mix, seed=2**33 + 7, vocab=151936, slots=16)
    b = _gen(mix).make(mix, seed=2**33 + 7, vocab=151936, slots=16)
    c = _gen(mix).make(mix, seed=12, vocab=151936, slots=16)
    ra, rb, rc = _requests(a), _requests(b), _requests(c)
    assert len(ra) == len(rb) == len(rc) > 0
    for x, y, z in zip(ra, rb, rc):
        assert np.array_equal(x["prompt"], y["prompt"])
        assert x["max_new"] == y["max_new"] == z["max_new"]
        assert len(x["prompt"]) == len(z["prompt"])
        assert x.get("due") == y.get("due") == z.get("due")
    assert any(not np.array_equal(x["prompt"], z["prompt"])
               for x, z in zip(ra, rc))


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_lengths_within_clips(path):
    mix = json.load(open(path))
    reqs = _requests(_gen(mix).make(mix, seed=3, vocab=50280, slots=16))
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_new"] for r in reqs])
    for arr, spec in ((p, mix["prompt"]), (o, mix["output"])):
        if "fixed" in spec:
            assert (arr == spec["fixed"]).all()
        else:
            assert arr.min() >= spec["min"] and arr.max() <= spec["max"]
            assert abs(np.median(arr) - spec["median"]) < 0.2 * spec["median"]
    assert max(int(r["prompt"].max()) for r in reqs) < 50280


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_closed_clients_start_on_token_counts(path):
    """A closed loop's clients start after counts of streamed tokens, the
    same for every seed: no start hangs on the clock."""
    mix = json.load(open(path))
    if mix["generator"] != "closed":
        pytest.skip("open loop")
    a = _gen(mix).make(mix, seed=2**31 + 5, vocab=151936, slots=8)
    b = _gen(mix).make(mix, seed=3, vocab=151936, slots=8)
    assert a["start_tokens"] == b["start_tokens"] == [
        i * mix["stagger_tokens"] for i in range(len(a["clients"]))]
    assert "start" not in a
