"""The traffic generator: same seed, same inputs; every seed the same
work in another order."""
import collections

import pytest

from harness import registry as R
from harness import traffic as T

MIX = {"arrival": "backlog", "requests": 40, "block": 8, "temperature": 1.0,
       "prompt": {"median": 256, "sigma": 0.5, "min": 32, "max": 1024},
       "output": {"median": 128, "sigma": 0.4, "min": 16, "max": 512}}
COMMITTED = R.load_json(R.ROOT, "traffic", "code-backlog-t1")


@pytest.mark.parametrize("mix", [MIX, COMMITTED], ids=["mix", "committed"])
@pytest.mark.parametrize("seed", [0, 7, 2 ** 33 + 5])
def test_same_seed_same_inputs(mix, seed):
    a, b = T.generate(mix, seed, 32256), T.generate(mix, seed, 32256)
    assert [(r.rid, r.prompt, r.max_new) for r in a] == \
        [(r.rid, r.prompt, r.max_new) for r in b]


def test_other_seed_other_tokens_same_work():
    a, b = T.generate(MIX, 1, 32256), T.generate(MIX, 2, 32256)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    for blk in range(0, 40, 8):
        def work(reqs):
            return (collections.Counter(len(r.prompt)
                                        for r in reqs[blk:blk + 8]),
                    collections.Counter(r.max_new for r in reqs[blk:blk + 8]))
        assert work(a) == work(b)
    with pytest.raises(ValueError):
        T.generate(dict(MIX, arrival="poisson"), 1, 32256)


def test_levels_are_clipped_lognormal_quantiles():
    lv = T.levels({"median": 100, "sigma": 1.0, "min": 50, "max": 150}, 4)
    assert lv == sorted(lv) and lv[0] == 50 and lv[-1] == 150
    assert T.levels({"median": 100, "sigma": 0.0, "min": 1, "max": 999},
                    3) == [100, 100, 100]
    assert all(0 <= t < 32256 for r in T.generate(MIX, 3, 32256)
               for t in r.prompt)


def test_warmup_covers_every_prompt_length():
    w = T.warmup(MIX, 5, 32256, max_new=4)
    assert sorted(len(r.prompt) for r in w) == sorted(set(T.levels(
        MIX["prompt"], 8)))
    assert all(r.max_new == 4 and r.rid >= T.WARMUP_RID for r in w)
