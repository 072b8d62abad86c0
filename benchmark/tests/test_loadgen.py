"""The load generator gives the same schedule for the same seed, and the same
multiset of work in another order for another seed. Run by hand:
``python -m pytest benchmark/tests -q`` (not part of tier-1)."""

import json
from pathlib import Path

import pytest

from benchmark import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def key(p):
    return (p.rid, p.prompt_tokens, p.max_tokens, p.text_seed)


@pytest.mark.parametrize("mix_name", MIXES)
def test_same_seed_same_schedule(mix_name):
    mix = json.loads((TRAFFIC / f"{mix_name}.json").read_text())
    a, b = (loadgen.build_schedule(mix, 2**31 + 7)["items"] for _ in range(2))
    assert [key(p) for p in a] == [key(p) for p in b]
    assert loadgen.prompt_text(a[3], 1) == loadgen.prompt_text(b[3], 1)
    assert len(loadgen.prompt_text(a[3], 1)) == a[3].prompt_tokens - 1


@pytest.mark.parametrize("mix_name", MIXES)
def test_other_seed_same_work_permuted_within_blocks(mix_name):
    mix = json.loads((TRAFFIC / f"{mix_name}.json").read_text())
    a, b = (loadgen.build_schedule(mix, s)["items"] for s in (11, 12))
    assert len(a) == len(b) == mix["cycle"]
    for field in ("prompt_tokens", "max_tokens"):
        va, vb = ([getattr(p, field) for p in x] for x in (a, b))
        assert va != vb
        for i in range(0, len(va), loadgen.SEED_BLOCK):     # one pattern
            assert (sorted(va[i:i + loadgen.SEED_BLOCK])
                    == sorted(vb[i:i + loadgen.SEED_BLOCK]))
    assert [p.text_seed for p in a] != [p.text_seed for p in b]


def test_sizes_are_the_quantiles_of_the_stated_distribution():
    uni = loadgen.quantile_sizes({"dist": "uniform", "min": 64, "max": 256}, 256)
    assert min(uni) == 64 and max(uni) == 256 and uni == sorted(uni)
    assert abs(sum(uni) / len(uni) - 160) < 1
    logn = loadgen.quantile_sizes({"dist": "lognormal", "median": 192,
                                   "sigma": 0.8, "min": 32, "max": 1536}, 255)
    assert logn[127] == 192 and min(logn) >= 32 and max(logn) <= 1536
    with pytest.raises(ValueError):
        loadgen.build_schedule({"kind": "open_poisson"}, 1)
