"""The traffic generator: seeded, and true to its mix file."""

import os
import sys
from collections import Counter

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import generator  # noqa: E402

MIXES = ("mixed", "longtail", "short")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_documents(name):
    mix = generator.load_mix(generator.mix_path(name))
    a = generator.block_docs(mix, 2**31 + 17, 1)
    b = generator.block_docs(mix, 2**31 + 17, 1)
    c = generator.block_docs(mix, 2**31 + 18, 1)
    assert a == b
    assert a[1] != c[1]
    assert len(set(a[0])) == len(a[0]) == mix["block_docs"]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_sizes(name):
    mix = generator.load_mix(generator.mix_path(name))
    sizes = [sorted(len(t) for t in generator.block_docs(mix, seed, 0)[1]) for seed in (1, 2**40)]
    assert sizes[0] == sizes[1]


@pytest.mark.parametrize("name", MIXES)
def test_shares_match_the_file(name):
    mix = generator.load_mix(generator.mix_path(name))
    n = mix["block_docs"]
    plan = generator.block_plan(mix)
    kinds = Counter(kind for _, _, kind in plan)
    assert abs(kinds[generator.REPEATED] - mix["repeated_line"]["share"] * n) <= 1
    assert abs(kinds[generator.FRAGMENT] - mix["fragment"]["share"] * n) <= 1
    for lang in mix["languages"]:
        got = sum(1 for _, v, _ in plan if v == lang["vocabulary"])
        assert abs(got - lang["share"] * n) <= 3
    fr = mix["fragment"]
    not_fragments = [t for t, _, k in plan if k != generator.FRAGMENT]
    for c in mix["length_classes"]:
        in_class = sum(1 for t in not_fragments if c["min_chars"] <= t < c["max_chars"])
        want = c["share"] * n * (1 - fr["share"])
        assert abs(in_class - want) <= 0.01 * n + 2
    ids, texts = generator.block_docs(mix, 5, 0, plan)
    assert sorted(len(t) for t in texts) == sorted(t for t, _, _ in plan)
