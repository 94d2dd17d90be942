"""The plain reference: its control (float32 where the configurations
state float64) must come out as not correct."""

import os
import sys

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import compare, generator  # noqa: E402
from benchmark.reference import Reference  # noqa: E402
from benchmark.reference import text as T  # noqa: E402

CELLS = (("danish_cc", "mixed"), ("fineweb_en", "longtail"), ("danish_cc", "short"))


def _pipeline(config):
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".yaml"), encoding="utf-8") as f:
        return yaml.safe_load(f)["pipeline"]


@pytest.mark.parametrize("config,mix", CELLS)
def test_control_is_not_correct(config, mix):
    m = generator.load_mix(generator.mix_path(mix))
    ids, texts = generator.block_docs(m, 31337, 0)
    ids, texts = ids[:150], texts[:150]
    ref = Reference(_pipeline(config), "float64")
    ctrl = Reference(_pipeline(config), "float32")
    expected = [ref(t) for t in texts]
    bad, notes = compare.mismatches(ids, expected, {i: [ctrl(t)] for i, t in zip(ids, texts)})
    assert bad >= len(ids) // 2, notes
    same, _ = compare.mismatches(ids, expected, {i: [ref(t)] for i, t in zip(ids, texts)})
    assert same == 0


def test_mismatches_counts_missing_duplicate_and_extra():
    exp = [("kept", "a", {}), ("excluded", "b", {"x": "1"})]
    got = {"1": [("kept", "a", {})], "2": [("excluded", "b", {"x": "1"})] * 2, "9": [("kept", "", {})]}
    bad, notes = compare.mismatches(["1", "2"], exp, got)
    assert bad == 2 and len(notes) == 2
    bad, _ = compare.mismatches(["1", "2"], exp, {"1": got["1"]})
    assert bad == 1


@pytest.mark.parametrize("text,words", [
    ("Det er en god dag.", ["Det", "er", "en", "god", "dag"]),
    ("3.5 kr, e.g. x-y", ["3.5", "kr", "e.g", "x", "y"]),
    ("áb __ ©", ["áb", "©"]),
])
def test_words(text, words):
    assert T.split_into_words(text) == words


def test_sentences():
    assert T.split_into_sentences("Hej. Det er godt! 3.5 er et tal. slut") == [
        "Hej.", "Det er godt!", "3.5 er et tal. slut"]
