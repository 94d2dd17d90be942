"""Seeded Common-Crawl-like traffic, driven by a mix file.

A mix is ``benchmark/traffic/<name>.json``: length classes, language shares
(each language a word list in ``benchmark/traffic/vocab/<name>.txt``), the
share of repeated-line spam pages and of short fragments, and the block and
row-group sizes.  Adding a mix, or a language, adds data files only.

Every block of ``block_docs`` documents holds the same multiset of
(length, language, kind) slots whatever the seed: class counts are the
shares rounded by largest remainder, lengths are spread evenly over each
class's range, and languages and spam kinds are interleaved evenly over the
slots.  The seed picks the order of the slots and every word, so two seeds
do the same amount of work in a different order, and no document repeats
within a run.

The prose generator is copied from the program's ``utils/synthetic.py``
(``_text``), generalised so that its parameters come from the mix.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(HERE, "traffic")

#: Kinds of document a slot can hold.
PROSE, REPEATED, FRAGMENT = "prose", "repeated_line", "fragment"

SOURCE = "cc-synthetic"


def mix_path(name: str) -> str:
    return os.path.join(TRAFFIC_DIR, name + ".json")


def load_mix(path: str) -> Dict:
    """The mix in ``path`` with its vocabularies loaded; raises on a bad
    file."""
    with open(path, encoding="utf-8") as f:
        mix = json.load(f)
    name = os.path.splitext(os.path.basename(path))[0]
    for key in ("block_docs", "row_group_docs", "length_classes", "languages",
                "repeated_line", "fragment", "prose"):
        if key not in mix:
            raise ValueError(f"traffic mix {name!r} has no {key!r}")
    vocab = {}
    for lang in mix["languages"]:
        path = os.path.join(TRAFFIC_DIR, "vocab", lang["vocabulary"] + ".txt")
        with open(path, encoding="utf-8") as f:
            vocab[lang["vocabulary"]] = f.read().split()
    mix["vocab"] = vocab
    mix["name"] = name
    return mix


def _largest_remainder(shares: Sequence[float], total: int) -> List[int]:
    """Whole counts summing to ``total`` in proportion to ``shares``."""
    s = np.asarray(shares, dtype=np.float64)
    exact = s / s.sum() * total
    counts = np.floor(exact).astype(np.int64)
    short = total - int(counts.sum())
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:short]] += 1
    return [int(c) for c in counts]


def _interleave(shares: Sequence[float], n: int) -> List[int]:
    """``n`` labels in proportion to ``shares``, spread evenly (each label
    is the one furthest behind its share so far)."""
    s = np.asarray(shares, dtype=np.float64)
    s = s / s.sum()
    acc = np.zeros_like(s)
    out = []
    for _ in range(n):
        acc += s
        k = int(np.argmax(acc))
        acc[k] -= 1.0
        out.append(k)
    return out


def block_plan(mix: Dict) -> List[Tuple[int, str, str]]:
    """The (target chars, vocabulary, kind) slots of one block, in a fixed
    order that does not depend on the seed."""
    n = int(mix["block_docs"])
    classes = mix["length_classes"]
    counts = _largest_remainder([c["share"] for c in classes], n)
    lengths: List[int] = []
    for c, k in zip(classes, counts):
        lo, hi = int(c["min_chars"]), int(c["max_chars"])
        lengths.extend(lo + int((i + 0.5) * (hi - lo) / k) for i in range(k))
    rep, frag = mix["repeated_line"]["share"], mix["fragment"]["share"]
    kinds = _interleave([1.0 - rep - frag, rep, frag], n)
    kind_names = (PROSE, REPEATED, FRAGMENT)
    fr = mix["fragment"]
    n_frag = kinds.count(2)
    frag_lengths = iter(
        int(fr["min_chars"]) + int((i + 0.5) * (fr["max_chars"] - fr["min_chars"]) / max(n_frag, 1))
        for i in range(n_frag)
    )
    langs = mix["languages"]
    lang_of = {}
    for kind in range(3):
        slots = [i for i in range(n) if kinds[i] == kind]
        for i, li in zip(slots, _interleave([l["share"] for l in langs], len(slots))):
            lang_of[i] = langs[li]["vocabulary"]
    plan = []
    for i in range(n):
        kind = kind_names[kinds[i]]
        target = next(frag_lengths) if kind == FRAGMENT else lengths[i]
        plan.append((target, lang_of[i], kind))
    return plan


def _text(rng: np.random.Generator, words: List[str], target: int, prose: Dict) -> str:
    """Web-like prose of exactly ``target`` chars: sentences of a drawn
    number of words, a drawn number of sentences per line."""
    n_words = target // 4 + 4
    idx = rng.integers(0, len(words), size=n_words)
    s_lo, s_hi = prose["sentence_words"]
    l_lo, l_hi = prose["sentences_per_line"]
    sent_lens = rng.integers(s_lo, s_hi + 1, size=n_words // s_lo + 1)
    line_lens = rng.integers(l_lo, l_hi + 1, size=len(sent_lens))
    toks = [words[i] for i in idx]
    lines, cur, pos, s = [], [], 0, 0
    while pos < n_words:
        k = int(sent_lens[s])
        sent = " ".join(toks[pos : pos + k])
        cur.append(sent[:1].upper() + sent[1:] + ".")
        pos += k
        if len(cur) >= line_lens[s]:
            lines.append(" ".join(cur))
            cur = []
        s += 1
    if cur:
        lines.append(" ".join(cur))
    return "\n".join(lines)[:target]


def _rng(seed: int, block: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), block])


def block_docs(mix: Dict, seed: int, block: int, plan=None) -> Tuple[List[str], List[str]]:
    """(ids, texts) of block ``block`` of the stream for ``seed``."""
    plan = plan if plan is not None else block_plan(mix)
    rng = _rng(seed, block)
    order = rng.permutation(len(plan))
    line = mix["repeated_line"]["line"] + "\n"
    ids, texts = [], []
    for j, slot in enumerate(order):
        target, vocab, kind = plan[slot]
        if kind == REPEATED:
            text = (line * (target // len(line) + 1))[:target]
        else:
            text = _text(rng, mix["vocab"][vocab], max(target, 60), mix["prose"])[:target]
        ids.append(f"cc-{seed}-{block:05d}-{j:05d}")
        texts.append(text)
    return ids, texts


def shard_path(out_dir: str, block: int) -> str:
    return os.path.join(out_dir, f"shard-{block:05d}.parquet")


def write_shard(path: str, ids: List[str], texts: List[str], row_group_docs: int) -> None:
    """One block as a Parquet file (``id``, ``source``, ``text``), written
    to a temporary name and renamed, so a reader never sees half a file."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    table = pa.table({"id": ids, "source": [SOURCE] * len(ids), "text": texts})
    tmp = path + ".tmp"
    pq.write_table(table, tmp, row_group_size=row_group_docs)
    os.replace(tmp, path)


def serve(mix_file: str, seed: int, out_dir: str, ahead: int, consumed, stop) -> None:
    """Generator process: write block after block until ``stop`` is set,
    keeping at most ``ahead`` blocks beyond ``consumed`` (a shared int the
    reader advances)."""
    mix = load_mix(mix_file)
    plan = block_plan(mix)
    block = 0
    while not stop.is_set():
        if block - consumed.value >= ahead:
            time.sleep(0.005)
            continue
        ids, texts = block_docs(mix, seed, block, plan)
        write_shard(shard_path(out_dir, block), ids, texts, int(mix["row_group_docs"]))
        block += 1
