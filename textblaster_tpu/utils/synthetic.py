"""Seeded synthetic corpora (no network): the word lists the benchmark's
generators draw from, and a Common-Crawl-like Parquet shard for the chip
smoke run (``chip_smoke.py``).

The shard is built in bulk with numpy — one draw per document for its
shape, one array of word indices per document — so 16k documents and
~50 MB of text take seconds, not minutes.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

__all__ = ["DANISH_WORDS", "ENGLISH_WORDS", "cc_like_texts", "write_cc_like_shard"]

DANISH_WORDS = (
    "det er en god dag og vi skal ud at gå tur i skoven solen skinner over "
    "byen der mange mennesker på gaden som arbejde nu efter turen vil gerne "
    "drikke kop kaffe spise lidt brød hjemme haven igen bliver dejlig "
    "eftermiddag fordi vejret så godt børnene kommer fra skole aftenen lave "
    "mad sammen se film stuen før seng huset store vinduer mod syd lyset "
    "falder ind om morgenen når står op tidligt cyklen til byen langs vandet "
    "møder venner torvet taler længe gamle dage planlægger næste rejse sydpå"
).split()

ENGLISH_WORDS = (
    "the quick brown fox jumps over lazy dog and runs through green fields "
    "near river where people walk their dogs every morning before work they "
    "stop for coffee at small cafe on corner watching boats pass slowly under "
    "old stone bridge while children play in park across street from market"
).split()

#: Length classes of the shard: (share of documents, min chars, max chars).
#: Long-tailed like a Common Crawl WET shard; the longest class stays under
#: the 32768-lane bucket so every document has a device program.
LENGTH_CLASSES = ((0.85, 60, 2_000), (0.12, 2_000, 8_000), (0.03, 8_000, 30_000))

#: Share of Danish documents (the rest are English, which langid drops).
DANISH_SHARE = 0.7


def _text(rng: np.random.Generator, words: List[str], target: int) -> str:
    """Web-like prose of about ``target`` chars: sentences of 4-17 words,
    1-4 sentences per line."""
    n_words = max(4, target // 6)
    idx = rng.integers(0, len(words), size=n_words)
    sent_lens = rng.integers(4, 18, size=n_words // 4 + 1)
    line_lens = rng.integers(1, 5, size=len(sent_lens))
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
    return "\n".join(lines)[: max(target, 60)]


def cc_like_texts(n_docs: int, seed: int) -> List[str]:
    """``n_docs`` seeded documents: a Danish/English mix over the length
    classes above, plus the spam a crawl carries — 4% repeated-line pages
    and 3% truncated fragments — so every filter both keeps and drops."""
    rng = np.random.default_rng(seed)
    shares = np.array([c[0] for c in LENGTH_CLASSES])
    cls = rng.choice(len(LENGTH_CLASSES), size=n_docs, p=shares / shares.sum())
    u = rng.random(size=(n_docs, 3))
    texts = []
    for i in range(n_docs):
        _, lo, hi = LENGTH_CLASSES[cls[i]]
        target = int(lo + u[i, 0] * (hi - lo))
        words = DANISH_WORDS if u[i, 1] < DANISH_SHARE else ENGLISH_WORDS
        kind = u[i, 2]
        if kind > 0.96:
            line = "Samme linje her igen og igen.\n"
            text = (line * (target // len(line) + 1))[:target]
        elif kind > 0.93:
            text = _text(rng, words, 200)[: 10 + int(kind * 1000) % 50]
        else:
            text = _text(rng, words, target)
        texts.append(text)
    return texts


def write_cc_like_shard(
    path: str, n_docs: int, seed: int, row_groups: int = 4
) -> Tuple[int, int]:
    """Write the shard as Parquet (``id``, ``text``) in ``row_groups`` row
    groups.  Returns (documents, UTF-8 bytes of text)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    texts = cc_like_texts(n_docs, seed)
    table = pa.table(
        {"id": [f"cc-{seed}-{i:06d}" for i in range(n_docs)], "text": texts}
    )
    pq.write_table(table, path, row_group_size=-(-n_docs // row_groups))
    return n_docs, sum(len(t.encode("utf-8")) for t in texts)
