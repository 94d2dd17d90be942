"""Text primitives of the plain reference, one character at a time.

Semantics of the upstream filters' text helpers (TextBlaster
``src/utils/text.rs``) as this system defines them:

* words: maximal runs of letters, digits and ``_``, joined across a
  mid-word character (``:`` ``·`` ``'`` ``’`` ``.`` between letters;
  ``,`` ``;`` ``.`` ``'`` ``’`` between digits); combining marks and format
  characters join the word before them; a run made only of PUNCTUATION is
  no word; any other character that is neither space nor PUNCTUATION is a
  word of its own (with the combining marks after it);
* sentences: breaks after paragraph separators, after ``!?…`` and the like,
  and after ``.`` unless the next character is lowercase or the ``.``
  touches a letter or digit;
* duplicate statistics count UTF-8 bytes.

Text in scripts that need a dictionary to split words (Han, kana, Thai and
the like) is outside what this reference covers: it raises.
"""

from __future__ import annotations

import re
import unicodedata
from typing import Dict, List, Sequence, Tuple

# The upstream PUNCTUATION set: literal characters plus control ranges.
PUNCTUATION_LIT = (
    "!/—”:％１〈&(、━\\【#%「」，】；+^]~“《„';’{|∶´[=-`*．（–？！：$～«〉,><》)?）。…@_.\"}►»"
)
PUNCTUATION = frozenset(PUNCTUATION_LIT) | frozenset(
    chr(cp) for lo, hi in ((0, 9), (11, 13), (13, 32), (127, 160)) for cp in range(lo, hi)
)

_MID_LETTER = frozenset("\u003a\u00b7\u05f4\u2027\ufe13\ufe55\uff1a")
_MID_NUM = frozenset("\u002c\u003b\u037e\u0589\u066c\ufe10\ufe14\uff0c\uff1b")
_MID_NUM_LET = frozenset("\u002e\u0027\u2019\u2024\ufe52\uff07\uff0e")
_JOIN_LETTERS = _MID_LETTER | _MID_NUM_LET
_JOIN_DIGITS = _MID_NUM | _MID_NUM_LET

# Codepoints at and above this have no letter, digit or space class; the
# variation selectors and tags of plane 14 are combining.
_CLASSIFIED_BELOW = 0x40000
_PLANE14 = range(0xE0000, 0xE0200)

_DICT_RANGES = (
    (0x0E00, 0x0EFF),  # Thai, Lao
    (0x1000, 0x109F),  # Myanmar
    (0x1780, 0x17FF),  # Khmer
    (0x3040, 0x30FF),  # Hiragana, Katakana
    (0x31F0, 0x31FF),  # Katakana phonetic extensions
    (0x3400, 0x4DBF),  # CJK extension A
    (0x4E00, 0x9FFF),  # CJK unified ideographs
    (0xF900, 0xFAFF),  # CJK compatibility ideographs
)
_DICT_SCRIPTS = re.compile(
    "[" + "".join(f"{chr(lo)}-{chr(hi)}" for lo, hi in _DICT_RANGES) + "]"
)


class UnsupportedText(ValueError):
    """Text this reference does not cover."""


class _Char:
    __slots__ = ("word", "alpha", "digit", "space", "punct", "extend")

    def __init__(self, ch: str) -> None:
        cp = ord(ch)
        if cp >= _CLASSIFIED_BELOW:
            alnum = self.alpha = self.digit = self.space = False
            self.extend = cp in _PLANE14
        else:
            alnum = ch.isalnum()
            self.alpha = ch.isalpha()
            self.digit = ch.isdigit()
            self.space = ch.isspace()
            self.extend = (
                not alnum
                and cp != 0x200B
                and unicodedata.category(ch) in ("Mn", "Mc", "Me", "Cf")
            )
        self.word = alnum or ch == "_"
        self.punct = ch in PUNCTUATION


_CLASSES: Dict[str, _Char] = {}


def _classes(text: str) -> Dict[str, _Char]:
    """The class of every distinct character of ``text``."""
    out = {}
    for ch in set(text):
        c = _CLASSES.get(ch)
        if c is None:
            c = _CLASSES[ch] = _Char(ch)
        out[ch] = c
    return out


_RUNS = re.compile(b"\x01+")


def word_spans(text: str) -> List[Tuple[int, int]]:
    """(start, end) character spans of the words of ``text``, in order."""
    if not text:
        return []
    if _DICT_SCRIPTS.search(text):
        raise UnsupportedText("dictionary-segmented script")
    n = len(text)
    table = _classes(text)
    cls = [table[ch] for ch in text]
    in_word = [c.word for c in cls]
    joiners = {ch for ch in table if ch in _JOIN_LETTERS or ch in _JOIN_DIGITS}
    if n >= 3 and joiners:
        for m in re.finditer("[" + "".join(re.escape(c) for c in joiners) + "]", text):
            i, ch = m.start(), m.group()
            if i == 0 or i == n - 1:
                continue
            if ch in _JOIN_LETTERS and cls[i - 1].alpha and cls[i + 1].alpha:
                in_word[i] = True
            elif ch in _JOIN_DIGITS and cls[i - 1].digit and cls[i + 1].digit:
                in_word[i] = True
    # Combining marks take the wordness of the nearest character before
    # them that is not one.
    if any(c.extend for c in table.values()):
        last = -1
        for i in range(n):
            if cls[i].extend:
                if last >= 0:
                    in_word[i] = in_word[last]
            else:
                last = i
    spans: List[Tuple[int, int]] = []
    for m in _RUNS.finditer(bytes(in_word)):
        i, j = m.span()
        if any(not cls[k].punct for k in range(i, j)):
            spans.append((i, j))
    # Any other character that is neither space nor PUNCTUATION is a word
    # of its own, with the combining marks after it.
    if any(not (c.word or c.space or c.punct or c.extend) for c in table.values()):
        for i in range(n):
            c = cls[i]
            if in_word[i] or c.space or c.punct or c.extend or text[i] == "\u200b":
                continue
            j = i + 1
            while j < n and cls[j].extend:
                j += 1
            spans.append((i, j))
        spans.sort()
    return spans


def split_into_words(text: str) -> List[str]:
    return [text[s:e] for s, e in word_spans(text)]


_PARA_SEP = "\n\r\x85\u2028\u2029"
_STERM = "!?\u2026\u3002\uff01\uff1f\uff61"
_CLOSE = ")]}\"'\u201d\u2019\u00bb\u300d\u300f\u3011\u3009\u300b\uff09"
_SP = " \t\u00a0\u2000\u2001\u2002\u2003\u2004\u2005\u2006\u2007\u2008\u2009\u200a\u202f\u205f\u3000"


def _cc(chars: str) -> str:
    return "[" + "".join(re.escape(c) for c in chars) + "]"


_SENT_RE = re.compile(
    "(?:\r\n|" + _cc(_PARA_SEP) + ")"
    "|(?:" + _cc("." + _STERM) + "+" + _cc(_CLOSE) + "*" + _cc(_SP) + "*)"
)


def split_into_sentences(text: str) -> List[str]:
    trimmed = text.strip()
    if not trimmed:
        return []
    n = len(trimmed)
    bounds: List[int] = []
    for m in _SENT_RE.finditer(trimmed):
        end = m.end()
        if end >= n:
            break
        g = m.group(0)
        if g[0] in _PARA_SEP:
            bounds.append(end)
            continue
        nxt = trimmed[end]
        if "." in g and not any(c in _STERM for c in g):
            if g[-1] == "." and (nxt.isalnum() or nxt == "_"):
                continue
            if nxt.islower():
                continue
        bounds.append(end)
    out: List[str] = []
    prev = 0
    for b in bounds + [n]:
        if b > prev:
            s = trimmed[prev:b].strip()
            if s:
                out.append(s)
        prev = b
    return out or [trimmed]


def rust_lines(text: str) -> List[str]:
    """Rust ``str::lines()``: split on ``\\n``, drop one trailing ``\\r``
    per line, no empty last line for newline-terminated text."""
    if not text:
        return []
    parts = text.split("\n")
    if parts[-1] == "":
        parts.pop()
    return [p[:-1] if p.endswith("\r") else p for p in parts]


def _bytes(s: str) -> int:
    return len(s.encode("utf-8"))


def find_duplicates(items: Sequence[str]) -> Tuple[int, int]:
    """(repeated items, UTF-8 bytes of the repeats)."""
    seen = set()
    n = b = 0
    for item in items:
        if item in seen:
            n += 1
            b += _bytes(item)
        else:
            seen.add(item)
    return n, b


def top_duplicate_bytes(words: Sequence[str], n: int) -> int:
    """Bytes of the most frequent space-joined n-gram times its count
    (largest such product among ties); 0 when no n-gram repeats."""
    if n <= 0 or n > len(words):
        return 0
    counts: Dict[str, int] = {}
    for i in range(len(words) - n + 1):
        g = " ".join(words[i : i + n])
        counts[g] = counts.get(g, 0) + 1
    top = max(counts.values())
    if top <= 1:
        return 0
    return max(_bytes(g) * top for g, c in counts.items() if c == top)


def all_duplicate_bytes(words: Sequence[str], n: int) -> int:
    """Bytes of the repeated n-grams (words concatenated without spaces),
    skipping past each repeat."""
    if n <= 0 or len(words) < n:
        return 0
    seen = set()
    total = i = 0
    while i + n <= len(words):
        g = "".join(words[i : i + n])
        if g in seen:
            total += _bytes(g)
            i += n
        else:
            seen.add(g)
            i += 1
    return total
