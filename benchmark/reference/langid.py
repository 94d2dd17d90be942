"""Plain language identifier of the reference.

The configuration's LanguageDetectionFilter is defined by this system's
hashed-trigram naive-Bayes model over five candidate languages.  Its
training data (ranked function words, a lexicon and running prose per
language) is copied into ``data/langid_profiles.json``; the model is
trained from it here, one character at a time:

* normalise: lowercase each character (a character whose lowercase is
  several characters stays as it is), keep letters, turn every run of other
  characters into one boundary 0, and wrap the text in boundaries;
* features: every character trigram, hashed ``(c1*961 + c2*31 + c3)`` mod
  2**16, plus one rolling hash ``h = h*31 + c`` (mod 2**32) per word, mod
  2**16;
* score: summed log-probabilities in integer millinats; confidence: the
  softmax of the length-normalised scores times a bounded evidence factor.

``precision`` is the float type of the confidence arithmetic.
"""

from __future__ import annotations

import json
import os
import re
from typing import List, Optional, Tuple

import numpy as np

_DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "langid_profiles.json")


def _normalize_training(text: str) -> List[int]:
    out = [0]
    for ch in text.lower():
        if ch.isalpha():
            out.append(ord(ch))
        elif out[-1] != 0:
            out.append(0)
    if out[-1] != 0:
        out.append(0)
    return out


def _normalize(text: str) -> List[int]:
    table = {}
    for ch in set(text):
        low = ch.lower()
        if len(low) != 1 or ord(low) >= 0x40000:
            low = ch
        table[ch] = low if ord(ch) < 0x40000 and low.isalpha() else "\0"
    s = _BOUNDARIES.sub("\0", "\0" + "".join([table[ch] for ch in text]))
    if s[-1] != "\0":
        s += "\0"
    return [ord(c) for c in s]


_BOUNDARIES = re.compile("\0+")


class LangId:
    def __init__(self, precision: str = "float64") -> None:
        with open(_DATA, encoding="utf-8") as f:
            d = json.load(f)
        self.languages: Tuple[str, ...] = tuple(d["languages"])
        self.iso = d["iso"]
        self.mask = (1 << d["table_bits"]) - 1
        self.scale = float(d["score_scale"])
        self.dtype = np.float32 if precision == "float32" else np.float64
        self.table_q = self._train(d)

    def _trigrams(self, cps: List[int]) -> List[int]:
        m = self.mask
        return [(cps[i] * 961 + cps[i + 1] * 31 + cps[i + 2]) & m for i in range(len(cps) - 2)]

    def _word_hashes(self, cps: List[int]) -> List[int]:
        out, h, inside = [], 0, False
        for c in cps:
            if c == 0:
                if inside:
                    out.append(h & self.mask)
                h, inside = 0, False
            else:
                h = (h * 31 + c) & 0xFFFFFFFF
                inside = True
        if inside:
            out.append(h & self.mask)
        return out

    def _train(self, d) -> np.ndarray:
        size = self.mask + 1
        counts = np.zeros((size, len(self.languages)), dtype=np.float64)
        for li, lang in enumerate(self.languages):
            col = counts[:, li]
            for rank, word in enumerate(d["ranked_words"][lang]):
                weight = 1.0 / (rank + 1.0)
                cps = _normalize_training(word)
                for h in self._trigrams(cps):
                    col[h] += weight
                for i in range(len(cps) - 1):
                    col[(cps[i] * 31 + cps[i + 1]) & self.mask] += 0.3 * weight
                np.add.at(col, np.asarray(self._word_hashes(cps), dtype=np.int64), 0.5 * weight)
            for word in d["lexicon"][lang].split():
                cps = _normalize_training(word)
                if len(cps) >= 3:
                    np.add.at(col, np.asarray(self._trigrams(cps), dtype=np.int64), 1.0)
                np.add.at(col, np.asarray(self._word_hashes(cps), dtype=np.int64), 1.0)
            cps = _normalize_training(d["prose"][lang])
            np.add.at(col, np.asarray(self._trigrams(cps), dtype=np.int64), 0.5)
            np.add.at(col, np.asarray(self._word_hashes(cps), dtype=np.int64), 0.25)
        alpha = 0.01
        totals = counts.sum(axis=0, keepdims=True)
        logp = np.log((counts + alpha) / (totals + alpha * size)).astype(np.float32)
        return np.round(logp * self.scale).astype(np.int64)

    def detect(self, text: str) -> Optional[Tuple[str, float]]:
        """(language name, confidence), or None for text with fewer than
        three normalised characters."""
        cps = _normalize(text)
        if len(cps) < 3:
            return None
        feats = self._trigrams(cps) + self._word_hashes(cps)
        scores = self.table_q[feats].sum(axis=0)
        t = self.dtype
        ng = np.maximum(np.array([len(feats)], dtype=np.int64), 1).astype(t)
        s = scores[None, :].astype(t) / t(self.scale)
        evidence = np.minimum(ng, t(400.0)) * (ng / (ng + t(25.0)))
        z = (s / ng[:, None]) * evidence[:, None]
        z = z - z.max(axis=1, keepdims=True)
        z = np.maximum(z, t(-30.0))
        p = np.exp(z)
        p /= p.sum(axis=1, keepdims=True)
        best = int(p.argmax(axis=1)[0])
        return self.languages[best], p[0, best]
