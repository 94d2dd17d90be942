"""The plain reference: the configuration's filters, one document at a time.

Each step follows the upstream TextBlaster filter it is named after
(``src/pipeline/filters/*.rs``, ``src/pipeline/token/token_counter.rs``):
the same checks in the same order, the same reason strings and metadata
stamps.  A step returns ``None`` to keep the document or the reason string
to exclude it; a document goes through the steps until one excludes it.

``precision`` is the float type in which every ratio and the language
confidence are computed and compared: ``float64`` as the configuration
states, ``float32`` for the control.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import text as T
from .langid import LangId

_HERE = os.path.dirname(os.path.abspath(__file__))


class _Num:
    """Division and comparison in one float type."""

    def __init__(self, precision: str) -> None:
        self.f32 = precision == "float32"

    def div(self, a, b):
        if self.f32:
            return np.float32(a) / np.float32(b)
        return a / b

    def val(self, x):
        return np.float32(x) if self.f32 else float(x)


def fmt2(v) -> str:
    return f"{float(v):.2f}"


def fmt4(v) -> str:
    return f"{float(v):.4f}"


def rust_float(v) -> str:
    s = repr(float(v))
    return s[:-2] if s.endswith(".0") else s


class Doc:
    __slots__ = ("content", "metadata")

    def __init__(self, content: str) -> None:
        self.content = content
        self.metadata: Dict[str, str] = {}


class LanguageDetection:
    def __init__(self, p: Dict, num: _Num, precision: str) -> None:
        self.model = LangId(precision)
        self.min_confidence = p["min_confidence"]
        self.allowed = [c for c in p["allowed_languages"] if c in self.model.iso]
        self.num = num

    def __call__(self, doc: Doc) -> Optional[str]:
        found = self.model.detect(doc.content)
        if found is None:
            return "Language could not be confidently detected"
        name, conf = found
        doc.metadata["Detected language"] = name
        doc.metadata["Detected language confidence"] = rust_float(conf)
        iso = {v: k for k, v in self.model.iso.items()}[name]
        if iso not in self.allowed:
            return f'Document is not any of the following languages: "{"; ".join(self.allowed)}"'
        if self.num.val(conf) < self.num.val(self.min_confidence):
            return (
                "Language detection confidence is not satified: "
                f"{rust_float(conf)} < {rust_float(self.min_confidence)}"
            )
        return None


_PARAGRAPHS = re.compile(r"\n{2,}")
_LINES = re.compile(r"\n+")


class GopherRepetition:
    def __init__(self, p: Dict, num: _Num) -> None:
        self.p = p
        self.num = num

    def __call__(self, doc: Doc) -> Optional[str]:
        p, num, md = self.p, self.num, doc.metadata
        trimmed = doc.content.strip()
        if not trimmed:
            md["gopher_repetition_filter_status"] = "filtered"
            md["gopher_repetition_filter_reason"] = "skipping empty content"
            return "skipping empty content"
        chars = max(len(trimmed), 1)
        reasons: List[str] = []

        def over(value, limit, label):
            if limit is not None and num.val(value) > num.val(limit):
                reasons.append(f"{label} (ratio {fmt2(value)}, max {fmt2(limit)})")

        paras = _PARAGRAPHS.split(trimmed)
        n, b = T.find_duplicates(paras)
        over(num.div(n, max(len(paras), 1)), p.get("dup_para_frac"), "dup_para_frac")
        over(num.div(b, chars), p.get("dup_para_char_frac"), "dup_para_char_frac")
        lines = _LINES.split(trimmed)
        n, b = T.find_duplicates(lines)
        over(num.div(n, max(len(lines), 1)), p.get("dup_line_frac"), "dup_line_frac")
        over(num.div(b, chars), p.get("dup_line_char_frac"), "dup_line_char_frac")
        words = T.split_into_words(trimmed)
        for n_, thr in p.get("top_n_grams") or []:
            over(num.div(T.top_duplicate_bytes(words, n_), chars), thr, f"top_{n_}_gram")
        for n_, thr in p.get("dup_n_grams") or []:
            over(num.div(T.all_duplicate_bytes(words, n_), chars), thr,
                 f"duplicated_{n_}_n_grams")
        if reasons:
            md["gopher_repetition_filter_status"] = "filtered"
            md["gopher_repetition_filter_reasons"] = "; ".join(reasons)
            return "; ".join(reasons)
        md["gopher_repetition_filter_status"] = "passed"
        return None


_GOPHER_STOP_WORDS = ("the", "be", "to", "of", "and", "that", "have", "with")


class GopherQuality:
    def __init__(self, p: Dict, num: _Num) -> None:
        self.p = p
        self.num = num
        sw = p.get("stop_words")
        self.stop_words = set(sw if sw is not None else _GOPHER_STOP_WORDS)

    def __call__(self, doc: Doc) -> Optional[str]:
        p, num, text = self.p, self.num, doc.content
        words = T.split_into_words(text)
        non_symbol = [w for w in words if any(c not in T.PUNCTUATION for c in w)]
        n_ns = len(non_symbol)
        avg = num.div(sum(len(w) for w in non_symbol), n_ns) if n_ns else num.val(0.0)
        n_words = max(len(words), 1)
        hash_ratio = num.div(text.count("#"), n_words)
        ellipsis_ratio = num.div(text.count("...") + text.count("…"), n_words)
        lines = T.rust_lines(text)
        n_lines = max(len(lines), 1)
        bullets = num.div(sum(1 for l in lines if l.lstrip().startswith(("•", "-"))), n_lines)
        end_ellipsis = num.div(
            sum(1 for l in lines if l.rstrip().endswith(("...", "…"))), n_lines
        )
        alpha = num.div(sum(1 for w in words if any(c.isalpha() for c in w)), n_words)
        stops = sum(1 for w in words if w.lower() in self.stop_words)
        v = num.val
        r: List[str] = []
        if p.get("min_doc_words") is not None and n_ns < p["min_doc_words"]:
            r.append(f"gopher_short_doc ({n_ns} non-symbol words, required {p['min_doc_words']})")
        if p.get("max_doc_words") is not None and n_ns > p["max_doc_words"]:
            r.append(f"gopher_long_doc ({n_ns} non-symbol words, max {p['max_doc_words']})")
        lo = p.get("min_avg_word_length")
        if lo is not None and v(avg) < v(lo):
            suffix = " - 0 non-symbol words" if n_ns == 0 and lo > 0.0 else ""
            r.append(f"gopher_below_avg_threshold (avg len {fmt2(avg)}, required {fmt2(lo)}{suffix})")
        hi = p.get("max_avg_word_length")
        if hi is not None and n_ns > 0 and v(avg) > v(hi):
            r.append(f"gopher_above_avg_threshold (avg len {fmt2(avg)}, max {fmt2(hi)})")
        sym = p.get("max_symbol_word_ratio")
        if sym is not None:
            if v(hash_ratio) > v(sym):
                r.append(f"gopher_too_many_hashes (ratio {fmt2(hash_ratio)}, max {fmt2(sym)})")
            if v(ellipsis_ratio) > v(sym):
                r.append(f"gopher_too_many_ellipsis_units (ratio {fmt2(ellipsis_ratio)}, max {fmt2(sym)})")
        lim = p.get("max_bullet_lines_ratio")
        if lim is not None and v(bullets) > v(lim):
            r.append(f"gopher_too_many_bullets (ratio {fmt2(bullets)}, max {fmt2(lim)})")
        lim = p.get("max_ellipsis_lines_ratio")
        if lim is not None and v(end_ellipsis) > v(lim):
            r.append(f"gopher_too_many_end_ellipsis_lines (ratio {fmt2(end_ellipsis)}, max {fmt2(lim)})")
        lim = p.get("max_non_alpha_words_ratio")
        if lim is not None and v(alpha) < v(lim):
            r.append(f"gopher_below_alpha_threshold (alpha ratio {fmt2(alpha)}, required min {fmt2(lim)})")
        lim = p.get("min_stop_words")
        if lim is not None and lim > 0 and stops < lim:
            r.append(f"gopher_too_few_stop_words (found {stops}, required {lim})")
        md = doc.metadata
        if r:
            md["gopher_quality_filter_status"] = "filtered"
            md["gopher_quality_filter_reasons"] = "; ".join(r)
            return "; ".join(r)
        md["gopher_quality_filter_status"] = "passed"
        return None


_END_PUNCT = (".", "!", "?", '"', "'", "”")
_POLICY = ("terms of use", "privacy policy", "cookie policy", "uses cookies",
           "use of cookies", "use cookies")
_CITATION = re.compile(r"\[\d+(?:,\s*\d+)*\]")


class C4Quality:
    def __init__(self, p: Dict, num: _Num) -> None:
        self.p = p

    def __call__(self, doc: Doc) -> Optional[str]:
        p, md = self.p, doc.metadata
        original = doc.content
        lines = T.rust_lines(original) if p["split_paragraph"] else T.split_into_sentences(original)
        early = []
        if p["filter_lorem_ipsum"] and "lorem ipsum" in original.lower():
            early.append("lorem_ipsum")
        if p["filter_curly_bracket"] and ("{" in original or "}" in original):
            early.append("curly_bracket")
        if early:
            md["c4_filter_status"] = "filtered"
            md["c4_filter_reasons"] = "; ".join(early)
            return "; ".join(early)
        stats: Dict[str, int] = {}
        kept: List[str] = []
        for line in lines:
            cur = line.strip()
            cur = _CITATION.sub("", cur) if p["remove_citations"] else cur
            low = cur.lower()
            words = T.split_into_words(cur)
            if p["max_word_length"] > 0 and any(len(w) > p["max_word_length"] for w in words):
                stats["line-filter-too_long_word"] = stats.get("line-filter-too_long_word", 0) + 1
                continue
            if p["filter_no_terminal_punct"]:
                if not (cur and cur[-1] in _END_PUNCT) or cur.endswith("..."):
                    stats["line-filter-no_terminal_punc"] = stats.get("line-filter-no_terminal_punc", 0) + 1
                    continue
            if p["min_words_per_line"] > 0 and len(words) < p["min_words_per_line"]:
                stats["line-filter-too_few_words"] = stats.get("line-filter-too_few_words", 0) + 1
                continue
            if p["filter_javascript"] and "javascript" in low:
                continue
            if p["filter_policy"] and any(s in low for s in _POLICY):
                continue
            kept.append(cur)
        doc.content = "\n".join(kept).strip()
        n_sent = len(T.split_into_sentences(doc.content))
        if p["min_num_sentences"] > 0 and n_sent < p["min_num_sentences"]:
            reason = f"too_few_sentences (found {n_sent}, required {p['min_num_sentences']})"
            md["c4_filter_status"] = "filtered"
            md["c4_filter_reasons"] = reason
            for k, val in stats.items():
                md[k] = str(val)
            return reason
        md["c4_filter_status"] = "passed"
        return None


class FineWebQuality:
    def __init__(self, p: Dict, num: _Num) -> None:
        self.p = p
        self.num = num
        sc = p.get("stop_chars")
        self.stop_chars = frozenset(sc) if sc is not None else frozenset(_END_PUNCT)

    def __call__(self, doc: Doc) -> Optional[str]:
        p, num, content, md = self.p, self.num, doc.content, doc.metadata
        v = num.val

        def fail(reason, outcome=None):
            md["fineweb_filter_status"] = "filtered"
            md["fineweb_filter_reason"] = reason
            return outcome or reason

        lines = [l for l in T.rust_lines(content) if l.strip()]
        if not lines:
            return fail("empty document", "empty")
        ending = sum(1 for l in lines if l.rstrip() and l.rstrip()[-1] in self.stop_chars)
        ratio = num.div(ending, len(lines))
        thr = p["line_punct_thr"]
        excl = p["line_punct_exclude_zero"]
        if v(ratio) < v(thr) and not (v(ratio) == 0.0 and excl):
            return fail(
                f"line_punct_ratio: {fmt4(ratio)} < threshold {fmt4(thr)} "
                f"(exclude_zero: {'true' if excl else 'false'})"
            )
        short = sum(1 for l in lines if len(l) <= p["short_line_length"])
        ratio = num.div(short, len(lines))
        if v(ratio) > v(p["short_line_thr"]):
            return fail(f"short_line_ratio: {fmt4(ratio)} > threshold {fmt4(p['short_line_thr'])}")
        total = sum(1 for c in content if c != "\n")
        _, dup = T.find_duplicates(lines)
        ratio = num.div(dup, total) if total > 0 else v(0.0)
        if v(ratio) > v(p["char_duplicates_ratio"]):
            return fail(
                f"char_dup_ratio: {fmt4(ratio)} > threshold {fmt4(p['char_duplicates_ratio'])}"
            )
        words = T.split_into_words(content)
        newlines = content.count("\n")
        if not words:
            if newlines > 0:
                return fail("list_ratio_no_words (newlines present but no words)")
        else:
            ratio = num.div(newlines, len(words))
            if v(ratio) > v(p["new_line_ratio"]):
                return fail(f"list_ratio: {fmt4(ratio)} > threshold {fmt4(p['new_line_ratio'])}")
        return None


class TokenCounter:
    """Byte-level BPE count with special tokens, from the tokenizer data
    the deployment resolves ``gpt2`` to (the vendored stand-in, copied)."""

    def __init__(self, p: Dict, num: _Num) -> None:
        from tokenizers import Tokenizer

        if p["tokenizer_name"] != "gpt2":
            raise ValueError(f"no reference tokenizer data for {p['tokenizer_name']!r}")
        self.tok = Tokenizer.from_file(os.path.join(_HERE, "data", "gpt2", "tokenizer.json"))

    def __call__(self, doc: Doc) -> Optional[str]:
        enc = self.tok.encode(doc.content, add_special_tokens=True)
        doc.metadata["token_count"] = str(len(enc.tokens))
        doc.metadata["token_count_tokenizer"] = "vendored-standin"
        return None


_STEPS = {
    "GopherRepetitionFilter": GopherRepetition,
    "GopherQualityFilter": GopherQuality,
    "C4QualityFilter": C4Quality,
    "FineWebQualityFilter": FineWebQuality,
    "TokenCounter": TokenCounter,
}


class Reference:
    """The configuration's pipeline as plain steps."""

    def __init__(self, pipeline: List[Dict], precision: str = "float64") -> None:
        num = _Num(precision)
        self.steps = []
        for step in pipeline:
            kind = step["type"]
            params = {k: v for k, v in step.items() if k != "type"}
            if kind == "LanguageDetectionFilter":
                self.steps.append(LanguageDetection(params, num, precision))
            elif kind in _STEPS:
                self.steps.append(_STEPS[kind](params, num))
            else:
                raise ValueError(f"the reference has no step {kind!r}")

    def __call__(self, content: str) -> Tuple[str, str, Dict[str, str]]:
        """(``kept`` or ``excluded``, final text, metadata) of one document."""
        doc = Doc(content)
        for step in self.steps:
            if step(doc) is not None:
                return "excluded", doc.content, doc.metadata
        return "kept", doc.content, doc.metadata
