"""Device-vs-oracle parity: the compiled pipeline must reproduce the host
filters' decisions, reason strings, metadata, and rewritten content.

This is the TPU analogue of the reference's filter unit suites (SURVEY.md §4:
"parity harness running reference-semantics CPU oracle vs TPU kernels per
filter per document").  Runs on the CPU backend (conftest pins JAX_PLATFORMS).
"""

import numpy as np
import pytest

from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.data_model import ProcessingOutcome, TextDocument
from textblaster_tpu.ops.pipeline import process_documents_device
from textblaster_tpu.orchestration import process_documents_host
from textblaster_tpu.pipeline_builder import build_pipeline_from_config

DANISH = (
    "Det er en rigtig god dag i dag, og vi skal ud at gå en lang tur i skoven. "
    "Solen skinner over byen, og der er mange mennesker på gaden i dag. "
    "Efter turen vil vi gerne drikke en kop kaffe og spise lidt brød hjemme. "
    "Det bliver en dejlig eftermiddag, fordi vejret er så godt i dag. "
    "Om aftenen skal vi lave mad sammen og se en god film i stuen."
)

CORPUS = [
    DANISH,
    "This is an English document about the weather and the people of the town. "
    "They have many things to do with their time. The market opens early.",
    "",
    "   \n  \t ",
    "Short.",
    "Lorem ipsum dolor sit amet. " + DANISH,
    "{ a curly document }. " + DANISH,
    "Samme linje her.\n" * 12,
    "spam ham spam ham spam ham spam ham spam ham spam ham.",
    DANISH + "\nThis line has javascript in it.\nRead our privacy policy now.",
    "En linje uden punktum\n" + DANISH,
    "Citat her [1]. Mere tekst [2, 3]. " + DANISH,
    "word " * 300 + ".",
    "- bullet et\n- bullet to\n- bullet tre\n" + DANISH,
    "Kort…\nOgså kort…\nMere…\n" + DANISH,
    "### overskrift ###\n" + DANISH,
    "1,000.5 tal og æøå-tegn virker fint her, og det er godt. " + DANISH,
    "don't can’t won't — apostrofferne er vigtige i dag. " + DANISH,
    "a\n\nb\n\nc\n\na\n\nb",
    "Tom & Jerry <3 😀 " + DANISH,
    "\n\n\n",
    "... --- !!!",
    "Hello World\nHello World\nHello World",
    "word.\nword.\nword.\nword.\nword.",
    DANISH + " " + DANISH + " " + DANISH,  # long repeated doc
]

PIPELINE_YAML = """
pipeline:
  - type: LanguageDetectionFilter
    min_confidence: 0.65
    allowed_languages: [ "dan" ]
  - type: GopherRepetitionFilter
    dup_line_frac: 0.3
    dup_para_frac: 0.3
    dup_line_char_frac: 0.2
    dup_para_char_frac: 0.2
    top_n_grams: [[2, 0.2], [3, 0.18], [4, 0.16]]
    dup_n_grams: [[5, 0.15], [6, 0.14], [7, 0.13], [8, 0.12], [9, 0.11], [10, 0.10]]
  - type: GopherQualityFilter
    min_doc_words: 20
    max_doc_words: 100000
    min_avg_word_length: 3.0
    max_avg_word_length: 10.0
    max_symbol_word_ratio: 0.1
    max_bullet_lines_ratio: 0.9
    max_ellipsis_lines_ratio: 0.3
    max_non_alpha_words_ratio: 0.8
    min_stop_words: 2
    stop_words: [ "og", "er", "det", "en", "vi", "at", "den", "i" ]
  - type: C4QualityFilter
    split_paragraph: true
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 3
    min_words_per_line: 3
    max_word_length: 1000
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
  - type: FineWebQualityFilter
    line_punct_thr: 0.12
    line_punct_exclude_zero: false
    short_line_thr: 0.67
    short_line_length: 30
    char_duplicates_ratio: 0.1
    new_line_ratio: 0.3
"""


def run_both(yaml_str, texts):
    config = parse_pipeline_config(yaml_str)
    docs_a = [TextDocument(id=f"d{i}", source="s", content=t) for i, t in enumerate(texts)]
    docs_b = [TextDocument(id=f"d{i}", source="s", content=t) for i, t in enumerate(texts)]
    host = list(process_documents_host(build_pipeline_from_config(config), docs_a))
    dev = list(process_documents_device(config, iter(docs_b), device_batch=8))
    # Device path yields per bucket, so order differs; align by doc id.
    host_by_id = {o.document.id: o for o in host}
    dev_by_id = {o.document.id: o for o in dev}
    assert set(host_by_id) == set(dev_by_id)
    return host_by_id, dev_by_id


def assert_outcomes_equal(host_by_id, dev_by_id):
    mismatches = []
    for doc_id, h in sorted(host_by_id.items()):
        d = dev_by_id[doc_id]
        if h.kind != d.kind:
            mismatches.append(f"{doc_id}: kind {h.kind} != {d.kind} ({d.reason!r} vs {h.reason!r})")
            continue
        if h.reason != d.reason:
            mismatches.append(f"{doc_id}: reason {h.reason!r} != {d.reason!r}")
        if h.document.content != d.document.content:
            mismatches.append(f"{doc_id}: content differs")
        if h.document.metadata != d.document.metadata:
            mismatches.append(
                f"{doc_id}: metadata {h.document.metadata} != {d.document.metadata}"
            )
    assert not mismatches, "\n".join(mismatches)


def test_full_pipeline_parity():
    host_by_id, dev_by_id = run_both(PIPELINE_YAML, CORPUS)
    assert_outcomes_equal(host_by_id, dev_by_id)


def test_single_step_parity_gopher_quality():
    yaml_str = """
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 10
    max_doc_words: 1000
    min_avg_word_length: 2.0
    max_avg_word_length: 12.0
    max_symbol_word_ratio: 0.2
    max_bullet_lines_ratio: 0.5
    max_ellipsis_lines_ratio: 0.3
    max_non_alpha_words_ratio: 0.6
    min_stop_words: 1
    stop_words: [ "the", "og" ]
"""
    host_by_id, dev_by_id = run_both(yaml_str, CORPUS)
    assert_outcomes_equal(host_by_id, dev_by_id)


def test_single_step_parity_gopher_repetition():
    yaml_str = """
pipeline:
  - type: GopherRepetitionFilter
    dup_line_frac: 0.2
    dup_para_frac: 0.2
    dup_line_char_frac: 0.15
    dup_para_char_frac: 0.15
    top_n_grams: [[2, 0.1], [3, 0.1]]
    dup_n_grams: [[4, 0.1], [5, 0.1]]
"""
    host_by_id, dev_by_id = run_both(yaml_str, CORPUS)
    assert_outcomes_equal(host_by_id, dev_by_id)


def test_single_step_parity_c4():
    yaml_str = """
pipeline:
  - type: C4QualityFilter
    split_paragraph: true
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 2
    min_words_per_line: 3
    max_word_length: 50
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
"""
    host_by_id, dev_by_id = run_both(yaml_str, CORPUS)
    assert_outcomes_equal(host_by_id, dev_by_id)


def test_single_step_parity_c4_sentence_mode():
    # split_paragraph: false — units are sentences (c4_filters.rs:150-156),
    # separators synthesized on device from inter-sentence whitespace.
    yaml_str = """
pipeline:
  - type: C4QualityFilter
    split_paragraph: false
    remove_citations: true
    filter_no_terminal_punct: true
    min_num_sentences: 2
    min_words_per_line: 3
    max_word_length: 50
    filter_lorem_ipsum: true
    filter_javascript: true
    filter_curly_bracket: true
    filter_policy: true
"""
    sentence_cases = [
        "Første sætning er her. Anden sætning følger efter! Og en tredje?",
        "En sætning med citat [1]. Endnu en med flere [2, 3] i midten.",
        "Multi\nline text. With sentences spanning\nnewlines here. Ja tak.",
        'Han sagde "Hej." Hun svarede "Farvel." De gik hver til sit.',
        "No terminal punctuation at all just words flowing along",
        "Kort. Kort igen. K. Og så en rigtig lang sætning til sidst her.",
        "Ends with ellipsis... Then another sentence. And more text here.",
        "  \n  Leading whitespace. Trailing too.  \n ",
        # Zero-gap boundary (terminator directly followed by the next
        # sentence) — device flags the row and host-fallbacks, still parity.
        "First sentence.Second sentence follows. Og en tredje sætning her.",
        "Dr. Hansen kom kl. 10. Mødet varede en time. Alle var glade.",
    ]
    host_by_id, dev_by_id = run_both(yaml_str, CORPUS + sentence_cases)
    assert_outcomes_equal(host_by_id, dev_by_id)


def test_single_step_parity_fineweb():
    yaml_str = """
pipeline:
  - type: FineWebQualityFilter
    line_punct_thr: 0.12
    line_punct_exclude_zero: false
    short_line_thr: 0.67
    short_line_length: 30
    char_duplicates_ratio: 0.1
    new_line_ratio: 0.3
"""
    host_by_id, dev_by_id = run_both(yaml_str, CORPUS)
    assert_outcomes_equal(host_by_id, dev_by_id)


def test_single_step_parity_langid():
    yaml_str = """
pipeline:
  - type: LanguageDetectionFilter
    min_confidence: 0.5
    allowed_languages: [ "dan", "eng" ]
"""
    host_by_id, dev_by_id = run_both(yaml_str, CORPUS)
    assert_outcomes_equal(host_by_id, dev_by_id)


def test_host_suffix_token_counter(tmp_path):
    # TokenCounter runs as a host suffix step after the device prefix.
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    tok = Tokenizer(WordLevel({"[UNK]": 0}, unk_token="[UNK]"))
    tok.pre_tokenizer = Whitespace()
    tok_path = str(tmp_path / "tokenizer.json")
    tok.save(tok_path)

    yaml_str = f"""
pipeline:
  - type: GopherQualityFilter
    min_doc_words: 2
  - type: TokenCounter
    tokenizer_name: "{tok_path}"
"""
    host_by_id, dev_by_id = run_both(yaml_str, ["hello world again", "one two"])
    assert_outcomes_equal(host_by_id, dev_by_id)
    assert dev_by_id["d0"].document.metadata["token_count"] == "3"


def _danish_stop_word_hashes():
    from textblaster_tpu.config.pipeline import load_pipeline_config
    from textblaster_tpu.ops.stats import hash_string

    cfg = load_pipeline_config("benchmark/configs/danish_cc.yaml")
    [words] = [
        s.params.stop_words for s in cfg.pipeline if s.type == "GopherQualityFilter"
    ]
    return np.array(sorted({hash_string(w) for w in words}), dtype=np.int32)


def _program_sets():
    from textblaster_tpu.ops import device as d
    from textblaster_tpu.ops import stats as s

    return {
        "MID_LETTER_CPS": d.MID_LETTER_CPS,
        "MID_NUM_CPS": d.MID_NUM_CPS,
        "MID_ALL_CPS": d.MID_ALL_CPS,
        "_TERM_SET": s._TERM_SET,
        "_STERM_SET": s._STERM_SET,
        "_CLOSE_SET": s._CLOSE_SET,
        "_SP_SET": s._SP_SET,
        "_PSEP_SET": s._PSEP_SET,
        "_END_PUNCT_SET": s._END_PUNCT_SET,
        "danish_stop_word_hashes": _danish_stop_word_hashes(),
        "empty": np.zeros(0, dtype=np.int32),
    }


@pytest.mark.parametrize("name", list(_program_sets()))
def test_isin_sorted_matches_np_isin(name):
    """Constant-set membership equals ``np.isin`` on the wire's uint16
    codepoints and on int32 codepoints and hashes: every member, its
    neighbours, the extremes, and uint16 values that alias a member's low
    16 bits."""
    import jax

    from textblaster_tpu.ops.device import isin_sorted

    values = _program_sets()[name]
    members = values.astype(np.int64)
    special = np.concatenate(
        [members, members - 1, members + 1, [0, 1, 0xFFFF, -1, -(2**31), 2**31 - 1]]
    )
    rng = np.random.default_rng(28)
    shape = (64, 512)

    def fill(specials, lo, hi, dtype):
        x = rng.integers(lo, hi, size=shape[0] * shape[1], dtype=np.int64)
        x[: len(specials)] = specials
        return rng.permutation(x).reshape(shape).astype(dtype)

    i32 = special[(special >= -(2**31)) & (special < 2**31)]
    hashes = rng.integers(-(2**31), 0, size=256)
    x_i32 = fill(np.concatenate([i32, hashes]), -(2**31), 2**31, np.int32)
    x_u16 = fill(np.unique(special & 0xFFFF), 0, 0x10000, np.uint16)
    for x in (x_u16, x_i32):
        got = np.asarray(jax.jit(lambda a: isin_sorted(a, values))(x))
        assert got.dtype == np.bool_
        np.testing.assert_array_equal(got, np.isin(x, values))
    if len(values):
        assert np.isin(x_i32, values).any()
