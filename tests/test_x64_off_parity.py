"""Parity smoke with x64 OFF (ADVICE r3 item 4).

The suite enables ``jax_enable_x64`` globally (conftest), so the main parity
tests validate the packed-int64 CPU sort configuration.  Real-TPU programs
run with x64 off — ``sort2`` then takes the stable two-operand ``lax.sort``
fallback and every kernel computes in strict int32.  This subprocess smoke
keeps that configuration's semantics exercised beyond the single sort2
agreement test: a mixed pipeline (including the sort-heavy repetition
filter) must match the host oracle bit-exactly with x64 off.
"""

import subprocess
import sys


def test_device_parity_smoke_x64_off():
    code = r"""
import os
os.environ["TEXTBLAST_HOST_TAILS"] = "off"
os.environ["JAX_PLATFORMS"] = "cpu"  # x64 deliberately left off
from textblaster_tpu.utils.compile_cache import enable_compilation_cache
enable_compilation_cache()
import jax
assert not jax.config.jax_enable_x64

from textblaster_tpu.config.pipeline import parse_pipeline_config
from textblaster_tpu.data_model import TextDocument
from textblaster_tpu.ops.pipeline import process_documents_device
from textblaster_tpu.orchestration import process_documents_host
from textblaster_tpu.pipeline_builder import build_pipeline_from_config

YAML = '''
pipeline:
  - type: LanguageDetectionFilter
    min_confidence: 0.5
    allowed_languages: [ "dan", "eng" ]
  - type: GopherRepetitionFilter
    dup_line_frac: 0.3
    dup_para_frac: 0.3
    dup_line_char_frac: 0.2
    dup_para_char_frac: 0.2
    top_n_grams: [[2, 0.2], [3, 0.18]]
    dup_n_grams: [[5, 0.15], [6, 0.14]]
  - type: GopherQualityFilter
    min_doc_words: 5
    min_stop_words: 1
    stop_words: [ "og", "the", "er", "i" ]
  - type: FineWebQualityFilter
    line_punct_thr: 0.1
    line_punct_exclude_zero: false
    short_line_thr: 0.95
    short_line_length: 8
    char_duplicates_ratio: 0.5
    new_line_ratio: 0.5
'''
TEXTS = [
    "Det er en god dag i dag, og vi skal ud at gå en lang tur i skoven nu.",
    "The quick brown fox jumps over the lazy dog and the old stone bridge.",
    "Samme linje her igen.\n" * 6,
    "kort.",
    "Endnu en dansk tekst om vejret, og den er ganske lang og fin at læse.",
    "a b a b a b a b a b a b a b a b a b a b.",
    "",
    "   \n \t ",
]
config = parse_pipeline_config(YAML)
mk = lambda i, t: TextDocument(id=f"x{i}", source="s", content=t)
host = {o.document.id: o for o in process_documents_host(
    build_pipeline_from_config(config), iter([mk(i, t) for i, t in enumerate(TEXTS)]))}
dev = {o.document.id: o for o in process_documents_device(
    config, iter([mk(i, t) for i, t in enumerate(TEXTS)]), device_batch=8)}
assert set(host) == set(dev)
for k, h in host.items():
    d = dev[k]
    assert h.kind == d.kind, (k, h.kind, d.kind, d.reason)
    assert h.reason == d.reason, (k, h.reason, d.reason)
    assert h.document.metadata == d.document.metadata, k
print("X64_OFF_PARITY_OK", len(host))
"""
    env = {
        "JAX_PLATFORMS": "cpu",
        "PATH": "/usr/bin:/bin:/usr/local/bin",
        "HOME": "/root",
    }
    res = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=540,
        cwd="/root/repo",
        env=env,
    )
    assert res.returncode == 0, (res.stderr or res.stdout)[-3000:]
    assert "X64_OFF_PARITY_OK" in res.stdout
