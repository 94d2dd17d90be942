"""Token counting step (never filters).

Re-implementation of ``TokenCounter``
(``/root/reference/src/pipeline/token/token_counter.rs:8-43``): loads a
HuggingFace tokenizer at build time, encodes content *with* special tokens,
and stamps ``metadata["token_count"]``.  A batch of documents is encoded
in one ``encode_batch`` call (:meth:`TokenCounter.process_batch`).

Loading resolution order (the reference only supports hub fetch,
token_counter.rs:14; this build adds offline paths first since TPU pods are
often egress-less):

1. a local path to a ``tokenizer.json`` file or a directory containing one;
2. a local ``merges.txt`` (GPT-2 byte-level BPE) counted by the native C++
   core (``textblaster_tpu/native``) — no vocab ids are needed for a count;
3. the HuggingFace hub cache / network via ``tokenizers.Tokenizer.from_pretrained``
   — or, under ``HF_HUB_OFFLINE=1``, the local hub cache alone (that call
   would still reach for the network);
4. a vendored stand-in under ``textblaster_tpu/data/tokenizers/<name>/`` —
   an in-repo-trained byte-level BPE shipped so the default config's
   ``TokenCounter(gpt2)`` executes on egress-less machines (see the README
   beside it; hub/cache wins whenever reachable).

A load failure raises ``UnexpectedError("Error in loading tokenizer")`` at
construction, matching the reference's build-time failure surface
(worker_logic.rs:115-122 panics on it).
"""

from __future__ import annotations

import os
from typing import List, Sequence, Union

from ..data_model import TextDocument
from ..errors import UnexpectedError
from ..executor import ProcessingStep

__all__ = ["TokenCounter"]


def _hub_tokenizer(name: str):
    """The hub tokenizer ``name``: from the hub cache or the network, or
    from the local hub cache only when ``HF_HUB_OFFLINE`` asks for that."""
    from tokenizers import Tokenizer

    if os.environ.get("HF_HUB_OFFLINE", "").strip().lower() in ("1", "true", "yes", "on"):
        from huggingface_hub import try_to_load_from_cache

        path = try_to_load_from_cache(name, "tokenizer.json")
        if not isinstance(path, str):
            raise FileNotFoundError(
                f"tokenizer {name!r} is not in the local hub cache (HF_HUB_OFFLINE)"
            )
        return Tokenizer.from_file(path)
    return Tokenizer.from_pretrained(name)


class TokenCounter(ProcessingStep):
    name = "TokenCounter"

    def __init__(self, tokenizer_name: str) -> None:
        self._tokenizer = None
        self._bpe = None
        #: True when the in-repo-trained stand-in replaced an unreachable hub
        #: tokenizer: counts then differ from the reference's, and every
        #: document is stamped so divergent runs are identifiable
        #: (ADVICE r4).
        self._standin = False
        try:
            json_path = tokenizer_name
            merges_path = None
            if os.path.isdir(tokenizer_name):
                json_path = os.path.join(tokenizer_name, "tokenizer.json")
                merges_path = os.path.join(tokenizer_name, "merges.txt")
            elif tokenizer_name.endswith("merges.txt"):
                json_path = None
                merges_path = tokenizer_name
            if json_path is not None and os.path.isfile(json_path):
                from tokenizers import Tokenizer

                self._tokenizer = Tokenizer.from_file(json_path)
            elif merges_path is not None and os.path.isfile(merges_path):
                # Byte-level BPE counting on the native core — the egress-less
                # path (vocab ids are not needed for a token *count*).
                from ..native import BpeCounter

                self._bpe = BpeCounter.from_file(merges_path)
            else:
                from tokenizers import Tokenizer

                try:
                    self._tokenizer = _hub_tokenizer(tokenizer_name)
                except Exception:
                    vendored = os.path.join(
                        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "data",
                        "tokenizers",
                        tokenizer_name,
                        "tokenizer.json",
                    )
                    if not os.path.isfile(vendored):
                        raise
                    import logging

                    logging.getLogger(__name__).warning(
                        "tokenizer %r unavailable from hub/cache; using the "
                        "vendored stand-in at %s (counts differ from the hub "
                        "tokenizer — see its README)",
                        tokenizer_name,
                        vendored,
                    )
                    self._tokenizer = Tokenizer.from_file(vendored)
                    self._standin = True
                    from ..utils.metrics import METRICS

                    METRICS.inc("worker_tokenizer_standin_total")
        except Exception as e:
            raise UnexpectedError("Error in loading tokenizer") from e

    def process(self, document: TextDocument) -> TextDocument:
        try:
            if self._bpe is not None:
                count = self._bpe.count(document.content)
            else:
                count = len(
                    self._tokenizer.encode(document.content, add_special_tokens=True)
                )
        except Exception as e:
            raise UnexpectedError(str(e)) from e
        return self._stamp(document, count)

    def process_batch(
        self, documents: Sequence[TextDocument]
    ) -> List[Union[TextDocument, Exception]]:
        """One ``encode_batch`` over the documents, run in the tokenizer's
        native core across the host's cores.  Padding would count every
        encoding at the longest one's length, so a padded tokenizer, the
        native BPE counter and a single document take the loop over
        :meth:`process`; so does a batch whose call raises, which gives each
        failing document its own error."""
        tok = self._tokenizer
        if tok is None or tok.padding is not None or len(documents) < 2:
            return super().process_batch(documents)
        try:
            encodings = tok.encode_batch(
                [d.content for d in documents], add_special_tokens=True
            )
        except Exception:
            return super().process_batch(documents)
        self.batched_docs += len(documents)
        return [self._stamp(d, len(e)) for d, e in zip(documents, encodings, strict=True)]

    def _stamp(self, document: TextDocument, count: int) -> TextDocument:
        document.metadata["token_count"] = str(count)
        if self._standin:
            # Not a reference metadata key: deliberately extra so downstream
            # consumers can tell stand-in counts from hub-gpt2 counts.
            document.metadata["token_count_tokenizer"] = "vendored-standin"
        return document
