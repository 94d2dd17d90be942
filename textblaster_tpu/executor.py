"""Host-path pipeline executor.

Re-design of the reference executor (``/root/reference/src/executor.rs:8-70``):
``ProcessingStep`` is the op interface and ``PipelineExecutor`` threads a
document through the ordered steps, wrapping any failure in :class:`StepError`
naming the step and short-circuiting (executor.rs:30-57).

Architecture note: the reference makes steps ``async`` because its workers
interleave broker I/O with compute; here the host path is synchronous (the
throughput path is the compiled TPU pipeline in
:mod:`textblaster_tpu.ops.pipeline`, where "steps" are fused into one XLA
program and short-circuiting becomes mask intersection — see SURVEY.md §7
stage 3).  This host executor is the parity oracle and the fallback for
documents the device path cannot handle.

``run_batch`` returns results in *input order* — deliberately not inheriting
the reference's completion-order quirk (executor.rs:60-70; SURVEY.md §7
behavioral quirk #12).  It runs step-major: each step's ``process_batch``
takes every document still alive, so a step with a batched implementation
(TokenCounter's batched encode) makes one call per batch.  Steps are
per-document, so the results equal ``run_single`` on each document.
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Union

from .data_model import TextDocument
from .errors import PipelineError, StepError, UnexpectedError

__all__ = ["ProcessingStep", "PipelineExecutor"]


class ProcessingStep:
    """One pipeline op (reference ``executor.rs:8-15``).

    Subclasses set :attr:`name` and implement :meth:`process`, which either
    returns the (possibly mutated) document or raises a
    :class:`~textblaster_tpu.errors.PipelineError` —
    :class:`~textblaster_tpu.errors.DocumentFiltered` to drop the document.
    """

    name: str = "ProcessingStep"
    #: Documents this step has handled through its own batched
    #: ``process_batch`` rather than the default loop over ``process``.
    batched_docs: int = 0

    def process(self, document: TextDocument) -> TextDocument:
        raise NotImplementedError

    def process_batch(
        self, documents: Sequence[TextDocument]
    ) -> List[Union[TextDocument, Exception]]:
        """Each document's :meth:`process` result, or the exception it
        raised, in input order.  A step overrides this only with a batch
        call whose per-document results equal :meth:`process`'s."""
        out: List[Union[TextDocument, Exception]] = []
        for doc in documents:
            try:
                out.append(self.process(doc))
            except Exception as e:
                out.append(e)
        return out


def _step_error(step_name: str, error: Exception) -> StepError:
    """``error`` raised by step ``step_name``, wrapped as ``run_single``
    raises it: a pipeline error as it is, any other as Unexpected."""
    source = error if isinstance(error, PipelineError) else UnexpectedError(str(error))
    wrapped = StepError(step_name, source)
    wrapped.__cause__ = error
    return wrapped


class PipelineExecutor:
    """Ordered step list + short-circuiting runner (executor.rs:17-70)."""

    def __init__(self, steps: Sequence[ProcessingStep]):
        self.steps: List[ProcessingStep] = list(steps)

    def run_single(self, document: TextDocument) -> TextDocument:
        """Thread one document through every step (executor.rs:30-57).

        Any step failure is wrapped as ``StepError(step_name, source)`` and
        propagated immediately.
        """
        current = document
        for step in self.steps:
            try:
                current = step.process(current)
            except Exception as e:  # non-pipeline bugs surface as Unexpected
                raise _step_error(step.name, e) from e
        return current

    def run_batch(
        self, documents: Iterable[TextDocument]
    ) -> List[Union[TextDocument, StepError]]:
        """Run many documents step by step; per-document results in input
        order.  A document leaves the batch at its first failing step, as
        ``run_single`` short-circuits."""
        out: List[Union[TextDocument, StepError]] = list(documents)
        alive = list(range(len(out)))
        for step in self.steps:
            if not alive:
                break
            results = step.process_batch([out[i] for i in alive])
            still = []
            for i, result in zip(alive, results, strict=True):
                if isinstance(result, Exception):
                    out[i] = _step_error(step.name, result)
                else:
                    out[i] = result
                    still.append(i)
            alive = still
        return out

    def batched_docs(self) -> int:
        """Documents the steps have handled through their own batch calls."""
        return sum(step.batched_docs for step in self.steps)
