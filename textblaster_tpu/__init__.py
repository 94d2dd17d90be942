"""textblaster_tpu — a TPU-native framework for large-scale text-dataset
cleaning with the capabilities of kris927b/TextBlaster.

Where the reference fans documents out to Rust workers over RabbitMQ, this
framework is a single SPMD JAX/XLA program: Parquet row-groups are sharded
across TPU chips, documents live in HBM as packed ragged UTF-8 byte tensors,
filters run as vectorized XLA/Pallas scans producing keep/drop masks and
reason codes, and masks are gathered over ICI/DCN so the host streams one
kept/excluded Parquet pair — no broker hop.

Layer map (TPU-native re-design of SURVEY.md §1):

* :mod:`~textblaster_tpu.data_model` / :mod:`~textblaster_tpu.errors` — L1
  foundations (document record, outcome, error hierarchy).
* :mod:`~textblaster_tpu.utils.text` — L1 text primitives (UAX#29-lite
  segmentation shared by host oracle and device kernels).
* :mod:`~textblaster_tpu.config` — YAML pipeline spec + validation + CLI.
* :mod:`~textblaster_tpu.io` — Parquet reader/writer (reference schema).
* :mod:`~textblaster_tpu.filters` — L3 host-path steps (parity oracle).
* :mod:`~textblaster_tpu.executor` — L4 host executor.
* :mod:`~textblaster_tpu.ops` — L3/L4 device path: packed batches + fused
  filter kernels compiled with jit.
* :mod:`~textblaster_tpu.parallel` — L5/L6 sharding runtime (mesh, pjit,
  collective aggregation) replacing the reference's AMQP layer.
* :mod:`~textblaster_tpu.models` — statistical language-ID model.
"""

__version__ = "0.1.0"

from .data_model import ProcessingOutcome, TextDocument  # noqa: F401
from .errors import (  # noqa: F401
    ConfigError,
    ConfigValidationError,
    DocumentFiltered,
    PipelineError,
    StepError,
)
from .executor import PipelineExecutor, ProcessingStep  # noqa: F401
