"""Device mesh + sharding for the compiled pipeline.

The reference scales by adding competing consumers on a RabbitMQ queue
(SURVEY.md §2.5); here the equivalent is SPMD data parallelism over a
``jax.sharding.Mesh``: packed batches are sharded along the ``data`` axis
(:func:`shard_batch`), the compiled filter program runs identically on every
chip over its shard (its Pallas kernels under ``shard_map``), and the (small)
integer stat outputs stay sharded and are fetched to the host.  A document's
statistics need no other row, and no row moves between chips: the row sorts
that stack several tables interleave them by row on a mesh
(``ops/stats.py`` ``_stack_rows``).  The programs' only collectives are the
scalar ORs of three batch-wide ``lax.cond`` gates, which skip real work on
every chip: one all-reduce in each phase-1 program and two in each phase-2
program of the Danish job, compiled for a described v5e 2x2
(``tests/test_tpu_compile.py``).  A process that drives the whole mesh gives
each chip the rows one chip holds alone (``CompiledPipeline``).

Multi-host: :mod:`textblaster_tpu.parallel.multihost` — every process joins a
``jax.distributed`` coordinator, the mesh spans all hosts' devices, each host
feeds its local shard (``jax.make_array_from_process_local_data``) and
assembles outcomes from its addressable output rows; cross-host traffic rides
DCN where XLA places it.  Exercised by ``tests/test_multihost.py`` as a
2-process CPU job.  Single-host multi-chip needs no extra code.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["data_mesh", "shard_batch", "batch_sharding"]

DATA_AXIS = "data"


def data_mesh(devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """1-D mesh over all (or the given) devices along the ``data`` axis."""
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), (DATA_AXIS,))


def batch_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard axis 0 (documents) across the mesh; other axes replicated."""
    spec = P(DATA_AXIS, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def shard_batch(mesh: Mesh, cps: np.ndarray, lengths: np.ndarray):
    """Place a packed batch on the mesh, sharded along the document axis
    (``CompiledPipeline.dispatch_batch`` times it as
    ``stage_mesh_upload_seconds``)."""
    cps_s = jax.device_put(cps, batch_sharding(mesh, 2))
    len_s = jax.device_put(lengths, batch_sharding(mesh, 1))
    return cps_s, len_s
