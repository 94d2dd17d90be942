"""The main path's kernels compile for a TPU v5e (no chip needed).

Interpret mode runs every kernel's program on the CPU but never asks Mosaic
to lower it, so a kernel can pass every parity suite and still be refused by
the chip's compiler (lane reversal, unaligned dynamic slices, bool selects,
scoped-VMEM overflows — each one happened).  These tests compile for a
*described* ``v5e:2x2`` chip: the TPU compiler is installed here and
compiles for a device that is not attached.  Nothing runs.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library at a time, and every xdist worker imports
every test file.  The persistent compilation cache is off around these
tests (an entry compiled for a described chip cannot be read back here).
"""

import os
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from textblaster_tpu.ops import pallas_scan as psc  # noqa: E402
from textblaster_tpu.ops import pallas_sort as pso  # noqa: E402

ROWS = 64  # the TPU default geometry's rows at the widest bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    x64_was = jax.config.jax_enable_x64
    # The chip runs with x64 off; the suite's CPU configuration turns it on.
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_enable_x64", x64_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Real (not interpret-mode) kernels, the hatch open."""
    for var in ("TEXTBLAST_PALLAS", "TEXTBLAST_PALLAS_INTERPRET"):
        monkeypatch.delenv(var, raising=False)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_scan_kernel_compiles(one_chip, compiled_kernels):
    def fn(m, a, _):
        return psc._pallas_scan_tuple(psc._affine_op, (1, 0), (m, a, a), False)

    _compile(fn, [((ROWS, psc._MAX_LANES), jnp.int32)] * 3, one_chip)


def test_fused_kernel_compiles(one_chip, compiled_kernels):
    def fn(m, a, ones, fns):
        return psc._fused_call(
            [
                psc.affine_group(m, (a,)),
                psc.add_group((ones,), emit="last"),
                psc.dfa_group(fns, 4),
            ],
            False,
        )

    _compile(fn, [((ROWS, psc._FUSED_MAX_LANES), jnp.int32)] * 4, one_chip)


def test_chain_kernel_compiles(one_chip, compiled_kernels):
    """Reverse-walk passes, shift taps across block edges, VMEM scratch and
    every group kind — the surface the probe checks on the chip."""

    def fn(m, vals, reset):
        passes = [
            psc.chain_pass([{"kind": "affine", "xs": (m, vals), "emit": "none"}]),
            psc.chain_pass(
                [
                    psc.chain_group(
                        "segmax",
                        (psc.Tap(0, 0), reset),
                        prep=lambda seg, r: (jnp.where(r != 0, seg, 0), r),
                        n_ops=2,
                    )
                ],
                reverse=True,
            ),
            psc.chain_pass(
                [
                    psc.chain_group(
                        "copy",
                        (psc.Tap(1, 0), psc.Tap(0, 0, shift=1, fill=0)),
                        prep=lambda rt, prev: (rt + prev,),
                        n_ops=1,
                        emit="scan",
                    ),
                    psc.chain_group(
                        "affine",
                        (psc.Tap(1, 0, shift=1, fill=3), vals),
                        n_ops=2,
                        emit="last",
                    ),
                ],
                reverse=True,
            ),
        ]
        return psc.chain_scan(passes)

    _compile(fn, [((ROWS, 8192), jnp.int32)] * 3, one_chip)


@pytest.mark.parametrize("n_keys", [2, 3])
def test_sort_kernel_compiles(one_chip, compiled_kernels, n_keys):
    # The widest row the gate admits: its looped network compiles in ~7 s
    # (unrolled, 8192 lanes took 41 s and 32768 never finished).
    def fn(*ks):
        return pso._pallas_sort_n(ks)

    _compile(fn, [((ROWS, pso._MAX_SORT_LANES), jnp.int32)] * n_keys, one_chip)


@pytest.fixture
def tpu_defaults(compiled_kernels, monkeypatch):
    """The TPU's own wire choice and kernel probes (this process's backend
    is the CPU, so they are steered here; scans and tables are the same
    program on every backend)."""
    monkeypatch.setenv("TEXTBLAST_WIRE", "u16")
    for mod, name in ((pso, "_probe_backend"), (psc, "_probe_backend"),
                      (psc, "_probe_fused"), (psc, "_probe_depfuse")):
        monkeypatch.setattr(mod, name, lambda: True)


#: An HLO instruction's name and the dims of its array result.
HLO_DEF = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]", re.M)
#: A gather and the name of its table operand.
HLO_GATHER = re.compile(r"= \S+ gather\(%([\w.\-]+),")


def _small_gather_tables(text):
    """Sizes of the 1-D tables of 5-196 entries that gathers read: the
    sizes of the constant sets (ops/device.py isin_sorted), whose binary
    search was a chain of full-batch gathers on the chip."""
    dims = dict(HLO_DEF.findall(text))
    sizes = [dims.get(name, "") for name in HLO_GATHER.findall(text)]
    return [d for d in sizes if d.isdigit() and 5 <= int(d) <= 196]


def test_shipped_pipeline_programs_compile(one_chip, tpu_defaults):
    """Every phase program of the shipped config at the 2048 bucket, with
    the TPU's own defaults: the chain preps in ops/stats.py lower inside
    Mosaic, and no constant-set membership searches or gathers."""
    from textblaster_tpu.config.pipeline import load_pipeline_config
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    pipeline = CompiledPipeline(
        load_pipeline_config("configs/pipeline_config_offline.yaml"),
        batch_size=ROWS,
    )
    for phase in range(len(pipeline.phases)):
        fn = pipeline._build_fn(2048, phase, jit=False)
        text = _compile(
            fn,
            [((ROWS, 2048), jnp.uint16), ((ROWS,), jnp.int32)],
            one_chip,
        ).as_text()
        assert not re.search(r'op_name="[^"]*searchsorted', text), phase
        assert _small_gather_tables(text) == [], phase


#: A collective operation in compiled HLO text, by its kind.
COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?\("
)

#: Collectives per phase program of the Danish job over a data mesh: one
#: all-reduce in phase 1, the OR of the duplicate walk's batch-wide gate
#: (ops/stats.py gopher_rep_stats), and two in phase 2, the ORs of C4's
#: citation and pattern gates (c4_stage, _pattern_union_starts; two of the
#: three share one all-reduce).  Each gate skips real work on every chip.
MESH_COLLECTIVES = {0: [], 1: ["all-reduce"], 2: ["all-reduce"] * 2}


@pytest.mark.parametrize("bucket", [2048, 32768])
def test_mesh_programs_keep_only_the_gate_all_reduces(topo, tpu_defaults, bucket):
    """Every phase program of the benchmark's Danish job over the 4-chip
    data mesh, at 64 rows per chip, on both sides of the fused kernels'
    16,384-lane gate.  The row sorts stack their jobs so that each chip's
    rows stay on it (``ops/stats.py`` ``_stack_rows``), so the compiler
    moves no row between chips; what is left are the scalar ORs of the
    batch-wide ``lax.cond`` gates."""
    from jax.sharding import Mesh

    from textblaster_tpu.config.pipeline import load_pipeline_config
    from textblaster_tpu.ops.pipeline import CompiledPipeline

    chips = len(topo.devices)
    pipeline = CompiledPipeline(
        load_pipeline_config("benchmark/configs/danish_cc.yaml"),
        batch_size=chips * ROWS,
        mesh=Mesh(np.array(topo.devices), ("data",)),
    )
    assert pipeline.wire_u16
    for phase in range(len(pipeline.phases)):
        compiled = pipeline._fn_for(bucket, phase).lower(
            jax.ShapeDtypeStruct((chips * ROWS, bucket), jnp.uint16),
            jax.ShapeDtypeStruct((chips * ROWS,), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        found = [kind for kind, _ in COLLECTIVE.findall(text)]
        assert found == MESH_COLLECTIVES[phase], (bucket, phase)
