"""The four-chip host's configuration and the mesh layer's metric readers."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.metrics import collective_ms_per_kdoc, mesh_upload_ms_per_kdoc  # noqa: E402
from benchmark.tests import test_reference  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "configs")


def test_host4_runs_the_shipped_danish_job_unchanged():
    with open(os.path.join(CONFIGS, "danish_cc_host4.yaml"), "rb") as f:
        host4 = f.read()
    with open(os.path.join(CONFIGS, "danish_cc.yaml"), "rb") as f:
        assert host4 == f.read()


def test_host4_control_is_not_correct():
    test_reference.test_control_is_not_correct("danish_cc_host4", "mixed")


OPS = {
    "%all-reduce.3 = (u32[]{:T(128)}, u32[]{:T(128)}) all-reduce(%bitcast.6, %bitcast.8), "
    "channel_id=2, replica_groups=[1,4]<=[4]": 0.002,
    "%collective-permute-start.58 = (s32[16,2048]{1,0}, s32[16,2048]{1,0}, u32[], u32[]) "
    "collective-permute-start(%slice.3), channel_id=9": 0.001,
    "%collective-permute-done.71 = s32[16,512]{1,0} collective-permute-done("
    "%collective-permute-start.71)": 0.003,
    "%all-to-all.50 = s32[4,1,1024]{2,1,0} all-to-all(%reshape.750), dimensions={0}": 0.004,
    "%all-gather-start = (s32[16]{0}, s32[64]{0}) all-gather-start(%p.1)": 0.001,
    "%fusion.12 = s32[64,2048]{1,0} fusion(%all-reduce.3, %p.2), kind=kLoop, "
    "calls=%fused_computation.12": 5.0,
    '%custom-call.3 = s32[64,2048]{1,0} custom-call(%p.3), custom_call_target="tpu_custom_call"': 2.0,
}


def test_collective_time_per_kdoc():
    record = {"docs": 2000, "trace": {"op_s": OPS, "devices": 4}}
    # 0.011 s of collectives over all chips, per 2 kdoc.
    assert collective_ms_per_kdoc.read(record) == pytest.approx(5.5)


def test_no_collective_reads_zero_and_no_trace_nothing():
    ops = {k: v for k, v in OPS.items() if k.startswith(("%fusion", "%custom-call"))}
    assert collective_ms_per_kdoc.read({"docs": 2000, "trace": {"op_s": ops}}) == 0.0
    assert collective_ms_per_kdoc.read({"docs": 2000, "trace": None}) is None
    assert collective_ms_per_kdoc.read({"docs": 0, "trace": {"op_s": OPS}}) is None


def test_mesh_upload_per_kdoc_and_a_program_without_the_counter():
    record = {"docs": 4000, "counters": {"stage_dispatch_seconds": 1.0}}
    assert mesh_upload_ms_per_kdoc.read(record) is None
    record["counters"]["stage_mesh_upload_seconds"] = 0.2
    assert mesh_upload_ms_per_kdoc.read(record) == pytest.approx(50.0)
