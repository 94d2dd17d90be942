"""``textblast run --backend tpu`` never runs on the CPU without saying so.

The compiled pipeline runs on JAX's default platform.  On a machine whose
TPU did not come up that platform is the CPU, so the CLI refuses to start
unless ``JAX_PLATFORMS=cpu`` asks for the CPU on purpose (as this suite and
the driver's test command do) — ``--backend cpu`` is the other explicit
spelling.
"""

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

jax = pytest.importorskip("jax")

from textblaster_tpu import cli  # noqa: E402
from textblaster_tpu.ops.device import tpu_refusal  # noqa: E402


@pytest.fixture
def platforms_unset():
    """JAX's platform list as if JAX_PLATFORMS were unset.  The backends of
    this process are already up (CPU), so nothing new initializes."""
    was = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    try:
        yield
    finally:
        jax.config.update("jax_platforms", was)


def _shard(tmp_path):
    path = tmp_path / "in.parquet"
    pq.write_table(pa.table({"id": ["a"], "text": ["Det er en god dag."]}), path)
    return path


def test_backend_tpu_refuses_a_cpu_default_platform(tmp_path, capsys, platforms_unset):
    assert jax.default_backend() == "cpu"
    out = tmp_path / "out.parquet"
    rc = cli.main([
        "run", "-i", str(_shard(tmp_path)),
        "-c", "configs/pipeline_config_offline.yaml",
        "-o", str(out), "-e", str(tmp_path / "exc.parquet"),
        "--backend", "tpu", "--quiet",
    ])
    assert rc == 1
    assert "needs a TPU" in capsys.readouterr().err
    assert not out.exists()


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_cpu_runs_when_asked_for(tmp_path, backend):
    """JAX_PLATFORMS=cpu (the suite's setting) or --backend cpu: the
    compiled pipeline runs on the CPU on purpose."""
    assert str(jax.config.jax_platforms) == "cpu"
    assert tpu_refusal() == ""
    out = tmp_path / "out.parquet"
    rc = cli.main([
        "run", "-i", str(_shard(tmp_path)),
        "-c", "configs/pipeline_config_offline.yaml",
        "-o", str(out), "-e", str(tmp_path / "exc.parquet"),
        "--backend", backend, "--quiet",
    ])
    assert rc == 0
    assert out.exists()


@pytest.mark.parametrize("elastic", [False, True])
def test_gang_refuses_a_cpu_default_platform(tmp_path, platforms_unset, elastic):
    """A --coordinator run checks once its gang has formed (it may not touch
    a backend before jax.distributed initializes), on every transport."""
    from textblaster_tpu.config.pipeline import load_pipeline_config
    from textblaster_tpu.errors import PipelineError
    from textblaster_tpu.parallel.multihost import run_multihost

    out = tmp_path / "out.parquet"
    with pytest.raises(PipelineError, match="needs a TPU"):
        run_multihost(
            load_pipeline_config("configs/pipeline_config_offline.yaml"),
            str(_shard(tmp_path)), str(out), str(tmp_path / "exc.parquet"),
            coordinator="localhost:1", num_processes=1, process_id=0,
            exchange_transport="kv" if elastic else "file", elastic=elastic,
        )
    assert not out.exists()
