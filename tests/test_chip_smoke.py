"""``chip_smoke.py``: refuses to run without a TPU, and its phases hold on
the CPU at the 10,880-config reference grid (a rehearsal of the chip
run's logic; the chip run itself needs the chip)."""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# The 10,880-config reference grid — keep in lockstep with
# benchmarks/sweep_bench.py::GRID (see tests/test_stream.py).
REFERENCE_GRID = dict(
    agg_nodes=("7nm", "16nm"),
    sensor_nodes=("7nm", "16nm"),
    weight_mems=("sram", "mram"),
    detnet_fps=(5.0, 10.0, 15.0, 20.0, 30.0),
    keynet_fps=(15.0, 30.0),
    num_cameras=(2, 4),
    mipi_energy_scale=(1.0, 2.0),
)
STUDY = dict(REFERENCE_GRID,
             constraints={"latency": 15e-3, "mipi_bytes_per_s": 25e6})


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


class TestRefusal:
    def test_exits_nonzero_without_a_tpu(self):
        out = _run(ROOT / "chip_smoke.py", ROOT)
        assert out.returncode == 2, out.stderr[-2000:]
        assert out.stdout == ""
        assert "TPU" in out.stderr

    def test_exits_nonzero_outside_a_checkout(self, tmp_path):
        shutil.copy(ROOT / "chip_smoke.py", tmp_path)
        out = _run(tmp_path / "chip_smoke.py", tmp_path)
        assert out.returncode == 2, out.stderr[-2000:]
        assert out.stdout == ""


@pytest.fixture(scope="module")
def cs():
    return _load()


@pytest.fixture(scope="module")
def clock(cs):
    return cs.CompileClock()


@pytest.fixture(scope="module")
def study(cs, clock):
    return cs.phase_study(STUDY, clock, "cpu")


@pytest.fixture(scope="module")
def invariant(cs, clock):
    return cs.phase_invariant(REFERENCE_GRID, clock)


class TestPhasesOnCpu:
    def test_study_is_clean(self, study):
        assert study.n_configs == 10_880 and not study.partial
        assert 0 < study.finite_counts["avg_power"] < study.n_configs

    def test_reference_is_bitwise_on_the_same_platform(self, cs, study,
                                                       clock, capsys):
        assert cs.phase_reference(STUDY, study, clock)
        assert '"bitwise": true' in capsys.readouterr().out

    def test_dense_and_streaming_agree_exactly(self, invariant):
        assert invariant[1]

    def test_served_results_are_bitwise_and_resubmit_dedupes(
            self, cs, study, invariant, clock):
        assert cs.phase_service(STUDY, REFERENCE_GRID, study, invariant[0],
                                clock)

    def test_same_result_sees_one_ulp(self, cs, study):
        import dataclasses

        import numpy as np
        tv = study.topk_val.copy()
        tv[0, 0] = np.nextafter(tv[0, 0], np.inf)
        other = dataclasses.replace(study, topk_val=tv)
        assert cs.same_result(study, study)
        assert not cs.same_result(study, other)
