"""The comparison rejects its control and every fault a cell can have.

The control is the plain reference one precision lower (float32) in the
program's place.  The faults are planted in the program underneath a
whole run of the harness on the CPU (the look for a chip skipped): an
answer altered where it is produced, half of each chunk left out of
every reduction, and, on four devices, the exchange that merges the
devices' carries left out.
"""

import dataclasses
import json
import os
import subprocess
import sys
from collections import OrderedDict
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(BENCH), str(REPO / "src")]

import compare  # noqa: E402
import control  # noqa: E402
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("traffic", ["batch", "tiny-open"])
@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_control_is_rejected(root, traffic, seed):
    t = json.loads((root / "bench" / "traffic"
                    / f"{traffic}.json").read_text())
    nums = control.readings(tiny.TINY, t, seed, 2.0, 2)
    assert not compare.within(nums)
    assert nums["rel_err"] > 30 * compare.LIMITS["rel_err"]
    sound = control.readings(tiny.TINY, t, seed, 2.0, 2, dtype=np.float64)
    assert compare.within(sound) and sound["rel_err"] == 0.0


def _alter_answer(monkeypatch):
    from repro.core import stream

    orig = stream.stream_grid

    def altered(*a, **kw):
        res = orig(*a, **kw)
        fv = np.array(res.front_values)
        fv[0, 0] *= 1 + 1e-6
        return dataclasses.replace(res, front_values=fv)

    monkeypatch.setattr(stream, "stream_grid", altered)


def _half_left_out(monkeypatch):
    from repro.core import backend

    orig = backend.chunk_partials

    def half(spec, F, flat, ingrid, aux):
        return orig(spec, F, flat, ingrid & (flat % 2 == 0), aux)

    monkeypatch.setattr(backend, "chunk_partials", half)


FAULTS = {"none": None, "answer_altered": _alter_answer,
          "half_left_out": _half_left_out}


@pytest.mark.parametrize("cell", ["tiny.batch", "tiny.open"])
@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_sound_run_is_correct_and_a_broken_one_is_not(root, monkeypatch,
                                                       cell, fault):
    from repro.core import backend

    # Compiled steps from earlier runs must not hide a planted fault.
    monkeypatch.setattr(backend, "_STEP_CACHE", OrderedDict())
    if FAULTS[fault]:
        FAULTS[fault](monkeypatch)
    out, _ = tiny.run_cell(root, cell, seed=11)
    assert out["correct"] is (fault == "none"), out["checks"]


def _fourth_channel_scaled(monkeypatch):
    """On-sensor compute, the four-objective study's fourth channel,
    off by one part in a million where the kernel produces it."""
    import functools

    from repro.core import backend, sweep

    orig = sweep.vmapped_kernel

    def scaled(S):
        kernel = orig(S)

        def wrong(*args):
            out = dict(kernel(*args))
            out["sensor_macs_per_s"] = out["sensor_macs_per_s"] * (1 + 1e-6)
            return out
        return wrong

    monkeypatch.setattr(sweep, "vmapped_kernel", scaled)
    monkeypatch.setattr(backend, "_cached_dense_eval", functools.lru_cache(
        maxsize=32)(backend._cached_dense_eval.__wrapped__))


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_the_four_objective_control_is_rejected(root, seed):
    t = json.loads((root / "bench" / "traffic" / "batch.json").read_text())
    nums = control.readings(tiny.TINY_4OBJ, t, seed, 2.0, 2)
    assert not compare.within(nums)
    assert nums["rel_err"] > 30 * compare.LIMITS["rel_err"]


@pytest.mark.parametrize("fault", ["none", "fourth_channel_scaled",
                                   "answer_altered"])
def test_a_four_objective_cell_added_as_files_and_entries_alone(
        root, monkeypatch, fault):
    """The four-objective study with a budget on on-sensor memory runs
    as data alone and is correct, and a fault in its fourth channel or
    in an answer is not."""
    from repro.core import backend

    monkeypatch.setattr(backend, "_STEP_CACHE", OrderedDict())
    if fault == "fourth_channel_scaled":
        _fourth_channel_scaled(monkeypatch)
    elif fault == "answer_altered":
        _alter_answer(monkeypatch)
    out, _ = tiny.run_cell(root, "tiny-4obj.batch", seed=17)
    assert out["correct"] is (fault == "none"), out["checks"]


CHILD = """
import sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import tiny
from pathlib import Path
if sys.argv[4] == "exchange_left_out":
    from repro.core import backend
    orig = backend.merge_device_carries
    backend.merge_device_carries = lambda c, k: orig(
        {n: v[:1] for n, v in c.items()}, k)
out, _ = tiny.run_cell(Path(sys.argv[3]), "tiny.batch-4", seed=13)
print("CORRECT", out["correct"], out["device"]["count"])
"""


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_four_devices_and_the_exchange_left_out(root, fault):
    env = {k: v for k, v in os.environ.items()
           if k not in ("ALLOW_MULTIPLE_LIBTPU_LOAD",)}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, str(BENCH),
                        str(REPO / "src"), str(root), fault],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert f"CORRECT {fault == 'none'} 4" in p.stdout, p.stdout[-2000:]
