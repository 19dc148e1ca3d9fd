"""Compile the engine's chip programs for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler, which is installed with
JAX, compiles for a v5e topology that is described and not attached, and
refuses what the chip's compiler would refuse (misaligned blocks, a
program that does not fit the device, unsupported dtypes).  The topology
is built inside a fixture, never at import, so every xdist worker
collects the same tests and only the worker given this file loads the
TPU library.
"""

import importlib.util
import os
import re
from pathlib import Path

import numpy as np
import pytest

import jax
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import backend as B
from repro.core import stream


def _chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


class _Captured(Exception):
    pass


@pytest.fixture(scope="module")
def study_step():
    """Plan the 10^7-config study on one CPU device and capture the spec
    and the argument pytree of its first chunk step."""
    seen = {}

    def spy(spec, backend=None, scan_chunks=1, n_dev=1, devices=None):
        def run(*args):
            seen.update(spec=spec, scan=scan_chunks, args=args)
            raise _Captured
        return run

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(B, "cached_step", spy)
        with pytest.raises(_Captured):
            stream.stream_grid(**_chip_smoke().STUDY_1E7, prefetch=0,
                               devices=jax.devices("cpu")[:1])
    return seen


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=sharding), tree)


@pytest.fixture(scope="module")
def compiled_study_step(study_step, one_chip, no_persistent_cache):
    spec, scan = study_step["spec"], study_step["scan"]
    assert spec.chunk == stream.DEFAULT_CHUNK == 1 << 17
    assert spec.n_total == 10_009_600 and spec.cons_static
    with jax.enable_x64(True):
        step = B.build_step(spec, "xla", scan, 1)
        return step.lower(*_shapes(study_step["args"], one_chip)).compile()


def test_study_chunk_step_compiles_for_one_v5e(compiled_study_step):
    mem = compiled_study_step.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16e9              # one v5e holds 16 GB of HBM


def test_study_chunk_step_dominance_filter_has_no_loop(compiled_study_step):
    # The prefix-min table's bin is a dense count on the device, not a
    # search loop of per-lane gathers (pareto.edge_rank).
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = .*?\s([a-z][\w-]*)\("
                     r".*op_name=\"([^\"]*)\"",
                     compiled_study_step.as_text(), re.M)
    scoped = [op for op, name in ops if "/dominance_filter/" in name]
    assert scoped and "while" not in scoped


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Mosaic refuses the sweep_grid kernel: its (n, 1) per-block partial "
    "outputs are not (8, 128)-tile aligned, and its outputs are float64 "
    "(ROADMAP Speed items 2-3)"))
def test_pallas_chunk_call_compiles_for_one_v5e(study_step, one_chip,
                                                no_persistent_cache):
    from repro.kernels.sweep_grid import build_chunk_call

    _, axvals, aux, start = study_step["args"]
    with jax.enable_x64(True):
        fn = jax.jit(build_chunk_call(study_step["spec"], interpret=False))
        fn.lower(*_shapes((axvals, aux, start), one_chip)).compile()
