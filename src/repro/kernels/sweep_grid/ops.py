"""Registry integration: the Pallas evaluation backend.

Importing this module (or calling ``backend.get_backend("pallas")``,
which imports it lazily) registers :class:`PallasGridBackend` under the
name ``"pallas"`` — after which ``sweep.evaluate_grid``,
``stream.stream_grid`` and ``partition.optimal_partition`` all accept
``backend="pallas"``.
"""

from __future__ import annotations

import jax

from repro.core import backend as B

from . import kernel


class PallasGridBackend(B.EvalBackend):
    """Evaluation backend lowering the chunk contract onto
    :mod:`.kernel`'s fused ``pallas_call``.

    The kernel runs in Pallas interpret mode on the CPU (the CI/parity
    configuration) and nowhere else: on any other platform building a
    step raises :class:`NotImplementedError` (see :func:`_interpret`)
    instead of silently interpreting on the host or failing deep in the
    Mosaic lowering.  The multi-device ``pmap`` path is not supported —
    shard across Pallas-capable devices by passing explicit
    single-device ``devices=`` lists per process instead.  Scenario
    sweeps (``scenarios=`` — the session ``lax.scan`` kernel of
    :mod:`repro.core.scenario`) are not supported either: this kernel
    re-implements the Eq. 1-11 evaluation as a fused block body and
    does not lower the per-lane scan; ``backend.check_scenario_support``
    routes such sweeps to the XLA backend with a clear error.
    """

    name = "pallas"
    supports_pmap = False
    supports_scenarios = False

    def build_chunk_eval(self, spec):
        return kernel.build_chunk_call(spec, interpret=_interpret())

    def build_dense_eval(self, S, shape, fields):
        fields = tuple(fields)
        shape = tuple(shape)
        interpret = _interpret()

        @jax.jit
        def evalfn(axvals, flat):
            return kernel.sweep_grid_eval(S, shape, fields, axvals, flat,
                                          interpret=interpret)

        return evalfn


def _interpret() -> bool:
    """Interpret mode on the CPU; refuse every other platform.

    The compiled Mosaic lowering does not exist yet, and interpreting
    the kernel on the host of an accelerator would hide the device."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    raise NotImplementedError(
        f"backend='pallas' cannot run on {platform!r}: the sweep_grid "
        f"kernel has no compiled Mosaic lowering yet.  The TPU compiler "
        f"refuses its per-block partial outputs ((n, 1) blocks of an "
        f"(n, n_blocks) array are not (8, 128)-aligned, the pallas_call "
        f"of kernels/sweep_grid/kernel.py build_chunk_call) and its "
        f"float64 outputs (ROADMAP Speed items 2-3: the float32 device "
        f"path, then the compiled lowering).  Use backend='xla' (the "
        f"default) on accelerators; Pallas interpret mode runs only on "
        f"the CPU.")


B.register_backend(PallasGridBackend())
