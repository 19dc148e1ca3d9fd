"""A checkout with tiny cells beside the real ones, for the CPU tests.

:func:`make_root` copies ``BENCHMARK.json`` and ``bench/`` into a
directory, links the program's ``src/``, and adds a 43,520-config
configuration with a batch, an open-loop and a four-device cell, and a
four-objective study of the same space with a batch cell, as files and
entries alone — the way a later change adds a cell.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

TINY = {
    "name": "tiny", "source": "https://arxiv.org/abs/2203.07474",
    "axes": {"cut": list(range(34)), "agg_nodes": ["7nm", "16nm"],
             "sensor_nodes": ["7nm", "16nm"],
             "weight_mems": ["sram", "mram"],
             "detnet_fps": {"linspace": [5.0, 30.0, 4]},
             "keynet_fps": [15.0, 30.0], "num_cameras": [2, 4],
             "mipi_energy_scale": [1.0, 2.0],
             "camera_fps": {"linspace": [20.0, 60.0, 5]}},
    "objectives": ["avg_power", "latency", "mipi_bytes_per_s"],
    "top_k": 4,
    "constraints": {"latency": {"uniform": [0.014, 0.016]},
                    "mipi_bytes_per_s": {"uniform": [22e6, 28e6]}},
    "jitter": {"detnet_fps": 0.1, "camera_fps": 0.1},
    "reduced": [], "assumed": []}

#: The same space with on-sensor compute as a fourth objective and a
#: budget on on-sensor memory power.
TINY_4OBJ = dict(
    TINY, name="tiny-4obj",
    objectives=["avg_power", "latency", "mipi_bytes_per_s",
                "sensor_macs_per_s"],
    constraints=dict(TINY["constraints"],
                     sensor_memory={"uniform": [1.0e-3, 2.0e-3]}))

TINY_OPEN = {"kind": "open_loop", "rate_per_s": 8.0,
             "query": {"detnet_fps": 2, "num_cameras": 1,
                       "mipi_energy_scale": 1, "camera_fps": 3},
             "tenants": {"interactive": {"weight": 3.0},
                         "batch": {"weight": 1.0, "max_pending": 8}},
             "tenant_share": {"interactive": 3, "batch": 1},
             "senders": 4, "warmup_queries": 1, "sample": 4}


def make_root(dest: Path) -> Path:
    dest = Path(dest)
    shutil.copytree(BENCH, dest / "bench", ignore=shutil.ignore_patterns(
        ".jax_cache", "__pycache__"))
    os.symlink(REPO / "src", dest / "src")
    (dest / "bench" / "traffic" / "tiny-open.json").write_text(
        json.dumps(TINY_OPEN))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for config in (TINY, TINY_4OBJ):
        path = f"bench/configs/{config['name']}.json"
        (dest / path).write_text(json.dumps(config))
        bench["configs"].append({"name": config["name"],
                                 "source": config["source"], "file": path,
                                 "reduced": [], "why": "CPU tests"})
    for name, config, traffic, chips in (
            ("tiny.batch", "tiny", "batch", 1),
            ("tiny.open", "tiny", "tiny-open", 1),
            ("tiny.batch-4", "tiny", "batch", 4),
            ("tiny-4obj.batch", "tiny-4obj", "batch", 1)):
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "CPU tests"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            like = {"tiny.open": "ht-partition-1e7.interactive"}.get(
                name, "ht-partition-1e7.batch")
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dest


def run_cell(root: Path, cell: str, seed: int = 5, seconds: float = 1.0,
             trace: int = 0) -> tuple:
    """``run.main`` on the CPU (the look for a chip skipped); returns its
    result line and the earlier lines.  JAX's compilation-cache setting
    is restored after."""
    import jax
    import run

    prev = jax.config.jax_compilation_cache_dir
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = run.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace",
                           str(trace)], require_chip=False, root=root)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    assert rc == 0, buf.getvalue()
    lines = buf.getvalue().strip().splitlines()
    return json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]
