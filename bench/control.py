"""The control of the comparison: the plain reference computed in
float32, one precision below the float64 the configurations state, put
in the program's place.  ``compare.py``'s limits have to reject it.

    python bench/control.py --workload <cell> --seeds 11,12,13

draws the answers a run of the cell with each seed compares (the same
studies or queries, at the cell's own sizes), solves each in float64 and
in float32, and prints one JSON line per seed with the worst reading of
each compared number.  It needs no accelerator and touches no JAX.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import compare                                                # noqa: E402
import reference                                              # noqa: E402
import traffic as traffic_mod                                 # noqa: E402


def drawn(config: dict, traffic: dict, seed: int, seconds: float,
          n: int) -> list:
    """The first ``n`` studies, or a seeded sample of ``n`` queries, of
    a run of the cell."""
    if traffic["kind"] == "closed_loop":
        return list(itertools.islice(traffic_mod.studies(config, seed), n))
    sched = traffic_mod.queries(config, traffic, seed, seconds)
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.choice(len(sched), size=min(n, len(sched)), replace=False)
    return [sched[i][1:3] for i in sorted(pick)]


def readings(config: dict, traffic: dict, seed: int, seconds: float,
             n: int, dtype=np.float32) -> dict:
    """Worst compared numbers of the reference at ``dtype`` against the
    float64 reference, over the answers of one seed."""
    out = []
    for study, budgets in drawn(config, traffic, seed, seconds, n):
        ref = reference.solve(study, budgets, config["objectives"],
                              config["top_k"])
        low = reference.solve(study, budgets, config["objectives"],
                              config["top_k"], dtype=dtype)
        out.append(compare.compare(compare.as_control(low), ref, study,
                                   budgets, config["objectives"]))
    return compare.worst(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51)
    args = ap.parse_args(argv)
    root = BENCH.parent
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((BENCH / "traffic"
                          / f"{cell['traffic']}.json").read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        nums = readings(config, traffic, seed, args.seconds,
                        int(traffic.get("sample", 1)))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": nums,
                          "rejected": not compare.within(nums)},
                         default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
