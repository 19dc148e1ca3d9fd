"""The one traffic generator: studies and queries drawn from a seed.

A configuration file (``configs/<name>.json``) fixes the design space:
its axes, objectives, top-k and the ranges its budgets and frame-rate
endpoints are drawn from.  A traffic file (``traffic/<name>.json``)
fixes how work arrives: ``closed_loop`` runs whole studies back to
back, ``open_loop`` sends sub-studies at fixed seeded arrival times.
The seed changes values only, never shapes, so one compiled program
serves every study or query of a cell.
"""

from __future__ import annotations

import numpy as np

AXES = ("cut", "agg_nodes", "sensor_nodes", "weight_mems", "detnet_fps",
        "keynet_fps", "num_cameras", "mipi_energy_scale", "camera_fps")


def _values(spec, rng=None, jitter=0.0) -> tuple:
    """An axis from its spec: a list, or ``{"linspace": [lo, hi, n]}``
    whose endpoints each move by at most ``jitter`` (a share) when a
    generator is given."""
    if isinstance(spec, dict):
        lo, hi, n = spec["linspace"]
        if rng is not None and jitter:
            lo *= 1.0 + rng.uniform(-jitter, jitter)
            hi *= 1.0 + rng.uniform(-jitter, jitter)
        return tuple(float(v) for v in np.linspace(lo, hi, int(n)))
    return tuple(spec)


def base_study(config: dict) -> dict:
    """The configuration's own axes, with no draw."""
    return {a: _values(config["axes"][a]) for a in AXES}


def _budgets(config: dict, rng) -> dict:
    return {f: float(rng.uniform(*spec["uniform"]))
            for f, spec in config.get("constraints", {}).items()}


def draw_study(config: dict, rng) -> tuple[dict, dict]:
    """One whole study: axes with jittered rate ranges, and budgets."""
    jit = config.get("jitter", {})
    study = {a: _values(config["axes"][a], rng, jit.get(a, 0.0))
             for a in AXES}
    return study, _budgets(config, rng)


def studies(config: dict, seed: int):
    """Endless stream of studies for ``seed``."""
    rng = np.random.default_rng([int(seed), 0])
    while True:
        yield draw_study(config, rng)


def draw_query(config: dict, traffic: dict, rng) -> tuple[dict, dict]:
    """One sub-study: a window of consecutive values on each sized
    axis of the configuration's grid, and budgets."""
    study = base_study(config)
    for a, n in traffic["query"].items():
        vals = study[a]
        start = int(rng.integers(0, len(vals) - n + 1))
        study[a] = vals[start:start + n]
    return study, _budgets(config, rng)


def queries(config: dict, traffic: dict, seed: int,
            seconds: float) -> list:
    """The open-loop schedule: ``[(due_s, study, budgets, tenant)]``.

    Every seed gets the same number of queries and the same multiset of
    inter-arrival gaps (the quantiles of an exponential distribution at
    ``rate_per_s``), in an order the seed shuffles; only the values of
    each query change with the seed."""
    rng = np.random.default_rng([int(seed), 1])
    rate = float(traffic["rate_per_s"])
    n = max(1, int(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    due = np.cumsum(gaps) - gaps[0]
    names = list(traffic["tenant_share"])
    share = np.asarray([traffic["tenant_share"][t] for t in names], float)
    tenants = rng.choice(len(names), size=n, p=share / share.sum())
    out = []
    for i in range(n):
        study, budgets = draw_query(config, traffic, rng)
        out.append((float(due[i]), study, budgets, names[tenants[i]]))
    return out


def stream_kwargs(study: dict) -> dict:
    """``stream_grid`` keyword arguments of a study."""
    kw = {a: study[a] for a in AXES[1:]}
    kw["cuts"] = tuple(int(c) for c in study["cut"])
    return kw
