"""The plain reference over every kernel channel, its exact front, and
the comparison over channels that can read 0.

The reference is checked against the engine channel by channel on the
tiny study, MRAM-on-7nm corners included; its front against the
block-compare front it replaced, kept here as the test's own reference.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(BENCH), str(REPO / "src")]

import compare  # noqa: E402
import reference  # noqa: E402
import tiny  # noqa: E402
import traffic as traffic_mod  # noqa: E402

STUDY = traffic_mod.base_study(tiny.TINY)
FOUR = ("avg_power", "latency", "mipi_bytes_per_s", "sensor_macs_per_s")


@pytest.fixture(scope="module")
def engine():
    from repro.core import sweep

    grid = sweep.evaluate_grid(**traffic_mod.stream_kwargs(STUDY))
    return {f: np.asarray(grid.data[f]).ravel() for f in sweep.FIELDS}


@pytest.fixture(scope="module")
def plain():
    n = int(np.prod(reference.shape_of(STUDY)))
    return reference.at(STUDY, np.arange(n))


def test_the_reference_has_every_channel_of_the_kernel():
    from repro.core import sweep

    assert reference.CHANNELS == sweep.FIELDS


@pytest.mark.parametrize("field", reference.CHANNELS)
def test_each_channel_agrees_with_the_engine(engine, plain, field):
    """NaN at the same configurations, finite values within 1e-13, the
    same count of finite values."""
    e, r = engine[field], plain[field]
    assert e.shape == r.shape
    assert np.array_equal(np.isnan(e), np.isnan(r))
    fin = np.isfinite(r)
    assert np.isfinite(e).sum() == fin.sum()
    gap = np.abs(e[fin] - r[fin]) / np.maximum(np.abs(r[fin]), 1e-300)
    assert gap.max() <= 1e-13


def test_invalid_corners_poison_exactly_the_channels_that_read_them(plain):
    """MRAM weights on 7 nm with an on-sensor deployment: on-sensor memory
    power and the total are NaN from the node table, the non-power
    channels explicitly; on-sensor compute power never reads the memory
    and stays finite."""
    shape = reference.shape_of(STUDY)
    cut, _, sen, wm = np.unravel_index(np.arange(int(np.prod(shape))),
                                       shape)[:4]
    bad = ((np.asarray(STUDY["cut"])[cut] > 0)
           & (np.asarray(STUDY["sensor_nodes"])[sen] == "7nm")
           & (np.asarray(STUDY["weight_mems"])[wm] == "mram"))
    assert bad.any()
    for field in reference.CHANNELS:
        nan = np.isnan(plain[field])
        poisoned = field in ("avg_power", "sensor_memory",
                             "mipi_bytes_per_s", "sensor_macs_per_s",
                             "latency")
        assert np.array_equal(nan, bad if poisoned else np.zeros_like(bad))


@pytest.mark.parametrize("objectives,constraints", [
    (("sensor_compute", "sensor_memory"), {}),
    (FOUR, {"latency": 15e-3, "sensor_memory": 1.5e-3}),
    (("camera", "utsv", "agg_memory"), {"agg_compute": 2e-3}),
])
def test_every_deliverable_over_other_channels_agrees_with_the_engine(
        objectives, constraints):
    """Counts (on-sensor compute counts the invalid corners, on-sensor
    memory does not), argmins, top-k and the exact front."""
    from repro.core import stream

    res = stream.stream_grid(**traffic_mod.stream_kwargs(STUDY),
                             constraints=constraints or None,
                             objectives=objectives, top_k=4)
    ref = reference.solve(STUDY, constraints, objectives, 4)
    assert res.finite_counts == ref["finite_counts"]
    assert res.min_idx == ref["min_idx"]
    assert (res.topk_idx == ref["topk_idx"]).all()
    assert (res.front_indices == ref["front_indices"]).all()
    got = compare.compare(res, ref, STUDY, constraints, objectives)
    assert compare.within(got), got


def old_pareto_mask(P):
    """The block-compare front ``reference.pareto_mask`` replaced."""
    n, d = P.shape
    if n == 0:
        return np.zeros(0, bool)
    order = np.lexsort(P.T[::-1])
    Q = P[order]
    alive = np.ones(n, bool)
    if d == 3:
        edges = np.unique(np.quantile(Q[:, 1], np.linspace(0, 1, 2049)))
        b = np.searchsorted(edges, Q[:, 1], side="right")
        best = np.full(edges.size + 2, np.inf)
        step = 8192
        for lo in range(0, n, step):
            bb, c2 = b[lo:lo + step], Q[lo:lo + step, 2]
            excl = np.concatenate(([np.inf], np.minimum.accumulate(best)))
            alive[lo:lo + step] = ~(excl[bb] < c2)
            np.minimum.at(best, bb, c2)
    idx = np.flatnonzero(alive)
    keep = np.zeros(n, bool)
    front = Q[:0]
    for lo in range(0, idx.size, 512):
        blk = Q[idx[lo:lo + 512]]
        if front.shape[0]:
            dom = ((front[None] <= blk[:, None]).all(-1)
                   & (front[None] < blk[:, None]).any(-1)).any(1)
            sel = np.flatnonzero(~dom)
        else:
            sel = np.arange(blk.shape[0])
        B = blk[sel]
        dom = ((B[None] <= B[:, None]).all(-1)
               & (B[None] < B[:, None]).any(-1)).any(1)
        sel = sel[~dom]
        keep[idx[lo + sel]] = True
        front = np.concatenate([front, blk[sel]])
    mask = np.zeros(n, bool)
    mask[order[keep]] = True
    return mask


def _rows(kind, d, n, seed):
    rng = np.random.default_rng([seed, d, n])
    if kind == "random":
        return rng.random((n, d))
    if kind == "tied":
        # Few values per column: ties in every column, duplicate rows.
        return rng.integers(0, 6, (n, d)).astype(float)
    # Columns with few values beside continuous ones, and an
    # anti-correlated pair, as the study's channels are.
    P = rng.random((n, d))
    P[:, 1] = 1.0 - P[:, 0] + 0.05 * rng.random(n)
    P[:, 2:] = np.round(P[:, 2:] * 40)
    return P


@pytest.mark.parametrize("kind", ["random", "tied", "mixed"])
@pytest.mark.parametrize("d", [2, 3, 4])
def test_the_front_is_the_block_compare_front(kind, d):
    for seed, n in ((0, 0), (1, 1), (2, 700), (3, 20000)):
        P = _rows(kind, d, n, seed)
        got = reference.pareto_mask(P)
        assert np.array_equal(got, old_pareto_mask(P)), (kind, d, n)
    # A row and its copies all survive; a row dominated by one tie does
    # not.
    P = np.array([[1.0] * d, [1.0] * d, [1.0] * (d - 1) + [2.0]])
    assert reference.pareto_mask(P).tolist() == [True, True, False]


@pytest.mark.parametrize("d", [3, 4])
def test_copies_of_a_row_survive_across_the_pre_culls_blocks(d):
    P = np.repeat([[1.0] * d, [1.0] * (d - 1) + [2.0]], [8300, 5], axis=0)
    assert reference.pareto_mask(P).tolist() == [True] * 8300 + [False] * 5


def test_front_gaps_over_a_channel_that_reads_zero():
    """A channel at 0 in a reference front member: a program member that
    falls short of it elsewhere still reads its gap (0/0 once read NaN,
    which the maxima dropped), and a dominated member reads its margin."""
    Rf = np.array([[1.0, 0.0], [0.5, 2.0]])
    assert compare._front_gaps(Rf, Rf) == (0.0, 0.0)
    short, excess = compare._front_gaps(np.array([[1.1, 0.0], [0.5, 2.0]]),
                                        Rf)
    assert short == pytest.approx(0.1) and excess == 0.0
    short, excess = compare._front_gaps(np.array([[2.0, 0.5], [0.5, 2.0]]),
                                        Rf)
    assert short > 1e290 and excess == pytest.approx(0.5)
    assert compare._front_gaps(Rf[:1], Rf)[0] == pytest.approx(1.0)
