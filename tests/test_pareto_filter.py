"""The dominance pre-filter's device form against its host mirror.

``pareto.dominance_filter_mask`` runs twice over: traced with
``jax.numpy`` inside the chunk step, and with ``numpy`` in the streaming
executor's host fallback and ``merge_fronts``' pre-filter.  The two forms
differ only in how they find a lane's bin of the prefix-min table
(``pareto.edge_rank``: a dense count on the device, a binary search on
the host).  Here both the bins and the whole mask must agree lane for
lane on the executor's own ``(rows, bins)`` state, and the compiled
device mask must hold no search loop.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import pareto, stream

D = 3
LANES = 4096


def _front(rng, n):
    pts = rng.random((n, D))
    return pts[pareto.non_dominated_mask(pts)]


def _random(rng):
    return _front(rng, 4000), rng.random((D, LANES)) * 1.2 - 0.1


def _ties(rng):
    # Objective values equal to an edge, and first objectives equal to
    # a table entry: the "<=" of the count and the "<=" of the lookup.
    front = _front(rng, 4000)
    state = pareto.build_dominance_filter(front, D, stream._FILTER_ROWS,
                                          stream._FILTER_BINS)
    edges, table = state["edges"], state["table"]
    b = rng.integers(0, edges.shape[1], size=(D - 1, LANES))
    osg = np.stack([table[b[0], b[1]], edges[0][b[0]], edges[1][b[1]]])
    return front, np.where(np.isfinite(osg), osg, 0.5)


def _duplicate_edges(rng):
    # A front on the plane m0 + m1 + m2 = 30 over a 10 x 10 integer grid:
    # ten distinct values an objective against 257 edges, so the
    # quantile edges repeat and many bins stay empty.
    m1, m2 = (g.ravel() for g in np.mgrid[0:10, 0:10].astype(float))
    front = np.stack([30.0 - m1 - m2, m1, m2], axis=1)
    osg = rng.integers(-2, 24, size=(D, LANES)) / 2.0
    osg[0] += 15.0
    return front, osg


def _small_front(rng):
    # Under 8 members there is no table: every edge is +inf.
    return _front(rng, 4000)[:5], rng.random((D, LANES))


def _masked_lanes(rng):
    # Infeasible lanes arrive as +inf (and NaN channel values) and never
    # survive, whatever bin they fall in.
    front, osg = _random(rng)
    osg[:, ::7] = np.inf
    osg[1, 3::11] = np.inf
    osg[2, 5::13] = np.nan
    return front, osg


def _out_of_range(rng):
    # Below the first edge (bin -2: the table is not consulted) and
    # above the last (the last bin), finite on every objective.
    front, osg = _random(rng)
    osg[1:, ::2] = -5.0 - rng.random((D - 1, LANES // 2))
    osg[1:, 1::4] = 5.0 + rng.random((D - 1, LANES // 4))
    return front, osg


CASES = {"random": _random, "ties": _ties,
         "duplicate_edges": _duplicate_edges, "small_front": _small_front,
         "masked_lanes": _masked_lanes, "out_of_range": _out_of_range}


@pytest.fixture(params=list(CASES), ids=list(CASES))
def case(request):
    front, osg = CASES[request.param](np.random.default_rng(14))
    state = pareto.build_dominance_filter(front, D, stream._FILTER_ROWS,
                                          stream._FILTER_BINS)
    assert state["rows"].shape == (stream._FILTER_ROWS, D)
    assert state["edges"].shape == (D - 1, stream._FILTER_BINS + 1)
    return request.param, state, np.asarray(osg, np.float64)


def test_device_bin_equals_host_search(case):
    name, state, osg = case
    rank = jax.jit(lambda e, x: pareto.edge_rank(e, x, jnp))
    for c in range(1, D):
        edges = state["edges"][c - 1]
        with jax.enable_x64(True):
            got = np.asarray(rank(jnp.asarray(edges), jnp.asarray(osg[c])))
        want = pareto.edge_rank(edges, osg[c])
        assert np.array_equal(want, np.searchsorted(edges, osg[c],
                                                    side="right"))
        # NaN has no rank; its lane is masked by the finite check.
        num = ~np.isnan(osg[c])
        assert np.array_equal(got[num], want[num]), name
    if name == "ties":
        assert np.isin(osg[1], state["edges"][0]).all()
    if name == "duplicate_edges":
        assert np.unique(state["edges"][0]).size < 64
    if name == "small_front":
        assert np.isinf(state["edges"]).all() and not got.any()


def test_device_mask_equals_host_mask(case):
    name, state, osg = case
    mask = jax.jit(lambda s, o: pareto.dominance_filter_mask(s, o, xp=jnp))
    with jax.enable_x64(True):
        got = np.asarray(mask(jax.tree_util.tree_map(jnp.asarray, state),
                              jnp.asarray(osg)))
    want = pareto.dominance_filter_mask(state, osg, xp=np)
    assert got.dtype == want.dtype == bool
    assert np.array_equal(got, want), name
    fin = np.isfinite(osg).all(axis=0)
    assert not want[~fin].any()
    if name in ("random", "ties", "duplicate_edges"):
        # The filter both keeps and discards finite lanes here.
        assert want.any() and (fin & ~want).any()


def test_device_mask_compiles_without_search_loop():
    """The jnp mask for a full executor chunk compiles to flat passes:
    no ``while`` op and one gather, the table lookup."""
    state = pareto.build_dominance_filter(
        _front(np.random.default_rng(14), 4000), D, stream._FILTER_ROWS,
        stream._FILTER_BINS)
    with jax.enable_x64(True):
        osg = jax.ShapeDtypeStruct((D, stream.DEFAULT_CHUNK), jnp.float64)
        text = jax.jit(
            lambda s, o: pareto.dominance_filter_mask(s, o, xp=jnp)
        ).lower(jax.tree_util.tree_map(jnp.asarray, state),
                osg).compile().as_text()
    assert not re.findall(r"\bwhile\(", text)
    assert len(re.findall(r"\bgather\(", text)) <= 1
