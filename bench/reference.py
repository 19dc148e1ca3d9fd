"""Plain NumPy reference of the hand-tracking partition study.

An independent float64 implementation of the paper's Eqs. 1-11 over the
DetNet/KeyNet partition space (arXiv:2203.07474, Sec. III-IV), written
from the published constants below and imported by nothing of the
program under test.  It evaluates the study one partition cut at a time
(NumPy broadcasting over the other eight axes) and reduces each study
to the same deliverables the sweep engine returns: per-channel argmin
with first-index ties, feasibility counts, channel bounds, per-objective
top-k ordered by (value, flat index), and the exact Pareto front (ties
survive; invalid or infeasible configurations never enter).

``dtype=np.float32`` evaluates everything one precision lower: that is
the control the correctness comparison has to reject.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

# -- Published constants (paper Tables 1-2, RBE [Conti, TCAD'18]) and the
# -- calibrated node tables the repository's model states.
CAMERA_SENSE_W, CAMERA_READ_W, CAMERA_IDLE_W = 15e-3, 36e-3, 1.5e-3
T_SENSE = 4.8e-3 + 1.0e-3                    # exposure + ADC
UTSV_E, UTSV_BW = 5e-12, 100e9               # J/B, B/s
MIPI_E, MIPI_BW = 100e-12, 0.5e9
FULL_FRAME = float(int(640 * 480 * 1.25))    # RAW10 VGA frame
ROI_BYTES = 96 * 96
BOX_BYTES = 64
SENSOR_L1, AGG_L1, L1_SCALE = 16 * 1024, 64 * 1024, 0.4
ON_SENSOR_SCALE = 0.25
RBE_PEAK, RBE_PORT = 133.0, 8.0
RBE_UTIL = {"conv": 0.92, "pw": 0.55, "dw": 0.16, "fc": 0.50}
L1_TILE = 48 * 1024
MIB = 1 << 20
#: name -> (e_mac J, f_clk Hz, SRAM (read, write, leak_on, leak_ret),
#: MRAM (same) or None where the node has no MRAM test vehicle).
NODES = {
    "16nm": (0.1635e-12, 500e6,
             (0.80e-12, 1.00e-12, 1.7701e-3 / MIB, 0.4662e-3 / MIB),
             (1.20e-12, 10.0e-12, 0.0531e-3 / MIB, 0.0)),
    "7nm": (0.109e-12, 700e6,
            (0.50e-12, 0.65e-12, 1.2986e-3 / MIB, 0.3420e-3 / MIB), None),
}
#: The channels :func:`channels` gives, one per output field of the
#: engine's kernel: total power, its seven groups, MIPI link traffic,
#: on-sensor compute and result latency.
CHANNELS = ("avg_power", "camera", "utsv", "mipi", "sensor_compute",
            "sensor_memory", "agg_compute", "agg_memory",
            "mipi_bytes_per_s", "sensor_macs_per_s", "latency")
AXES = ("cut", "agg_nodes", "sensor_nodes", "weight_mems", "detnet_fps",
        "keynet_fps", "num_cameras", "mipi_energy_scale", "camera_fps")


# -- The two networks as (kind, macs, weights, in_act, out_act) rows.

def _conv(h, w, cin, cout, k=3, stride=1, kind="conv"):
    ho, wo = math.ceil(h / stride), math.ceil(w / stride)
    if kind == "dw":
        return (kind, k * k * cin * ho * wo, k * k * cin, h * w * cin,
                ho * wo * cout)
    return (kind, k * k * cin * cout * ho * wo, k * k * cin * cout,
            h * w * cin, ho * wo * cout)


def _dws(h, w, cin, cout, stride=1):
    ho, wo = math.ceil(h / stride), math.ceil(w / stride)
    return [_conv(h, w, cin, cin, 3, stride, "dw"),
            _conv(ho, wo, cin, cout, 1, 1, "pw")]


def detnet():
    """DetNet over the 320x240 downsample: 18 layers, 64 B of boxes."""
    w, h = 320, 240
    L = [_conv(w, h, 1, 16, 3, 2)]
    w, h = 160, 120
    L += _dws(w, h, 16, 48, 2)
    w, h = 80, 60
    L += _dws(w, h, 48, 48) + _dws(w, h, 48, 96, 2)
    w, h = 40, 30
    L += _dws(w, h, 96, 96) + [_conv(w, h, 96, 96)] + _dws(w, h, 96, 192, 2)
    w, h = 20, 15
    L += _dws(w, h, 192, 192) + [_conv(w, h, 192, 192),
                                 _conv(w, h, 192, 192),
                                 _conv(w, h, 192, 6, 1, 1, "pw"),
                                 _conv(w, h, 192, 24, 1, 1, "pw")]
    return L, 320 * 240, 64


def keynet():
    """KeyNet over the 96x96 ROI crop: 15 layers, 21 x 3 x 16-bit out."""
    L = [_conv(96, 96, 1, 32, 3, 2)]
    L += _dws(48, 48, 32, 64, 2) + _dws(24, 24, 64, 64)
    L += _dws(24, 24, 64, 128, 2) + _dws(12, 12, 128, 128)
    L += [_conv(12, 12, 128, 128)] + _dws(12, 12, 128, 256, 2)
    L += _dws(6, 6, 256, 256) + [("fc", 9216 * 63, 9216 * 63, 9216, 63)]
    return L, 96 * 96, 21 * 3 * 2


def _prefix(vals):
    return np.concatenate(([0.0], np.cumsum(np.asarray(vals, np.float64))))


def _net_tables(layers):
    """Prefix sums over the layer list (index k = first k layers
    on-sensor) and the running activation peaks of prefix and suffix."""
    n = len(layers)
    stream = [wt * max(1, math.ceil(out / L1_TILE))
              for _, _, wt, _, out in layers]

    def cycles(scale):
        out = []
        for (kind, macs, _, _, _), s in zip(layers, stream):
            mpc = max(1e-9, min(RBE_PEAK * scale * RBE_UTIL[kind],
                                macs / max(s, 1) * RBE_PORT * scale))
            out.append(macs / mpc)
        return out

    peaks = [float(max(i, o)) for _, _, _, i, o in layers]
    pre, suf = np.zeros(n + 1), np.zeros(n + 1)
    for k in range(n):
        pre[k + 1] = max(pre[k], peaks[k])
        suf[n - 1 - k] = max(suf[n - k], peaks[n - 1 - k])
    return {"n": n,
            "macs": _prefix([l[1] for l in layers]),
            "wbytes": _prefix([l[2] for l in layers]),
            "wstream": _prefix(stream),
            "act": _prefix([l[3] + l[4] for l in layers]),
            "cyc_s": _prefix(cycles(ON_SENSOR_SCALE)),
            "cyc_a": _prefix(cycles(1.0)),
            "pre": pre, "suf": suf,
            "out": [float(l[4]) for l in layers]}


@dataclass
class Tables:
    """Per-cut tables: index ``[cut]`` gives the sensor-side prefix and
    the MIPI payload plan of that partition."""
    det: dict
    key: dict
    det_in: float
    det_out: float
    n_cuts: int
    pay_cam: np.ndarray
    pay_det: np.ndarray
    pay_key: np.ndarray
    pay_max: np.ndarray


def tables() -> Tables:
    dl, det_in, det_out = detnet()
    kl, _, _ = keynet()
    det, key = _net_tables(dl), _net_tables(kl)
    nd, nk = det["n"], key["n"]
    n_cuts = nd + nk + 1
    pay = np.zeros((3, n_cuts))
    pay_max = np.zeros(n_cuts)
    for cut in range(n_cuts):
        if cut == 0:            # raw frame at camera rate
            plan = [(FULL_FRAME, 0)]
        elif cut < nd:          # DetNet split: activation + boxes + ROI
            plan = [(det["out"][cut - 1], 1), (BOX_BYTES, 1),
                    (ROI_BYTES, 2)]
        elif cut == nd:         # the paper's split
            plan = [(det_out, 1), (ROI_BYTES, 2)]
        else:                   # KeyNet split
            plan = [(key["out"][cut - nd - 1], 2), (det_out, 1)]
        for b, r in plan:
            pay[r, cut] += b
        pay_max[cut] = max(b for b, _ in plan)
    return Tables(det, key, float(det_in), float(det_out), n_cuts,
                  pay[0], pay[1], pay[2], pay_max)


_T = None


def _tables() -> Tables:
    global _T
    if _T is None:
        _T = tables()
    return _T


def _site(macs, wread, act, cyc, f_clk, e_mac, wm, sram, cap_w, cap_a, l1):
    """Eqs. 7-11 for one processor site over one second."""
    wm_r, _, wm_on, wm_ret = wm
    s_r, s_w, s_on, s_ret = sram
    half = act / 2
    l1_traffic = wread + half + half
    mem = (wread * wm_r + half * s_r + half * s_w
           + l1_traffic / 2 * (L1_SCALE * s_r)
           + l1_traffic / 2 * (L1_SCALE * s_w))
    t_on = np.minimum(1.0, cyc / f_clk)
    t_off = np.maximum(0.0, 1.0 - t_on)
    leak = (cap_w * (wm_on * t_on + wm_ret * t_off)
            + cap_a * (s_on * t_on + s_ret * t_off)
            + l1 * (s_on * t_on + s_ret * t_off))
    return macs * e_mac, mem + leak


def _node_arrays(names, mems, dtype):
    """Per-axis-value node constants; NaN where (node, MRAM) is invalid."""
    f = lambda v: np.asarray(v, dtype)       # noqa: E731
    e_mac = f([NODES[n][0] for n in names])
    f_clk = f([NODES[n][1] for n in names])
    sram = [f([NODES[n][2][i] for n in names]) for i in range(4)]
    wm = []
    for i in range(4):
        rows = []
        for n in names:
            rows.append([NODES[n][2][i] if m == "sram" else
                         (NODES[n][3][i] if NODES[n][3] else np.nan)
                         for m in mems])
        wm.append(f(rows))                   # (len(names), len(mems))
    return e_mac, f_clk, sram, wm


def channels(study: dict, cut, agg_i, sen_i, wm_i, det_fps, key_fps, ncam,
             mipi, cam_fps, dtype=np.float64) -> dict:
    """Eqs. 1-11 for configurations given by broadcastable per-axis
    arrays (indices into the study's node and memory axes, axis values
    for the rates).  Returns every channel of :data:`CHANNELS`.

    NaN marks an invalid configuration (MRAM weights on a node without
    MRAM, with an on-sensor deployment): ``sensor_memory`` and the
    ``avg_power`` that sums it inherit NaN from the node table, the other
    power groups never read it and stay finite, and the three non-power
    channels carry it explicitly."""
    T = _tables()
    c = lambda a: np.asarray(a, dtype)       # noqa: E731
    cut = np.asarray(cut)
    det_fps, key_fps, ncam, mipi, cam_fps = map(
        c, (det_fps, key_fps, ncam, mipi, cam_fps))
    a_emac, a_fclk, a_sram, _ = _node_arrays(study["agg_nodes"], ("sram",),
                                             dtype)
    s_emac, s_fclk, s_sram, s_wm = _node_arrays(
        study["sensor_nodes"], study["weight_mems"], dtype)
    D, K = T.det, T.key
    nd, nk = D["n"], K["n"]
    cd = np.clip(cut, 0, nd)
    ck = np.clip(cut - nd, 0, nk)
    has_sensor = cut > 0
    has_agg = cut < nd + nk
    g = lambda tab, i: c(tab)[i]             # noqa: E731

    t_comm_cam = c(FULL_FRAME) / np.where(has_sensor, c(UTSV_BW),
                                          c(MIPI_BW))
    t_off = np.maximum(c(0), c(1) / cam_fps - c(T_SENSE) - t_comm_cam)
    e_cam = (c(CAMERA_SENSE_W) * c(T_SENSE) + c(CAMERA_READ_W) * t_comm_cam
             + c(CAMERA_IDLE_W) * t_off)
    p_camera = e_cam * cam_fps * ncam
    p_utsv = np.where(has_sensor, c(FULL_FRAME) * c(UTSV_E) * cam_fps * ncam,
                      c(0))
    bps = (g(T.pay_cam, cut) * cam_fps + g(T.pay_det, cut) * det_fps
           + g(T.pay_key, cut) * key_fps)
    p_mipi = bps * (c(MIPI_E) * mipi) * ncam
    mipi_bps = bps * ncam

    def side(name, i_det, i_key, sensor):
        if sensor:
            return (g(D[name], i_det) * det_fps
                    + g(K[name], i_key) * key_fps)
        return ((g(D[name], nd) - g(D[name], i_det)) * (det_fps * ncam)
                + (g(K[name], nk) - g(K[name], i_key)) * (key_fps * ncam))

    sw = [s_wm[i][sen_i, wm_i] for i in range(4)]
    ss = [s_sram[i][sen_i] for i in range(4)]
    cap_a_s = (np.maximum(g(D["pre"], cd), g(K["pre"], ck)) + c(T.det_in))
    macs_sen = side("macs", cd, ck, True)
    pc_s, pm_s = _site(macs_sen, side("wstream", cd, ck, True),
                       side("act", cd, ck, True), side("cyc_s", cd, ck, True),
                       s_fclk[sen_i], s_emac[sen_i], sw, ss,
                       g(D["wbytes"], cd) + g(K["wbytes"], ck), cap_a_s,
                       c(SENSOR_L1))
    sensor_compute = np.where(has_sensor, pc_s * ncam, c(0))
    sensor_memory = np.where(has_sensor, pm_s * ncam, c(0))
    p_sensor = sensor_compute + sensor_memory
    asr = [a_sram[i][agg_i] for i in range(4)]
    cap_w_a = ((g(D["wbytes"], nd) - g(D["wbytes"], cd))
               + (g(K["wbytes"], nk) - g(K["wbytes"], ck)))
    cap_a_a = (np.maximum(g(D["suf"], cd), g(K["suf"], ck))
               + g(T.pay_max, cut) * ncam)
    pc_a, pm_a = _site(side("macs", cd, ck, False),
                       side("wstream", cd, ck, False),
                       side("act", cd, ck, False),
                       side("cyc_a", cd, ck, False),
                       a_fclk[agg_i], a_emac[agg_i], asr, asr, cap_w_a,
                       cap_a_a, c(AGG_L1))
    agg_compute = np.where(has_agg, pc_a, c(0))
    agg_memory = np.where(has_agg, pm_a, c(0))
    p_agg = agg_compute + agg_memory

    amort = np.minimum(c(1), det_fps / cam_fps)
    f_s, f_a = s_fclk[sen_i], a_fclk[agg_i]
    t_det_sen = g(D["cyc_s"], cd) / f_s * amort
    t_det_agg = (g(D["cyc_a"], nd) - g(D["cyc_a"], cd)) / f_a * amort
    t_key_sen = g(K["cyc_s"], ck) / f_s
    t_key_agg = (g(K["cyc_a"], nk) - g(K["cyc_a"], ck)) / f_a
    t_comm_cut = (g(T.pay_det, cut) * amort + g(T.pay_key, cut)) / c(MIPI_BW)
    latency = (c(T_SENSE) + t_comm_cam + t_det_sen + t_det_agg + t_comm_cut
               + (ncam - c(1)) * (t_det_agg + t_key_agg) + t_key_sen
               + t_key_agg)
    invalid = np.where(has_sensor, sw[0] * c(0), c(0))
    return {"avg_power": p_camera + p_utsv + p_mipi + p_sensor + p_agg,
            "camera": p_camera, "utsv": p_utsv, "mipi": p_mipi,
            "sensor_compute": sensor_compute, "sensor_memory": sensor_memory,
            "agg_compute": agg_compute, "agg_memory": agg_memory,
            "mipi_bytes_per_s": mipi_bps + invalid,
            "sensor_macs_per_s": (np.where(has_sensor, macs_sen * ncam, c(0))
                                  + invalid),
            "latency": latency + invalid}


def shape_of(study: dict) -> tuple:
    return tuple(len(study[a]) for a in AXES)


def _axis_values(study, dtype):
    return [np.asarray(study[a], dtype) for a in AXES[4:]]


def at(study: dict, flat, dtype=np.float64) -> dict:
    """Channel values of the configurations at C-order flat indices."""
    flat = np.asarray(flat, np.int64)
    coords = np.unravel_index(flat, shape_of(study))
    cuts = np.asarray(study["cut"], np.int64)[coords[0]]
    rates = [v[i] for v, i in zip(_axis_values(study, dtype), coords[4:])]
    return channels(study, cuts, coords[1], coords[2], coords[3], *rates,
                    dtype=dtype)


def feasible(values: dict, constraints: dict) -> np.ndarray:
    """Upper-bound constraints; NaN fails every predicate."""
    ok = np.ones(np.shape(next(iter(values.values()))), bool)
    with np.errstate(invalid="ignore"):
        for f, bound in constraints.items():
            ok &= values[f] <= bound
    return ok


#: Bins per pre-culled column of the staircase table, by the number of
#: objectives: one column at d = 3, two at d = 4.
_STAIR_BINS = {3: 2048, 4: 256}


def _staircase(Q: np.ndarray) -> np.ndarray:
    """Rows of ``Q``, sorted on column 0, that survive a cheap pre-cull.
    After the sort every earlier row is <= in column 0.  Each of columns
    1 to d-2 is binned: by value where it has at most the bin count of
    distinct values (an earlier row in the same or a lower bin is <=
    there), else by quantile (an earlier row in a strictly lower bin is
    < there).  An earlier row that is so in every binned column, and <=
    in column d-1 where some binned column made it strictly lower (else
    < there), dominates.  A table of the lowest column d-1 seen per bin
    answers that for a block of rows at a time by its prefix minimum."""
    n, d = Q.shape
    nb = _STAIR_BINS[d]
    keys, dims, incl = [], [], []
    for j in range(1, d - 1):
        col = np.sort(Q[:, j])
        vals = col[np.concatenate(([True], col[1:] != col[:-1]))]
        if vals.size <= nb:
            keys.append(np.searchsorted(vals, Q[:, j]))
            dims.append(vals.size)
            incl.append(1)
        else:
            edges = np.unique(col[np.linspace(0, n - 1, nb + 1).astype(int)])
            keys.append(np.searchsorted(edges, Q[:, j], side="right"))
            dims.append(edges.size + 1)
            incl.append(0)
    strict = not all(incl)
    best = np.full(dims, np.inf)
    alive = np.ones(n, bool)
    step = 8192
    for lo in range(0, n, step):
        low = best
        for ax in range(len(dims)):
            low = np.minimum.accumulate(low, axis=ax)
        # Shifted by one bin on each axis: entry b is the minimum over
        # the bins below b, and entry b + 1 over those up to b.
        low = np.pad(low, [(1, 0)] * len(dims), constant_values=np.inf)
        at = tuple(k[lo:lo + step] for k in keys)
        last = Q[lo:lo + step, -1]
        seen = low[tuple(k + i for k, i in zip(at, incl))]
        alive[lo:lo + step] = ~(seen <= last if strict else seen < last)
        np.minimum.at(best, at, last)
    return alive


def _dominated(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """Rows of ``B`` that some row of ``A`` dominates."""
    return ((A[None] <= B[:, None]).all(-1)
            & (A[None] < B[:, None]).any(-1)).any(1)


def pareto_mask(P: np.ndarray) -> np.ndarray:
    """Exact non-dominated rows of a finite ``(n, d)`` matrix
    (minimisation; identical rows all survive)."""
    n, d = P.shape
    if n == 0:
        return np.zeros(0, bool)
    idx = np.arange(n)
    if d in _STAIR_BINS:
        by0 = np.argsort(P[:, 0], kind="stable")
        idx = by0[_staircase(P[by0])]
    # In lexicographic order a row never dominates an earlier row, so
    # every row kept is final, and the kept rows are the front so far.
    order = idx[np.lexsort(P[idx].T[::-1])]
    Q = P[order]
    keep = np.zeros(order.size, bool)
    front = Q[:0]
    for lo in range(0, order.size, 512):
        blk = Q[lo:lo + 512]
        # Only front rows <= the block's largest value in every column
        # can dominate a row of the block.
        near = front[(front <= blk.max(0)).all(1)]
        sel = np.flatnonzero(~_dominated(near, blk)) if near.shape[0] \
            else np.arange(blk.shape[0])
        B = blk[sel]
        sel = sel[~_dominated(B, B)]
        keep[lo + sel] = True
        front = np.concatenate([front, blk[sel]])
    mask = np.zeros(n, bool)
    mask[order[keep]] = True
    return mask


def solve(study: dict, constraints: dict, objectives, top_k: int,
          dtype=np.float64) -> dict:
    """Every deliverable of one study, from the plain evaluation."""
    shape = shape_of(study)
    per_cut = int(np.prod(shape[1:]))
    n_total = per_cut * shape[0]
    fields = list(objectives) + [f for f in constraints
                                 if f not in objectives]
    rates = _axis_values(study, dtype)
    bshape = shape[1:]
    idx = [np.arange(s).reshape([-1 if i == j else 1
                                 for j in range(len(bshape))])
           for i, s in enumerate(bshape)]
    best = {f: (np.inf, n_total) for f in fields}
    count = dict.fromkeys(fields, 0)
    lo_hi = {f: [np.inf, -np.inf] for f in fields}
    top_v = {o: np.empty(0) for o in objectives}
    top_i = {o: np.empty(0, np.int64) for o in objectives}
    cand_v, cand_i = [], []
    for ci, cut in enumerate(study["cut"]):
        vals = channels(study, np.int64(cut), idx[0], idx[1], idx[2],
                        *[r.reshape(i.shape) for r, i in zip(rates,
                                                             idx[3:])],
                        dtype=dtype)
        vals = {f: np.broadcast_to(vals[f], bshape).ravel().astype(
            np.float64) for f in fields}
        ok = feasible(vals, constraints)
        base = ci * per_cut
        for f in fields:
            v = np.where(ok & np.isfinite(vals[f]), vals[f], np.inf)
            good = np.isfinite(v)
            n = int(good.sum())
            count[f] += n
            if n:
                j = int(np.argmin(v))
                if v[j] < best[f][0]:
                    best[f] = (float(v[j]), base + j)
                lo_hi[f][0] = min(lo_hi[f][0], float(v[good].min()))
                lo_hi[f][1] = max(lo_hi[f][1], float(v[good].max()))
            if f in top_v and n:
                kk = min(top_k, n)
                # Every entry tied with the k-th value competes, so the
                # lowest flat indices win ties as in the engine.
                part = np.flatnonzero(v <= np.partition(v, kk - 1)[kk - 1])
                tv = np.concatenate([top_v[f], v[part]])
                ti = np.concatenate([top_i[f], base + part])
                o = np.lexsort((ti, tv))[:top_k]
                top_v[f], top_i[f] = tv[o], ti[o]
        O = np.stack([vals[o] for o in objectives], axis=1)
        rows = np.flatnonzero(ok & np.isfinite(O).all(axis=1))
        m = pareto_mask(O[rows])
        cand_v.append(O[rows[m]])
        cand_i.append(base + rows[m])
    cv, cidx = np.concatenate(cand_v), np.concatenate(cand_i)
    m = pareto_mask(cv)
    order = np.argsort(cidx[m], kind="stable")
    k = min(top_k, n_total)

    def padded(a, fill, dt):
        out = np.full(k, fill, dt)
        out[:a.size] = a[:k]
        return out

    topk_idx = np.stack([padded(top_i[o], n_total, np.int64)
                         for o in objectives])
    topk_val = np.stack([padded(top_v[o], np.inf, np.float64)
                         for o in objectives])
    # Reported values of top-k entries beyond the feasible set are inf.
    return {"n_total": n_total,
            "min_val": {f: best[f][0] for f in fields},
            "min_idx": {f: best[f][1] for f in fields},
            "finite_counts": count,
            "channel_min": {f: lo_hi[f][0] for f in fields},
            "channel_max": {f: lo_hi[f][1] for f in fields},
            "topk_idx": topk_idx, "topk_val": topk_val,
            "front_indices": cidx[m][order],
            "front_values": cv[m][order]}
