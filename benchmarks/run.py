"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only <name>] [--smoke]

Emits ``name,value,derived`` CSV rows:
  * power_tables  — Fig. 5a / Fig. 5b / Table 2 reproduction
  * rbe_roofline  — Fig. 4 RBE accelerator roofline
  * tpu_roofline  — the 40-cell (arch x shape) TPU roofline + energy table
  * kernel_bench  — Pallas kernel validation/timing + VMEM budgets
  * dosc_advisor  — the two-tier (ICI/DCN) communication-plan table
  * sweep_bench   — scalar vs vectorized design-space engine throughput
                    (also snapshots BENCH_sweep.json for the perf trail)
  * pareto_bench  — Pareto-front extraction + gradient knob-search
                    throughput (snapshots BENCH_pareto.json)
  * stream_bench  — streaming vs dense sweep executor: throughput + peak
                    RSS at 10^5..10^7 configs (snapshots BENCH_stream.json)
  * scenario_bench — session scenario engine: closed-form oracles +
                    10^6 (config x trace) streaming throughput over the
                    battery/thermal channels (BENCH_scenario.json)
  * service_bench — worker-pool scale-out: 1/2/4-worker throughput at
                    10^7 configs + p50/p95 ticket latency under 8
                    tenants, bitwise-anchored (BENCH_service.json)

``--smoke`` runs the fast CI gate instead: a sequence of *named steps*
(tiny grids, hard asserts), each bounded by a per-step SIGALRM timeout
(``REPRO_SMOKE_STEP_TIMEOUT_S``, default 300 s) so one wedged step
fails loudly with its name instead of hanging the whole CI job:
exact streaming/dense parity (argmin, top-k, Pareto front, counts),
async double-buffered pipeline parity across prefetch depths, the
backend registry (``backend="pallas"`` in interpret mode and
``scan_chunks=4`` fused dispatch, both exact vs dense), compiled
``constraints=`` masking vs the dense host post-filter,
stacked-workload parity end-to-end, the scenario engine
(constant-trace degeneracy bitwise vs the static kernel, the
time-to-empty closed-form oracle, and session-channel
argmin/top-k(maximize) stream-vs-dense parity), the fault-tolerance
recovery paths — a SIGKILLed checkpointed sweep must resume in a fresh
process with bitwise-identical results, and seeded transient faults
must retry to exact parity — and the sweep service: a served request
must match the solo run bitwise, a deadline-exceeded request must
return a consistent prefix snapshot, an over-capacity submission must
be rejected without disturbing admitted work, and a SIGKILL'd server
restarted over its spool must resume to bitwise-identical results.
The networked path has its own gates: ``net-kill-reconnect`` SIGKILLs
a *listening* server mid-request with a connected client and requires
the client to reconnect, dedupe its idempotent resubmit onto the
recovered ticket and decode a bitwise-identical result; and
``net-fairness`` asserts the 1:3 weight share under sustained
overload, priority aging (no starvation), and wire-carried
backpressure fields (depth, capacity, tenant, retry-after);
``net-scaleout`` serves a watched request through a 2-process worker
pool behind an HMAC-authenticated server — bitwise parity, >= 2
leased parts folded, per-chunk deltas on the wire, bad tokens
rejected before parsing; and ``worker-kill-reclaim`` SIGKILLs one of
three live workers mid-lease and requires the survivors to reclaim
the orphaned lease (attempt >= 2) and drain to the bitwise solo
answer.
Perf-path *and* resilience regressions fail CI, not just benchmarks.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import signal as _signal
import sys
import threading
import time

#: Per-smoke-step watchdog (seconds); override with the env var.
SMOKE_STEP_TIMEOUT_ENV = "REPRO_SMOKE_STEP_TIMEOUT_S"
DEFAULT_SMOKE_STEP_TIMEOUT_S = 300.0


class SmokeStepTimeout(RuntimeError):
    """A smoke step exceeded its watchdog — named, so CI logs say
    *which* gate wedged instead of timing out the whole job."""


@contextlib.contextmanager
def _step_timeout(name: str, seconds: float):
    """SIGALRM watchdog around one smoke step (main thread only; a
    no-op where SIGALRM is unavailable, e.g. Windows)."""
    usable = (seconds > 0 and hasattr(_signal, "SIGALRM")
              and threading.current_thread() is threading.main_thread())
    if not usable:
        yield
        return

    def _alarm(signum, frame):
        raise SmokeStepTimeout(
            f"smoke step '{name}' exceeded {seconds:.0f}s "
            f"(raise {SMOKE_STEP_TIMEOUT_ENV} if the host is just slow)")

    prev = _signal.signal(_signal.SIGALRM, _alarm)
    _signal.setitimer(_signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        _signal.setitimer(_signal.ITIMER_REAL, 0.0)
        _signal.signal(_signal.SIGALRM, prev)


def dosc_advisor_rows():
    from repro.core import dosc
    out = []
    ranked = dosc.advise(grad_elems_per_chip=100e6, pods=2,
                         intra_pod_chips=256, objective="time")
    for c in ranked:
        out.append((f"dosc.{c.plan.name}.t_comm_ms", c.t_comm_s * 1e3,
                    f"dcn_edge={c.dcn_edge_bytes/2**20:.1f}MiB "
                    f"e={c.e_comm_j*1e3:.2f}mJ/chip"))
    flat = next(c for c in ranked if c.plan.name == "flat-ar-f32")
    best = ranked[0]
    out.append(("dosc.best_vs_flat_speedup",
                flat.t_comm_s / best.t_comm_s,
                f"best={best.plan.name} (the paper's two-tier insight)"))
    return out


SUITES = ["power_tables", "rbe_roofline", "tpu_roofline", "kernel_bench",
          "dosc_advisor", "sweep_bench", "pareto_bench", "stream_bench",
          "scenario_bench", "service_bench"]


def _smoke_stream_parity(ctx):
    """Dense reference + exact streaming parity (shared by later steps)."""
    import numpy as np

    from repro.core import pareto, stream, sweep

    grid_kw = dict(sensor_nodes=("7nm", "16nm"),
                   weight_mems=("sram", "mram"),
                   detnet_fps=(5.0, 30.0))     # 34 cuts x 2x2x2 = 272
    dense = sweep.evaluate_grid(**grid_kw)
    res = stream.stream_grid(**grid_kw, chunk_size=97, track="all",
                             hist_bins=8)
    assert all(res.argmin(f) == dense.argmin(f) for f in sweep.FIELDS), \
        "streaming argmin drifted from dense"
    assert all(res.top_k(o) == dense.top_k(o, 4)
               for o in res.objectives), "streaming top-k drifted"
    df, sf = pareto.pareto_front(dense), res.pareto_front()
    assert np.array_equal(df.indices, sf.indices) and \
        np.array_equal(df.values, sf.values), "streaming front drifted"
    assert all(res.finite_counts[f] ==
               int(np.isfinite(dense.data[f]).sum())
               for f in sweep.FIELDS), "validity counts drifted"
    ctx.update(grid_kw=grid_kw, dense=dense, res=res, df=df)
    return [
        ("smoke.stream_dense_parity", 1.0,
         f"argmin/top-k/front/counts exact on {dense.n_configs} configs"),
        ("smoke.front_size", float(sf.size), "reference-front members"),
    ]


def _smoke_async_pipeline(ctx):
    """Prefetch depths (0 = synchronous reference) change no result."""
    import numpy as np

    from repro.core import stream

    grid_kw, dense, df = ctx["grid_kw"], ctx["dense"], ctx["df"]
    piped = stream.stream_grid(**grid_kw, chunk_size=97, prefetch=4)
    sync = stream.stream_grid(**grid_kw, chunk_size=97, prefetch=0)
    for r in (piped, sync):
        assert all(r.argmin(o) == dense.argmin(o)
                   for o in r.objectives), "async pipeline drifted"
        pf = r.pareto_front()
        assert np.array_equal(pf.indices, df.indices) and \
            np.array_equal(pf.values, df.values), "async front drifted"
    return [("smoke.async_pipeline_parity", 1.0,
             "prefetch 0/4 exact vs dense (double-buffered path)")]


def _smoke_constraints(ctx):
    """Compiled constraint predicates == dense host post-filter."""
    import numpy as np

    from repro.core import pareto, stream

    grid_kw, dense = ctx["grid_kw"], ctx["dense"]
    lat_budget = float(np.nanquantile(dense.data["latency"], 0.5))
    cons = {"latency": lat_budget}
    constrained = stream.stream_grid(**grid_kw, chunk_size=97,
                                     constraints=cons, prefetch=4)
    dense_con = dense.constrain(cons)
    assert constrained.argmin() == dense_con.argmin(), \
        "constrained argmin drifted from host post-filter"
    cf, dcf = constrained.pareto_front(), pareto.pareto_front(dense_con)
    assert np.array_equal(cf.indices, dcf.indices) and \
        np.array_equal(cf.values, dcf.values), "constrained front drifted"
    assert constrained.finite_counts["latency"] == \
        int(np.isfinite(dense_con.data["latency"]).sum()), \
        "feasible counts drifted"
    return [("smoke.constrained_parity", 1.0,
             f"compiled latency<= {lat_budget:.3g} mask == dense "
             f"post-filter")]


def _smoke_backends(ctx):
    """Pallas (interpret on CPU) + scan-fused dispatch, exact vs dense."""
    import numpy as np

    from repro.core import stream, sweep

    grid_kw, dense, df = ctx["grid_kw"], ctx["dense"], ctx["df"]
    pallas = stream.stream_grid(**grid_kw, chunk_size=97, track="all",
                                backend="pallas")
    assert all(pallas.argmin(f) == dense.argmin(f)
               for f in sweep.FIELDS), "pallas backend argmin drifted"
    assert all(pallas.top_k(o) == dense.top_k(o, 4)
               for o in pallas.objectives), "pallas backend top-k drifted"
    pf = pallas.pareto_front()
    assert np.array_equal(pf.indices, df.indices) and \
        np.array_equal(pf.values, df.values), "pallas front drifted"
    dense_pallas = sweep.evaluate_grid(**grid_kw, backend="pallas")
    assert all(np.array_equal(dense.data[f], dense_pallas.data[f],
                              equal_nan=True)
               for f in sweep.FIELDS), "pallas dense eval drifted"
    scanned = stream.stream_grid(**grid_kw, chunk_size=97, scan_chunks=4,
                                 prefetch=4)
    assert all(scanned.argmin(o) == dense.argmin(o)
               for o in scanned.objectives), "scan-fused argmin drifted"
    sc = scanned.pareto_front()
    assert np.array_equal(sc.indices, df.indices) and \
        np.array_equal(sc.values, df.values), "scan-fused front drifted"
    return [
        ("smoke.pallas_backend_parity", 1.0,
         "backend='pallas' (interpret) exact vs dense: stream + grid"),
        ("smoke.scan_fused_parity", 1.0,
         "scan_chunks=4 fused dispatch exact vs dense"),
    ]


def _smoke_stacked(ctx):
    """Stacked-workload axis: every model row reproduces its own grid;
    optimal_partition routes sequence knobs through the grid engines."""
    import numpy as np

    from repro.core import partition, sweep
    from repro.core.handtracking import build_detnet, build_keynet

    det, key = build_detnet(), build_keynet()
    pairs = ((det, key), (det.scaled(0.5), key))
    stacked = sweep.evaluate_grid(models=pairs, detnet_fps=(10.0, 30.0))
    for mi, (d_wl, k_wl) in enumerate(pairs):
        single = sweep.evaluate_grid(detnet=d_wl, keynet=k_wl,
                                     detnet_fps=(10.0, 30.0))
        a, b = stacked.avg_power[mi], single.avg_power
        ok = np.isfinite(a) & np.isfinite(b)
        rel = np.abs(a[ok] - b[ok]) / np.maximum(np.abs(b[ok]), 1e-30)
        assert rel.max() <= 1e-6, f"stacked model {mi} drifted: {rel.max()}"
    best = partition.optimal_partition(sensor_node=("7nm", "16nm"))
    assert best.avg_power <= partition.optimal_partition().avg_power * (
        1 + 1e-12)
    return [("smoke.stacked_parity", 1.0,
             f"{len(pairs)} stacked models <=1e-6 vs single grids")]


def _smoke_scenario(ctx):
    """Scenario engine: constant-trace degeneracy bitwise vs the static
    kernel, time-to-empty closed form, stream-vs-dense session parity."""
    import numpy as np

    from repro.core import scenario as SC
    from repro.core import stream, sweep
    from repro.core.constants import DEFAULT_BATTERY

    grid_kw, dense = ctx["grid_kw"], ctx["dense"]
    const = SC.ScenarioSet(
        traces=(SC.ScenarioTrace("const", (SC.Phase(600.0),)),),
        throttle=False)
    scen = sweep.evaluate_grid(**grid_kw, scenarios=const)
    assert all(np.array_equal(dense.data[f], scen.data[f][..., 0],
                              equal_nan=True) for f in sweep.FIELDS), \
        "constant-trace degeneracy drifted from the static kernel"
    P = scen.data["avg_power"][..., 0]
    okm = np.isfinite(P)
    tte_ref = DEFAULT_BATTERY.soc0 * DEFAULT_BATTERY.capacity_j / P[okm]
    tte_err = float(np.max(np.abs(
        scen.data["time_to_empty_s"][..., 0][okm] - tte_ref) / tte_ref))
    assert tte_err <= 1e-6, f"time-to-empty oracle drift: {tte_err}"
    scen_obj = ("time_to_empty_s", "peak_case_temp_c")
    scen_ref = sweep.evaluate_grid(**grid_kw, scenarios="all")
    scen_stream = stream.stream_grid(
        **grid_kw, scenarios="all", chunk_size=97, objectives=scen_obj,
        maximize=("time_to_empty_s",))
    assert scen_stream.argmin("peak_case_temp_c")["peak_case_temp_c"] == \
        np.nanmin(scen_ref.data["peak_case_temp_c"]), \
        "scenario streaming argmin drifted from dense"
    tr = scen_ref.data["time_to_empty_s"]
    assert scen_stream.top_k("time_to_empty_s")[0]["time_to_empty_s"] \
        == np.nanmax(tr[np.isfinite(tr)]), \
        "scenario top-k(maximize) drifted from dense"
    return [
        ("smoke.scenario_oracle_parity", 1.0,
         f"const-trace degeneracy bitwise; tte oracle <= {tte_err:.2g}"),
        ("smoke.scenario_stream_parity", 1.0,
         f"session argmin/top-k(maximize) exact on "
         f"{scen_ref.n_configs} (config x trace)"),
    ]


def _smoke_transient_faults(ctx):
    """Seeded transient faults retry in place to untouched results."""
    import numpy as np

    from repro.core import stream, sweep
    from repro.runtime import FaultInjector, FaultPlan

    grid_kw, dense, df = ctx["grid_kw"], ctx["dense"], ctx["df"]
    inj = FaultInjector(FaultPlan(fail_chunks=(1,), transient_rate=0.5,
                                  seed=3))
    faulted = stream.stream_grid(**grid_kw, chunk_size=97, track="all",
                                 fault_injector=inj)
    assert inj.injected["transient"] >= 1, "no transient faults fired"
    assert faulted.stats["retries"] == inj.injected["transient"], \
        "retry accounting drifted from injected fault count"
    assert all(faulted.argmin(f) == dense.argmin(f)
               for f in sweep.FIELDS), "retried sweep argmin drifted"
    ff = faulted.pareto_front()
    assert np.array_equal(ff.indices, df.indices) and \
        np.array_equal(ff.values, df.values), "retried sweep front drifted"
    return [("smoke.transient_fault_parity", 1.0,
             f"{int(faulted.stats['retries'])} injected faults retried "
             f"to exact parity")]


def _smoke_kill_resume_step(ctx):
    """SIGKILL a checkpointed sweep mid-flight in a subprocess, resume
    in a fresh process, require bitwise-identical deliverables."""
    resumed_step = _smoke_kill_resume(ctx["grid_kw"])
    return [("smoke.kill_resume_parity", 1.0,
             f"CPU child: SIGKILL at chunk 2 -> resumed from step "
             f"{resumed_step} bitwise-identical")]


def _smoke_service(ctx):
    """The sweep service end to end: served-request bitwise parity,
    deadline partial snapshot (consistent prefix), backpressure
    rejection without disturbing admitted work, and server SIGKILL ->
    restart -> bitwise resume over the same spool."""
    import numpy as np

    from repro.core.service import SweepRequest, SweepService
    from repro.runtime import BackpressureError, FaultInjector, FaultPlan

    grid_kw, dense, ref = ctx["grid_kw"], ctx["dense"], ctx["res"]
    req = SweepRequest(grid=grid_kw, track="all", chunk_size=97,
                       hist_bins=8)

    # (a) A served request reproduces the solo stream run bitwise.
    with SweepService() as svc:
        served = svc.submit(req).result(timeout=600)
    assert not served.partial
    assert served.min_val == ref.min_val and \
        served.min_idx == ref.min_idx, "served argmin drifted from solo"
    assert np.array_equal(served.topk_idx, ref.topk_idx) and \
        np.array_equal(served.topk_val, ref.topk_val), \
        "served top-k drifted from solo"
    assert np.array_equal(served.front_indices, ref.front_indices) and \
        np.array_equal(served.front_values, ref.front_values), \
        "served front drifted from solo"

    # (b) Deadline-exceeded request: consistent partial prefix snapshot.
    inj = FaultInjector(FaultPlan(straggle={1: 2.0}))
    with SweepService(fault_injector=inj) as svc:
        part = svc.submit(SweepRequest(
            grid=grid_kw, chunk_size=97,
            deadline_s=0.5)).result(timeout=600)
        n_expired = svc.health()["counters"]["deadline_expired"]
    assert part.partial, "deadline did not yield a partial snapshot"
    frac = part.stats["fraction_complete"]
    assert 0.0 < frac < 1.0, f"fraction_complete {frac} out of range"
    assert n_expired == 1, "deadline_expired counter drifted"
    base = round(frac * dense.data["avg_power"].size)
    for field in part.objectives:
        prefix = np.asarray(dense.data[field]).ravel()[:base]
        assert part.min_val[field] == float(np.nanmin(prefix)), \
            f"partial snapshot not prefix-consistent on {field}"

    # (c) Backpressure: over-capacity submission rejected with depth/cap,
    # admitted work unaffected.
    with SweepService(capacity=1) as svc:
        svc.pause()
        admitted = svc.submit(req)
        try:
            svc.submit(req)
            raise AssertionError("over-capacity submit was not rejected")
        except BackpressureError as e:
            assert e.queue_depth == 1 and e.capacity == 1
        svc.resume()
        ok = admitted.result(timeout=600)
    assert not ok.partial and ok.min_val == ref.min_val, \
        "backpressure rejection disturbed admitted work"

    # (d) SIGKILL the server mid-request; a restart over the same spool
    # resumes the journaled request to the bitwise solo answer.
    resumed_step = _smoke_service_kill_resume(grid_kw)
    return [
        ("smoke.service_request_parity", 1.0,
         "served request bitwise == solo stream run"),
        ("smoke.service_deadline_partial", 1.0,
         f"deadline snapshot prefix-consistent at {frac:.0%}"),
        ("smoke.service_backpressure", 1.0,
         "over-capacity submit rejected; admitted work exact"),
        ("smoke.service_kill_resume", 1.0,
         f"CPU child: server SIGKILL -> restart resumed from step "
         f"{resumed_step} bitwise-identical"),
    ]


def _smoke_net_kill_reconnect(ctx):
    """The networked chaos gate: SIGKILL a listening server mid-request
    with a connected client; the client must reconnect to the restarted
    server, its idempotent resubmit must dedupe onto the recovered
    ticket (same request id, no double execution), and the final result
    must be bitwise-identical to the fault-free in-process run."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import tempfile
    import threading

    import numpy as np

    from repro.core import stream
    from repro.core.client import SweepClient
    from repro.core.service import SweepRequest

    # Enough steps (~88 x 31-config chunks, several hundred ms of
    # steady-state work after the first snapshot) that the kill
    # reliably lands mid-request, not after completion.
    grid_kw = dict(ctx["grid_kw"],
                   detnet_fps=tuple(float(f) for f in range(5, 105, 5)))
    req = SweepRequest(grid=grid_kw, track="all", chunk_size=31, top_k=4)

    # The child is a CPU rehearsal: this process already holds JAX, and
    # on a chip host a second process would contend for the chip.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=1")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])

    def start_server(sock_path, spool):
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.service", "--unix", sock_path,
             "--spool", spool, "--checkpoint-every-steps", "1"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        ready = json.loads(proc.stdout.readline())
        assert ready["listening"] == sock_path, f"bad ready line {ready}"
        return proc

    with tempfile.TemporaryDirectory(prefix="smoke_net_") as tmp:
        sock_path = os.path.join(tmp, "svc.sock")
        spool = os.path.join(tmp, "spool")
        server_a = start_server(sock_path, spool)
        cli = SweepClient(sock_path, reconnect_timeout_s=240.0,
                          heartbeat_grace_s=8.0)
        ticket = cli.submit(req, client_id="smoke-chaos-1")
        first_id = ticket.id
        seen = {"frac": 0.0}
        box: dict = {}

        def wait_result():
            try:
                box["res"] = ticket.result(
                    timeout=600,
                    on_progress=lambda s: seen.__setitem__(
                        "frac", s["fraction_complete"]))
            except BaseException as e:
                box["err"] = e

        th = threading.Thread(target=wait_result)
        th.start()
        deadline = time.time() + 300
        while seen["frac"] == 0.0 and th.is_alive() \
                and time.time() < deadline:
            time.sleep(0.02)
        assert seen["frac"] > 0.0, "no progress snapshot before kill"
        server_a.kill()
        server_a.wait(30)
        server_b = start_server(sock_path, spool)
        try:
            th.join(600)
            assert "err" not in box, \
                f"client failed across restart: {box.get('err')!r}"
            res = box["res"]
            assert ticket.id == first_id, \
                "idempotent resubmit minted a new ticket"
            assert res.stats["resumed_from_step"] > 0, res.stats
            assert cli.counters["reconnects"] >= 2, cli.counters
            ref = stream.stream_grid(**grid_kw, track="all",
                                     chunk_size=31, top_k=4)
            assert res.min_val == ref.min_val and \
                res.min_idx == ref.min_idx, "networked argmin drifted"
            assert np.array_equal(res.topk_idx, ref.topk_idx) and \
                np.array_equal(res.topk_val, ref.topk_val), \
                "networked top-k drifted"
            assert np.array_equal(res.front_indices,
                                  ref.front_indices) and \
                np.array_equal(res.front_values, ref.front_values), \
                "networked front drifted"
        finally:
            cli.close()
            server_b.send_signal(signal.SIGTERM)
            server_b.wait(60)
    return [("smoke.net_kill_reconnect", 1.0,
             f"CPU child: server SIGKILL -> client reconnect + dedupe "
             f"resumed from step {int(res.stats['resumed_from_step'])} "
             f"bitwise")]


def _smoke_net_fairness(ctx):
    """The fairness gate: tenants at weights 1:3 under sustained
    overload converge to their weight share of claimed work (within
    10%), a starved low-priority request ages past fresh high-priority
    arrivals, and over-the-wire overload rejections carry queue depth
    and a retry-after hint."""
    import tempfile

    from repro.core.client import SweepClient
    from repro.core.service import SweepRequest, SweepService
    from repro.runtime import (AdmissionQueue, BackpressureError,
                               SweepServer, TenantPolicy)

    # (a) Deficit round-robin weight share under sustained overload.
    q = AdmissionQueue(4096, tenants={"small": TenantPolicy(weight=1.0),
                                      "big": TenantPolicy(weight=3.0)})
    for i in range(600):
        q.offer(f"s{i}", tenant="small")
        q.offer(f"b{i}", tenant="big")
    n_big = 0
    for _ in range(400):
        (item,) = q.take_batch(timeout=1.0)
        tenant = "big" if item.startswith("b") else "small"
        n_big += tenant == "big"
        q.release(tenant)
    share = n_big / 400.0
    assert abs(share - 0.75) <= 0.10, \
        f"weight-1:3 share drifted to {share:.2f}"

    # (b) Aging: a starved low-priority request eventually runs.
    aq = AdmissionQueue(8, aging_s=0.02)
    aq.offer("starved", priority=0)
    time.sleep(0.09)
    aq.offer("fresh-high", priority=2)
    assert aq.take_batch(timeout=1.0) == ["starved"], \
        "low-priority request starved behind fresh high-priority work"

    # (c) Overload rejections over the wire keep the in-process
    # BackpressureError semantics: depth, capacity, tenant, retry hint.
    grid_kw = ctx["grid_kw"]
    req = SweepRequest(grid=grid_kw, chunk_size=97)
    with tempfile.TemporaryDirectory(prefix="smoke_fair_") as tmp:
        svc = SweepService(capacity=2)
        svc.set_tenant("capped", weight=1.0, max_pending=1)
        svc.pause()
        with SweepServer(svc, unix_path=f"{tmp}/svc.sock",
                         own_service=True) as server:
            with SweepClient(server.address) as cli:
                t1 = cli.submit(SweepRequest(grid=grid_kw, chunk_size=97,
                                             tenant="capped"))
                try:
                    cli.submit(SweepRequest(grid=grid_kw, chunk_size=101,
                                            tenant="capped"))
                    raise AssertionError(
                        "tenant over-cap submit was not rejected")
                except BackpressureError as e:
                    assert e.tenant == "capped", e
                    assert e.queue_depth == 1 and e.capacity == 1, e
                    assert e.retry_after_s is not None and \
                        e.retry_after_s > 0, e
                t2 = cli.submit(req)    # other tenants unaffected
                try:
                    cli.submit(SweepRequest(grid=grid_kw,
                                            chunk_size=103))
                    raise AssertionError(
                        "over-capacity submit was not rejected")
                except BackpressureError as e:
                    assert e.tenant is None and e.queue_depth == 2, e
                    assert e.retry_after_s is not None, e
                for t in (t1, t2):
                    cli.cancel(t.id)
            svc.resume()
            server.close(drain=True, timeout=30.0)
    return [
        ("smoke.net_fairness_share", share,
         "weights 1:3 under overload: big-tenant share within 10% of "
         "0.75"),
        ("smoke.net_fairness_aging", 1.0,
         "starved low-priority request aged past fresh high-priority"),
        ("smoke.net_fairness_backpressure", 1.0,
         "wire rejections carry depth/capacity/tenant/retry-after"),
    ]


def _smoke_net_scaleout(ctx):
    """The scale-out gate: a SweepService with a 2-process worker pool
    behind an HMAC-authenticated SweepServer must serve a watched
    request bitwise-identical to the solo run, fold >= 2 leased parts,
    stream per-chunk deltas after the first full snapshot, and reject
    a bad token before parsing any frame."""
    import tempfile

    import numpy as np

    from repro.core import stream
    from repro.core.client import AuthenticationError, SweepClient
    from repro.core.service import SweepRequest, SweepService
    from repro.runtime import SweepServer

    grid_kw = ctx["grid_kw"]
    req = SweepRequest(grid=grid_kw, track="all", chunk_size=31,
                       scan_chunks=1, top_k=4)
    ref = stream.stream_grid(**grid_kw, track="all", chunk_size=31,
                             scan_chunks=1, top_k=4)
    with tempfile.TemporaryDirectory(prefix="smoke_scaleout_") as tmp:
        svc = SweepService(capacity=8, snapshot_every_s=0.0, workers=2,
                           spool_dir=f"{tmp}/spool")
        with SweepServer(svc, unix_path=f"{tmp}/svc.sock",
                         own_service=True, heartbeat_s=0.1,
                         auth_token="smoke-secret") as server:
            try:
                with SweepClient(server.address, auth="bad-token") as bad:
                    bad.ping()
                raise AssertionError("bad auth token was accepted")
            except AuthenticationError:
                pass
            assert server.counters["auth_failures"] >= 1
            with SweepClient(server.address,
                             auth="smoke-secret") as cli:
                snaps: list = []
                t = cli.submit(req, client_id="smoke-scaleout-1")
                res = t.result(timeout=600,
                               on_progress=snaps.append)
                tr = cli.health()["transport"]
            assert svc.counters["pooled_executions"] == 1, svc.counters
            assert res.stats["n_parts"] >= 2, res.stats
            assert res.stats["watch_wire_bytes"] > 0, res.stats
            assert tr["watch_delta_bytes"] > 0, tr
            assert all(s["fraction_complete"] <=
                       s2["fraction_complete"] for s, s2 in
                       zip(snaps, snaps[1:])), "snapshots regressed"
        assert res.min_val == ref.min_val and \
            res.min_idx == ref.min_idx, "pooled argmin drifted"
        assert np.array_equal(res.topk_idx, ref.topk_idx) and \
            np.array_equal(res.topk_val, ref.topk_val), \
            "pooled top-k drifted"
        assert np.array_equal(res.front_indices, ref.front_indices) \
            and np.array_equal(res.front_values, ref.front_values), \
            "pooled front drifted"
    return [("smoke.net_scaleout", 1.0,
             f"2-worker pool behind auth'd server: "
             f"{int(res.stats['n_parts'])} parts folded bitwise, "
             f"deltas on the wire")]


def _smoke_worker_kill_reclaim(ctx):
    """The reclaim gate: SIGKILL one live worker of a pool mid-lease;
    the survivors must reclaim the orphaned lease after its heartbeat
    expires (attempt >= 2) and drain the job to the bitwise solo
    answer."""
    import os
    import signal
    import tempfile

    import numpy as np

    from repro.core import stream
    from repro.core.service import SweepRequest
    from repro.runtime import workers as wk

    grid_kw = ctx["grid_kw"]
    req = SweepRequest(grid=grid_kw, track="all", chunk_size=31,
                       scan_chunks=1, top_k=4)
    ref = stream.stream_grid(**grid_kw, track="all", chunk_size=31,
                             scan_chunks=1, top_k=4)
    with tempfile.TemporaryDirectory(prefix="smoke_reclaim_") as spool:
        handle = wk.dispatch_job(spool, req, n_leases=6,
                                 checkpoint_every_steps=1)
        with wk.WorkerPool(spool, 3, ttl_s=2.0, respawn=False) as pool:
            victim = None
            deadline = time.time() + 240
            while victim is None and time.time() < deadline:
                st = handle.poll()
                if st["done"]:
                    break
                for ls in st["leases"]:
                    if ls["state"] == "leased" \
                            and ls["owner"] in pool.pids():
                        victim = int(ls["owner"])
                        break
                time.sleep(0.02)
            assert victim is not None, "no worker claimed a lease"
            os.kill(victim, signal.SIGKILL)
            deadline = time.time() + 240
            while time.time() < deadline:
                st = handle.poll()
                assert not st["failed"], st["failed"]
                if st["done"]:
                    break
                time.sleep(0.1)
            st = handle.poll()
            assert st["done"], f"job did not drain: {st['states']}"
        attempts = max(int(ls["attempt"]) for ls in st["leases"])
        assert attempts >= 2, \
            "killed worker's lease was never reclaimed"
        res = handle.result()
        assert res.min_val == ref.min_val and \
            res.min_idx == ref.min_idx, "reclaimed argmin drifted"
        assert np.array_equal(res.topk_idx, ref.topk_idx) and \
            np.array_equal(res.topk_val, ref.topk_val), \
            "reclaimed top-k drifted"
        assert np.array_equal(res.front_indices, ref.front_indices) \
            and np.array_equal(res.front_values, ref.front_values), \
            "reclaimed front drifted"
    return [("smoke.worker_kill_reclaim", 1.0,
             f"worker SIGKILL -> lease reclaimed (max attempt "
             f"{attempts}) -> {int(res.stats['n_parts'])} parts folded "
             f"bitwise")]


#: The named, individually-timed smoke steps, in dependency order
#: (``stream_parity`` seeds the shared dense reference).
SMOKE_STEPS = [
    ("stream_parity", _smoke_stream_parity),
    ("async_pipeline", _smoke_async_pipeline),
    ("constraints", _smoke_constraints),
    ("backends", _smoke_backends),
    ("stacked", _smoke_stacked),
    ("scenario", _smoke_scenario),
    ("transient_faults", _smoke_transient_faults),
    ("kill_resume", _smoke_kill_resume_step),
    ("service", _smoke_service),
    ("net-kill-reconnect", _smoke_net_kill_reconnect),
    ("net-fairness", _smoke_net_fairness),
    ("net-scaleout", _smoke_net_scaleout),
    ("worker-kill-reclaim", _smoke_worker_kill_reclaim),
]


def smoke_rows(step_timeout_s: float | None = None):
    """Fast CI gate: run every named smoke step under its watchdog."""
    if step_timeout_s is None:
        step_timeout_s = float(os.environ.get(
            SMOKE_STEP_TIMEOUT_ENV, DEFAULT_SMOKE_STEP_TIMEOUT_S))
    ctx: dict = {}
    rows = []
    for name, fn in SMOKE_STEPS:
        t0 = time.time()
        with _step_timeout(name, step_timeout_s):
            rows.extend(fn(ctx))
        rows.append((f"smoke.step.{name}.wall_s", time.time() - t0,
                     f"<= {step_timeout_s:.0f}s watchdog"))
    return rows


def _smoke_kill_resume(grid_kw: dict) -> int:
    """SIGKILL a checkpointed subprocess sweep, resume in a fresh one.

    Returns the resumed-from step index (> 0).  The resume child
    recomputes the dense reference itself and asserts bitwise parity on
    every deliverable, so the gate fails on any divergence, not just a
    crash."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import tempfile

    with tempfile.TemporaryDirectory(prefix="smoke_ckpt_") as ckpt:
        common = f"""
import numpy as np
from repro.core import pareto, stream, sweep
GRID = {grid_kw!r}
KW = dict(chunk_size=97, top_k=4, track="all",
          checkpoint_dir={ckpt!r}, checkpoint_every_steps=1)
"""
        kill = common + """
from repro.runtime import FaultInjector, FaultPlan
inj = FaultInjector(FaultPlan(kill_at=2))
stream.stream_grid(**GRID, **KW, fault_injector=inj)
raise SystemExit("unreachable: SIGKILL did not fire")
"""
        resume = common + """
import json
dense = sweep.evaluate_grid(**GRID)
res = stream.stream_grid(**GRID, **KW)
assert res.stats["resumed_from_step"] > 0, res.stats
assert all(res.argmin(f) == dense.argmin(f) for f in sweep.FIELDS)
assert all(res.top_k(o) == dense.top_k(o, 4) for o in res.objectives)
df = pareto.pareto_front(dense); sf = res.pareto_front()
assert np.array_equal(df.indices, sf.indices)
assert np.array_equal(df.values, sf.values)
print(json.dumps({"resumed_from_step": res.stats["resumed_from_step"]}))
"""
        # CPU children: this process already holds JAX (see
        # _smoke_net_kill_reconnect).
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # Pin the child to one device so the dispatch geometry (and
        # with it the kill_at trigger) is independent of any inherited
        # ``XLA_FLAGS`` — appending wins, the last flag takes effect.
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=1")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
               if p])
        out1 = subprocess.run([sys.executable, "-c", kill], env=env,
                              capture_output=True, text=True, timeout=600)
        assert out1.returncode == -signal.SIGKILL, (
            f"kill child exited {out1.returncode}, expected SIGKILL: "
            f"{out1.stderr[-1000:]}")
        out2 = subprocess.run([sys.executable, "-c", resume], env=env,
                              capture_output=True, text=True, timeout=600)
        assert out2.returncode == 0, \
            f"resume child failed: {out2.stderr[-2000:]}"
        return int(json.loads(out2.stdout.strip().splitlines()[-1])
                   ["resumed_from_step"])


def _smoke_service_kill_resume(grid_kw: dict) -> int:
    """SIGKILL a spool-backed SweepService mid-request; a fresh service
    over the same spool must re-admit the journaled request and resume
    it to the bitwise solo-run answer.  Returns the resumed step."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import tempfile

    with tempfile.TemporaryDirectory(prefix="smoke_svc_") as spool:
        common = f"""
import numpy as np
from repro.core import stream
from repro.core.service import SweepRequest, SweepService
GRID = {grid_kw!r}
REQ = SweepRequest(grid=GRID, track="all", chunk_size=97, top_k=4)
SPOOL = {spool!r}
"""
        kill = common + """
from repro.runtime import FaultInjector, FaultPlan
inj = FaultInjector(FaultPlan(kill_at=2))
svc = SweepService(spool_dir=SPOOL, checkpoint_every_steps=1,
                   fault_injector=inj)
svc.submit(REQ).result(timeout=600)
raise SystemExit("unreachable: SIGKILL did not fire")
"""
        resume = common + """
import json
svc = SweepService(spool_dir=SPOOL, checkpoint_every_steps=1)
ts = svc.tickets()
assert len(ts) == 1, "recovery did not re-admit the journaled request"
res = ts[0].result(timeout=600)
svc.close()
assert not res.partial
assert res.stats["resumed_from_step"] > 0, res.stats
ref = stream.stream_grid(**GRID, track="all", chunk_size=97, top_k=4)
assert res.min_val == ref.min_val and res.min_idx == ref.min_idx
assert res.finite_counts == ref.finite_counts
assert np.array_equal(res.topk_idx, ref.topk_idx)
assert np.array_equal(res.topk_val, ref.topk_val)
assert np.array_equal(res.front_indices, ref.front_indices)
assert np.array_equal(res.front_values, ref.front_values)
print(json.dumps({"resumed_from_step": res.stats["resumed_from_step"]}))
"""
        # CPU children: this process already holds JAX (see
        # _smoke_net_kill_reconnect).
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        # Pin the child to one device (see _smoke_kill_resume_step):
        # the kill_at trigger depends on the dispatch geometry, which
        # inherited ``XLA_FLAGS`` would otherwise change.
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=1")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
               if p])
        out1 = subprocess.run([sys.executable, "-c", kill], env=env,
                              capture_output=True, text=True, timeout=600)
        assert out1.returncode == -signal.SIGKILL, (
            f"service kill child exited {out1.returncode}, expected "
            f"SIGKILL: {out1.stderr[-1000:]}")
        out2 = subprocess.run([sys.executable, "-c", resume], env=env,
                              capture_output=True, text=True, timeout=600)
        assert out2.returncode == 0, \
            f"service resume child failed: {out2.stderr[-2000:]}"
        return int(json.loads(out2.stdout.strip().splitlines()[-1])
                   ["resumed_from_step"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=SUITES)
    ap.add_argument("--smoke", action="store_true",
                    help="fast streaming/dense parity gate (CI)")
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        print("name,value,derived")
        t0 = time.time()
        for name, val, derived in smoke_rows():
            print(f"{name},{val:.6g},{derived}")
        print(f"smoke.wall_s,{time.time()-t0:.1f},streaming parity gate")
        return
    suites = [args.only] if args.only else SUITES
    print("name,value,derived")
    t0 = time.time()
    failures = 0
    for s in suites:
        try:
            if s == "dosc_advisor":
                rows = dosc_advisor_rows()
            else:
                mod = __import__(f"benchmarks.{s}", fromlist=["rows"])
                rows = mod.rows()
            for name, val, derived in rows:
                print(f"{name},{val:.6g},{derived}")
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{s}.FAILED,0,{type(e).__name__}: {e}")
    print(f"benchmarks.wall_s,{time.time()-t0:.1f},"
          f"{len(suites)} suites, {failures} failures")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
