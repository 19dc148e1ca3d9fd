"""Sweep service: admission control, fusion, deadlines, crash recovery.

The service (`repro.core.service.SweepService`) wraps the streaming
executor in a long-lived server; everything it adds on top must be
*exactness-preserving*: a served request returns bitwise what a solo
`stream_grid` call would, fusion slices each member's deliverables
exactly out of the stacked dispatch, a deadline or cancel yields the
executor's consistent prefix snapshot (never garbage), and a SIGKILL'd
server restarted over the same spool resumes to bitwise-identical
results.  Backpressure is reject-at-the-door: admitted work is never
dropped and submission never deadlocks.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core import pareto, stream, sweep
from repro.core.service import (CancelledError, ServiceClosedError,
                                SweepRequest, SweepService, _fusable,
                                _fused_request)
from repro.runtime import (AdmissionQueue, BackpressureError, Deadline,
                           FaultInjector, FaultPlan)

# A smaller grid than test_stream's reference (1,632 configs with the
# default cut axis) so multi-request scenarios stay fast; chunk 97 does
# not divide it, exercising the ragged tail through the service path.
GRID = dict(
    agg_nodes=("7nm", "16nm"),
    sensor_nodes=("7nm", "16nm"),
    detnet_fps=(10.0, 20.0, 30.0),
    keynet_fps=(30.0, 45.0),
    num_cameras=(2.0, 4.0),
)
CHUNK = 97
TOP_K = 4
OBJS = pareto.DEFAULT_OBJECTIVES


@pytest.fixture(scope="module")
def dense():
    return sweep.evaluate_grid(**GRID)


@pytest.fixture(scope="module")
def dense_front(dense):
    return pareto.pareto_front(dense)


@pytest.fixture(scope="module")
def solo(dense):
    """The reference solo run every served request must reproduce."""
    return stream.stream_grid(**GRID, track="all", chunk_size=CHUNK,
                              top_k=TOP_K)


def _request(**kw):
    kw.setdefault("grid", GRID)
    kw.setdefault("track", "all")
    kw.setdefault("chunk_size", CHUNK)
    kw.setdefault("top_k", TOP_K)
    return SweepRequest(**kw)


def _assert_bitwise(res, ref):
    """Bitwise equality on every deliverable of two stream results."""
    assert res.min_val == ref.min_val
    assert res.min_idx == ref.min_idx
    assert res.finite_counts == ref.finite_counts
    assert res.channel_min == ref.channel_min
    assert res.channel_max == ref.channel_max
    assert np.array_equal(res.topk_idx, ref.topk_idx)
    assert np.array_equal(res.topk_val, ref.topk_val)
    assert np.array_equal(res.front_indices, ref.front_indices)
    assert np.array_equal(res.front_values, ref.front_values)


# ---------------------------------------------------------------------------
# Admission primitives
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_none_never_expires(self):
        d = Deadline.after(None)
        assert not d.expired()
        assert d.remaining_s() is None

    def test_expiry_and_remaining(self):
        d = Deadline.after(0.0)
        assert d.expired()
        assert d.remaining_s() <= 0.0
        far = Deadline.after(60.0)
        assert not far.expired()
        assert 0.0 < far.remaining_s() <= 60.0

    def test_earliest_picks_tightest(self):
        a, b = Deadline.after(10.0), Deadline.after(60.0)
        assert Deadline.earliest(a, b, Deadline.after(None)).at == a.at
        assert Deadline.earliest(Deadline.after(None)).at is None
        assert Deadline.earliest().at is None


class TestAdmissionQueue:
    def test_capacity_validated(self):
        with pytest.raises(ValueError):
            AdmissionQueue(0)

    def test_offer_rejects_at_capacity_with_fields(self):
        q = AdmissionQueue(2)
        q.offer("a")
        q.offer("b")
        with pytest.raises(BackpressureError) as ei:
            q.offer("c")
        assert ei.value.queue_depth == 2
        assert ei.value.capacity == 2
        assert q.depth == 2            # rejected item was not enqueued
        assert q.snapshot() == ["a", "b"]

    def test_take_batch_fifo_and_timeout(self):
        q = AdmissionQueue(4)
        assert q.take_batch(timeout=0.01) == []
        q.offer("a")
        q.offer("b")
        assert q.take_batch(timeout=0.01) == ["a"]
        assert q.take_batch(timeout=0.01) == ["b"]

    def test_take_batch_claims_compatible_followers(self):
        q = AdmissionQueue(8)
        for item in ("a1", "b1", "a2", "a3", "b2"):
            q.offer(item)
        same = lambda head, other: other[0] == head[0]
        batch = q.take_batch(timeout=0.01, compatible=same, max_batch=3)
        assert batch == ["a1", "a2", "a3"]
        # Incompatible items keep their FIFO order.
        assert q.snapshot() == ["b1", "b2"]

    def test_take_batch_respects_max_batch(self):
        q = AdmissionQueue(8)
        for item in ("a1", "a2", "a3"):
            q.offer(item)
        batch = q.take_batch(timeout=0.01,
                             compatible=lambda h, o: True, max_batch=2)
        assert batch == ["a1", "a2"]
        assert q.snapshot() == ["a3"]

    def test_readmit_prepends_and_bypasses_capacity(self):
        q = AdmissionQueue(1)
        q.offer("new")
        q.readmit("recovered")         # full queue must still accept it
        assert q.snapshot() == ["recovered", "new"]

    def test_remove(self):
        q = AdmissionQueue(4)
        q.offer("a")
        assert q.remove("a") is True
        assert q.remove("a") is False
        assert q.depth == 0


# ---------------------------------------------------------------------------
# Request validation & fusion rules (pure functions — no executor)
# ---------------------------------------------------------------------------


class TestSweepRequest:
    def test_unknown_grid_axis_rejected(self):
        with pytest.raises(ValueError, match="unknown grid axes"):
            _request(grid={"not_an_axis": (1, 2)}).normalized()

    def test_json_round_trip(self):
        req = _request(constraints={"avg_power": 1.0},
                       deadline_s=2.5).normalized()
        clone = SweepRequest.from_json(
            json.loads(json.dumps(req.to_json())))
        assert clone == req

    def test_deadlines_never_fuse(self):
        a, b = _request(), _request(deadline_s=5.0)
        assert not _fusable(a, b)
        assert not _fusable(b, a)
        assert _fusable(a, _request())

    def test_sense_conflict_never_fuses(self):
        a = _request(objectives=OBJS, maximize=())
        b = _request(objectives=OBJS, maximize=(OBJS[0],))
        assert not _fusable(a, b)

    def test_front_containment_rules(self):
        head = _request(objectives=OBJS)                 # wants the front
        sub = _request(objectives=OBJS[:1], need_front=False)
        assert _fusable(head, sub)
        # A follower wanting a *different* front cannot ride along.
        assert not _fusable(head, _request(objectives=OBJS[:1]))
        # A no-front head cannot carry a front-wanting follower.
        assert not _fusable(_request(need_front=False), _request())

    def test_fused_request_covers_members(self):
        a = _request(objectives=OBJS[:2], track=("detnet_power",),
                     top_k=2)
        b = _request(objectives=OBJS[:1], need_front=False, top_k=6)
        fused = _fused_request([a, b])
        assert fused.objectives == tuple(OBJS[:2])   # head order first
        assert fused.top_k == 6
        assert fused.need_front
        assert fused.deadline_s is None


# ---------------------------------------------------------------------------
# The service itself
# ---------------------------------------------------------------------------


class TestServiceBasics:
    def test_served_request_bitwise_parity(self, solo):
        with SweepService() as svc:
            t = svc.submit(_request())
            res = t.result(timeout=600)
        assert not res.partial
        assert res.stats["fraction_complete"] == 1.0
        assert t.state == "done" and t.done()
        _assert_bitwise(res, solo)

    def test_plan_and_step_cache_hit_on_resubmit(self, solo):
        with SweepService() as svc:
            r1 = svc.submit(_request()).result(timeout=600)
            r2 = svc.submit(_request()).result(timeout=600)
            health = svc.health()
        _assert_bitwise(r1, r2)
        # Second submission resolves to the same content signature: the
        # plan LRU hits, and the plan's cached ChunkSpec makes the
        # compiled-step LRU hit (no recompilation across requests).
        assert health["plan_cache"]["misses"] == 1
        assert health["plan_cache"]["hits"] == 1
        assert health["step_cache"]["hits"] >= 1

    def test_health_surface_is_jsonable(self):
        with SweepService(capacity=3) as svc:
            svc.submit(_request()).result(timeout=600)
            health = svc.health()
        json.dumps(health)      # the whole surface must serialize
        assert health["capacity"] == 3
        assert health["queue_depth"] == 0
        assert health["counters"]["admitted"] == 1
        assert health["counters"]["completed"] == 1
        assert health["counters"]["executions"] == 1
        for key in ("retries", "restarts", "elastic_replans",
                    "stragglers", "deadline_expired"):
            assert key in health["counters"], key
        tid = next(iter(health["requests"]))
        assert health["requests"][tid]["state"] == "done"
        assert health["requests"][tid]["progress"] == 1.0

    def test_submit_after_close_raises(self):
        svc = SweepService()
        svc.close()
        with pytest.raises(RuntimeError, match="shut down"):
            svc.submit(_request())

    def test_malformed_request_rejected_before_admission(self):
        with SweepService() as svc:
            with pytest.raises(ValueError):
                svc.submit(_request(grid={"bogus_axis": (1,)}))
            assert svc.health()["counters"]["admitted"] == 0


class TestFusion:
    def test_compatible_requests_fuse_to_one_dispatch(self, solo, dense,
                                                      dense_front):
        with SweepService(capacity=8) as svc:
            svc.pause()        # let the backlog build deterministically
            ta = svc.submit(_request())
            tb = svc.submit(_request(top_k=2))
            tc = svc.submit(_request(objectives=OBJS[:1],
                                     need_front=False, track=None))
            svc.resume()
            ra = ta.result(timeout=600)
            rb = tb.result(timeout=600)
            rc = tc.result(timeout=600)
            counters = svc.health()["counters"]
        assert counters["executions"] == 1
        assert counters["fused_requests"] == 3
        for r in (ra, rb, rc):
            assert r.stats["fused_members"] == 3.0

        # Member A asked for the full reference request: bitwise parity.
        _assert_bitwise(ra, solo)
        # Member B differs only in top-k: its table is the first two
        # columns of the head's.
        assert np.array_equal(rb.topk_idx, solo.topk_idx[:, :2])
        assert np.array_equal(rb.topk_val, solo.topk_val[:, :2])
        assert np.array_equal(rb.front_indices, solo.front_indices)
        # Member C narrowed to one objective and no front.
        assert rc.objectives == tuple(OBJS[:1])
        assert rc.front_indices.size == 0
        obj = OBJS[0]
        assert rc.argmin(obj) == dense.argmin(obj)
        assert rc.top_k(obj) == dense.top_k(obj, TOP_K)

    def test_incompatible_requests_do_not_fuse(self):
        with SweepService(capacity=8) as svc:
            svc.pause()
            ta = svc.submit(_request())
            tb = svc.submit(_request(maximize=(OBJS[0],),
                                     need_front=False))
            svc.resume()
            ta.result(timeout=600)
            tb.result(timeout=600)
            counters = svc.health()["counters"]
        assert counters["executions"] == 2
        assert counters["fused_requests"] == 0


class TestBackpressure:
    def test_reject_at_capacity_without_dropping_work(self, solo):
        with SweepService(capacity=2) as svc:
            svc.pause()
            ta = svc.submit(_request())
            tb = svc.submit(_request(top_k=2))
            with pytest.raises(BackpressureError) as ei:
                svc.submit(_request())
            assert ei.value.queue_depth == 2
            assert ei.value.capacity == 2
            counters = svc.health()["counters"]
            assert counters["rejected"] == 1
            assert counters["admitted"] == 2
            svc.resume()
            # Rejection must not have disturbed the admitted work.
            ra = ta.result(timeout=600)
            tb.result(timeout=600)
        _assert_bitwise(ra, solo)


class TestIdempotentSubmit:
    def test_same_client_id_returns_same_ticket(self, solo):
        with SweepService() as svc:
            t1 = svc.submit(_request(), client_id="cid-1")
            t2 = svc.submit(_request(), client_id="cid-1")
            assert t1 is t2
            res = t2.result(timeout=600)
            counters = svc.health()["counters"]
        _assert_bitwise(res, solo)
        assert counters["deduped"] == 1
        assert counters["admitted"] == 1
        assert counters["executions"] == 1

    def test_same_client_id_different_request_rejected(self):
        with SweepService() as svc:
            svc.pause()
            svc.submit(_request(), client_id="cid-2")
            with pytest.raises(ValueError, match="already used"):
                svc.submit(_request(top_k=TOP_K + 1), client_id="cid-2")

    def test_rejected_submit_does_not_burn_the_client_id(self):
        with SweepService(capacity=1) as svc:
            svc.pause()
            svc.submit(_request())
            with pytest.raises(BackpressureError):
                svc.submit(_request(top_k=2), client_id="cid-3")
            svc.resume()
            svc.tickets()[0].result(timeout=600)
            # The id must be reusable: the rejection rolled back its
            # reservation instead of poisoning future submits.
            t = svc.submit(_request(top_k=2), client_id="cid-3")
            t.result(timeout=600)

    def test_finished_request_recovered_with_result(self, tmp_path,
                                                    solo):
        """A DONE request journals its result; a fresh service over the
        same spool re-attaches the idempotent client id to the finished
        ticket without re-executing."""
        spool = str(tmp_path / "spool")
        with SweepService(spool_dir=spool) as svc:
            first = svc.submit(_request(), client_id="cid-4")
            r1 = first.result(timeout=600)
        with SweepService(spool_dir=spool) as svc2:
            counters = svc2.health()["counters"]
            assert counters["recovered_finished"] == 1
            t = svc2.submit(_request(), client_id="cid-4")
            assert t.done() and t.state == "done"
            r2 = t.result(timeout=10)
            assert svc2.health()["counters"]["executions"] == 0
        _assert_bitwise(r1, solo)
        _assert_bitwise(r2, solo)

    def test_tenant_and_priority_round_trip(self):
        req = _request(tenant="alice", priority=3).normalized()
        clone = SweepRequest.from_json(
            json.loads(json.dumps(req.to_json())))
        assert clone.tenant == "alice" and clone.priority == 3
        assert clone == req


class TestServiceShutdown:
    def test_queued_ticket_fails_fast_when_service_closes(self):
        """`Ticket.result()` must never hang on a ticket nothing will
        ever finish: closing the service fails leftovers with
        ServiceClosedError instead of leaving waiters blocked."""
        svc = SweepService()
        svc.pause()
        t = svc.submit(_request())
        svc.close(drain=False)
        with pytest.raises(ServiceClosedError, match="service closed"):
            t.result(timeout=30)
        assert t.done()

    def test_closed_queued_ticket_resumes_on_restarted_spool(self,
                                                             tmp_path,
                                                             solo):
        """The fail-fast close keeps the journal state pre-shutdown, so
        a service restarted over the same spool still recovers and
        finishes the request."""
        spool = str(tmp_path / "spool")
        svc = SweepService(spool_dir=spool)
        svc.pause()
        t = svc.submit(_request())
        svc.close(drain=False)
        with pytest.raises(ServiceClosedError):
            t.result(timeout=30)
        with SweepService(spool_dir=spool) as svc2:
            assert svc2.health()["counters"]["recovered"] == 1
            res = svc2.tickets()[0].result(timeout=600)
        _assert_bitwise(res, solo)


class TestDeadlinesAndCancel:
    def test_deadline_returns_consistent_partial_snapshot(self, dense):
        # A 2 s straggle injected at chunk 1 guarantees the 0.8 s
        # deadline lapses mid-sweep regardless of host speed.
        inj = FaultInjector(FaultPlan(straggle={1: 2.0}))
        with SweepService(fault_injector=inj) as svc:
            t = svc.submit(_request(deadline_s=0.8))
            res = t.result(timeout=600)
            counters = svc.health()["counters"]
        assert res.partial
        frac = res.stats["fraction_complete"]
        assert 0.0 < frac < 1.0
        assert counters["deadline_expired"] == 1
        assert t.state == "done"
        # Prefix consistency: the snapshot is the exact reduction over
        # the first `base` flat configs, not an arbitrary mix.
        base = round(frac * dense.data[OBJS[0]].size)
        for field in OBJS:
            prefix = np.asarray(dense.data[field]).ravel()[:base]
            assert res.min_val[field] == float(np.nanmin(prefix)), field
            assert res.min_idx[field] == int(np.nanargmin(prefix)), field
            assert res.finite_counts[field] == \
                int(np.isfinite(prefix).sum()), field

    def test_cancel_before_execution(self):
        with SweepService() as svc:
            svc.pause()
            t = svc.submit(_request())
            t.cancel()
            svc.resume()
            assert t.done()
            assert t.state == "cancelled"
            with pytest.raises(CancelledError):
                t.result(timeout=10)

    def test_cancel_mid_run_yields_partial(self):
        inj = FaultInjector(FaultPlan(straggle={1: 1.0}))
        with SweepService(fault_injector=inj) as svc:
            t = svc.submit(_request())
            # Wait for the first chunk to land (the injected 1 s
            # straggle on chunk 1 then holds the run open) so the
            # cancel is observably mid-sweep, not pre-dispatch.
            deadline = time.monotonic() + 120
            while t.progress == 0.0 and time.monotonic() < deadline:
                time.sleep(0.01)
            t.cancel()
            res = t.result(timeout=600)
            counters = svc.health()["counters"]
        assert t.state == "cancelled"
        assert res.partial
        assert 0.0 < res.stats["fraction_complete"] < 1.0
        assert counters["cancelled"] == 1


class TestServiceCrashRecovery:
    """SIGKILL the server mid-request; a fresh service over the same
    spool must re-admit the journaled request, resume from the newest
    checkpoint, and deliver the bitwise solo-run answer."""

    _COMMON = """
import sys
import numpy as np
from repro.core import stream
from repro.core.service import SweepService, SweepRequest
GRID = dict(agg_nodes=("7nm","16nm"), sensor_nodes=("7nm","16nm"),
            detnet_fps=(10.,20.,30.), keynet_fps=(30.,45.),
            num_cameras=(2.,4.))
REQ = SweepRequest(grid=GRID, track="all", chunk_size=97, top_k=4)
"""

    KILL = _COMMON + """
from repro.runtime import FaultInjector, FaultPlan
inj = FaultInjector(FaultPlan(kill_at=4))
svc = SweepService(spool_dir=sys.argv[1], capacity=4,
                   checkpoint_every_steps=1, fault_injector=inj)
svc.submit(REQ).result(timeout=600)
print("UNREACHABLE")
"""

    RESUME = _COMMON + """
import json
svc = SweepService(spool_dir=sys.argv[1], capacity=4,
                   checkpoint_every_steps=1)
ts = svc.tickets()
assert len(ts) == 1, [t.id for t in ts]
assert svc.health()["counters"]["recovered"] == 1
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
print(json.dumps({"resumed_from_step": res.stats["resumed_from_step"],
                  "ok": True}))
"""

    @staticmethod
    def _run(code: str, spool: str) -> subprocess.CompletedProcess:
        env = dict(os.environ)
        # Pin the child to one device: earlier test modules import
        # repro.launch.dryrun, which writes a 512-device
        # ``XLA_FLAGS`` into os.environ at import time.  Inherited
        # unpinned, that collapses this 17-dispatch job into a single
        # sharded dispatch and ``kill_at=4`` never fires.  Appending
        # wins (last flag takes effect), mirroring test_stream /
        # test_elastic.
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=1")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        return subprocess.run([sys.executable, "-c", code, spool],
                              env=env, capture_output=True, text=True,
                              timeout=600)

    def test_sigkill_restart_resumes_bitwise(self, tmp_path):
        spool = str(tmp_path / "spool")
        out1 = self._run(self.KILL, spool)
        assert out1.returncode == -signal.SIGKILL, \
            (out1.returncode, out1.stderr[-2000:])
        assert "UNREACHABLE" not in out1.stdout
        out2 = self._run(self.RESUME, spool)
        assert out2.returncode == 0, out2.stderr[-2000:]
        payload = json.loads(out2.stdout.strip().splitlines()[-1])
        assert payload["ok"] is True
        assert payload["resumed_from_step"] > 0


class TestCLI:
    def test_module_entry_point_serves_requests(self, tmp_path):
        """`python -m repro.service` over a request file: one JSON
        summary per request plus a health snapshot."""
        reqfile = tmp_path / "reqs.jsonl"
        reqfile.write_text(json.dumps(
            _request(track=None).to_json()) + "\n")
        env = dict(os.environ)
        # Pin device count (see TestServiceCrashRecovery._run).
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=1")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        out = subprocess.run(
            [sys.executable, "-m", "repro.service",
             "--spool", str(tmp_path / "spool"),
             "--requests", str(reqfile), "--timeout-s", "600"],
            env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
        assert lines[0]["state"] == "done"
        assert lines[0]["fraction_complete"] == 1.0
        assert "argmin" in lines[0]
        assert lines[-1]["health"]["counters"]["completed"] == 1

    def test_module_entry_point_fails_when_a_request_fails(self, tmp_path):
        """A failed request makes `python -m repro.service` exit 1."""
        reqfile = tmp_path / "reqs.jsonl"
        reqfile.write_text(json.dumps(
            _request(objectives=("no_such_channel",)).to_json()) + "\n")
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src")]
            + env.get("PYTHONPATH", "").split(os.pathsep))
        out = subprocess.run(
            [sys.executable, "-m", "repro.service",
             "--requests", str(reqfile), "--timeout-s", "600"],
            env=env, capture_output=True, text=True, timeout=600)
        assert out.returncode == 1, out.stderr[-2000:]
        lines = [json.loads(l) for l in out.stdout.strip().splitlines()]
        assert lines[0]["state"] == "failed"
        assert "no_such_channel" in lines[0]["error"]
        assert lines[-1]["health"]["counters"]["failed"] == 1
