"""Worker pool: lease board transitions, range parity, kill-reclaim.

The scale-out path (`repro.runtime.workers`) must add *zero* numeric
semantics: a job drained by any number of worker processes folds to a
result bitwise-identical to one solo `stream_grid` call.  The lease
board is the only coordination state — claims, steals, reclaims and
completion all go through `board.json` under a flock — so these tests
drive the board directly (state machine), drain a job in-process
(parity), and finally SIGKILL a live worker mid-lease (chaos) to prove
the reclaim path reissues from the carry snapshot and still lands the
exact answer.
"""

import json
import os
import signal
import time

import numpy as np
import pytest

from repro.core import stream
from repro.core.service import SweepRequest, SweepService
from repro.runtime import workers as wk

# 2 * 2 * 12 * 2 * 2 = 192 configs; chunk 31 with scan_chunks=1 gives a
# 31-config lease quantum -> 7 dispatch steps, so a multi-lease board
# has interior boundaries that must respect flat_range alignment.
GRID = dict(
    agg_nodes=("7nm", "16nm"),
    sensor_nodes=("7nm", "16nm"),
    detnet_fps=tuple(float(f) for f in range(5, 65, 5)),
    keynet_fps=(30.0, 45.0),
    num_cameras=(2.0, 4.0),
)
CHUNK = 31
TOP_K = 4


def _request(**kw):
    kw.setdefault("grid", GRID)
    kw.setdefault("track", "all")
    kw.setdefault("chunk_size", CHUNK)
    kw.setdefault("scan_chunks", 1)
    kw.setdefault("top_k", TOP_K)
    return SweepRequest(**kw)


@pytest.fixture(scope="module")
def solo():
    return stream.stream_grid(**GRID, track="all", chunk_size=CHUNK,
                              scan_chunks=1, top_k=TOP_K)


def _assert_bitwise(res, ref):
    assert res.min_val == ref.min_val
    assert res.min_idx == ref.min_idx
    assert res.finite_counts == ref.finite_counts
    assert np.array_equal(res.topk_idx, ref.topk_idx)
    assert np.array_equal(res.topk_val, ref.topk_val)
    assert np.array_equal(res.front_indices, ref.front_indices)
    assert np.array_equal(res.front_values, ref.front_values)


# ---------------------------------------------------------------------------
# Lease board state machine (no execution)
# ---------------------------------------------------------------------------


class TestLeaseBoard:
    def _board(self, tmp_path, **kw):
        handle = wk.dispatch_job(str(tmp_path), _request(), **kw)
        return handle, handle.board

    def test_dispatch_tiles_the_flat_space_aligned(self, tmp_path):
        handle, board = self._board(tmp_path, n_leases=5)
        doc = board.read()
        leases = doc["leases"]
        assert leases[0]["start"] == 0
        assert leases[-1]["stop"] == handle.n_total
        q = doc["quantum"]
        for prev, cur in zip(leases, leases[1:]):
            assert prev["stop"] == cur["start"]       # contiguous tiling
            assert cur["start"] % q == 0              # aligned interior cut
        assert all(ls["state"] == "free" for ls in leases)

    def test_dispatch_is_idempotent_by_signature(self, tmp_path):
        h1, board = self._board(tmp_path, n_leases=3)
        assert board.claim("w-a", ttl=60.0) is not None
        h2 = wk.dispatch_job(str(tmp_path), _request(), n_leases=3)
        assert h2.job_dir == h1.job_dir
        # Reattach keeps the existing board — the claim survived.
        assert h2.board.read()["leases"][0]["state"] == "leased"

    def test_claim_heartbeat_steal(self, tmp_path):
        _, board = self._board(tmp_path, n_leases=2)
        lease = board.claim("w-a", ttl=0.05)
        assert lease["i"] == 0 and lease["attempt"] == 1
        assert board.heartbeat(0, "w-a", 0.25)
        time.sleep(0.1)                       # let the heartbeat go stale
        stolen = board.claim("w-b", ttl=0.05)
        assert stolen["i"] == 0 and stolen["attempt"] == 2
        # The old owner learns about the steal on its next beat ...
        assert not board.heartbeat(0, "w-a")
        # ... and its late fail() must not clobber the thief's lease.
        board.fail(0, "w-a", "boom")
        assert board.read()["leases"][0]["state"] == "leased"
        assert board.read()["leases"][0]["wid"] == "w-b"

    def test_fail_frees_then_attempt_cap_fails_terminally(self, tmp_path):
        _, board = self._board(tmp_path, n_leases=1, max_attempts=2)
        lease = board.claim("w-a", ttl=60.0)
        board.fail(lease["i"], "w-a", "transient")
        assert board.read()["leases"][0]["state"] == "free"
        lease = board.claim("w-a", ttl=60.0)
        assert lease["attempt"] == 2
        board.fail(lease["i"], "w-a", "again")
        assert board.read()["leases"][0]["state"] == "failed"
        assert board.claim("w-a", ttl=60.0) is None
        st = board.poll()
        assert not st["done"] and len(st["failed"]) == 1
        assert "again" in st["failed"][0]["error"]

    def test_done_wins_over_steal(self, tmp_path):
        _, board = self._board(tmp_path, n_leases=1)
        board.claim("w-a", ttl=0.05)
        time.sleep(0.1)
        board.claim("w-b", ttl=0.05)          # steal
        # The straggler completes anyway: deterministic execution means
        # its part is byte-identical, so "done" is accepted.
        board.complete(0, "w-a", {"fake": "part"})
        doc = board.read()
        assert doc["leases"][0]["state"] == "done"
        with open(board.part_path(0)) as f:
            assert json.load(f) == {"fake": "part"}

    def test_cancel_flag_round_trip(self, tmp_path):
        handle, board = self._board(tmp_path)
        assert not board.cancelled()
        handle.cancel()
        assert board.cancelled()
        # Re-dispatch (idempotent reattach) clears the stale flag.
        wk.dispatch_job(str(tmp_path), _request())
        assert not board.cancelled()


# ---------------------------------------------------------------------------
# In-process drain: parity and checkpoint-resume on reclaim
# ---------------------------------------------------------------------------


class TestWorkerDrain:
    def test_once_drain_is_bitwise_exact(self, tmp_path, solo):
        handle = wk.dispatch_job(str(tmp_path), _request(), n_leases=5)
        assert wk.worker_loop(str(tmp_path), wid="w-test", once=True) == 0
        st = handle.poll()
        assert st["done"] and st["fraction"] == 1.0
        res = handle.result()
        _assert_bitwise(res, solo)
        assert res.stats["n_parts"] == 5.0
        snap = handle.snapshot()
        assert snap["fraction_complete"] == 1.0
        assert snap["best"] is not None

    def test_reclaim_resumes_from_carry_snapshot(self, tmp_path, solo):
        """A lease abandoned mid-range (owner died after checkpointing)
        is reclaimed and *resumed* — the finished prefix is not
        recomputed — and the fold is still bitwise-exact."""
        handle = wk.dispatch_job(str(tmp_path), _request(), n_leases=2,
                                 checkpoint_every_steps=1)
        board = handle.board
        lease = board.claim("w-dead", ttl=0.05)
        plan = handle.plan
        stops = [0]

        def stop_after_two():
            stops[0] += 1
            return stops[0] > 2

        part = stream.stream_grid(
            plan=plan,
            flat_range=(lease["start"], lease["stop"]),
            checkpoint_dir=board.ckpt_dir(lease["i"]),
            checkpoint_every_steps=1,
            should_stop=stop_after_two)
        assert part.partial                   # died mid-lease
        assert os.listdir(board.ckpt_dir(lease["i"]))
        time.sleep(0.1)                       # heartbeat goes stale
        reclaimed = board.claim("w-heir", ttl=0.05)
        assert reclaimed["i"] == lease["i"]
        assert reclaimed["attempt"] == 2
        assert wk.run_lease(board, reclaimed, "w-heir", ttl=60.0)
        with open(board.part_path(reclaimed["i"])) as f:
            stats = json.load(f)["stats"]
        assert stats["resumed_from_step"] > 0
        assert wk.worker_loop(str(tmp_path), wid="w-rest", once=True) == 0
        _assert_bitwise(handle.result(), solo)


# ---------------------------------------------------------------------------
# Pooled service path
# ---------------------------------------------------------------------------


class TestPooledService:
    def test_service_dispatches_to_pool_bitwise(self, tmp_path, solo):
        svc = SweepService(capacity=4, snapshot_every_s=0.0, workers=2,
                           spool_dir=str(tmp_path / "spool"))
        try:
            t = svc.submit(_request())
            res = t.result(timeout=600)
            _assert_bitwise(res, solo)
            assert res.stats["n_parts"] >= 2.0
            assert svc.counters["pooled_executions"] == 1
            assert svc.health()["workers"]["n"] == 2
            # Snapshot path: the coordinator folds finished parts into
            # progress snapshots of the executor's shape.
            if t.snapshot is not None:
                assert 0.0 <= t.snapshot["fraction_complete"] <= 1.0
                assert t.snapshot["partial"] is True
        finally:
            svc.close()

    @pytest.mark.parametrize("make", ["pool", "service"])
    def test_refused_off_the_cpu_backend(self, tmp_path, monkeypatch,
                                         make):
        # Pool children are pinned by a host-platform flag only: on a
        # chip host they would all contend for the parent's chip.
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="only on the CPU backend"):
            if make == "pool":
                wk.WorkerPool(str(tmp_path / "spool"), 2)
            else:
                SweepService(workers=2, spool_dir=str(tmp_path / "spool"))
        assert not (tmp_path / "spool").exists()   # nothing spawned

    def test_deadline_requests_bypass_the_pool(self, tmp_path, solo):
        svc = SweepService(capacity=4, snapshot_every_s=0.0, workers=1,
                           spool_dir=str(tmp_path / "spool"))
        try:
            t = svc.submit(_request(deadline_s=600.0))
            res = t.result(timeout=600)
            _assert_bitwise(res, solo)
            assert svc.counters["pooled_executions"] == 0
        finally:
            svc.close()


# ---------------------------------------------------------------------------
# Chaos: SIGKILL a live worker mid-lease (the reclaim gate)
# ---------------------------------------------------------------------------


class TestWorkerKillReclaim:
    def test_sigkill_one_of_three_workers_reclaims_bitwise(
            self, tmp_path, solo):
        spool = str(tmp_path / "spool")
        os.makedirs(spool)
        handle = wk.dispatch_job(spool, _request(), n_leases=6,
                                 checkpoint_every_steps=1)
        ttl = 2.0
        with wk.WorkerPool(spool, 3, ttl_s=ttl, respawn=False) as pool:
            victim = None
            deadline = time.monotonic() + 300
            while victim is None and time.monotonic() < deadline:
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
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                st = handle.poll()
                assert not st["failed"], st["failed"]
                if st["done"]:
                    break
                time.sleep(0.1)
            st = handle.poll()
            assert st["done"], f"job did not drain: {st['states']}"
        # The killed worker's lease went stale and was reissued: at
        # least one lease needed a second attempt ...
        assert max(int(ls["attempt"]) for ls in st["leases"]) >= 2
        # ... and the fold is still exactly the solo answer.
        _assert_bitwise(handle.result(), solo)
