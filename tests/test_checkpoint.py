"""CheckpointManager: atomicity, dtype fidelity, retention, raw restore.

The checkpoint layer underwrites every recovery path of the streaming
executor (kill-resume parity, elastic replan, graceful degradation), so
its core guarantees are pinned directly here:

* a crash at *any* point mid-save never corrupts or shadows the latest
  durable checkpoint (writes land in a ``.tmp`` dir renamed into place);
* bf16 and other ``ml_dtypes`` leaves round-trip bit-exactly (npz cannot
  hold them natively, so they travel as raw bytes + manifest dtype);
* retention keeps exactly the ``keep`` most recent steps;
* ``restore_items`` returns ``{path: array}`` without a like-tree, for
  state with data-dependent shapes (the executor's Pareto-front rows).
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager


def _restore(mgr, step, like):
    """``restore`` places leaves as jnp arrays; keep 64-bit dtypes
    intact (the executor itself restores via ``restore_items``, which
    stays in numpy and never downcasts)."""
    with jax.enable_x64(True):
        return mgr.restore(step, like=like)


def _state(step: int):
    rng = np.random.default_rng(step)
    return {
        "carry": {
            "min_val": rng.random(3),
            "min_idx": rng.integers(0, 1000, 3),
        },
        "front_values": rng.random((step + 1, 3)),
    }


def _assert_tree_equal(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        if isinstance(a[k], dict):
            _assert_tree_equal(a[k], b[k])
        else:
            assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


class TestRoundTrip:
    def test_save_restore_like_tree(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        state = _state(3)
        mgr.save(3, state, metadata={"next_flat": 12})
        got = _restore(mgr, 3, state)
        _assert_tree_equal(state, got)
        assert mgr.metadata(3)["next_flat"] == 12

    def test_restore_items_without_like_tree(self, tmp_path):
        """Data-dependent shapes (Pareto front rows) restore by path."""
        mgr = CheckpointManager(str(tmp_path))
        state = _state(5)
        mgr.save(5, state)
        items = mgr.restore_items(5)
        assert set(items) == {"carry/min_val", "carry/min_idx",
                              "front_values"}
        assert np.array_equal(items["front_values"],
                              state["front_values"])
        assert items["front_values"].shape == (6, 3)
        assert np.array_equal(items["carry/min_idx"],
                              state["carry"]["min_idx"])

    def test_bf16_round_trips_bitwise(self, tmp_path):
        """npz can't store bf16; the manager must anyway (raw bytes)."""
        mgr = CheckpointManager(str(tmp_path))
        vals = jnp.asarray(
            np.random.default_rng(0).random(64), jnp.bfloat16)
        mgr.save(0, {"w": vals})
        got = mgr.restore_items(0)["w"]
        assert got.dtype == jnp.bfloat16
        assert np.asarray(vals).tobytes() == got.tobytes()

    def test_restore_rejects_shape_mismatch(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(0, {"x": np.zeros(4)})
        with pytest.raises(ValueError, match="shape mismatch"):
            mgr.restore(0, like={"x": np.zeros(5)})
        with pytest.raises(ValueError, match="leaves"):
            mgr.restore(0, like={"x": np.zeros(4), "y": np.zeros(1)})


class TestAtomicity:
    """A crash at any point mid-save leaves the previous step intact."""

    def test_crash_during_array_write(self, tmp_path, monkeypatch):
        mgr = CheckpointManager(str(tmp_path))
        good = _state(1)
        mgr.save(1, good, metadata={"next_flat": 8})

        def boom(*a, **kw):
            raise OSError("disk full (injected)")

        monkeypatch.setattr(np, "savez", boom)
        with pytest.raises(OSError):
            mgr.save(2, _state(2))
        monkeypatch.undo()

        # The failed step is invisible; the prior one is untouched.
        assert mgr.all_steps() == [1]
        assert mgr.latest_step() == 1
        _assert_tree_equal(good, _restore(mgr, 1, good))

    def test_crash_during_rename(self, tmp_path, monkeypatch):
        """Crash after the payload is written but before the atomic
        rename: the ``.tmp`` debris must never be listed as a step."""
        mgr = CheckpointManager(str(tmp_path))
        good = _state(1)
        mgr.save(1, good)

        real_rename = os.rename

        def boom(src, dst):
            if src.endswith(".tmp"):
                raise OSError("killed before rename (injected)")
            return real_rename(src, dst)

        monkeypatch.setattr(os, "rename", boom)
        with pytest.raises(OSError):
            mgr.save(2, _state(2))
        monkeypatch.undo()

        assert os.path.isdir(str(tmp_path / "step_000000002.tmp"))
        assert mgr.all_steps() == [1]
        _assert_tree_equal(good, _restore(mgr, 1, good))
        # A retry of the same step succeeds over the debris.
        mgr.save(2, _state(2))
        assert mgr.all_steps() == [1, 2]

    def test_fresh_manager_sweeps_crash_debris(self, tmp_path):
        """A restart over a spool left by a SIGKILL'd process clears
        ``*.tmp`` debris (the only artifact an atomic-rename crash can
        leave) — long-lived service spools must not accumulate orphan
        dirs across crash/restart cycles."""
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, _state(1))
        debris = tmp_path / "step_000000007.tmp"
        debris.mkdir()
        (debris / "arrays.npz").write_bytes(b"torn write")
        mgr2 = CheckpointManager(str(tmp_path))
        assert not debris.exists()
        assert mgr2.all_steps() == [1]

    def test_manifestless_dir_is_not_a_step(self, tmp_path):
        """A foreign/truncated step dir without manifest.json is not a
        checkpoint (the executor's resume scan must skip it)."""
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(4, _state(4))
        os.makedirs(str(tmp_path / "step_000000009"))
        assert mgr.all_steps() == [4]
        assert mgr.latest_step() == 4

    def test_resave_same_step_replaces(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(0, {"x": np.zeros(3)})
        mgr.save(0, {"x": np.ones(3)})
        assert np.array_equal(mgr.restore_items(0)["x"], np.ones(3))


class TestRetention:
    def test_keep_prunes_oldest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in range(5):
            mgr.save(s, _state(s))
        assert mgr.all_steps() == [3, 4]
        # Survivors remain fully restorable.
        _assert_tree_equal(_state(4), _restore(mgr, 4, _state(4)))

    def test_keep_zero_disables_pruning(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=0)
        for s in range(4):
            mgr.save(s, _state(s))
        assert mgr.all_steps() == [0, 1, 2, 3]


class TestManifest:
    def test_manifest_records_paths_shapes_dtypes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(7, _state(7), metadata={"signature": "abc"})
        with open(str(tmp_path / "step_000000007" /
                      "manifest.json")) as f:
            man = json.load(f)
        assert man["step"] == 7
        assert man["metadata"] == {"signature": "abc"}
        by_path = {e["path"]: e for e in man["leaves"]}
        assert by_path["front_values"]["shape"] == [8, 3]
        assert by_path["carry/min_idx"]["dtype"] == "int64"
