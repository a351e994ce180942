"""Sharded-checkpoint suite (ISSUE 9): manifest/commit protocol, elastic
mesh-reshape restore, async saves with retry/drain/abandon semantics,
shard-level fault injection with checksum-verified fallback, partial-dir
cleanup, the ``python -m apex_tpu.checkpoint verify`` fsck, preemption
during an in-flight async write, and monitor reconciliation of the
``ckpt_*`` counters.

Everything here runs on the 8 virtual CPU devices the conftest forces;
the compile-bound reshape-parity TRAINING runs live in
``test_checkpoint_reshape_parity.py`` (slow tier).
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.checkpoint import (
    CheckpointCorruptionError,
    RetryingCheckpointManager,
    ShardedCheckpointManager,
    verify_directory,
)
from apex_tpu.checkpoint.manifest import (
    COMMIT_NAME,
    atomic_write_bytes,
    read_commit,
    validate_step_dir,
)
from apex_tpu.checkpoint.verify import main as verify_main
from apex_tpu.observability import JsonlSink, MetricsRegistry, build_report
from apex_tpu.observability.report import CHECKPOINT_INCIDENT_COUNTERS
from apex_tpu.resilience import (
    ResilienceConfig,
    make_resilient_train_step,
    make_train_state,
    run_training,
)
from apex_tpu.testing_faults import (
    FaultInjector,
    corrupt_shard,
    tear_manifest,
)


def _mesh(rows, cols):
    devs = np.array(jax.devices()[:rows * cols]).reshape(rows, cols)
    return Mesh(devs, ("data", "tensor"))


def _sharded_state(mesh, scale=1.0):
    """A small train-state-shaped pytree with the dryrun sharding mix:
    2-D sharded, 1-D sharded, dp-replicated, and an unsharded scalar."""
    w = jax.device_put(scale * jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh, P("data", "tensor")))
    b = jax.device_put(scale * jnp.arange(8.0),
                       NamedSharding(mesh, P("tensor")))
    full = jax.device_put(scale * jnp.arange(16.0).reshape(4, 4),
                          NamedSharding(mesh, P()))
    return {"params": {"w": w, "b": b}, "full": full,
            "step": jnp.asarray(3, jnp.int32)}


def _template(mesh):
    if mesh is None:
        return {"params": {"w": jnp.zeros((8, 8)), "b": jnp.zeros(8)},
                "full": jnp.zeros((4, 4)),
                "step": jnp.asarray(0, jnp.int32)}
    zeros = _sharded_state(mesh, scale=0.0)
    zeros["step"] = jnp.asarray(0, jnp.int32)
    return zeros


def _assert_state_equal(restored, reference):
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(restored), jax.device_get(reference))


# ---------------------------------------------------------------------------
# manifest / commit protocol
# ---------------------------------------------------------------------------

class TestCommitProtocol:
    def test_committed_step_validates_clean(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(5, _sharded_state(_mesh(4, 2)))
        step_dir = str(tmp_path / "5")
        marker = read_commit(step_dir)
        assert marker is not None and marker["step"] == 5
        assert validate_step_dir(step_dir, deep=True) == []
        # shards are addressed by (param-path, global-shard-index)
        names = sorted(os.listdir(step_dir))
        assert "manifest.json" in names and COMMIT_NAME in names
        assert any(n.startswith("leaf0000_s") for n in names)

    def test_replicas_deduplicated_on_save(self, tmp_path):
        # the dp-replicated leaf ("full", spec P()) exists on all 8
        # devices but must be written exactly once
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(0, _sharded_state(_mesh(4, 2)))
        manifest = json.loads((tmp_path / "0" / "manifest.json").read_text())
        leaves = manifest["leaves"]
        full_key = next(k for k in leaves if "full" in k)
        w_key = next(k for k in leaves if "'w'" in k)
        assert len(leaves[full_key]["shards"]) == 1
        assert len(leaves[w_key]["shards"]) == 8  # 4x2 distinct tiles

    def test_no_commit_marker_means_invisible(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(1, _sharded_state(_mesh(4, 2)))
        # simulate a writer killed between the data/manifest writes and
        # the commit rename: a full step directory minus COMMIT
        mgr.save(2, _sharded_state(_mesh(4, 2)))
        os.remove(str(tmp_path / "2" / COMMIT_NAME))
        assert mgr.all_steps() == [1]
        assert mgr.latest_step() == 1
        assert mgr.uncommitted_steps() == [2]
        assert mgr.restore(_template(_mesh(4, 2)))[0] == 1

    def test_cleanup_partial_removes_debris_not_excluded(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(1, _sharded_state(_mesh(4, 2)))
        for junk in (2, 3):
            os.makedirs(str(tmp_path / str(junk)))
        assert mgr.cleanup_partial(exclude=[3]) == [2]
        assert not (tmp_path / "2").exists()
        assert (tmp_path / "3").exists()     # mid-write step protected
        assert mgr.all_steps() == [1]

    def test_atomic_write_leaves_no_temp_droppings(self, tmp_path):
        atomic_write_bytes(str(tmp_path / "x"), b"payload")
        assert sorted(os.listdir(tmp_path)) == ["x"]
        assert (tmp_path / "x").read_bytes() == b"payload"

    def test_max_to_keep_prunes_oldest(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path), max_to_keep=2)
        state = _sharded_state(_mesh(4, 2))
        for step in (1, 2, 3, 4):
            mgr.save(step, state)
        assert mgr.all_steps() == [3, 4]


# ---------------------------------------------------------------------------
# elastic (mesh-reshape) restore
# ---------------------------------------------------------------------------

class TestElasticRestore:
    @pytest.mark.parametrize("target", ["dp2tp4", "dp8tp1", "single"])
    def test_reshape_restore_matches(self, tmp_path, target):
        """Save under dp=4×tp=2; restore under a different layout. The
        acceptance matrix: values must be identical bit-for-bit."""
        save_mesh = _mesh(4, 2)
        state = _sharded_state(save_mesh)
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(7, state)

        tmpl_mesh = {"dp2tp4": _mesh(2, 4), "dp8tp1": _mesh(8, 1),
                     "single": None}[target]
        step, restored = mgr.restore(_template(tmpl_mesh))
        assert step == 7
        _assert_state_equal(restored, state)
        if tmpl_mesh is not None:
            # the restore landed in the TARGET layout, not the saved one
            restored_w = restored["params"]["w"]
            assert restored_w.sharding.mesh.shape == dict(
                tmpl_mesh.shape)

    def test_single_device_save_restores_onto_mesh(self, tmp_path):
        # the reverse direction: unsharded save, sharded restore
        plain = {"params": {"w": jnp.arange(64.0).reshape(8, 8),
                            "b": jnp.arange(8.0)},
                 "full": jnp.arange(16.0).reshape(4, 4),
                 "step": jnp.asarray(3, jnp.int32)}
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(0, plain)
        _, restored = mgr.restore(_template(_mesh(2, 4)))
        _assert_state_equal(restored, plain)

    def test_structure_mismatch_raises(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(0, _sharded_state(_mesh(4, 2)))
        with pytest.raises(ValueError, match="no leaf"):
            mgr.restore_step(0, {"something": jnp.zeros((8, 8))})

    def test_global_shape_mismatch_raises(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(0, _sharded_state(_mesh(4, 2)))
        bad = _template(_mesh(4, 2))
        bad["full"] = jnp.zeros((2, 2))
        with pytest.raises(ValueError, match="global shape"):
            mgr.restore_step(0, bad)


# ---------------------------------------------------------------------------
# shard-level fault injection -> checksum detection -> fallback
# ---------------------------------------------------------------------------

class TestIntegrityFaults:
    def _two_steps(self, tmp_path):
        mesh = _mesh(4, 2)
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(1, _sharded_state(mesh, scale=1.0))
        mgr.save(2, _sharded_state(mesh, scale=2.0))
        return mgr, mesh

    @pytest.mark.parametrize("kind", ["bitflip", "truncate", "missing"])
    def test_single_damaged_shard_detected_and_fallback(self, tmp_path,
                                                        kind):
        mgr, mesh = self._two_steps(tmp_path)
        # leaf 2 is params['w'] in keystr order ('full', params 'b', 'w',
        # 'step'): the 4×2-sharded leaf, so shard 3 is one of 8 tiles
        corrupt_shard(str(tmp_path), 2, leaf=2, shard=3, kind=kind)
        # direct restore of the damaged step: the checksum catches it
        with pytest.raises(CheckpointCorruptionError):
            mgr.restore_step(2, _template(mesh))
        # through the retry layer: fall back to the older committed step
        rmgr = RetryingCheckpointManager(mgr, backoff_base=0.0)
        step, restored = rmgr.restore_latest(_template(mesh))
        assert step == 1
        _assert_state_equal(restored, _sharded_state(mesh, scale=1.0))
        assert rmgr.telemetry["verify_failures"] == 1
        assert rmgr.telemetry["restore_fallbacks"] == 1
        assert rmgr.telemetry["deleted_corrupt"] == 1
        assert mgr.all_steps() == [1]

    def test_torn_manifest_detected(self, tmp_path):
        mgr, mesh = self._two_steps(tmp_path)
        tear_manifest(str(tmp_path), 2)
        with pytest.raises(CheckpointCorruptionError, match="manifest"):
            mgr.restore_step(2, _template(mesh))
        rmgr = RetryingCheckpointManager(mgr, backoff_base=0.0)
        assert rmgr.restore_latest(_template(mesh))[0] == 1

    def test_verify_step_raises_with_problem_list(self, tmp_path):
        mgr, _ = self._two_steps(tmp_path)
        mgr.verify_step(2)  # healthy: no raise
        corrupt_shard(str(tmp_path), 2, kind="bitflip")
        with pytest.raises(CheckpointCorruptionError, match="sha256"):
            mgr.verify_step(2)


# ---------------------------------------------------------------------------
# async saves: retry on the writer, drain vs abandon, partial cleanup
# ---------------------------------------------------------------------------

class _ExplodingManager(ShardedCheckpointManager):
    """Fails the first N write attempts AFTER creating partial debris —
    the disk-full-mid-write shape the cleanup satellite targets."""

    def __init__(self, directory, explosions, **kw):
        super().__init__(directory, **kw)
        self.explosions = explosions

    def write_snapshot(self, step, snap, *, force=False):
        if self.explosions > 0:
            self.explosions -= 1
            os.makedirs(self._step_dir(step), exist_ok=True)
            with open(os.path.join(self._step_dir(step),
                                   "leaf0000_s00.npy"), "wb") as f:
                f.write(b"partial")
            raise IOError("injected: disk full mid-write")
        return super().write_snapshot(step, snap, force=force)


class TestAsyncSaves:
    def test_async_save_returns_before_commit_and_drains(self, tmp_path):
        state = _sharded_state(_mesh(4, 2))
        inj = FaultInjector(save_delays={1: 0.3})
        rmgr = RetryingCheckpointManager(
            ShardedCheckpointManager(str(tmp_path)), backoff_base=0.0,
            before_save=inj.before_checkpoint_save)
        t0 = time.monotonic()
        assert rmgr.save(1, state) is True
        accepted_in = time.monotonic() - t0
        # only the host snapshot blocked the caller, not the delayed write
        assert accepted_in < 0.25
        assert rmgr.pending_saves == [1]
        rmgr.drain()
        assert rmgr.manager.all_steps() == [1]
        assert verify_directory(str(tmp_path))[0].status == "ok"
        rmgr.close()

    def test_writer_errors_surface_in_retry_loop(self, tmp_path):
        state = _sharded_state(_mesh(4, 2))
        inj = FaultInjector(save_failures={1: 2})
        rmgr = RetryingCheckpointManager(
            ShardedCheckpointManager(str(tmp_path)), max_retries=3,
            backoff_base=0.0, before_save=inj.before_checkpoint_save)
        assert rmgr.save(1, state) is True
        rmgr.drain()
        assert rmgr.manager.all_steps() == [1]   # retried to success
        assert rmgr.telemetry["save_retries"] == 2
        assert rmgr.telemetry["save_failures"] == 0
        rmgr.close()

    def test_terminal_writer_failure_counted_step_absent(self, tmp_path):
        state = _sharded_state(_mesh(4, 2))
        inj = FaultInjector(save_failures={1: 99})
        rmgr = RetryingCheckpointManager(
            ShardedCheckpointManager(str(tmp_path)), max_retries=2,
            backoff_base=0.0, before_save=inj.before_checkpoint_save)
        rmgr.save(1, state)
        rmgr.drain()
        assert rmgr.manager.all_steps() == []
        assert rmgr.telemetry["save_failures"] == 1
        rmgr.close()

    def test_forced_save_drains_inflight_write(self, tmp_path):
        state = _sharded_state(_mesh(4, 2))
        inj = FaultInjector(save_delays={1: 0.3})
        rmgr = RetryingCheckpointManager(
            ShardedCheckpointManager(str(tmp_path)), backoff_base=0.0,
            drain_on_force=True, before_save=inj.before_checkpoint_save)
        rmgr.save(1, state)
        assert rmgr.save(2, state, force=True) is True
        # the emergency save waited for the pending write: both committed
        assert rmgr.manager.all_steps() == [1, 2]
        assert rmgr.telemetry["saves_abandoned"] == 0
        rmgr.close()

    def test_forced_save_abandons_queued_write(self, tmp_path):
        state = _sharded_state(_mesh(4, 2))
        inj = FaultInjector(save_delays={1: 0.5})
        rmgr = RetryingCheckpointManager(
            ShardedCheckpointManager(str(tmp_path)), backoff_base=0.0,
            drain_on_force=False, before_save=inj.before_checkpoint_save)
        rmgr.save(1, state)   # running (held by the delay)
        rmgr.save(2, state)   # queued behind it on the single writer
        assert rmgr.save(3, state, force=True) is True
        # the running write still commits (atomicity holds), the queued
        # one is dropped, the emergency save lands — never a torn step
        assert rmgr.manager.all_steps() == [1, 3]
        assert rmgr.telemetry["saves_abandoned"] == 1
        assert all(r.status == "ok" for r in verify_directory(
            str(tmp_path)))
        rmgr.close()

    def test_failed_attempts_sweep_their_partial_debris(self, tmp_path):
        state = _sharded_state(_mesh(4, 2))
        mgr = _ExplodingManager(str(tmp_path), explosions=2)
        rmgr = RetryingCheckpointManager(mgr, max_retries=3,
                                         backoff_base=0.0,
                                         async_writes=False)
        assert rmgr.save(1, state, force=True) is True
        assert rmgr.telemetry["partials_cleaned"] == 2
        assert mgr.uncommitted_steps() == []
        assert mgr.all_steps() == [1]

    def test_restore_sweeps_and_never_adopts_partials(self, tmp_path):
        mesh = _mesh(4, 2)
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(1, _sharded_state(mesh))
        os.makedirs(str(tmp_path / "9"))   # interrupted-save debris
        rmgr = RetryingCheckpointManager(mgr, backoff_base=0.0)
        step, _ = rmgr.restore_latest(_template(mesh))
        assert step == 1
        assert rmgr.telemetry["partials_cleaned"] == 1
        assert not (tmp_path / "9").exists()
        rmgr.close()

    def test_donated_buffers_cannot_corrupt_inflight_snapshot(self,
                                                              tmp_path):
        # the snapshot must deep-copy: overwrite the source arrays while
        # the (delayed) write is in flight, then restore and compare
        mesh = _mesh(4, 2)
        state = _sharded_state(mesh, scale=1.0)
        expect = jax.device_get(state)
        inj = FaultInjector(save_delays={1: 0.3})
        rmgr = RetryingCheckpointManager(
            ShardedCheckpointManager(str(tmp_path)), backoff_base=0.0,
            before_save=inj.before_checkpoint_save)
        rmgr.save(1, state)
        # donate every param buffer while the write is still sleeping:
        # if the snapshot aliased device memory the checksum would be a
        # valid hash of garbage
        clobber = jax.jit(lambda x: x * -7.0, donate_argnums=0)
        state["params"] = jax.tree.map(clobber, state["params"])
        jax.block_until_ready(state["params"])
        rmgr.drain()
        _, restored = rmgr.restore_latest(_template(mesh))
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
            jax.device_get(restored), expect)
        rmgr.close()


# ---------------------------------------------------------------------------
# preemption during an in-flight async save (satellite 3)
# ---------------------------------------------------------------------------

TARGET = jnp.full((4, 4), 0.3)


def _loss_fn(p, batch, rng):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _batch_fn(step):
    x = jax.random.normal(jax.random.PRNGKey(step), (8, 4))
    return {"x": x, "y": x @ TARGET}


def _fresh():
    from apex_tpu.optimizers import FusedSGD
    opt = FusedSGD(lr=0.05)
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    return make_train_state(params, opt.init(params))


def _step_fn():
    from apex_tpu.optimizers import FusedSGD
    return make_resilient_train_step(_loss_fn, FusedSGD(lr=0.05))


def _cfg(**kw):
    base = dict(poll_interval_steps=2, save_interval_steps=4,
                max_consecutive_skips=3, min_history=4,
                save_backoff_base=0.0, handle_sigterm=False)
    base.update(kw)
    return ResilienceConfig(**base)


class TestPreemptionDuringAsyncSave:
    @pytest.mark.parametrize("drain", [True, False])
    def test_committed_set_is_never_torn(self, tmp_path, drain):
        """Preempt while the step-8 save is still on the writer. The
        committed-step set afterward must be exactly the pre-save set or
        include the new step — and every committed step must pass a deep
        fsck; an uncommitted hybrid may exist only as invisible debris."""
        run_dir = str(tmp_path / "run")
        inj = FaultInjector(save_delays={8: 0.4}, preempt_at_call=8)
        res = run_training(_step_fn(), _fresh(), _batch_fn, 40,
                           checkpoint_dir=run_dir,
                           config=_cfg(preemption_drain=drain,
                                       save_final=False),
                           fault_injector=inj)
        assert res.status == "preempted"
        assert res.telemetry["emergency_saves"] == 1
        reports = verify_directory(run_dir)
        committed = [r.step for r in reports if r.status != "uncommitted"]
        assert all(r.status == "ok" for r in reports
                   if r.step in committed), reports
        # pre-save set {4} plus the new step(s): 8 from the drained (or
        # still-running) write and/or the forced emergency save
        assert 4 in committed and 8 in committed
        assert res.telemetry["ckpt_save_failures"] == 0

        # and the run is resumable from what was committed
        resumed = run_training(_step_fn(), _fresh(), _batch_fn, 12,
                               checkpoint_dir=run_dir, config=_cfg())
        assert resumed.status == "completed"
        assert resumed.telemetry["resumes"] == 1
        assert resumed.steps_completed == 12

    def test_writer_killed_before_commit_leaves_invisible_debris(
            self, tmp_path):
        # the on-disk shape a hard kill mid-write leaves: shards +
        # manifest, no COMMIT. restore_latest must not see it, and the
        # next resume sweeps it.
        mesh = _mesh(4, 2)
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(1, _sharded_state(mesh))
        mgr.save(2, _sharded_state(mesh, scale=2.0))
        os.remove(str(tmp_path / "2" / COMMIT_NAME))
        rmgr = RetryingCheckpointManager(mgr, backoff_base=0.0)
        step, _ = rmgr.restore_latest(_template(mesh))
        assert step == 1
        assert rmgr.telemetry["restore_fallbacks"] == 0  # never adopted
        assert mgr.uncommitted_steps() == []             # swept
        rmgr.close()


# ---------------------------------------------------------------------------
# fsck CLI (satellite 1)
# ---------------------------------------------------------------------------

class TestVerifyCLI:
    def _populate(self, root):
        mesh = _mesh(4, 2)
        mgr = ShardedCheckpointManager(str(root), max_to_keep=10)
        mgr.save(1, _sharded_state(mesh))
        mgr.save(2, _sharded_state(mesh, scale=2.0))
        mgr.save(3, _sharded_state(mesh, scale=3.0))
        corrupt_shard(str(root), 2, kind="bitflip")
        os.remove(str(root / "3" / COMMIT_NAME))  # uncommitted debris

    def test_verify_main_exit_codes_and_listing(self, tmp_path, capsys):
        self._populate(tmp_path)
        assert verify_main(["verify", str(tmp_path)]) == 1  # damage
        out = capsys.readouterr().out
        assert "adoptable steps: [1]" in out
        assert "DAMAGED steps:   [2]" in out
        assert "uncommitted" in out and "sha256 mismatch" in out

    def test_verify_clean_dir_exits_zero(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(1, _sharded_state(_mesh(4, 2)))
        assert verify_main(["verify", str(tmp_path)]) == 0

    def test_gc_removes_uncommitted_only(self, tmp_path, capsys):
        self._populate(tmp_path)
        verify_main(["verify", str(tmp_path), "--gc"])
        capsys.readouterr()
        assert not (tmp_path / "3").exists()
        assert (tmp_path / "2").exists()  # damaged-but-committed is kept

    def test_shallow_misses_bitflip_catches_truncation(self, tmp_path):
        mgr = ShardedCheckpointManager(str(tmp_path))
        mgr.save(1, _sharded_state(_mesh(4, 2)))
        corrupt_shard(str(tmp_path), 1, kind="bitflip")
        assert verify_main(["verify", str(tmp_path), "--shallow"]) == 0
        assert verify_main(["verify", str(tmp_path)]) == 1
        corrupt_shard(str(tmp_path), 1, leaf=2, shard=1, kind="truncate")
        assert verify_main(["verify", str(tmp_path), "--shallow"]) == 1

    @pytest.mark.slow
    def test_cli_subprocess_contract(self, tmp_path):
        """The real entry point: ``python -m apex_tpu.checkpoint verify``
        exits non-zero on damage, zero once the damage is gone."""
        self._populate(tmp_path)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.checkpoint", "verify",
             str(tmp_path)],
            capture_output=True, text=True, timeout=300, env=env)
        assert proc.returncode == 1, proc.stderr
        assert "DAMAGED" in proc.stdout
        import shutil
        shutil.rmtree(str(tmp_path / "2"))
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.checkpoint", "verify",
             str(tmp_path)],
            capture_output=True, text=True, timeout=300, env=env)
        assert proc.returncode == 0, proc.stderr


# ---------------------------------------------------------------------------
# monitor reconciliation of checkpoint telemetry
# ---------------------------------------------------------------------------

class TestCheckpointTelemetryReconciliation:
    def test_counters_and_events_reconcile(self, tmp_path):
        run_dir = str(tmp_path / "run")

        # run 1: a transient save failure exercises the retry counters
        jsonl1 = str(tmp_path / "run1.jsonl")
        reg1 = MetricsRegistry([JsonlSink(jsonl1)])
        inj = FaultInjector(save_failures={4: 1})
        res1 = run_training(_step_fn(), _fresh(), _batch_fn, 8,
                            checkpoint_dir=run_dir,
                            config=_cfg(metrics=reg1, save_final=False),
                            fault_injector=inj)
        reg1.close()
        assert res1.status == "completed"
        assert res1.telemetry["ckpt_save_retries"] == 1
        report1 = build_report(jsonl1)
        assert report1["counters"] == res1.telemetry

        # damage the newest step, then resume: checksum-verified fallback
        corrupt_shard(run_dir, 8, kind="bitflip")
        jsonl2 = str(tmp_path / "run2.jsonl")
        reg2 = MetricsRegistry([JsonlSink(jsonl2)])
        res2 = run_training(_step_fn(), _fresh(), _batch_fn, 12,
                            checkpoint_dir=run_dir,
                            config=_cfg(metrics=reg2))
        reg2.close()
        assert res2.status == "completed"
        assert res2.telemetry["resumes"] == 1
        assert res2.telemetry["ckpt_verify_failures"] == 1
        assert res2.telemetry["ckpt_restore_fallbacks"] == 1
        assert res2.telemetry["ckpt_deleted_corrupt"] == 1

        report2 = build_report(jsonl2)
        # the headline contract: the monitor's final counter snapshot IS
        # the result telemetry, ckpt_* keys included
        assert report2["counters"] == res2.telemetry
        # and the checkpoints section reconciles event-for-counter
        ckpt = report2["checkpoints"]
        assert ckpt is not None
        for event, counter in CHECKPOINT_INCIDENT_COUNTERS.items():
            assert ckpt["counts"].get(event, 0) == \
                report2["counters"].get(counter, 0), (event, counter)
        # write/snapshot histograms observed
        assert ckpt["timings"]["ckpt_write_s"]["count"] >= 1
        assert ckpt["timings"]["ckpt_snapshot_blocked_s"]["count"] >= 1

    def test_render_includes_checkpoint_section(self, tmp_path):
        from apex_tpu.observability import render_report

        jsonl = str(tmp_path / "run.jsonl")
        reg = MetricsRegistry([JsonlSink(jsonl)])
        run_training(_step_fn(), _fresh(), _batch_fn, 8,
                     checkpoint_dir=str(tmp_path / "run"),
                     config=_cfg(metrics=reg))
        reg.close()
        text = render_report(build_report(jsonl))
        assert "checkpoints:" in text
        assert "save attempts:" in text
