"""Resilience-layer tests (docs/fault_tolerance.md).

Three layers of proof:

- **unit**: the commit protocol primitives (manifests, markers, discovery)
  and the watchdog/preemption/backoff machinery in-process;
- **fault-injected**: every injected fault (truncate, bit-flip, delayed
  rename, rename-without-marker, kill-during-save) must leave
  ``load_state(resume="latest")`` recovering the last *committed*
  checkpoint, never a corrupt one;
- **subprocess**: real SIGTERM mid-training → emergency checkpoint →
  bit-identical resumed loss trajectory; real kill -9 mid-save with
  ``total_limit=1`` → the previous checkpoint survives (the
  rotation-before-durability regression); a wedged step → watchdog stack
  dump + nonzero exit; a preempted worker group → elastic resume without
  burning a --max_restarts attempt.
"""

import io
import logging
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.heavy  # compile-heavy / subprocess lane

import accelerate_tpu as atx
from accelerate_tpu import checkpointing, resilience
from accelerate_tpu.resilience import commit as commit_mod
from accelerate_tpu.resilience.watchdog import Watchdog
from accelerate_tpu.test_utils import faults
from accelerate_tpu.utils.dataclasses import ProjectConfiguration

from tests.launch_helpers import REPO_ROOT, clean_env, launch

SCRIPTS = os.path.join(REPO_ROOT, "tests", "scripts")


@pytest.fixture(autouse=True)
def _reset_resilience_state():
    yield
    resilience.clear_preemption()
    import accelerate_tpu.resilience.watchdog as wmod

    if wmod._ENV_WATCHDOG is not None:
        wmod._ENV_WATCHDOG.stop()
        wmod._ENV_WATCHDOG = None


def _auto_acc(tmp_path, **cfg):
    return atx.Accelerator(
        project_config=ProjectConfiguration(
            project_dir=str(tmp_path), automatic_checkpoint_naming=True, **cfg
        ),
        seed=0,
    )


def _w_state(acc, offset=0.0):
    return acc.create_train_state({"w": jnp.arange(8.0) + offset}, optax.sgd(0.1))


def _child_env(extra=None):
    env = clean_env({"JAX_PLATFORMS": "cpu"})
    env.update(extra or {})
    return env


def _run_script(script, *argv, env=None, timeout=240):
    return subprocess.run(
        [sys.executable, os.path.join(SCRIPTS, script), *argv],
        cwd=REPO_ROOT,
        env=env or _child_env(),
        capture_output=True,
        text=True,
        timeout=timeout,
    )


# ===================================================================== commit
class TestCommitPrimitives:
    def test_manifest_verify_roundtrip(self, tmp_path):
        d = str(tmp_path)
        with open(os.path.join(d, "a.bin"), "wb") as f:
            f.write(b"hello world" * 100)
        os.makedirs(os.path.join(d, "sub"))
        with open(os.path.join(d, "sub", "b.json"), "w") as f:
            f.write("{}")
        commit_mod.write_manifest(d, 0, ["a.bin", os.path.join("sub", "b.json")])
        assert commit_mod.verify_checkpoint(d) == []

    def test_verify_catches_truncate_bitflip_and_missing(self, tmp_path):
        d = str(tmp_path)
        path = os.path.join(d, "a.bin")
        with open(path, "wb") as f:
            f.write(os.urandom(4096))
        commit_mod.write_manifest(d, 0, ["a.bin"])

        faults.truncate_file(path, keep_fraction=0.5)
        assert any("size mismatch" in e for e in commit_mod.verify_checkpoint(d))

        with open(path, "wb") as f:
            f.write(os.urandom(4096))
        commit_mod.write_manifest(d, 0, ["a.bin"])
        faults.flip_bit(path)
        assert any("sha256 mismatch" in e for e in commit_mod.verify_checkpoint(d))

        os.remove(path)
        assert any("missing file" in e for e in commit_mod.verify_checkpoint(d))

    def test_discovery_only_sees_committed(self, tmp_path):
        root = str(tmp_path)
        for name in ("checkpoint_0", "checkpoint_1", "checkpoint_2.tmp", "other"):
            os.makedirs(os.path.join(root, name))
        commit_mod.commit_dir(
            os.path.join(root, "checkpoint_0"), os.path.join(root, "checkpoint_0_f")
        )
        os.rename(os.path.join(root, "checkpoint_0_f"), os.path.join(root, "checkpoint_0"))
        found = commit_mod.committed_checkpoints(root)
        assert [n for n, _ in found] == [0]
        assert commit_mod.latest_committed(root).endswith("checkpoint_0")
        removed = commit_mod.remove_stale_tmp(root)
        assert len(removed) == 1 and removed[0].endswith("checkpoint_2.tmp")
        # non-checkpoint names and uncommitted dirs are left alone
        assert os.path.isdir(os.path.join(root, "other"))
        assert os.path.isdir(os.path.join(root, "checkpoint_1"))

    def test_commit_marker_is_written_last(self, tmp_path):
        tmp = str(tmp_path / "checkpoint_0.tmp")
        final = str(tmp_path / "checkpoint_0")
        os.makedirs(tmp)
        with faults.raise_at("commit.before_marker"):
            with pytest.raises(faults.FaultInjected):
                commit_mod.commit_dir(tmp, final, {"step": 1})
        # renamed but uncommitted: invisible to discovery
        assert os.path.isdir(final) and not commit_mod.is_committed(final)
        assert commit_mod.committed_checkpoints(str(tmp_path)) == []

    def _committed_two_proc(self, tmp_path, meta, *, steps=(3, 3)):
        tmp = str(tmp_path / "checkpoint_0.tmp")
        final = str(tmp_path / "checkpoint_0")
        os.makedirs(tmp)
        for proc, step in enumerate(steps):
            fname = f"shards_{proc}.bin"
            with open(os.path.join(tmp, fname), "wb") as f:
                f.write(os.urandom(64))
            commit_mod.write_manifest(tmp, proc, [fname], step=step)
        commit_mod.commit_dir(tmp, final, meta)
        return final

    def test_verify_rejects_missing_process_manifest(self, tmp_path):
        """Completeness: deleting an entire process's manifest + shard pair
        from a committed multi-process checkpoint must NOT verify clean
        (resume would pick the amputated checkpoint over the previous good
        one and load partial state)."""
        final = self._committed_two_proc(
            tmp_path, {"step": 3, "num_processes": 2}
        )
        assert commit_mod.verify_checkpoint(final) == []
        os.remove(os.path.join(final, "manifest_1.json"))
        os.remove(os.path.join(final, "shards_1.bin"))
        errors = commit_mod.verify_checkpoint(final)
        assert any("manifest count mismatch" in e for e in errors), errors

    def test_verify_save_on_each_node_exempt_from_completeness(self, tmp_path):
        """save_on_each_node commits one per-node directory per process —
        a single manifest against num_processes=2 is by design, not loss."""
        tmp = str(tmp_path / "checkpoint_0.tmp")
        final = str(tmp_path / "checkpoint_0")
        os.makedirs(tmp)
        with open(os.path.join(tmp, "shards_1.bin"), "wb") as f:
            f.write(os.urandom(64))
        commit_mod.write_manifest(tmp, 1, ["shards_1.bin"], step=3)
        commit_mod.commit_dir(
            tmp, final, {"step": 3, "num_processes": 2, "save_on_each_node": True}
        )
        assert commit_mod.verify_checkpoint(final) == []

    def test_verify_rejects_cross_process_step_mismatch(self, tmp_path):
        """Manifests recording different steps = shards from different
        steps in one directory; per-file hashes all pass, the checkpoint
        must still be rejected."""
        final = self._committed_two_proc(
            tmp_path, {"step": 3, "num_processes": 2}, steps=(3, 4)
        )
        errors = commit_mod.verify_checkpoint(final)
        assert any("cross-process step mismatch" in e for e in errors), errors

    def test_verify_rejects_marker_step_disagreement(self, tmp_path):
        final = self._committed_two_proc(
            tmp_path, {"step": 7, "num_processes": 2}, steps=(3, 3)
        )
        errors = commit_mod.verify_checkpoint(final)
        assert any("marker's step 7" in e for e in errors), errors

    def test_precommit_file_barrier(self, tmp_path):
        d = str(tmp_path)
        commit_mod.mark_precommit(d, 0)
        commit_mod.mark_precommit(d, 1)
        commit_mod.wait_for_precommit(d, 2, timeout_secs=1.0)
        assert not any(n.startswith(".precommit") for n in os.listdir(d))
        with pytest.raises(RuntimeError, match="timed out"):
            commit_mod.wait_for_precommit(d, 2, timeout_secs=0.2)


# ==================================================== fault-injected resume
class TestVerifiedResume:
    """Every injected fault must leave resume="latest" recovering the last
    committed checkpoint — never a corrupt one, never crash debris."""

    def _two_checkpoints(self, tmp_path, **cfg):
        acc = _auto_acc(tmp_path, **cfg)
        state = _w_state(acc)
        p0 = acc.save_state(None, state)
        state1 = state.replace(
            params={"w": state.params["w"] + 100.0}, step=state.step + 1
        )
        p1 = acc.save_state(None, state1)
        return acc, state, p0, p1

    def _resume(self, acc):
        target = _w_state(acc)
        return acc.load_state(None, target, resume="latest")

    def test_healthy_resume_picks_newest(self, tmp_path):
        acc, _, _, _ = self._two_checkpoints(tmp_path)
        restored = self._resume(acc)
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), np.arange(8.0) + 100.0
        )
        assert int(jax.device_get(restored.step)) == 1

    @pytest.mark.parametrize("corrupt", ["truncate", "bitflip", "missing"])
    def test_corrupt_newest_falls_back_with_warning(self, tmp_path, corrupt):
        acc, _, p0, p1 = self._two_checkpoints(tmp_path)
        shards = os.path.join(p1, checkpointing.MODEL_DIR, "shards_0.npz")
        if corrupt == "truncate":
            faults.truncate_file(shards)
        elif corrupt == "bitflip":
            faults.flip_bit(shards)
        else:
            os.remove(os.path.join(p1, "rng_state_0.json"))
        with pytest.warns(resilience.CheckpointIntegrityWarning, match="falling back"):
            restored = self._resume(acc)
        np.testing.assert_array_equal(np.asarray(restored.params["w"]), np.arange(8.0))
        assert int(jax.device_get(restored.step)) == 0

    def test_delayed_rename_tmp_dir_is_invisible(self, tmp_path):
        acc, state, _, p1 = self._two_checkpoints(tmp_path)
        newer = state.replace(
            params={"w": state.params["w"] + 999.0}, step=state.step + 2
        )
        with faults.raise_at("commit.before_rename"):
            with pytest.raises(faults.FaultInjected):
                acc.save_state(None, newer)
        root = os.path.dirname(p1)
        assert os.path.isdir(os.path.join(root, "checkpoint_2.tmp"))
        restored = self._resume(acc)
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), np.arange(8.0) + 100.0
        )
        # the next successful save reclaims the crashed save's tmp dir
        acc.save_state(None, newer)
        assert not os.path.isdir(os.path.join(root, "checkpoint_2.tmp"))

    def test_rename_without_marker_is_invisible(self, tmp_path):
        acc, state, _, p1 = self._two_checkpoints(tmp_path)
        newer = state.replace(
            params={"w": state.params["w"] + 999.0}, step=state.step + 2
        )
        with faults.raise_at("commit.before_marker"):
            with pytest.raises(faults.FaultInjected):
                acc.save_state(None, newer)
        root = os.path.dirname(p1)
        debris = os.path.join(root, "checkpoint_2")
        assert os.path.isdir(debris) and not resilience.is_committed(debris)
        restored = self._resume(acc)
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), np.arange(8.0) + 100.0
        )

    def test_all_committed_corrupt_raises(self, tmp_path):
        acc, _, p0, p1 = self._two_checkpoints(tmp_path)
        for p in (p0, p1):
            faults.flip_bit(os.path.join(p, checkpointing.MODEL_DIR, "shards_0.npz"))
        with pytest.warns(resilience.CheckpointIntegrityWarning):
            with pytest.raises(ValueError, match="every committed checkpoint"):
                self._resume(acc)

    def test_no_committed_checkpoint_raises(self, tmp_path):
        acc = _auto_acc(tmp_path)
        with pytest.raises(FileNotFoundError, match="no committed checkpoint"):
            acc.load_state(None, _w_state(acc), resume="latest")

    def test_explicit_dir_corruption_raises(self, tmp_path):
        acc, _, _, p1 = self._two_checkpoints(tmp_path)
        faults.flip_bit(os.path.join(p1, checkpointing.MODEL_DIR, "shards_0.npz"))
        with pytest.raises(ValueError, match="integrity verification"):
            acc.load_state(p1, _w_state(acc))

    def test_total_limit_1_crash_mid_save_keeps_previous(self, tmp_path):
        """The rotation-before-durability regression, in-process variant
        (the kill -9 subprocess variant is TestKillDuringSave): with
        total_limit=1 a crashed second save must leave the first
        checkpoint committed and loadable."""
        acc = _auto_acc(tmp_path, total_limit=1)
        state = _w_state(acc)
        p0 = acc.save_state(None, state)
        newer = state.replace(params={"w": state.params["w"] + 1.0}, step=state.step + 1)
        with faults.raise_at("save.files_written"):
            with pytest.raises(faults.FaultInjected):
                acc.save_state(None, newer)
        assert resilience.is_committed(p0)
        restored = self._resume(acc)
        np.testing.assert_array_equal(np.asarray(restored.params["w"]), np.arange(8.0))

    def test_async_save_commits_and_rotates_after(self, tmp_path):
        acc = _auto_acc(tmp_path, total_limit=2)
        state = _w_state(acc)
        for k in range(3):
            acc.save_state(
                None,
                state.replace(step=jnp.asarray(k, jnp.int32)),
                async_save=True,
            )
        checkpointing.wait_for_checkpoint()
        root = tmp_path / "checkpoints"
        assert sorted(os.listdir(root)) == ["checkpoint_1", "checkpoint_2"]
        assert all(
            resilience.is_committed(str(root / n)) for n in os.listdir(root)
        )
        assert resilience.verify_checkpoint(str(root / "checkpoint_2")) == []


# ================================================================ async saver
class TestAsyncSaverErrors:
    def test_failure_logged_immediately_then_reraised_on_wait(self, caplog):
        saver = checkpointing._AsyncSaver()

        def boom():
            raise RuntimeError("disk full")

        with caplog.at_level(logging.ERROR, logger="accelerate_tpu.checkpointing"):
            saver.submit(boom)
            saver._thread.join()
        assert any(
            "async checkpoint save failed" in r.message for r in caplog.records
        )
        with pytest.raises(RuntimeError, match="disk full"):
            saver.wait()

    def test_atexit_hook_joins_and_swallows(self, caplog):
        """The registered atexit hook must drain the in-flight save and log
        (not raise) so a clean interpreter exit never truncates it."""
        checkpointing._ASYNC_SAVER.submit(
            lambda: (_ for _ in ()).throw(RuntimeError("late failure"))
        )
        with caplog.at_level(logging.ERROR, logger="accelerate_tpu.checkpointing"):
            checkpointing._wait_for_checkpoint_at_exit()  # must not raise
        assert any("interpreter exit" in r.message for r in caplog.records)
        checkpointing.wait_for_checkpoint()  # drained: no error left behind


# ================================================================= preemption
class TestPreemption:
    def test_sigterm_sets_flag(self):
        from accelerate_tpu.resilience import preemption as pmod

        try:
            assert pmod.install_preemption_handler()
            assert pmod.install_preemption_handler()  # idempotent
            pmod.clear_preemption()
            os.kill(os.getpid(), signal.SIGTERM)
            deadline = time.time() + 2.0
            while not pmod.preemption_requested() and time.time() < deadline:
                time.sleep(0.01)
            assert pmod.preemption_requested()
        finally:
            pmod._reset_for_tests()

    def test_step_helper_writes_emergency_checkpoint_and_exits_75(self, tmp_path):
        acc = _auto_acc(tmp_path)
        state = acc.create_train_state({"w": jnp.arange(8.0)}, optax.adam(1e-2))
        step = acc.make_train_step(lambda p, b, r: jnp.sum(p["w"] ** 2) * b["s"])
        batch = {"s": jnp.float32(1.0)}
        state, _ = step(state, batch)
        resilience.request_preemption()
        with pytest.raises(SystemExit) as e:
            step(state, batch)
        assert e.value.code == resilience.PREEMPTION_EXIT_CODE == 75
        latest = resilience.latest_committed(str(tmp_path / "checkpoints"))
        assert latest is not None
        assert resilience.verify_checkpoint(latest) == []
        resilience.clear_preemption()
        restored = acc.load_state(
            None,
            acc.create_train_state({"w": jnp.zeros(8)}, optax.adam(1e-2)),
            resume="latest",
        )
        assert int(jax.device_get(restored.step)) == 1
        np.testing.assert_array_equal(
            np.asarray(restored.params["w"]), np.asarray(state.params["w"])
        )

    def test_agreement_collective_spreads_peer_notice(self, tmp_path, monkeypatch):
        """The step-entry hook must act on the GROUP's or-reduced flag, not
        the local one (REVIEW high: signal-delivery skew on pods). Simulated
        2-process world: the or-reduce runs at every step entry, a
        peer-only notice triggers the emergency exit here, and the local
        flag is adopted so polls/escalation see consistent state."""
        import accelerate_tpu.accelerator as amod

        acc = _auto_acc(tmp_path)
        state = _w_state(acc)
        step = acc.make_train_step(lambda p, b, r: jnp.sum(p["w"] ** 2))
        state, _ = step(state, {})  # compile before patching the world

        monkeypatch.setattr(type(acc), "num_processes", property(lambda self: 2))
        calls, peer_flag = [], {"v": 0}

        def fake_or_reduce(tree, reduction="sum"):
            local = int(np.asarray(tree["flag"]))
            calls.append(local)
            return {"flag": np.int32(local + peer_flag["v"])}

        monkeypatch.setattr(amod._ops, "reduce", fake_or_reduce)
        state, _ = step(state, {})  # no notice anywhere: collective ran, no exit
        assert calls == [0]
        peer_flag["v"] = 1  # the PEER was notified; this process never was
        with pytest.raises(SystemExit) as e:
            step(state, {})
        assert e.value.code == resilience.PREEMPTION_EXIT_CODE
        assert calls == [0, 0]  # the local flag was still unset when reduced
        assert resilience.preemption_requested()  # adopted from the peer
        latest = resilience.latest_committed(str(tmp_path / "checkpoints"))
        assert latest is not None and resilience.verify_checkpoint(latest) == []

    def test_agreement_sync_interval_knob(self, tmp_path, monkeypatch):
        """ATX_PREEMPTION_SYNC_STEPS=N runs the or-reduce only every Nth
        step entry (all processes share the entry count, so they still
        sync at the same steps)."""
        import accelerate_tpu.accelerator as amod

        acc = _auto_acc(tmp_path)
        state = _w_state(acc)
        step = acc.make_train_step(lambda p, b, r: jnp.sum(p["w"] ** 2))
        state, _ = step(state, {})

        monkeypatch.setattr(type(acc), "num_processes", property(lambda self: 2))
        monkeypatch.setenv("ATX_PREEMPTION_SYNC_STEPS", "3")
        calls = []

        def fake_reduce(tree, reduction="sum"):
            calls.append(int(np.asarray(tree["flag"])))
            return {"flag": np.int32(0)}

        monkeypatch.setattr(amod._ops, "reduce", fake_reduce)
        for _ in range(6):
            state, _ = step(state, {})
        assert len(calls) == 2  # entries 3 and 6 only

    def test_second_sigterm_kills_even_with_sig_ign_history(self):
        """Escalation: a process that started with SIGTERM *ignored*
        (SIG_IGN) must still die on the second notice — restoring the
        pre-install disposition would re-deliver TERM into an ignoring
        handler, leaving the process unkillable until SIGKILL."""
        code = (
            "import os, signal, sys, time\n"
            "from accelerate_tpu.resilience import preemption\n"
            "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
            "assert preemption.install_preemption_handler()\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "deadline = time.time() + 5\n"
            "while not preemption.preemption_requested() and time.time() < deadline:\n"
            "    time.sleep(0.01)\n"
            "assert preemption.preemption_requested()\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "time.sleep(30)\n"
            "print('STILL ALIVE')\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code],
            cwd=REPO_ROOT,
            env=_child_env(),
            capture_output=True,
            text=True,
            timeout=60,
        )
        assert r.returncode == -signal.SIGTERM, (r.returncode, r.stdout, r.stderr)
        assert "STILL ALIVE" not in r.stdout

    def test_without_automatic_naming_flag_is_left_for_the_loop(self):
        acc = atx.Accelerator(seed=0)
        state = acc.create_train_state({"w": jnp.arange(4.0)}, optax.sgd(0.1))
        step = acc.make_train_step(lambda p, b, r: jnp.sum(p["w"] ** 2))
        resilience.request_preemption()
        state, _ = step(state, {})  # no SystemExit: the loop owns the policy
        assert acc.preemption_requested()


# =================================================================== watchdog
class TestWatchdog:
    def test_fires_dumps_stacks_and_aborts(self):
        out = io.StringIO()
        fired = []
        wd = Watchdog(0.2, out=out, abort=lambda: fired.append(True))
        try:
            wd.arm()
            assert wd.fired.wait(timeout=5.0)
            assert fired
            text = out.getvalue()
            assert "exceeded its" in text and "MainThread" in text
            assert str(resilience.WATCHDOG_EXIT_CODE) in text
        finally:
            wd.stop()

    def test_disarm_prevents_firing(self):
        wd = Watchdog(0.2, abort=lambda: None)
        try:
            wd.arm()
            wd.disarm()
            time.sleep(0.7)
            assert not wd.fired.is_set()
        finally:
            wd.stop()

    def test_first_arm_gets_compile_headroom(self):
        out = io.StringIO()
        wd = Watchdog(0.2, first_deadline_secs=10.0, out=out, abort=lambda: None)
        try:
            wd.arm()  # first arm: 10s deadline absorbs "compilation"
            time.sleep(0.6)
            assert not wd.fired.is_set()
            wd.disarm()
            wd.arm()  # steady state: 0.2s deadline
            assert wd.fired.wait(timeout=5.0)
        finally:
            wd.stop()

    def test_paused_suppresses_firing_and_rearms(self):
        fired = []
        wd = Watchdog(0.2, abort=lambda: fired.append(True))
        try:
            wd.arm()
            with wd.paused():
                time.sleep(0.6)  # would have fired without the pause
                assert not wd.fired.is_set() and not fired
            # countdown restarted on exit: still armed, fires on its own
            assert wd.fired.wait(timeout=5.0)
        finally:
            wd.stop()

    def test_paused_never_arms_an_unarmed_watchdog(self):
        wd = Watchdog(0.2, abort=lambda: None)
        try:
            with wd.paused():
                pass
            time.sleep(0.6)
            assert not wd.fired.is_set()
        finally:
            wd.stop()

    def test_save_and_load_state_pause_env_watchdog(
        self, tmp_path, monkeypatch
    ):
        """A routine synchronous save/load slower than ATX_WATCHDOG_SECS
        must not trip the armed watchdog (REVIEW: false-positive abort
        mid-commit lost the in-flight checkpoint)."""
        import accelerate_tpu.resilience.watchdog as wmod

        fired = []
        wd = Watchdog(0.4, abort=lambda: fired.append(True))
        monkeypatch.setenv("ATX_WATCHDOG_SECS", "0.4")
        monkeypatch.setattr(wmod, "_ENV_WATCHDOG", wd)
        try:
            acc = _auto_acc(tmp_path)
            state = _w_state(acc)

            class SlowExtra:
                def state_dict(self):
                    time.sleep(1.0)  # > deadline: the save itself is "slow"
                    return {"x": 1}

                def load_state_dict(self, d):
                    time.sleep(1.0)

            acc.register_for_checkpointing(SlowExtra())
            wd.arm()  # a step is in flight — heartbeat armed
            acc.save_state(None, state)
            assert not wd.fired.is_set() and not fired
            acc.load_state(None, _w_state(acc), resume="latest")
            assert not wd.fired.is_set() and not fired
        finally:
            wd.stop()

    def test_watchdog_from_env(self, monkeypatch):
        import accelerate_tpu.resilience.watchdog as wmod

        monkeypatch.delenv("ATX_WATCHDOG_SECS", raising=False)
        assert wmod.watchdog_from_env() is None
        monkeypatch.setenv("ATX_WATCHDOG_SECS", "120")
        wd = wmod.watchdog_from_env()
        assert wd is not None and wd.deadline == 120.0
        assert wd.first_deadline == 1200.0
        assert wmod.watchdog_from_env() is wd  # one instance per deadline


# ======================================================= coordinator backoff
class TestCoordInitBackoff:
    def test_retries_with_growing_jittered_backoff(self, monkeypatch):
        import accelerate_tpu.state as smod

        calls, sleeps = [], []

        def flaky_init(**kwargs):
            calls.append(dict(kwargs))
            if len(calls) < 3:
                raise RuntimeError("coordination service heartbeat timeout")

        monkeypatch.setattr(smod.jax.distributed, "initialize", flaky_init)
        monkeypatch.setattr(smod._time, "sleep", lambda s: sleeps.append(s))
        monkeypatch.setenv("ATX_COORD_INIT_RETRIES", "5")
        monkeypatch.setenv("ATX_COORD_TIMEOUT_SECS", "7")
        smod._initialize_distributed_with_retries(
            coordinator_address="127.0.0.1:1", num_processes=2, process_id=0
        )
        assert len(calls) == 3
        assert all(c["initialization_timeout"] == 7 for c in calls)
        assert len(sleeps) == 2
        assert 1.0 <= sleeps[0] < 2.0 and 2.0 <= sleeps[1] < 4.0  # 2x + jitter

    def test_budget_exhausted_reraises(self, monkeypatch):
        import accelerate_tpu.state as smod

        calls = []

        def dead_init(**kwargs):
            calls.append(1)
            raise RuntimeError("no coordinator")

        monkeypatch.setattr(smod.jax.distributed, "initialize", dead_init)
        monkeypatch.setattr(smod._time, "sleep", lambda s: None)
        monkeypatch.setenv("ATX_COORD_INIT_RETRIES", "2")
        with pytest.raises(RuntimeError, match="no coordinator"):
            smod._initialize_distributed_with_retries(
                coordinator_address="127.0.0.1:1", num_processes=2
            )
        assert len(calls) == 3  # 1 try + 2 retries

    def test_timeout_forwarded_as_initialization_timeout(self, monkeypatch):
        import accelerate_tpu.state as smod

        calls = []
        monkeypatch.setattr(
            smod.jax.distributed, "initialize", lambda **kwargs: calls.append(kwargs)
        )
        monkeypatch.setenv("ATX_COORD_TIMEOUT_SECS", "5")
        smod._initialize_distributed_with_retries(
            coordinator_address="127.0.0.1:1", num_processes=2
        )
        assert calls == [
            {
                "coordinator_address": "127.0.0.1:1",
                "num_processes": 2,
                "initialization_timeout": 5,
            }
        ]


# ============================================================== subprocesses
class TestKillDuringSave:
    @pytest.mark.parametrize(
        "point", ["save.files_written", "save.manifest_written", "commit.before_marker"]
    )
    def test_kill9_mid_save_previous_checkpoint_survives(self, tmp_path, point):
        """total_limit=1 + kill -9 mid-second-save: the FIRST checkpoint
        must still be committed and loadable (the old rotation deleted it
        before the new save was durable, losing both)."""
        r = _run_script("resilience_ckpt_crash.py", str(tmp_path), point)
        assert r.returncode == faults.KILL_EXIT_CODE == 137, (r.stdout, r.stderr)
        assert "first checkpoint committed" in r.stdout
        root = str(tmp_path / "checkpoints")
        committed = resilience.committed_checkpoints(root)
        assert [n for n, _ in committed] == [0]

        acc = atx.Accelerator(seed=0)
        target = acc.create_train_state({"w": jnp.zeros(16)}, optax.sgd(0.1))
        restored = acc.load_state(root, target, resume="latest")
        np.testing.assert_array_equal(np.asarray(restored.params["w"]), np.arange(16.0))
        assert int(jax.device_get(restored.step)) == 0


def test_sigterm_emergency_checkpoint_and_bitidentical_resume(tmp_path):
    """SIGTERM mid-training → emergency checkpoint + exit 75; the resumed
    run's loss trajectory must be BIT-identical to an uninterrupted run of
    the same total steps."""
    base_loss = str(tmp_path / "baseline.losses")
    r = _run_script(
        "resilience_train.py",
        "--project_dir", str(tmp_path / "baseline"),
        "--steps", "6",
        "--loss_file", base_loss,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)

    run_loss = str(tmp_path / "run.losses")
    interrupted = _run_script(
        "resilience_train.py",
        "--project_dir", str(tmp_path / "run"),
        "--steps", "6",
        "--loss_file", run_loss,
        "--sigterm_at", "3",
    )
    assert interrupted.returncode == resilience.PREEMPTION_EXIT_CODE, (
        interrupted.stdout,
        interrupted.stderr,
    )
    assert "emergency checkpoint committed" in interrupted.stderr
    latest = resilience.latest_committed(str(tmp_path / "run" / "checkpoints"))
    assert latest is not None and resilience.verify_checkpoint(latest) == []

    resumed = _run_script(
        "resilience_train.py",
        "--project_dir", str(tmp_path / "run"),
        "--steps", "6",
        "--loss_file", run_loss,
        "--resume",
    )
    assert resumed.returncode == 0, (resumed.stdout, resumed.stderr)
    assert "resumed at step 3" in resumed.stdout

    with open(base_loss) as f:
        baseline = f.read().splitlines()
    with open(run_loss) as f:
        spliced = f.read().splitlines()
    assert len(baseline) == 6
    assert spliced == baseline  # bit-identical: same hex floats per step


def test_watchdog_aborts_wedged_step_with_stack_dump(tmp_path):
    env = _child_env(
        {"ATX_WATCHDOG_SECS": "2", "ATX_WATCHDOG_FIRST_STEP_SECS": "120"}
    )
    r = _run_script(
        "resilience_train.py",
        "--project_dir", str(tmp_path),
        "--steps", "4",
        "--loss_file", str(tmp_path / "l"),
        "--wedge_at", "2",
        env=env,
    )
    assert r.returncode == resilience.WATCHDOG_EXIT_CODE == 114, (r.stdout, r.stderr)
    assert "atx watchdog" in r.stderr
    assert "MainThread" in r.stderr  # the wedged thread's stack was dumped
    assert "WEDGED STEP RETURNED" not in r.stdout


def test_disk_offload_sentinel_kill_refuses_resume(tmp_path):
    """Satellite for the PR-1 dirty sentinel: kill -9 between the sentinel
    write and the moment flush; resume over the dir must refuse with the
    recovery options spelled out."""
    d = str(tmp_path / "moments")
    r = _run_script("resilience_disk_crash.py", d)
    assert r.returncode == faults.KILL_EXIT_CODE, (r.stdout, r.stderr)
    assert "healthy step done" in r.stdout
    assert os.path.exists(os.path.join(d, "dirty.json"))
    with pytest.raises(ValueError) as e:
        atx.disk_offloaded_adamw(1e-2, offload_dir=d)
    msg = str(e.value)
    assert "dirty sentinel" in msg
    assert "fresh directory" in msg and "restore a full checkpoint" in msg


@pytest.mark.multiprocess
@pytest.mark.slow
def test_preemption_notice_on_one_rank_becomes_group_decision(tmp_path):
    """The multihost agreement collective (REVIEW high): only rank 0 is
    notified mid-training, yet BOTH ranks must exit 75 at the same step
    with ONE consistent emergency checkpoint — every process's manifest
    present, all recording the same step — and the elastic resume must
    verify it and complete."""
    r = launch(
        os.path.join(SCRIPTS, "preempt_one_rank.py"),
        str(tmp_path / "proj"),
        num_processes=2,
        host_devices=1,
        timeout=360,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "NEVER PREEMPTED" not in r.stdout
    assert "not counted against --max_restarts" in r.stderr
    for rank in range(2):
        assert f"[proc {rank}] RESUMED CONSISTENT step=2" in r.stdout, r.stdout
        assert f"[proc {rank}] DONE" in r.stdout, r.stdout


def test_launcher_resumes_preempted_group_without_burning_restarts(tmp_path):
    """Exit-code contract: a worker group dying with PREEMPTION_EXIT_CODE is
    relaunched even with --max_restarts 0, and the resume is logged as not
    counted."""
    marker = str(tmp_path / "preempted_once")
    script = os.path.join(SCRIPTS, "exit_preempted_once.py")
    r = subprocess.run(
        [
            sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
            "--num_processes", "2", "--max_restarts", "0",
            "--mixed_precision", "no", script, marker,
        ],
        cwd=REPO_ROOT,
        env=_child_env(),
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "PREEMPTING" in r.stdout
    assert "not counted against --max_restarts" in r.stderr
    for rank in range(2):
        assert f"[proc {rank}] RESUMED OK" in r.stdout, r.stdout
    assert os.path.exists(marker)


# ------------------------------------------------- GCE maintenance poller
class TestGceMaintenancePoller:
    """resilience/gce.py against a stub metadata server: the poller must
    stay silent on benign values, fire `request_preemption()` exactly once
    on a maintenance notice, and stay entirely off without
    ATX_GCE_PREEMPT_POLL_SECS."""

    @pytest.fixture
    def metadata_server(self):
        import http.server
        import threading

        values = {"maintenance-event": "NONE", "preempted": "FALSE"}
        hits = []

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                hits.append((self.path, self.headers.get("Metadata-Flavor")))
                name = self.path.rsplit("/", 1)[-1]
                if name not in values:
                    self.send_response(404)
                    self.end_headers()
                    return
                body = values[name].encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # keep pytest output clean
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        thread = threading.Thread(target=srv.serve_forever, daemon=True)
        thread.start()
        url = f"http://127.0.0.1:{srv.server_address[1]}/computeMetadata/v1/instance"
        try:
            yield url, values, hits
        finally:
            srv.shutdown()
            srv.server_close()

    def test_benign_values_do_not_preempt(self, metadata_server):
        url, values, hits = metadata_server
        poller = resilience.MaintenancePoller(poll_secs=60, metadata_url=url)
        assert poller.check_once() is None
        assert poller.notice is None
        assert not resilience.preemption_requested()
        # Requests carried the mandatory metadata header.
        assert hits and all(flavor == "Google" for _, flavor in hits)

    def test_maintenance_event_fires_preemption_once(self, metadata_server):
        url, values, _ = metadata_server
        values["maintenance-event"] = "TERMINATE_ON_HOST_MAINTENANCE"
        fired = []
        poller = resilience.MaintenancePoller(
            poll_secs=0.05, metadata_url=url, on_preempt=lambda: fired.append(1)
        )
        poller.start()
        deadline = time.time() + 5.0
        while not fired and time.time() < deadline:
            time.sleep(0.01)
        poller.stop()
        assert fired == [1]  # fired exactly once, then the thread returned
        assert poller.notice == "maintenance-event=TERMINATE_ON_HOST_MAINTENANCE"
        assert not poller.running

    def test_preempted_true_trips_default_callback(self, metadata_server):
        url, values, _ = metadata_server
        values["preempted"] = "TRUE"
        poller = resilience.MaintenancePoller(poll_secs=60, metadata_url=url)
        assert poller.check_once() == "preempted=TRUE"

    def test_unreachable_server_is_benign(self):
        poller = resilience.MaintenancePoller(
            poll_secs=60, metadata_url="http://127.0.0.1:9/nope", request_timeout=0.2
        )
        assert poller.check_once() is None

    def test_rejects_non_positive_poll_interval(self):
        with pytest.raises(ValueError, match="poll_secs"):
            resilience.MaintenancePoller(poll_secs=0)

    def test_from_env_off_by_default(self, monkeypatch):
        monkeypatch.delenv("ATX_GCE_PREEMPT_POLL_SECS", raising=False)
        assert resilience.maintenance_poller_from_env() is None
        monkeypatch.setenv("ATX_GCE_PREEMPT_POLL_SECS", "not-a-number")
        assert resilience.maintenance_poller_from_env() is None
        monkeypatch.setenv("ATX_GCE_PREEMPT_POLL_SECS", "0")
        assert resilience.maintenance_poller_from_env() is None

    def test_from_env_starts_poller_and_requests_preemption(
        self, metadata_server, monkeypatch
    ):
        url, values, _ = metadata_server
        values["maintenance-event"] = "TERMINATE_ON_HOST_MAINTENANCE"
        monkeypatch.setenv("ATX_GCE_PREEMPT_POLL_SECS", "0.05")
        monkeypatch.setenv("ATX_GCE_METADATA_URL", url)
        poller = resilience.maintenance_poller_from_env()
        assert poller is not None
        try:
            deadline = time.time() + 5.0
            while not resilience.preemption_requested() and time.time() < deadline:
                time.sleep(0.01)
            assert resilience.preemption_requested()
        finally:
            poller.stop()

    def test_accelerator_init_starts_poller_from_env(
        self, metadata_server, monkeypatch, tmp_path
    ):
        url, _, _ = metadata_server
        monkeypatch.setenv("ATX_GCE_PREEMPT_POLL_SECS", "30")
        monkeypatch.setenv("ATX_GCE_METADATA_URL", url)
        acc = _auto_acc(tmp_path)
        try:
            assert acc._gce_poller is not None and acc._gce_poller.running
        finally:
            acc._gce_poller.stop()
