"""REAL multi-process tests: subprocess-launch driver scripts through the
framework's own launcher with `jax.process_count() > 1` on CPU.

This is the reference's central distributed-test pattern
(`test_utils/testing.py:709` `execute_subprocess_async` +
`tests/test_multigpu.py:50` driving `accelerate launch` scripts) — the paths
exercised here (gather_object over the multihost object channel, Gloo CPU
collectives, cross-process checkpoint coordination, verify_operation
mismatch detection) cannot run under the in-process 8-device simulation.
"""

import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.heavy, pytest.mark.slow]  # real multi-process launches; excluded from the tier-1 smoke lane

from launch_helpers import REPO_ROOT, assert_all_ranks, clean_env, free_port, launch

DRIVER = os.path.join(REPO_ROOT, "tests", "scripts", "distributed_checks.py")


@pytest.mark.multiprocess
def test_two_process_collectives_and_checkpoint(tmp_path):
    proc = launch(
        DRIVER,
        "--ckpt_dir",
        str(tmp_path / "ckpt"),
        num_processes=2,
        host_devices=2,
    )
    assert_all_ranks(proc, "ALL OK", 2)


@pytest.mark.multiprocess
def test_four_process_collectives(tmp_path):
    proc = launch(
        DRIVER,
        "--ckpt_dir",
        str(tmp_path / "ckpt"),
        num_processes=4,
        host_devices=1,
        timeout=360,
    )
    assert_all_ranks(proc, "ALL OK", 4)


@pytest.mark.multiprocess
def test_debug_mode_flags_collective_mismatch():
    proc = launch(
        DRIVER,
        "--mode",
        "mismatch",
        num_processes=2,
        host_devices=1,
        env_extra={"ATX_DEBUG_MODE": "1"},
    )
    assert_all_ranks(proc, "MISMATCH DETECTED OK", 2)


@pytest.mark.multiprocess
def test_failed_worker_tears_down_job(tmp_path):
    # One worker dies -> the launcher must propagate a nonzero exit code
    # (reference: torch-elastic behavior the launcher owns here).
    crasher = tmp_path / "crash_if_rank1.py"
    crasher.write_text(
        "import os, sys\n"
        "sys.path.insert(0, %r)\n"
        "from accelerate_tpu.state import ProcessState\n"
        "ps = ProcessState()\n"
        "if ps.process_index == 1:\n"
        "    sys.exit(17)\n"
        "ps.wait_for_everyone()\n" % REPO_ROOT
    )
    cmd = [
        sys.executable,
        "-m",
        "accelerate_tpu.commands.cli",
        "launch",
        "--num_processes",
        "2",
        "--host_devices",
        "1",
        "--coordinator_address",
        f"127.0.0.1:{free_port()}",
        str(crasher),
    ]
    proc = subprocess.run(
        cmd, cwd=REPO_ROOT, env=clean_env(), capture_output=True, text=True, timeout=180
    )
    assert proc.returncode != 0


@pytest.mark.multiprocess
def test_two_process_fsdp_training_and_sharded_checkpoint(tmp_path):
    """The pod regime: 2 processes x 4 local devices,
    params sharded over fsdp as non-addressable global arrays, sharded
    save/load across process boundaries, loss parity vs single device."""
    proc = launch(
        DRIVER,
        "--mode", "fsdp",
        "--ckpt_dir", str(tmp_path / "ckpt"),
        num_processes=2,
        host_devices=4,
        timeout=420,
    )
    assert_all_ranks(proc, "SHARDED FSDP OK", 2)


@pytest.mark.multiprocess
def test_two_process_tensor_parallel_training(tmp_path):
    proc = launch(
        DRIVER,
        "--mode", "tp",
        "--ckpt_dir", str(tmp_path / "ckpt"),
        num_processes=2,
        host_devices=4,
        timeout=420,
    )
    assert_all_ranks(proc, "SHARDED TP OK", 2)


@pytest.mark.multiprocess
def test_two_process_ring_attention_training():
    """Sequence parallelism with the ring axis SPANNING the process boundary:
    KV ppermute hops cross hosts; loss parity vs a
    single-device dot-attention oracle (ring attention is exact)."""
    proc = launch(
        DRIVER,
        "--mode", "ring",
        num_processes=2,
        host_devices=4,
        timeout=420,
    )
    assert_all_ranks(proc, "LONGCTX RING OK", 2)


@pytest.mark.multiprocess
def test_two_process_expert_parallel_training():
    """Expert parallelism with experts sharded across hosts: the MoE
    dispatch all-to-all crosses the process boundary; loss parity vs a
    single-device oracle of identical math."""
    proc = launch(
        DRIVER,
        "--mode", "moe",
        num_processes=2,
        host_devices=4,
        timeout=420,
    )
    assert_all_ranks(proc, "LONGCTX MOE OK", 2)
