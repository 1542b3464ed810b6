"""CLI tests (reference `tests/test_cli.py`, 545 LoC: runs the binaries)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

pytestmark = pytest.mark.heavy  # compile-heavy / subprocess lane

from accelerate_tpu.commands.cli import main as cli_main
from accelerate_tpu.commands.config import LaunchConfig
from accelerate_tpu.commands.launch import build_child_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestConfig:
    def test_round_trip(self, tmp_path):
        cfg = LaunchConfig(num_processes=4, mesh_fsdp=2, sharding_strategy="FSDP")
        path = cfg.save(str(tmp_path / "cfg.yaml"))
        loaded = LaunchConfig.load(path)
        assert loaded == cfg

    def test_default_flag_writes_file(self, tmp_path, capsys):
        path = str(tmp_path / "cfg.yaml")
        assert cli_main(["config", "--default", "--config_file", path]) == 0
        assert os.path.exists(path)
        assert LaunchConfig.load(path) == LaunchConfig()

    def test_interactive_covers_every_launch_knob(self, tmp_path, monkeypatch):
        """Every knob `launch` consumes must be reachable
        from the config Q&A, and the answers must round-trip through the
        YAML file into the launch env contract."""
        from accelerate_tpu.commands.config import interactive_config

        answers = iter(
            [
                "2",                    # num_processes
                "10.0.0.1:7801",        # coordinator address
                "-1", "4", "1", "1", "1",  # mesh axes
                "FSDP",                 # strategy
                "y",                    # offload_optimizer
                "fp8",                  # mixed precision
                "y",                    # force_fp8
                "2",                    # grad accumulation
                "3",                    # max_restarts
                "json,tensorboard",     # trackers
                str(tmp_path / "proj"),  # project dir
                "n",                    # pod launch
            ]
        )
        monkeypatch.setattr("builtins.input", lambda prompt="": next(answers))
        cfg = interactive_config()
        # Every Q&A answer must land in a config field (no dead questions),
        # and every launch-consumed field must be askable: the set of
        # LaunchConfig fields not answered here is exactly the pod trio
        # (answered on the 'y' branch) + coordinator_port + extra_env.
        assert (cfg.offload_optimizer, cfg.force_fp8) == (True, True)
        assert cfg.max_restarts == 3
        assert cfg.log_with == "json,tensorboard"
        assert cfg.project_dir == str(tmp_path / "proj")
        assert cfg.sharding_strategy == "FSDP" and cfg.mesh_fsdp == 4
        # Round trip: YAML -> LaunchConfig -> child env contract.
        path = cfg.save(str(tmp_path / "cfg.yaml"))
        loaded = LaunchConfig.load(path)
        assert loaded == cfg
        env = build_child_env(loaded, process_id=0, base={})
        assert env["ATX_OFFLOAD_OPTIMIZER"] == "1"
        assert env["ATX_LOG_WITH"] == "json,tensorboard"
        assert env["ATX_PROJECT_DIR"] == str(tmp_path / "proj")
        assert env["ATX_SHARDING_STRATEGY"] == "FSDP"

    def test_accelerator_reads_tracker_env_contract(self, tmp_path, monkeypatch):
        """The launched child's Accelerator picks up ATX_LOG_WITH /
        ATX_PROJECT_DIR the way it picks up the mesh env vars."""
        from accelerate_tpu.accelerator import Accelerator
        from accelerate_tpu.state import AcceleratorState

        monkeypatch.setenv("ATX_LOG_WITH", "json")
        monkeypatch.setenv("ATX_PROJECT_DIR", str(tmp_path / "proj"))
        AcceleratorState._reset_state()
        acc = Accelerator(seed=0)
        assert acc.log_with == ["json"]
        assert acc.project_config.project_dir == str(tmp_path / "proj")
        AcceleratorState._reset_state()


class TestLaunch:
    def test_env_contract(self):
        cfg = LaunchConfig(
            num_processes=2,
            coordinator_address="127.0.0.1:1234",
            mesh_data=2,
            mesh_fsdp=4,
            mixed_precision="bf16",
            sharding_strategy="FSDP",
            gradient_accumulation_steps=3,
        )
        env = build_child_env(cfg, process_id=1, base={})
        assert env["ATX_NUM_PROCESSES"] == "2"
        assert env["ATX_PROCESS_ID"] == "1"
        assert env["ATX_COORDINATOR_ADDRESS"] == "127.0.0.1:1234"
        assert env["ATX_MESH_DATA"] == "2"
        assert env["ATX_MESH_FSDP"] == "4"
        assert env["ATX_MIXED_PRECISION"] == "bf16"
        assert env["ATX_SHARDING_STRATEGY"] == "FSDP"
        assert env["ATX_GRADIENT_ACCUMULATION_STEPS"] == "3"

    def test_dry_run_single(self, capsys, tmp_path):
        script = tmp_path / "t.py"
        script.write_text("print('hi')")
        assert cli_main(["launch", "--dry_run", str(script), "--flag"]) == 0
        out = capsys.readouterr().out
        assert str(script) in out and "--flag" in out

    def test_dry_run_pod_assembles_gcloud(self, capsys, tmp_path):
        script = tmp_path / "t.py"
        script.write_text("")
        assert (
            cli_main(
                [
                    "launch", "--dry_run", "--tpu_name", "mypod", "--tpu_zone",
                    "us-central2-b", "--num_processes", "4", str(script),
                ]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert "gcloud compute tpus tpu-vm ssh mypod" in out
        assert "--worker=all" in out
        assert "ATX_MULTIHOST=1" in out

    def _fake_gcloud(self, tmp_path, exit_code=0):
        """PATH-shim gcloud that logs each invocation's argv as a JSON line
        (the pod SSH path must be tested, not just dry-run)."""
        bin_dir = tmp_path / "bin"
        bin_dir.mkdir(exist_ok=True)
        log = tmp_path / "gcloud_calls.jsonl"
        shim = bin_dir / "gcloud"
        shim.write_text(
            "#!/usr/bin/env python3\n"
            "import json, sys\n"
            f"open({str(log)!r}, 'a').write(json.dumps(sys.argv[1:]) + '\\n')\n"
            f"sys.exit({exit_code})\n"
        )
        shim.chmod(0o755)
        return bin_dir, log

    def test_pod_launch_runs_gcloud_with_env_contract(
        self, tmp_path, monkeypatch
    ):
        bin_dir, log = self._fake_gcloud(tmp_path, exit_code=0)
        monkeypatch.setenv("PATH", f"{bin_dir}:{os.environ['PATH']}")
        script = tmp_path / "train.py"
        script.write_text("")
        rc = cli_main(
            [
                "launch", "--tpu_name", "mypod", "--tpu_zone", "us-central2-b",
                "--tpu_project", "proj-1", "--num_processes", "4",
                "--strategy", "FSDP", "--fsdp", "4", "--mixed_precision",
                "bf16", str(script), "--epochs", "2",
            ]
        )
        assert rc == 0
        calls = [json.loads(l) for l in log.read_text().splitlines()]
        assert len(calls) == 1
        argv = calls[0]
        # Command shape: gcloud compute tpus tpu-vm ssh --project=… NAME …
        assert argv[:4] == ["compute", "tpus", "tpu-vm", "ssh"]
        assert "--project=proj-1" in argv and argv.index("--project=proj-1") < argv.index("mypod")
        assert "--zone=us-central2-b" in argv
        assert "--worker=all" in argv  # fan-out to every pod worker
        remote = [a for a in argv if a.startswith("--command=")][0]
        # Per-worker env contract is injected into the remote command; pod
        # rendezvous goes through TPU metadata (no coordinator address).
        for frag in (
            "ATX_SHARDING_STRATEGY=FSDP", "ATX_MESH_FSDP=4",
            "ATX_MIXED_PRECISION=bf16", "ATX_NUM_PROCESSES=4",
            "ATX_MULTIHOST=1", "train.py", "--epochs 2",
        ):
            assert frag in remote, f"{frag!r} missing from remote command"
        assert "ATX_COORDINATOR_ADDRESS" not in remote

    def test_pod_launch_propagates_failure_and_restarts(
        self, tmp_path, monkeypatch
    ):
        bin_dir, log = self._fake_gcloud(tmp_path, exit_code=3)
        monkeypatch.setenv("PATH", f"{bin_dir}:{os.environ['PATH']}")
        script = tmp_path / "train.py"
        script.write_text("")
        rc = cli_main(
            [
                "launch", "--tpu_name", "mypod", "--tpu_zone", "us-central2-b",
                "--num_processes", "4", "--max_restarts", "2", str(script),
            ]
        )
        assert rc == 3  # nonzero remote exit propagates
        # Initial attempt + 2 restarts, all through the same gcloud fan-out.
        assert len(log.read_text().splitlines()) == 3

    def test_single_host_subprocess_env(self, tmp_path):
        """Launch a real child that dumps its env contract."""
        script = tmp_path / "dump.py"
        script.write_text(
            "import os, json; print(json.dumps({k: v for k, v in os.environ.items() if k.startswith('ATX_')}))"
        )
        result = subprocess.run(
            [
                sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
                "--mixed_precision", "fp16", "--strategy", "ZERO1",
                "--data", "4", "--fsdp", "2", str(script),
            ],
            capture_output=True,
            text=True,
            cwd=REPO,
            env={**os.environ, "PYTHONPATH": REPO},
            timeout=120,
        )
        assert result.returncode == 0, result.stderr
        env = json.loads(result.stdout.strip().splitlines()[-1])
        assert env["ATX_MIXED_PRECISION"] == "fp16"
        assert env["ATX_SHARDING_STRATEGY"] == "ZERO1"
        assert env["ATX_MESH_DATA"] == "4"
        assert env["ATX_MESH_FSDP"] == "2"


class TestEstimate:
    def test_llama_tiny_fits(self, capsys):
        assert cli_main(["estimate", "llama-tiny", "--batch_size", "2", "--seq_len", "64"]) == 0
        out = capsys.readouterr().out
        assert "FITS" in out and "params" in out

    def test_llama_70b_does_not_fit_one_chip(self, capsys):
        assert cli_main(["estimate", "llama3-70b"]) == 0
        out = capsys.readouterr().out
        assert "DOES NOT FIT" in out and "--shards" in out

    def test_param_count_exact(self):
        from accelerate_tpu.commands.estimate import estimate
        from accelerate_tpu.models import llama

        r = estimate("llama-tiny", 1, 64, "bf16", "adamw", 1, False)
        assert r["n_params"] == llama.LlamaConfig.tiny().param_count()


class TestMergeCommand:
    def test_merge_cli(self, tmp_path):
        import jax.numpy as jnp

        from accelerate_tpu import checkpointing

        d = str(tmp_path / "ck")
        checkpointing.save_pytree({"w": jnp.arange(8.0)}, d)
        out = str(tmp_path / "merged.npz")
        assert cli_main(["merge", d, out]) == 0
        data = np.load(out)
        np.testing.assert_array_equal(data["w"], np.arange(8.0))


class TestDiagnostic:
    def test_diagnostic_passes_in_process(self):
        """The bundled self-test must pass on the simulated 8-device mesh."""
        from accelerate_tpu.test_utils import diagnostic

        assert diagnostic.main() == 0


# The crashed rank's peer sits in a collective with its SIGTERM trapped (the
# preemption handler), so every group teardown lasts the whole TERM -> KILL
# grace window: 30 s by default, a third of these tests' wall time each.
_SHORT_GRACE = {"ATX_TERM_GRACE_SECS": "2"}


def test_max_restarts_recovers_crashed_group(tmp_path):
    """A rank crashes on the first group attempt; --max_restarts relaunches
    the whole group on a fresh coordinator port and the job completes
    (the torch-elastic restart analog, reference commands/launch.py:142-771)."""
    from tests.launch_helpers import REPO_ROOT, clean_env, retry_coordination_flakes

    marker = str(tmp_path / "crashed_once")
    script = os.path.join(REPO_ROOT, "tests", "scripts", "crash_once.py")
    cmd = [
        sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
        "--num_processes", "2", "--host_devices", "1",
        "--max_restarts", "2", "--mixed_precision", "no",
        script, marker,
    ]

    def run_once(attempt):
        # Each attempt must see a crash-then-recover cycle from scratch.
        if os.path.exists(marker):
            os.remove(marker)
        return subprocess.run(
            cmd, cwd=REPO_ROOT, env=clean_env(_SHORT_GRACE), capture_output=True,
            text=True, timeout=240,
        )

    proc = retry_coordination_flakes(run_once)
    assert proc.returncode == 0, f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
    assert "CRASHING ONCE" in proc.stdout
    assert "restarting group (1/2)" in proc.stderr
    for rank in range(2):
        assert f"[proc {rank}] RESTART OK" in proc.stdout, proc.stdout
    assert os.path.exists(marker)


def test_max_restarts_exhausted_fails(tmp_path):
    """A persistently-crashing rank exhausts the restart budget and the
    launcher reports the failure exit code."""
    from tests.launch_helpers import REPO_ROOT, clean_env

    script = os.path.join(REPO_ROOT, "tests", "scripts", "crash_once.py")
    # Point the marker at an uncreatable path so rank 1 crashes every time.
    cmd = [
        sys.executable, "-m", "accelerate_tpu.commands.cli", "launch",
        "--num_processes", "2", "--host_devices", "1",
        "--max_restarts", "1", "--mixed_precision", "no",
        script, "/dev/null/nope/marker",
    ]
    proc = subprocess.run(
        cmd, cwd=REPO_ROOT, env=clean_env(_SHORT_GRACE), capture_output=True,
        text=True, timeout=240,
    )
    assert proc.returncode != 0
    assert "restarting group (1/1)" in proc.stderr


def test_estimate_accepts_local_hf_repo(tmp_path, capsys):
    """Estimate any HF model from its config.json —
    the zero-egress analog of the reference's Hub-backed estimate."""
    import json

    json.dump(
        {"model_type": "llama", "vocab_size": 256, "hidden_size": 64,
         "intermediate_size": 128, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2},
        open(tmp_path / "config.json", "w"),
    )
    assert cli_main(["estimate", str(tmp_path), "--batch_size", "2", "--seq_len", "32"]) == 0
    out = capsys.readouterr().out
    assert "106,816 params" in out and "training total/chip" in out


def test_fp8_lose_lose_gate(tmp_path, monkeypatch, capsys):
    """fp8 on a device kind with recorded speedup <= 1 must
    refuse unless --force_fp8 (no silent lose-lose configuration)."""
    from accelerate_tpu.commands.launch import _probe_device_kind
    from accelerate_tpu.utils import fp8_telemetry

    monkeypatch.setenv("ATX_CACHE_DIR", str(tmp_path))
    # Record under the kind the launcher's own probe will see (the probe
    # subprocess may resolve a real accelerator even when tests run on the
    # CPU-simulated mesh).
    kind = _probe_device_kind()
    assert kind, "device-kind probe failed"
    fp8_telemetry.record(kind, 0.51)
    assert fp8_telemetry.lookup(kind) == 0.51

    script = tmp_path / "noop.py"
    script.write_text("print('hi')\n")
    rc = cli_main(
        ["launch", "--dry_run", "--mixed_precision", "fp8", str(script)]
    )
    assert rc == 2
    # --force_fp8 overrides the gate; dry_run then succeeds.
    rc = cli_main(
        ["launch", "--dry_run", "--mixed_precision", "fp8", "--force_fp8",
         str(script)]
    )
    assert rc == 0
    # A kind measured fast keeps fp8 available without the flag.
    fp8_telemetry.record(kind, 1.8)
    rc = cli_main(
        ["launch", "--dry_run", "--mixed_precision", "fp8", str(script)]
    )
    assert rc == 0


@pytest.mark.parametrize(
    "kind, host_devices, refused",
    [("TPU v5 lite", None, True), ("TPU v5 lite", 1, False), ("cpu", None, False)],
)
def test_launch_refuses_local_multiprocess_on_a_tpu_host(
    monkeypatch, capsys, kind, host_devices, refused
):
    """One process drives all local chips: N local children would each try
    to open the same TPU. Children pinned to the CPU simulation are fine."""
    from accelerate_tpu.commands import launch

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(launch, "_probe_device_kind", lambda: kind)
    launched = []
    monkeypatch.setattr(
        launch, "_local_multiprocess_launch", lambda *a: launched.append(a) or 0
    )
    argv = ["launch", "--num_processes", "2", "--mixed_precision", "no"]
    if host_devices:
        argv += ["--host_devices", str(host_devices)]
    rc = cli_main(argv + ["train.py"])
    assert (rc, len(launched)) == ((2, 0) if refused else (0, 1))
    assert ("one process drives all local chips" in capsys.readouterr().err) == refused
