"""`accelerate-tpu launch` — spawn training processes with the env contract.

Analog of the reference launcher (`commands/launch.py:142-1194`). Key shift
(SURVEY.md §7): one process **per host**, not per device — JAX SPMD drives all
local chips from a single process, so the reference's elastic-agent / 1-proc-
per-GPU machinery collapses into three modes:

- single host: exec the script in-place with the ``ATX_*`` env contract;
- local multi-process (CPU simulation & single-host multi-proc testing):
  spawn N children with ``ATX_COORDINATOR_ADDRESS/ATX_NUM_PROCESSES/
  ATX_PROCESS_ID`` — the `jax.distributed.initialize` rendezvous analog of
  MASTER_ADDR/RANK/WORLD_SIZE (`utils/launch.py:98-470`);
- TPU pod: run the same command on every pod worker over
  ``gcloud compute tpus tpu-vm ssh --worker=all`` (reference
  `tpu_pod_launcher`, `commands/launch.py:909-965`), where each worker
  self-discovers rank via TPU metadata.

Env contract consumed by the library (`state.py`, `utils/dataclasses.py`):
ATX_COORDINATOR_ADDRESS, ATX_NUM_PROCESSES, ATX_PROCESS_ID, ATX_MULTIHOST,
ATX_MIXED_PRECISION, ATX_SHARDING_STRATEGY, ATX_MESH_{DATA,FSDP,TENSOR,
SEQUENCE,EXPERT}, ATX_GRADIENT_ACCUMULATION_STEPS.
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import subprocess
import sys
import time

from ..resilience.preemption import PREEMPTION_EXIT_CODE
from .config import LaunchConfig, load_default_config


def _term_grace_secs() -> float:
    """How long group teardown waits between SIGTERM and SIGKILL. Children
    trap SIGTERM for emergency checkpoints (resilience/preemption.py), so a
    teardown TERM no longer guarantees death — the grace window lets the
    emergency save commit before escalation."""
    try:
        return float(os.environ.get("ATX_TERM_GRACE_SECS", "") or 30.0)
    except ValueError:
        return 30.0


def _max_preemption_resumes() -> int:
    try:
        return int(os.environ.get("ATX_MAX_PREEMPTION_RESUMES", "") or 100)
    except ValueError:
        return 100


def register(subparsers: argparse._SubParsersAction) -> None:
    p = subparsers.add_parser(
        "launch", help="Launch a training script on this host / a pod"
    )
    p.add_argument("--config_file", default=None, help="Launch config file")
    p.add_argument("--num_processes", type=int, default=None)
    p.add_argument("--coordinator_address", default=None, help="host:port of process 0")
    p.add_argument("--coordinator_port", type=int, default=None)
    p.add_argument("--mixed_precision", default=None, choices=["no", "bf16", "fp16", "fp8"])
    p.add_argument(
        "--force_fp8",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="Run fp8 even on device kinds whose recorded fp8 matmul "
        "speedup is <= 1x (where fp8 costs accuracy for zero gain)",
    )
    p.add_argument(
        "--strategy",
        default=None,
        help="DATA_PARALLEL | ZERO1 | ZERO2 | FSDP | TENSOR_PARALLEL | HYBRID",
    )
    p.add_argument("--data", type=int, default=None, help="mesh data axis size")
    p.add_argument("--fsdp", type=int, default=None, help="mesh fsdp axis size")
    p.add_argument("--tensor", type=int, default=None, help="mesh tensor axis size")
    p.add_argument("--sequence", type=int, default=None, help="mesh sequence axis size")
    p.add_argument("--expert", type=int, default=None, help="mesh expert axis size")
    p.add_argument("--gradient_accumulation_steps", type=int, default=None)
    p.add_argument(
        "--offload_optimizer",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="Keep optimizer moments in pinned host RAM "
        "(parallel/host_offload.py; the DeepSpeed offload_optimizer "
        "analog); --no-offload_optimizer overrides a config-file true",
    )
    p.add_argument(
        "--log_with",
        default=None,
        help="Comma-separated experiment trackers "
        "(json/tensorboard/wandb/mlflow/comet_ml/aim/clearml/dvclive)",
    )
    p.add_argument(
        "--project_dir", default=None, help="Project/logging directory for trackers"
    )
    p.add_argument("--tpu_name", default=None, help="GCE TPU name (pod launch)")
    p.add_argument("--tpu_zone", default=None)
    p.add_argument("--tpu_project", default=None)
    p.add_argument(
        "--host_devices",
        type=int,
        default=None,
        help="Simulate N CPU devices per process (sets "
        "--xla_force_host_platform_device_count; testing without TPUs)",
    )
    p.add_argument(
        "--max_restarts",
        type=int,
        default=None,
        help="Relaunch the worker group (fresh coordinator port) up to N "
        "times after a worker death (torch-elastic max_restarts analog); "
        "default 0 = fail on first death",
    )
    p.add_argument(
        "--replicate_url",
        default=None,
        help="Object-store URL for durable checkpoint replication "
        "(sets ATX_REPLICATE_URL in every worker: file:///path or a plain "
        "path for the filesystem store, other schemes via "
        "resilience.replicate.register_store_scheme — "
        "docs/fault_tolerance.md)",
    )
    p.add_argument(
        "--elastic_devices_file",
        default=None,
        help="Path to a file holding 'H' (the --host_devices value) or "
        "'P H' (num_processes and host_devices) for each worker-group "
        "(re)start. Re-read before every group launch, so an elastic "
        "restart (preemption exit-75, health escalation) can come back at "
        "a SMALLER topology and the workers reshard their checkpoint on "
        "restore. The path is also exported as ATX_ELASTIC_DEVICES_FILE so "
        "a running group with ATX_ELASTIC_SHRINK=1 can watch it and "
        "shrink/grow IN PLACE without a relaunch "
        "(docs/fault_tolerance.md, shrink/grow in place)",
    )
    p.add_argument("--dry_run", action="store_true", help="Print commands, don't run")
    p.add_argument("script", help="Training script to run")
    p.add_argument("script_args", nargs=argparse.REMAINDER, help="Script arguments")
    p.set_defaults(func=run)


def _merge_config(args: argparse.Namespace) -> LaunchConfig:
    """CLI > config file > defaults (reference `_validate_launch_command`,
    `commands/launch.py:988-1167`)."""
    if args.config_file:
        cfg = LaunchConfig.load(args.config_file)
    else:
        cfg = load_default_config() or LaunchConfig()
    overrides = {
        "num_processes": args.num_processes,
        "coordinator_address": args.coordinator_address,
        "coordinator_port": args.coordinator_port,
        "mixed_precision": args.mixed_precision,
        "sharding_strategy": args.strategy,
        "mesh_data": args.data,
        "mesh_fsdp": args.fsdp,
        "mesh_tensor": args.tensor,
        "mesh_sequence": args.sequence,
        "mesh_expert": args.expert,
        "gradient_accumulation_steps": args.gradient_accumulation_steps,
        "offload_optimizer": args.offload_optimizer,
        "force_fp8": getattr(args, "force_fp8", None),
        "log_with": args.log_with,
        "project_dir": args.project_dir,
        "tpu_name": args.tpu_name,
        "tpu_zone": args.tpu_zone,
        "tpu_project": args.tpu_project,
        "max_restarts": args.max_restarts,
    }
    for key, value in overrides.items():
        if value is not None:
            setattr(cfg, key, value)
    if getattr(args, "replicate_url", None):
        # Replication is plain env contract (workers read ATX_REPLICATE_URL
        # in Accelerator.__init__); extra_env is applied last in
        # build_child_env so the flag also wins over a config-file value.
        cfg.extra_env = {**cfg.extra_env, "ATX_REPLICATE_URL": args.replicate_url}
    if getattr(args, "elastic_devices_file", None):
        # Exported so workers running with ATX_ELASTIC_SHRINK=1 can watch
        # the same file and resize IN PLACE; the launcher keeps re-reading
        # it per group (re)start as the relaunch fallback.
        cfg.extra_env = {
            **cfg.extra_env,
            "ATX_ELASTIC_DEVICES_FILE": args.elastic_devices_file,
        }
    return cfg


def build_child_env(
    cfg: LaunchConfig,
    process_id: int | None = None,
    *,
    base: dict[str, str] | None = None,
    host_devices: int | None = None,
) -> dict[str, str]:
    """The env contract a child process configures itself from."""
    env = dict(os.environ if base is None else base)
    env["ATX_MIXED_PRECISION"] = cfg.mixed_precision
    env["ATX_SHARDING_STRATEGY"] = cfg.sharding_strategy
    env["ATX_MESH_DATA"] = str(cfg.mesh_data)
    env["ATX_MESH_FSDP"] = str(cfg.mesh_fsdp)
    env["ATX_MESH_TENSOR"] = str(cfg.mesh_tensor)
    env["ATX_MESH_SEQUENCE"] = str(cfg.mesh_sequence)
    env["ATX_MESH_EXPERT"] = str(cfg.mesh_expert)
    env["ATX_GRADIENT_ACCUMULATION_STEPS"] = str(cfg.gradient_accumulation_steps)
    if cfg.offload_optimizer:
        env["ATX_OFFLOAD_OPTIMIZER"] = "1"
    if cfg.log_with:
        env["ATX_LOG_WITH"] = cfg.log_with
    if cfg.project_dir:
        env["ATX_PROJECT_DIR"] = cfg.project_dir
    if cfg.num_processes > 1:
        env["ATX_NUM_PROCESSES"] = str(cfg.num_processes)
        if process_id is not None:
            env["ATX_PROCESS_ID"] = str(process_id)
        if cfg.coordinator_address:
            env["ATX_COORDINATOR_ADDRESS"] = cfg.coordinator_address
        else:
            env["ATX_MULTIHOST"] = "1"  # TPU metadata autodetect
    if host_devices:
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={host_devices}".strip()
        )
        env["JAX_PLATFORMS"] = "cpu"
    env.update(cfg.extra_env)
    return env


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _port_stolen(port: int) -> bool:
    """After a group death: is the rendezvous port held by ANOTHER process?
    Our own (dead) coordinator leaves at most a TIME_WAIT entry, which
    SO_REUSEADDR binds through — so a failed bind here means someone else
    grabbed the port between the `_free_port` probe and the coordinator's
    bind, i.e. the failure was the launcher's race, not the workload's."""
    import socket

    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind(("127.0.0.1", port))
            return False
        except OSError:
            return True


def _run_worker_group(cfg: LaunchConfig, cmd: list[str], args) -> int:
    """Spawn one group of num_processes children and babysit it: first
    worker death tears the whole group down (the reference relies on
    torch-elastic for this; here the launcher owns it)."""
    procs: list[subprocess.Popen] = []
    try:
        for i in range(cfg.num_processes):
            env = build_child_env(cfg, i, host_devices=args.host_devices)
            procs.append(subprocess.Popen(cmd, env=env))
        exit_code = 0
        term_deadline = None
        while procs:
            for p in list(procs):
                ret = p.poll()
                if ret is None:
                    continue
                procs.remove(p)
                if ret != 0 and exit_code == 0:
                    # Keep the FIRST failure's code: the peers reaped after
                    # the teardown die with -SIGTERM, which would mask the
                    # root cause in the restart log and the final status.
                    # (A preempted worker's PREEMPTION_EXIT_CODE survives
                    # the same way — its SIGTERMed peers write their own
                    # emergency checkpoints and exit with the same code.)
                    exit_code = ret
                    for q in procs:
                        q.send_signal(signal.SIGTERM)
                    term_deadline = time.time() + _term_grace_secs()
            if procs:
                if term_deadline is not None and time.time() > term_deadline:
                    # Peers trapped the TERM (emergency save wedged, or a
                    # hung collective): escalate so the group actually dies
                    # and the restart policy can run.
                    for q in procs:
                        q.kill()
                    term_deadline = None
                time.sleep(0.2)
        return exit_code
    finally:
        for p in procs:
            p.kill()


def _apply_elastic_devices(args, cfg=None) -> None:
    """Re-read ``--elastic_devices_file`` (when given) before a worker-group
    (re)start: the file holds either ``H`` (the ``--host_devices`` value) or
    ``P H`` (num_processes and host_devices) for the NEXT group, so an
    external controller (or a test) can shrink the simulated topology
    between an emergency exit and the elastic resume. Unreadable /
    non-integer content keeps the previous values — a live elastic loop must
    not die on a torn write."""
    path = getattr(args, "elastic_devices_file", None)
    if not path:
        return
    try:
        with open(path) as f:
            fields = [int(tok) for tok in f.read().split()]
        if len(fields) == 1:
            processes, devices = None, fields[0]
        elif len(fields) == 2:
            processes, devices = fields
        else:
            raise ValueError(f"expected 'H' or 'P H', got {len(fields)} fields")
    except (OSError, ValueError) as e:
        print(
            f"[accelerate-tpu launch] could not read --elastic_devices_file "
            f"{path!r} ({e}); keeping host_devices={args.host_devices}",
            file=sys.stderr,
            flush=True,
        )
        return
    if devices > 0 and devices != args.host_devices:
        print(
            f"[accelerate-tpu launch] elastic devices file: next worker "
            f"group starts with host_devices={devices} "
            f"(was {args.host_devices})",
            file=sys.stderr,
            flush=True,
        )
        args.host_devices = devices
    if (
        cfg is not None
        and processes is not None
        and processes > 0
        and processes != cfg.num_processes
    ):
        print(
            f"[accelerate-tpu launch] elastic devices file: next worker "
            f"group starts with num_processes={processes} "
            f"(was {cfg.num_processes})",
            file=sys.stderr,
            flush=True,
        )
        cfg.num_processes = processes


def _local_multiprocess_launch(cfg: LaunchConfig, cmd: list[str], args) -> int:
    """Spawn num_processes children on this machine (rendezvous over
    localhost) — the CPU-simulation / single-host-multi-proc path that the
    reference covers with its gloo `debug_launcher` (`launchers.py:268`).

    With ``max_restarts > 0``, a dead worker group is relaunched whole, on a
    FRESH coordinator port (the old rendezvous may linger in TIME_WAIT /
    stale `jax.distributed` state), up to the limit — the torch-elastic
    restart policy the reference forwards (`commands/launch.py:142-771`).
    Restarted scripts resume from their own checkpoints exactly as they
    would under torch-elastic.
    """
    if args.dry_run:
        for i in range(cfg.num_processes):
            print(f"[proc {i}] {' '.join(shlex.quote(c) for c in cmd)}")
        return 0
    pinned_address = cfg.coordinator_address  # user-supplied: reuse as-is
    exit_code = 0
    # _free_port probes by bind-and-close, so another process can steal the
    # port in the window before the coordinator binds it. Such a failure is
    # the launcher's fault, not the workload's: retry the same attempt on a
    # fresh port (bounded) instead of burning the user's max_restarts budget.
    rendezvous_retries = 3
    first_group = True
    attempt = 0
    preemption_resumes = 0
    while attempt <= cfg.max_restarts:
        if pinned_address:
            cfg.coordinator_address = pinned_address
        elif first_group:
            cfg.coordinator_address = f"127.0.0.1:{cfg.coordinator_port}"
        else:
            cfg.coordinator_address = f"127.0.0.1:{_free_port()}"
        first_group = False
        _apply_elastic_devices(args, cfg)
        exit_code = _run_worker_group(cfg, cmd, args)
        if exit_code == 0:
            return 0
        if (
            exit_code == PREEMPTION_EXIT_CODE
            and preemption_resumes < _max_preemption_resumes()
        ):
            # Exit-code contract (resilience/preemption.py): the group was
            # preempted AFTER committing an emergency checkpoint — this is
            # not a failure, so resume immediately on a fresh port without
            # consuming a --max_restarts attempt. Bounded by
            # ATX_MAX_PREEMPTION_RESUMES against a pathological script that
            # always exits preempted.
            preemption_resumes += 1
            print(
                "[accelerate-tpu launch] worker group preempted (exit "
                f"{PREEMPTION_EXIT_CODE}, emergency checkpoint committed); "
                f"resuming immediately (resume {preemption_resumes}, not "
                "counted against --max_restarts)",
                file=sys.stderr,
                flush=True,
            )
            continue
        # Only launcher-chosen addresses are "127.0.0.1:<port>"; a pinned
        # address may have no numeric port, so parse under the guard.
        if not pinned_address and rendezvous_retries > 0 and _port_stolen(
            chosen_port := int(cfg.coordinator_address.rsplit(":", 1)[1])
        ):
            rendezvous_retries -= 1
            print(
                "[accelerate-tpu launch] rendezvous port "
                f"{chosen_port} was taken by another process; retrying on a "
                "fresh port (not counted against --max_restarts)",
                file=sys.stderr,
                flush=True,
            )
            continue
        if attempt < cfg.max_restarts:
            print(
                f"[accelerate-tpu launch] worker group failed (exit "
                f"{exit_code}); restarting group "
                f"({attempt + 1}/{cfg.max_restarts})",
                file=sys.stderr,
                flush=True,
            )
        attempt += 1
    return exit_code


def build_tpu_ssh_command(
    tpu_name: str, tpu_zone: str, tpu_project: str | None, remote: str
) -> list[str]:
    """`gcloud compute tpus tpu-vm ssh --worker=all` invocation shared by
    `launch` (pod training) and `tpu-config` (pod setup)."""
    gcloud = [
        "gcloud",
        "compute",
        "tpus",
        "tpu-vm",
        "ssh",
        tpu_name,
        f"--zone={tpu_zone}",
        "--worker=all",
        f"--command={remote}",
    ]
    if tpu_project:
        gcloud.insert(5, f"--project={tpu_project}")
    return gcloud


def _tpu_pod_launch(cfg: LaunchConfig, cmd: list[str], args) -> int:
    """Run the training command on every pod worker via gcloud SSH
    (reference `tpu_pod_launcher`, `commands/launch.py:909`). A nonzero pod
    run is retried up to ``max_restarts`` times (same elastic policy as the
    local group path; the pod re-rendezvouses through TPU metadata, so no
    port rotation is needed).

    Exit-code caveat (docs/fault_tolerance.md §exit-code contract): the
    preemption fast-path below relies on ``gcloud ... ssh --worker=all``
    surfacing the remote training process's exit status, and with multiple
    workers gcloud's SSH fan-out does NOT reliably propagate a specific
    worker's code. A real pod preemption may therefore be classified as an
    ordinary failure and consume a ``--max_restarts`` attempt instead of
    taking the free-resume path. This is safe — the emergency checkpoint
    was committed before the workers exited, and the ordinary restart
    resumes from it via ``load_state(resume="latest")`` — but budget
    ``--max_restarts`` with headroom on preemptible pods. (The local
    worker-group path reaps each child directly and is not affected.)"""
    env_exports = " ".join(
        f"{k}={shlex.quote(v)}"
        for k, v in build_child_env(cfg, None, base={}).items()
    )
    remote = f"{env_exports} {' '.join(shlex.quote(c) for c in cmd)}"
    gcloud = build_tpu_ssh_command(cfg.tpu_name, cfg.tpu_zone, cfg.tpu_project, remote)
    if args.dry_run:
        print(" ".join(shlex.quote(c) for c in gcloud))
        return 0
    attempt = 0
    preemption_resumes = 0
    while True:
        exit_code = subprocess.call(gcloud)
        if exit_code == 0:
            return 0
        if (
            exit_code == PREEMPTION_EXIT_CODE
            and preemption_resumes < _max_preemption_resumes()
        ):
            # Same exit-code contract as the local group path: a preempted
            # pod committed its emergency checkpoint, so the re-run is a
            # resume, not a burned restart attempt.
            preemption_resumes += 1
            print(
                "[accelerate-tpu launch] pod run preempted (exit "
                f"{PREEMPTION_EXIT_CODE}); resuming immediately (resume "
                f"{preemption_resumes}, not counted against --max_restarts)",
                file=sys.stderr,
                flush=True,
            )
            continue
        if attempt >= cfg.max_restarts:
            return exit_code
        print(
            f"[accelerate-tpu launch] pod run failed (exit {exit_code}); "
            f"restarting ({attempt + 1}/{cfg.max_restarts})",
            file=sys.stderr,
            flush=True,
        )
        attempt += 1


def _fp8_speedup_for_local_devices() -> float | None:
    """Recorded fp8 speedup for the local device kind; None when unknown or
    when devices can't be queried (e.g. pod SSH launch — the remote kind is
    unknown here, so the gate stays permissive).

    The device kind is probed in a SUBPROCESS: importing jax here would
    initialize libtpu in the launcher process and hold the chips, so every
    spawned worker would then fail with 'TPU already in use'. The probe
    process exits (releasing the devices) before any worker starts."""
    from ..utils import fp8_telemetry

    kind = _probe_device_kind()
    if not kind:
        return None
    return fp8_telemetry.lookup(kind)


def _local_children_would_share_tpu(cfg: LaunchConfig, args) -> bool:
    """Would N local children each try to open this host's TPU? Not when
    they are pinned to the CPU (``--host_devices`` or ``JAX_PLATFORMS=cpu``
    in the environment they inherit); otherwise ask a probe process what
    the default device is."""
    platforms = cfg.extra_env.get("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", ""))
    if args.host_devices or platforms.strip().lower() == "cpu":
        return False
    return (_probe_device_kind() or "").startswith("TPU")


def _probe_device_kind() -> str | None:
    import subprocess

    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].device_kind)"],
            capture_output=True, text=True, timeout=120,
        )
        lines = out.stdout.strip().splitlines()
        return lines[-1] if out.returncode == 0 and lines else None
    except Exception:
        return None


def run(args: argparse.Namespace) -> int:
    cfg = _merge_config(args)
    cmd = [sys.executable, args.script, *args.script_args]
    if cfg.mixed_precision == "fp8":
        print(
            "[accelerate-tpu launch] fp8 selected: only beneficial on chips "
            "with native fp8 MXU support; elsewhere XLA upcasts the values — "
            "quantization error with no speedup (see the table in "
            "accelerate_tpu/utils/fp8_telemetry.py and docs/performance.md).",
            file=sys.stderr,
        )
        speedup = _fp8_speedup_for_local_devices()
        if speedup is not None and speedup <= 1.0 and not cfg.force_fp8:
            print(
                "[accelerate-tpu launch] refusing --mixed_precision fp8: "
                f"measured fp8 matmul speedup on this device kind is "
                f"{speedup:.2f}x (<= 1) — you would pay fp8 quantization "
                "error for a slowdown. Pass --force_fp8 to override.",
                file=sys.stderr,
            )
            return 2

    if cfg.tpu_name:
        return _tpu_pod_launch(cfg, cmd, args)
    if cfg.num_processes > 1:
        if _local_children_would_share_tpu(cfg, args):
            print(
                f"[accelerate-tpu launch] refusing --num_processes "
                f"{cfg.num_processes} on a TPU host: one process drives all "
                "local chips (a chip belongs to one process at a time, so "
                "the children would fail or hang on each other). Launch one "
                "process and shard over the chips with the mesh flags "
                "(--data/--fsdp/--tensor), or pass --host_devices N for the "
                "CPU simulation.",
                file=sys.stderr,
            )
            return 2
        return _local_multiprocess_launch(cfg, cmd, args)
    # Single host process: exec in place with the env contract.
    if cfg.max_restarts:
        print(
            "[accelerate-tpu launch] --max_restarts applies to worker groups "
            "(num_processes > 1 or pod launches); a single exec'd process is "
            "not restarted.",
            file=sys.stderr,
        )
    _apply_elastic_devices(args, cfg)
    env = build_child_env(cfg, None, host_devices=args.host_devices)
    if args.dry_run:
        print(" ".join(shlex.quote(c) for c in cmd))
        return 0
    os.environ.update(env)
    os.execvpe(cmd[0], cmd, os.environ)
    return 0  # pragma: no cover - execvpe does not return
