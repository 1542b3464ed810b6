"""`accelerate-tpu config` — write the launch configuration file.

Analog of the reference interactive config command (`commands/config/
config.py:31`, `cluster.py:55` Q&A, `config_args.py` schema, default path
``~/.cache/huggingface/accelerate/default_config.yaml``). The TPU schema is
radically smaller: no backend zoo, no DeepSpeed/Megatron/dynamo trees — a
mesh shape, a sharding strategy, precision, and (for pods) host topology.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any

DEFAULT_CONFIG_DIR = os.path.join(
    os.path.expanduser(os.environ.get("ATX_HOME", "~/.cache/accelerate_tpu"))
)
DEFAULT_CONFIG_PATH = os.path.join(DEFAULT_CONFIG_DIR, "default_config.yaml")


@dataclass
class LaunchConfig:
    """Serializable launch configuration (reference `ClusterConfig`,
    `commands/config/config_args.py`)."""

    num_processes: int = 1
    coordinator_address: str = ""
    coordinator_port: int = 7801
    mesh_data: int = -1
    mesh_fsdp: int = 1
    mesh_tensor: int = 1
    mesh_sequence: int = 1
    mesh_expert: int = 1
    mixed_precision: str = "bf16"
    sharding_strategy: str = "DATA_PARALLEL"
    gradient_accumulation_steps: int = 1
    # Optimizer moments in pinned host RAM (parallel/host_offload.py; the
    # DeepSpeed offload_optimizer analog) — forwarded as ATX_OFFLOAD_OPTIMIZER.
    offload_optimizer: bool = False
    # Run fp8 even where the recorded matmul speedup is <= 1 (the launch
    # lose-lose gate, `commands/launch.py`).
    force_fp8: bool = False
    # Comma-separated tracker names (tracking.filter_trackers; "" = none),
    # forwarded as ATX_LOG_WITH; project_dir feeds ProjectConfiguration.
    log_with: str = ""
    project_dir: str = ""
    # Relaunch the whole worker group (fresh coordinator port) up to this
    # many times after a worker death — the torch-elastic max_restarts analog
    # (reference `commands/launch.py:142-771`). 0 = fail on first death.
    max_restarts: int = 0
    # TPU pod orchestration (reference tpu_pod_launcher, commands/launch.py:909)
    tpu_name: str = ""
    tpu_zone: str = ""
    tpu_project: str = ""
    extra_env: dict[str, str] = field(default_factory=dict)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "LaunchConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        data = self.to_dict()
        try:
            import yaml

            with open(path, "w") as f:
                yaml.safe_dump(data, f, sort_keys=False)
        except ImportError:  # pragma: no cover - yaml ships with transformers
            path = os.path.splitext(path)[0] + ".json"
            with open(path, "w") as f:
                json.dump(data, f, indent=2)
        return path

    @classmethod
    def load(cls, path: str) -> "LaunchConfig":
        with open(path) as f:
            text = f.read()
        try:
            import yaml

            data = yaml.safe_load(text)
        except ImportError:  # pragma: no cover
            data = json.loads(text)
        return cls.from_dict(data or {})


def load_default_config() -> LaunchConfig | None:
    for path in (DEFAULT_CONFIG_PATH, os.path.splitext(DEFAULT_CONFIG_PATH)[0] + ".json"):
        if os.path.exists(path):
            return LaunchConfig.load(path)
    return None


def _ask(prompt: str, default: Any, cast=str) -> Any:
    raw = input(f"{prompt} [{default}]: ").strip()
    if not raw:
        return default
    try:
        return cast(raw)
    except (TypeError, ValueError):
        print(f"  invalid value {raw!r}; keeping {default}")
        return default


def interactive_config() -> LaunchConfig:
    """Q&A flow (reference `get_cluster_input`, `commands/config/cluster.py:55`)."""
    cfg = LaunchConfig()
    print("accelerate-tpu configuration")
    print("----------------------------")
    cfg.num_processes = _ask("How many host processes (1 per TPU host)?", 1, int)
    if cfg.num_processes > 1:
        cfg.coordinator_address = _ask(
            "Coordinator address (host:port of process 0; blank = TPU metadata autodetect)",
            "",
        )
    shape_help = "devices on each mesh axis; data=-1 means all remaining"
    cfg.mesh_data = _ask(f"Mesh: data-parallel size ({shape_help})", -1, int)
    cfg.mesh_fsdp = _ask("Mesh: fsdp size", 1, int)
    cfg.mesh_tensor = _ask("Mesh: tensor-parallel size", 1, int)
    cfg.mesh_sequence = _ask("Mesh: sequence-parallel size", 1, int)
    cfg.mesh_expert = _ask("Mesh: expert-parallel size", 1, int)
    cfg.sharding_strategy = _ask(
        "Sharding strategy (DATA_PARALLEL/ZERO1/ZERO2/FSDP/TENSOR_PARALLEL/HYBRID)",
        "FSDP" if cfg.mesh_fsdp > 1 else "DATA_PARALLEL",
    ).upper()
    if cfg.sharding_strategy in ("FSDP", "ZERO1", "ZERO2", "HYBRID"):
        cfg.offload_optimizer = (
            _ask(
                "Offload optimizer moments to pinned host RAM? (y/n; the "
                "DeepSpeed offload_optimizer analog — fits ~3x larger "
                "models at a per-step streaming cost)",
                "n",
            )
            .lower()
            .startswith("y")
        )
    cfg.mixed_precision = _ask("Mixed precision (no/bf16/fp16/fp8)", "bf16")
    if cfg.mixed_precision == "fp8":
        print(
            "  NOTE: fp8 only pays off on chips with native fp8 MXU support; "
            "on other hardware (e.g. TPU v5e) XLA upcasts the fp8 values — "
            "you keep the quantization error and get NO speedup. See the "
            "table in accelerate_tpu/utils/fp8_telemetry.py and "
            "docs/performance.md (fp8) first."
        )
        cfg.force_fp8 = (
            _ask(
                "Force fp8 even where the recorded speedup is <= 1x? (y/n; "
                "otherwise launch refuses the lose-lose configuration)",
                "n",
            )
            .lower()
            .startswith("y")
        )
    cfg.gradient_accumulation_steps = _ask("Gradient accumulation steps", 1, int)
    cfg.max_restarts = _ask(
        "Max worker-group restarts after a crash (torch-elastic "
        "max_restarts analog; 0 = fail on first death)",
        0,
        int,
    )
    cfg.log_with = _ask(
        "Experiment trackers, comma-separated (json/tensorboard/wandb/"
        "mlflow/comet_ml/aim/clearml/dvclive; blank = none)",
        "",
    )
    if cfg.log_with:
        cfg.project_dir = _ask(
            "Project directory (checkpoints + tracker logging dir)", ""
        )
    if _ask("Launching on a GCE TPU pod via gcloud? (y/n)", "n").lower().startswith("y"):
        cfg.tpu_name = _ask("TPU name", "")
        cfg.tpu_zone = _ask("TPU zone", "")
        cfg.tpu_project = _ask("GCP project (blank = default)", "")
    return cfg


def register(subparsers: argparse._SubParsersAction) -> None:
    p = subparsers.add_parser("config", help="Create the launch configuration file")
    p.add_argument("--config_file", default=DEFAULT_CONFIG_PATH, help="Where to write")
    p.add_argument(
        "--default",
        action="store_true",
        help="Write a non-interactive single-host default config "
        "(reference `write_basic_config`, commands/config/default.py:165)",
    )
    p.set_defaults(func=run)


def run(args: argparse.Namespace) -> int:
    cfg = LaunchConfig() if args.default else interactive_config()
    path = cfg.save(args.config_file)
    print(f"Configuration saved to {path}")
    return 0
