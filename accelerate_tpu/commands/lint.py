"""`accelerate-tpu lint` / `atx lint` — ahead-of-time step analyzer CLI.

Lints the `examples/` entry points (and any registered scenario) without
running them: each scenario rebuilds the example's exact training
configuration — model family/config, strategy, precision, batch shapes —
abstractly via `analysis.lint_training`, so the REAL compiled train step is
traced, lowered, and byte-audited with zero parameters materialized and
zero steps executed. Exit code 1 when any finding at/above ``--severity``
(default: error) is present — the `make lint-graph` CI gate.

Rule catalogue: docs/static_analysis.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Callable


def register(subparsers: argparse._SubParsersAction) -> None:
    p = subparsers.add_parser(
        "lint",
        help="Ahead-of-time sharding/donation/recompilation lint for train steps",
    )
    p.add_argument(
        "targets",
        nargs="*",
        help="example scripts, directories of them, or scenario names "
        "(default: every built-in example scenario; see --list)",
    )
    p.add_argument(
        "--severity",
        default="error",
        choices=["info", "warning", "error"],
        help="exit non-zero when a finding at/above this severity exists",
    )
    p.add_argument(
        "--show",
        default="info",
        choices=["info", "warning", "error"],
        help="minimum severity to print",
    )
    p.add_argument("--format", dest="fmt", default="text", choices=["text", "json"])
    p.add_argument(
        "--json",
        dest="json_lines",
        action="store_true",
        help="emit findings as JSON lines (one finding object per line, "
        "machine-readable `data` included — e.g. the ATX404 byte table)",
    )
    p.add_argument(
        "--multihost",
        type=int,
        default=0,
        metavar="N",
        help="also verify multi-host SPMD consistency (ATX5xx) by replaying "
        "each scenario under N simulated processes; adds the host-loop "
        "scenarios (save_path, preemption_exit, router_drain, "
        "replicated_save, elastic_restore, telemetry, tracing) to the "
        "default set",
    )
    p.add_argument(
        "--chip",
        default=None,
        metavar="GEN",
        help="chip generation the ATX6xx roofline rates against (v4, v5e, "
        "v5p, v6e, cpu; default: auto-detect the local device). The "
        "lint-perf lane pins v5e so the budget series is TPU-shaped even "
        "on the CPU container",
    )
    p.add_argument(
        "--budgets",
        metavar="FILE",
        default=None,
        help="ratchet the static series against this committed budgets "
        "JSON: the ATX601 roofline series (static_mfu_bound, "
        "exposed_comms_bytes, padding_waste_fraction), the ATX701 "
        "peak_hbm_mib, and the ATX706 serve_static_max_slots; any "
        "regression past tolerance fails the run (the `make lint-perf` / "
        "`make lint-memory` gates, docs/performance.md)",
    )
    p.add_argument(
        "--write-budgets",
        dest="write_budgets",
        metavar="FILE",
        default=None,
        help="write/re-baseline the budgets JSON from this run's "
        "ATX601/ATX701/ATX706 series (one entry per scenario that "
        "produced any)",
    )
    p.add_argument("--list", action="store_true", help="list lintable scenarios")
    p.add_argument(
        "--rules", action="store_true", help="list the registered rule catalogue"
    )
    p.add_argument(
        "--host_devices",
        type=int,
        default=None,
        help="simulate N host devices (XLA_FLAGS) so sharding/collective "
        "rules see a real mesh on CPU; must be set before jax initializes",
    )
    p.set_defaults(func=run)


# --------------------------------------------------------------- scenarios
# Each scenario mirrors one examples/ entry point's training configuration.
# Builders return (description, Report).


def _fresh_accelerator(**kwargs: Any):
    from ..accelerator import Accelerator
    from ..state import AcceleratorState

    AcceleratorState._reset_state()
    return Accelerator(seed=0, **kwargs)


def _scenario_nlp_example(**options: Any):
    """examples/nlp_example.py: BERT-tiny pair classification, DP, fp32."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import analysis
    from ..models import bert
    from ..utils.dataclasses import DataLoaderConfiguration

    acc = _fresh_accelerator(
        max_grad_norm=1.0,
        dataloader_config=DataLoaderConfiguration(split_batches=True),
    )
    config = bert.BertConfig.tiny(
        vocab_size=128, max_seq_len=64, d_model=64, d_ff=128
    )
    batch_size, seq_len = 64, 64
    batch = {
        "input_ids": np.zeros((batch_size, seq_len), np.int32),
        "token_type_ids": np.zeros((batch_size, seq_len), np.int32),
        "attention_mask": np.zeros((batch_size, seq_len), np.int32),
        "labels": np.zeros((batch_size,), np.int32),
    }
    report = analysis.lint_training(
        acc,
        lambda r: bert.init(r, config),
        optax.adamw(2e-3, weight_decay=0.01),
        lambda params, b, rng: bert.loss_fn(params, b, config, rng),
        batch,
        target="examples/nlp_example.py",
        **options,
    )
    desc = f"BERT-tiny pair classification, {acc!r}"
    return desc, report


def _scenario_lm_example(**options: Any):
    """examples/lm_example.py: GPT causal LM, bf16, grad clipping."""
    import numpy as np
    import optax

    from .. import analysis
    from ..models import gpt

    acc = _fresh_accelerator(mixed_precision="bf16", max_grad_norm=1.0)
    config = gpt.GPTConfig(
        vocab_size=128, d_model=128, n_layers=4, num_heads=4, d_ff=512,
        max_seq_len=64,
    )
    batch = {"input_ids": np.zeros((8, 64), np.int32)}
    report = analysis.lint_training(
        acc,
        lambda r: gpt.init(r, config),
        optax.adamw(3e-3),
        lambda params, b, rng: gpt.loss_fn(params, b, config, rng),
        batch,
        target="examples/lm_example.py",
        **options,
    )
    return f"GPT causal LM, {acc!r}", report


def _scenario_llama2b(**options: Any):
    """llama 1.64B train step, linted fully
    abstractly: the real 24-layer seq-4096 config with remat +
    adafactor is traced/lowered/compiled with zero parameters
    materialized — the scenario the ATX601 roofline bounds for real
    (attention_impl="dot": the pallas flash kernel has no abstract CPU
    lowering; same dot/collective structure either way). Sharded FSDP
    over the 8 simulated devices: that is the deployment the v5e-rated
    lanes judge — a fully-replicated 1.64B fp32 state (~21 GiB static)
    cannot fit one 16 GiB chip, which the ATX702 OOM-ahead-of-time gate
    would rightly fail."""
    import numpy as np
    import optax

    from .. import analysis
    from ..parallel.mesh import MeshConfig

    from ..models import llama

    acc = _fresh_accelerator(
        mixed_precision="bf16",
        max_grad_norm=1.0,
        mesh_config=MeshConfig(data=1, fsdp=8),
        strategy="FSDP",
    )
    config = llama.LlamaConfig(
        vocab_size=32000,
        d_model=2048,
        n_layers=24,
        num_heads=16,
        num_kv_heads=8,
        head_dim=128,
        d_ff=8192,
        max_seq_len=4096,
        remat=True,
        remat_policy="attn_and_outputs",
        attention_impl="dot",
        loss_chunk_size=512,
    )
    # Abstractly the batch axis must divide the 8 simulated devices the
    # lint lanes force.
    batch = {"input_ids": np.zeros((8, 4096), np.int32)}
    report = analysis.lint_training(
        acc,
        lambda r: llama.init(r, config),
        optax.adafactor(3e-4),
        lambda params, b, rng: llama.loss_fn(params, b, config, rng),
        batch,
        target="llama2b",
        **options,
    )
    return f"llama 1.64B seq-4096 train step, {acc!r}", report


def _scenario_cv_example(**options: Any):
    """examples/cv_example.py: inline convnet quadrant classification, DP."""
    import importlib.util

    import numpy as np
    import optax

    from .. import analysis

    path = _examples_dir() / "cv_example.py"
    spec = importlib.util.spec_from_file_location("atx_lint_cv_example", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    from ..utils.dataclasses import DataLoaderConfiguration

    acc = _fresh_accelerator(
        dataloader_config=DataLoaderConfiguration(split_batches=True)
    )
    image_size = 32
    batch = {
        "image": np.zeros((64, image_size, image_size, 1), np.float32),
        "label": np.zeros((64,), np.int32),
    }
    report = analysis.lint_training(
        acc,
        lambda r: mod.init_convnet(r, image_size=image_size),
        optax.adam(1e-3),
        mod.loss_fn,
        batch,
        target="examples/cv_example.py",
        **options,
    )
    return f"convnet quadrant classifier, {acc!r}", report


def _scenario_serving(**options: Any):
    """serving hot paths behind a 2-replica Router: EACH replica engine's
    slot-batched decode function is linted with its own abstract call
    signature (donation of the slot cache, no host syncs/callbacks in the
    compiled step, stable shapes), and when the prefix cache is on, each
    replica's bucketed prefix-copy function too — the per-replica device
    programs the multi-replica front-end dispatches (docs/serving.md)."""
    import jax
    import jax.numpy as jnp

    from .. import analysis
    from ..generation import GenerationConfig
    from ..models import llama
    from ..serving import Engine, Router

    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq_len=128)
    params = llama.init(jax.random.PRNGKey(0), config)

    def mk_engine() -> Engine:
        return Engine(
            lambda p, t, c: llama.forward_with_cache(p, t, c, config),
            lambda b, m: llama.init_cache(config, b, m),
            params,
            GenerationConfig(eos_token_id=0),
            slots=4,
            buckets=(16, 32),
            max_len=96,
        )

    # threads=False: nothing is dispatched here, so no replica threads —
    # the router only names/owns the replica engines being linted.
    router = Router([mk_engine(), mk_engine()], threads=False)
    findings: list = []
    for rep in router.replicas:
        engine = rep.engine
        report = analysis.lint_step(
            engine._decode_fn,
            *engine.abstract_decode_args(),
            donate_argnums=(3,),
            target=f"serving.Router.replica{rep.id}.decode",
            **options,
        )
        findings += report.findings
        if rep.id == router.replicas[0].id:
            # ATX706 capacity plan for the fleet's engine shape (replicas
            # are identical): weights + slot pool + prefix pool vs the
            # chip, with the decode step's at-peak working bytes from the
            # ATX701 timeline just computed. Emitted here — not as a
            # registered rule — because the planner needs a constructed
            # engine, not a step function.
            atx701 = next(
                (f for f in report.findings if f.rule_id == "ATX701"), None
            )
            act = 0
            if atx701 is not None and atx701.data:
                cats = atx701.data.get("categories_at_peak", {})
                act = sum(
                    v for k, v in cats.items()
                    if k in ("activations", "xla_temp", "collective")
                )
            findings += analysis.capacity_findings(
                engine, chip=options.get("roofline_chip"), act_peak_bytes=act
            )
        if engine.prefix_cache is not None:
            copy_report = analysis.lint_step(
                engine.copy_fn_for_bucket(engine.buckets[0]),
                *engine.abstract_copy_args(),
                donate_argnums=(0,),
                target=f"serving.Router.replica{rep.id}.prefix_copy",
                **options,
            )
            findings += copy_report.findings
    n_slots = router.replicas[0].engine.n_slots
    desc = (
        f"2-replica router: decode + prefix copy per replica, "
        f"{n_slots} slots each"
    )
    return desc, analysis.Report(
        findings=findings, target="serving.Router.decode+prefix_copy"
    )


def _scenario_kernels(**options: Any):
    """Pallas kernel tier (`native/pallas/`): the serving decode step and a
    host-offloaded-AdamW train step with every kernel forced into interpret
    mode — proving the kernel lowerings keep the donation and host-sync
    contracts (no new ATX2xx/3xx findings relative to the fallbacks the
    other scenarios lint)."""
    import jax
    import numpy as np

    from .. import analysis
    from ..generation import GenerationConfig
    from ..models import gpt, llama
    from ..native.pallas import force_kernels
    from ..parallel import host_offload
    from ..serving import Engine

    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq_len=128)
    params = llama.init(jax.random.PRNGKey(0), config)
    findings: list = []
    with force_kernels("interpret"):
        engine = Engine(
            lambda p, t, c: llama.forward_with_cache(p, t, c, config),
            lambda b, m: llama.init_cache(config, b, m),
            params,
            GenerationConfig(eos_token_id=0),
            slots=4,
            buckets=(16, 32),
            max_len=96,
        )
        report = analysis.lint_step(
            engine._decode_fn,
            *engine.abstract_decode_args(),
            donate_argnums=(3,),
            target="kernels.decode_attn",
            **options,
        )
        findings += report.findings

        acc = _fresh_accelerator(mixed_precision="bf16", max_grad_norm=1.0)
        gpt_config = gpt.GPTConfig(
            vocab_size=128, d_model=128, n_layers=4, num_heads=4, d_ff=512,
            max_seq_len=64,
        )
        batch = {"input_ids": np.zeros((8, 64), np.int32)}
        train_report = analysis.lint_training(
            acc,
            lambda r: gpt.init(r, gpt_config),
            host_offload.host_offloaded_adamw(3e-3),
            lambda params, b, rng: gpt.loss_fn(params, b, gpt_config, rng),
            batch,
            target="kernels.fused_adamw",
            **options,
        )
        findings += train_report.findings
    desc = "kernel-tier decode + fused-AdamW train step, interpret mode"
    return desc, analysis.Report(findings=findings, target="kernels")


SCENARIOS: dict[str, Callable[..., tuple[str, Any]]] = {
    "nlp_example": _scenario_nlp_example,
    "lm_example": _scenario_lm_example,
    "cv_example": _scenario_cv_example,
    "llama2b": _scenario_llama2b,
    "serving": _scenario_serving,
    "kernels": _scenario_kernels,
}

# `atx lint perf`: the scenario set the ATX6xx budget ratchet covers
# (`make lint-perf`) — the example train steps plus the 1.64B llama.
PERF_SCENARIOS = ("nlp_example", "lm_example", "cv_example", "llama2b")

# `atx lint memory`: the ATX7xx HBM-timeline set (`make lint-memory`) —
# the perf scenarios plus the serving scenario, whose ATX706 capacity
# plan feeds the serve_static_max_slots budget series.
MEMORY_SCENARIOS = PERF_SCENARIOS + ("serving",)


# ----------------------------------------------- multi-host (ATX5xx) scenarios
# Host-side loops replayed under N simulated processes via
# `analysis.lint_host_loop` — these verify the COLLECTIVE SCHEDULE (barrier /
# commit / broadcast ordering across processes), not the compiled step.
# Builders take `processes` and return (description, Report).


def _mh_scenario_save_path(processes: int = 2):
    """checkpointing.save_state: train one step then save synchronously,
    then another step and an ASYNC save — the precommit markers, commit
    barrier, and final-dir broadcast must issue an identical collective
    schedule on every process, in both save modes (the replay models the
    async writer by running the submitted job inline, so its precommit
    file-barrier schedule is checked too)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import analysis, checkpointing
    from ..accelerator import Accelerator, TrainState
    from ..state import AcceleratorState
    from ..utils.dataclasses import ProjectConfiguration

    def save_loop():
        AcceleratorState._reset_state()
        root = tempfile.mkdtemp(prefix="atx_lint_mh_save_")
        acc = Accelerator(
            seed=0,
            project_config=ProjectConfiguration(
                project_dir=root, automatic_checkpoint_naming=True
            ),
        )
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)}
        state = acc.prepare_train_state(
            TrainState.create(params=params, tx=optax.sgd(1e-2))
        )
        step = acc.make_train_step(
            lambda p, b, r=None: jnp.mean((b["x"] @ p["w"]) ** 2)
        )
        state, _ = step(state, {"x": np.ones((8, 8), np.float32)})
        checkpointing.save_state(acc, None, state, async_save=False)
        state, _ = step(state, {"x": np.ones((8, 8), np.float32)})
        checkpointing.save_state(acc, None, state, async_save=True)
        checkpointing.wait_for_checkpoint()

    report = analysis.lint_host_loop(
        save_loop, processes=processes, target="save_path"
    )
    return (
        f"train step + sync save_state + async save_state, "
        f"{processes} processes",
        report,
    )


def _mh_scenario_preemption_exit(processes: int = 2):
    """Emergency-save path: one process gets the preemption notice; the
    group must still agree (or-reduce) before the synchronized emergency
    checkpoint + exit — the schedule every process runs must match."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import analysis
    from ..accelerator import Accelerator, TrainState
    from ..state import AcceleratorState
    from ..utils.dataclasses import ProjectConfiguration

    def train_loop():
        AcceleratorState._reset_state()
        root = tempfile.mkdtemp(prefix="atx_lint_mh_preempt_")
        acc = Accelerator(
            seed=0,
            project_config=ProjectConfiguration(
                project_dir=root, automatic_checkpoint_naming=True
            ),
        )
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)}
        state = acc.prepare_train_state(
            TrainState.create(params=params, tx=optax.sgd(1e-2))
        )
        step = acc.make_train_step(
            lambda p, b, r=None: jnp.mean((b["x"] @ p["w"]) ** 2)
        )
        batch = {"x": np.ones((8, 8), np.float32)}
        for _ in range(3):
            state, _ = step(state, batch)

    report = analysis.lint_host_loop(
        train_loop,
        processes=processes,
        preempted=[0],
        target="preemption_exit",
    )
    return (
        f"preemption notice on process 0 of {processes} — emergency save + exit",
        report,
    )


def _mh_scenario_router_drain(processes: int = 2):
    """serving.Router drain + failover host loop (the ROADMAP follow-up
    for serving's multi-host dispatch): a 2-replica inline router serves a
    small trace while replica 0 is fault-injected dead mid-trace and a
    preemption notice arrives — the dispatch/flag schedule every process
    replays must stay identical (deterministic inline routing), and the
    drain must finish every accepted request."""
    from .. import analysis

    def router_loop():
        import jax
        import numpy as np

        from .. import resilience
        from ..generation import GenerationConfig
        from ..models import llama
        from ..serving import Engine, Request, Router
        from ..test_utils import faults
        from ..utils.environment import patch_environment

        config = llama.LlamaConfig.tiny(vocab_size=64, max_seq_len=64)
        params = llama.init(jax.random.PRNGKey(0), config)

        def mk_engine() -> Engine:
            return Engine(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config),
                lambda b, m: llama.init_cache(config, b, m),
                params,
                GenerationConfig(
                    max_new_tokens=4, eos_token_id=None, pad_token_id=0
                ),
                slots=2,
                buckets=(8,),
                max_len=32,
                prefix_cache=False,
            )

        rng = np.random.RandomState(0)
        reqs = [
            Request(prompt=rng.randint(1, 64, (6,)).astype(np.int32), rid=i)
            for i in range(4)
        ]
        faults._reset_counters()  # the @N counter must restart per process
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@2"):
            router = Router([mk_engine(), mk_engine()], threads=False)
            for r in reqs:
                router.submit_request(r)
            for _ in range(3):  # replica 0 dies on its second pumped step
                router.poll()
            resilience.request_preemption()
            out = router.join()
            router.close()
        assert len(out) == len(reqs), f"drain lost requests: {len(out)}"
        assert router.draining and router.drain_reason == "preemption"
        assert router.stats["replicas_lost"] == 1

    report = analysis.lint_host_loop(
        router_loop, processes=processes, target="router_drain"
    )
    return (
        f"2-replica router, replica-0 fault + preemption drain, "
        f"{processes} processes",
        report,
    )


def _mh_scenario_replicated_save(processes: int = 2):
    """checkpointing.save_state WITH checkpoint replication enabled
    (ATX_REPLICATE_URL): the collective schedule must be IDENTICAL to the
    plain save path — replication is queue + background object IO on the
    committing process only, so turning it on must add zero collectives
    (the acceptance gate for resilience/replicate.py). The loop also
    drains the replicator and asserts the committing process actually
    uploaded a remote-committed checkpoint."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import analysis, checkpointing
    from ..accelerator import Accelerator, TrainState
    from ..state import AcceleratorState
    from ..utils.dataclasses import ProjectConfiguration
    from ..utils.environment import patch_environment

    def replicated_save_loop():
        AcceleratorState._reset_state()
        root = tempfile.mkdtemp(prefix="atx_lint_mh_repl_")
        store_root = tempfile.mkdtemp(prefix="atx_lint_mh_repl_store_")
        with patch_environment(ATX_REPLICATE_URL=store_root):
            acc = Accelerator(
                seed=0,
                project_config=ProjectConfiguration(
                    project_dir=root, automatic_checkpoint_naming=True
                ),
            )
            assert acc._replicator is not None, "replication did not arm"
            params = {
                "w": jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)
            }
            state = acc.prepare_train_state(
                TrainState.create(params=params, tx=optax.sgd(1e-2))
            )
            step = acc.make_train_step(
                lambda p, b, r=None: jnp.mean((b["x"] @ p["w"]) ** 2)
            )
            state, _ = step(state, {"x": np.ones((8, 8), np.float32)})
            checkpointing.save_state(acc, None, state, async_save=False)
            assert acc._replicator.drain(60.0), "replication queue stuck"
            if jax.process_index() == 0:
                from ..resilience import replicate

                assert acc._replicator.failures == 0, acc._replicator.last_error
                remote = replicate.remote_committed_checkpoints(
                    acc._replicator.store
                )
                assert remote, "committing process uploaded no remote commit"

    report = analysis.lint_host_loop(
        replicated_save_loop, processes=processes, target="replicated_save"
    )
    return (
        f"train step + synchronous save_state with replication armed, "
        f"{processes} processes",
        report,
    )


def _mh_scenario_elastic_restore(processes: int = 2):
    """Elastic reshard-on-restore: save a committed checkpoint, doctor its
    recorded topology signature so the restore sees a world-size mismatch,
    then ``load_state(resume="latest")``. The whole restore — discovery,
    verification, topology detection, peer-shard coverage probing, shard
    assembly — must be COLLECTIVE-FREE (sentinel polling + file IO only):
    a SMALLER surviving group restores without the dead ranks, so any
    collective here would hang the resume. The replay pins exactly that:
    zero new collective-log events between save and restored state."""
    import json as _json
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import analysis, checkpointing
    from ..accelerator import Accelerator, TrainState
    from ..state import AcceleratorState
    from ..utils.dataclasses import ProjectConfiguration

    # ONE root shared by every simulated process (and every replay round):
    # the save path broadcasts process 0's directory choice, so per-process
    # roots would leave process 1's own root empty at restore time. Rounds
    # just stack checkpoint_<n> dirs; names never enter event signatures.
    root = tempfile.mkdtemp(prefix="atx_lint_mh_elastic_")

    def restore_loop():
        AcceleratorState._reset_state()
        # save_on_each_node: each simulated process commits a self-contained
        # checkpoint (the per-node-filesystem shape), so whichever process
        # committed last, the directory it restores from is complete.
        acc = Accelerator(
            seed=0,
            project_config=ProjectConfiguration(
                project_dir=root,
                automatic_checkpoint_naming=True,
                save_on_each_node=True,
            ),
        )
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)}
        state = acc.prepare_train_state(
            TrainState.create(params=params, tx=optax.sgd(1e-2))
        )
        step = acc.make_train_step(
            lambda p, b, r=None: jnp.mean((b["x"] @ p["w"]) ** 2)
        )
        state, _ = step(state, {"x": np.ones((8, 8), np.float32)})
        final_dir = checkpointing.save_state(acc, None, state, async_save=False)
        # Doctor the recorded topology (num_devices) so the restore takes
        # the elastic mismatch path — detection, coverage probe and all.
        from ..resilience.commit import COMMIT_MARKER

        marker = os.path.join(final_dir, COMMIT_MARKER)
        with open(marker) as f:
            meta = _json.load(f)
        meta["num_devices"] = int(meta.get("num_devices") or 1) * 2
        with open(marker, "w") as f:
            _json.dump(meta, f)
        from ..analysis import host_trace

        rec = host_trace._ACTIVE_RECORDER
        before = len(rec.collective_events) if rec is not None else None
        restored = checkpointing.load_state(acc, None, state, resume="latest")
        if rec is not None:
            grew = len(rec.collective_events) - before
            assert grew == 0, (
                f"elastic restore issued {grew} collective(s); the restore "
                "path must stay collective-free so a smaller surviving "
                "group can resume without the dead ranks"
            )
        assert int(jax.device_get(restored.step)) == int(
            jax.device_get(state.step)
        ), "restore returned the wrong step"

    report = analysis.lint_host_loop(
        restore_loop, processes=processes, target="elastic_restore"
    )
    return (
        f"committed save + topology-mismatched resume='latest' restore "
        f"(must add zero collectives), {processes} processes",
        report,
    )


def _mh_scenario_shrink(processes: int = 2):
    """Shrink-in-place (resilience/elastic.py): a devices-file retarget
    escalates at a step boundary, survivors run the agreement round, and
    the accelerator reshards params/opt-state/step in memory onto the
    smaller mesh — then keeps training. The whole escalate -> agree ->
    reshard window must be COLLECTIVE-FREE (proposal/decision objects +
    file IO only): in a real shrink the departed peer is dead, and any
    collective in this window would park the survivors forever. The replay
    pins exactly that, plus identical post-shrink schedules across the
    surviving processes (the ATX501/502/503 gates)."""
    import math
    import tempfile

    import jax

    from .. import analysis
    from ..resilience import elastic as _elastic

    total = jax.device_count()
    host = total // processes if processes else 0
    if host < 2 or total % processes != 0:
        raise RuntimeError(
            f"the shrink scenario needs >= 2 simulated devices per process "
            f"(got {total} device(s) for {processes} process(es)); run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    new_host = host - 1
    new_total = processes * new_host
    # Batch rows must divide the data axis both before and after the shrink
    # (constrain_batch binds activations to the mesh).
    rows = math.lcm(total, new_total)

    # ONE root shared by every simulated process and every replay round:
    # the agreement surface is how the survivors see each other. The
    # devices file, peer proposals, and decision are seeded ONCE up front —
    # the replay runs simulated processes SEQUENTIALLY, so a blocking
    # follower could never observe a live coordinator; pre-seeding plus the
    # coordinator's idempotent decision write make every round converge on
    # identical bytes.
    root = tempfile.mkdtemp(prefix="atx_lint_mh_shrink_")
    edir = os.path.join(root, "elastic")
    dfile = os.path.join(root, "devices")
    with open(dfile, "w") as f:
        f.write(f"{processes} {new_host}\n")
    decision = _elastic.TopologyDecision(
        epoch=1,
        survivors=tuple(range(processes)),
        host_devices=new_host,
        step=0,
    )
    surface = _elastic._FileSurface(edir)
    _elastic.post_peer_proposals(surface, range(processes), decision)
    surface.write(_elastic.DECISION_FILE.format(epoch=1), decision.to_payload())

    env = {
        "ATX_ELASTIC_SHRINK": "1",
        "ATX_ELASTIC_DIR": edir,
        "ATX_ELASTIC_DEVICES_FILE": dfile,
        "ATX_ELASTIC_AGREE_SECS": "5",
    }

    def shrink_loop():
        import jax.numpy as jnp
        import numpy as np
        import optax

        from ..accelerator import Accelerator, TrainState
        from ..analysis import host_trace
        from ..state import AcceleratorState

        AcceleratorState._reset_state()
        acc = Accelerator(seed=0)
        assert acc._elastic is not None, "elastic controller did not arm"
        params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)}
        state = acc.prepare_train_state(
            TrainState.create(params=params, tx=optax.sgd(1e-2))
        )
        step = acc.make_train_step(
            lambda p, b, r=None: jnp.mean((b["x"] @ p["w"]) ** 2)
        )
        rec = host_trace._ACTIVE_RECORDER
        before = len(rec.collective_events) if rec is not None else None
        resized = acc._maybe_elastic_resize(state, 0)
        if rec is not None:
            grew = len(rec.collective_events) - before
            assert grew == 0, (
                f"shrink agreement+reshard issued {grew} collective(s); the "
                "escalate -> agree -> reshard window must stay collective-"
                "free — the departed peer is dead and would park any "
                "collective forever"
            )
        assert resized is not None, "in-place shrink did not engage"
        assert acc.mesh.devices.size == new_total, (
            f"mesh has {acc.mesh.devices.size} devices after shrink, "
            f"wanted {new_total}"
        )
        state = resized
        batch = {"x": np.ones((rows, 8), np.float32)}
        state, _ = step(state, batch)
        state, _ = step(state, batch)
        assert int(jax.device_get(state.step)) == 2, "post-shrink steps lost"
        assert acc.mesh.devices.size == new_total, "mesh reverted after steps"

    report = analysis.lint_host_loop(
        shrink_loop, processes=processes, env=env, target="shrink"
    )
    return (
        f"live shrink-in-place: devices-file retarget {total} -> {new_total} "
        f"devices, collective-free agree + in-memory reshard + resumed "
        f"steps, {processes} processes",
        report,
    )


def _mh_scenario_telemetry(processes: int = 2):
    """Runtime telemetry (telemetry/): train steps with ATX_METRICS=1 plus
    the cross-host export path — per-process snapshot write, proc-0 merge,
    Prometheus render — must add ZERO collectives to the step schedule
    (PR-11 shared-surface rule: metrics travel as files, never as
    collectives; a collective here would park survivors when a peer dies
    mid-step). The replay also pins the schedule identical across
    processes with metrics armed (the ATX5xx gates)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from .. import analysis
    from ..accelerator import Accelerator, TrainState
    from ..state import AcceleratorState
    from ..utils.environment import patch_environment

    def telemetry_loop():
        from .. import telemetry
        from ..analysis import host_trace

        AcceleratorState._reset_state()
        snap_dir = tempfile.mkdtemp(prefix="atx_lint_mh_tel_")
        with patch_environment(
            ATX_METRICS="1", ATX_METRICS_SAMPLE_EVERY="2"
        ):
            acc = Accelerator(seed=0)
            params = {
                "w": jax.random.normal(jax.random.PRNGKey(0), (8, 8), jnp.float32)
            }
            state = acc.prepare_train_state(
                TrainState.create(params=params, tx=optax.sgd(1e-2))
            )
            step = acc.make_train_step(
                lambda p, b, r=None: jnp.mean((b["x"] @ p["w"]) ** 2)
            )
            batch = {"x": np.ones((8, 8), np.float32)}
            for _ in range(3):
                state, _ = step(state, batch)
            assert step.step_stats is not None, "ATX_METRICS=1 did not arm"
            assert step.step_stats.steps == 3
            # The export surface is pure file IO + host math: pin the
            # collective count across it.
            rec = host_trace._ACTIVE_RECORDER
            before = len(rec.collective_events) if rec is not None else 0
            telemetry.write_snapshot(snap_dir, process_index=0)
            telemetry.write_snapshot(snap_dir, process_index=1)
            merged = telemetry.aggregate_snapshots(snap_dir)
            text = telemetry.render_snapshot_prometheus(merged)
            after = len(rec.collective_events) if rec is not None else 0
            assert after == before, (
                f"telemetry export added {after - before} collective(s)"
            )
            # Two identical snapshots merged: counters double, gauges
            # reduce — the cross-host invariant the fleet endpoint serves.
            def _val(snap, name):
                for entry in snap["metrics"]:
                    if entry["name"] == name:
                        return entry["series"][0]["value"]
                raise AssertionError(f"{name} missing from snapshot")

            local = telemetry.snapshot()
            assert _val(merged, "train_steps") == 2 * _val(
                local, "train_steps"
            ), "cross-host counter merge did not sum"
            assert "train_steps" in text and "# TYPE" in text

    report = analysis.lint_host_loop(
        telemetry_loop, processes=processes, target="telemetry"
    )
    return (
        f"3 train steps with ATX_METRICS=1 + snapshot write/merge/render, "
        f"{processes} processes",
        report,
    )


def _mh_scenario_router_recovery(processes: int = 2):
    """Self-healing router path (docs/serving.md): quarantine ->
    prefix-cache migration -> probation probe -> re-admission is pure host
    logic plus single-replica device steps, so it must add ZERO collectives
    to the schedule (a collective inside recovery would park every healthy
    process on the dead peer), and the recovery schedule every process
    replays must be identical."""
    from .. import analysis

    def recovery_loop():
        import time as _time

        import jax
        import numpy as np

        from ..analysis import host_trace
        from ..generation import GenerationConfig
        from ..models import llama
        from ..serving import Engine, Request, Router
        from ..test_utils import faults
        from ..utils.environment import patch_environment

        config = llama.LlamaConfig.tiny(vocab_size=64, max_seq_len=64)
        params = llama.init(jax.random.PRNGKey(0), config)

        def mk_engine() -> Engine:
            return Engine(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config),
                lambda b, m: llama.init_cache(config, b, m),
                params,
                GenerationConfig(
                    max_new_tokens=4, eos_token_id=None, pad_token_id=0
                ),
                slots=2,
                buckets=(8,),
                max_len=32,
                prefix_cache=True,
            )

        rng = np.random.RandomState(0)
        prefix = rng.randint(1, 64, (8,)).astype(np.int32)

        def req(i):
            tail = rng.randint(1, 64, (2,)).astype(np.int32)
            return Request(prompt=np.concatenate([prefix, tail]), rid=i)

        engines = [mk_engine(), mk_engine()]
        # Warm replica 0's prefix cache (and both compile caches) OUTSIDE
        # the router so quarantine deterministically has a hot committed
        # prefix to migrate.
        for eng in engines:
            eng.submit(np.concatenate([prefix, np.asarray([1, 2], np.int32)]), 2)
            eng.run_until_idle()
        reqs = [req(i) for i in range(4)]
        faults._reset_counters()  # the @N counter must restart per process
        rec = host_trace._ACTIVE_RECORDER

        def n_collectives() -> int:
            # Jitted single-replica dispatches (canary replay, migration
            # warm-ups) are aligned schedule events but not cross-process
            # traffic; the recovery ban is on TRUE collectives.
            if rec is None:
                return 0
            return sum(1 for e in rec.collective_events if e.kind != "dispatch")

        before = n_collectives()
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@2"):
            router = Router(
                engines,
                threads=False,
                readmit_secs=0.001,
                probation_completions=1,
                engine_factory=lambda _replica: mk_engine(),
            )
            for r in reqs:
                router.submit_request(r)
            out = router.join()
            deadline = _time.time() + 30.0
            while int(router.metrics()["readmissions"]) < 1:
                assert _time.time() < deadline, "no re-admission within 30s"
                router.poll(0.002)
            router.close()
        after = n_collectives()
        m = router.metrics()
        assert len(out) == len(reqs), f"recovery lost requests: {len(out)}"
        assert m["replicas_lost"] == 1 and m["readmissions"] >= 1, m
        assert m["migrated_prefixes"] >= 1, m
        assert m["replicas_alive"] == 2, m
        assert after == before, (
            f"quarantine/probe/readmit/migration added {after - before} "
            "collective(s)"
        )

    report = analysis.lint_host_loop(
        recovery_loop, processes=processes, target="router_recovery"
    )
    return (
        f"2-replica router: replica-0 fault, prefix migration, probe + "
        f"re-admission, {processes} processes",
        report,
    )


def _mh_scenario_tracing(processes: int = 2):
    """Request-scoped tracing (telemetry/flight.py): a full 2-replica serve
    pass with ATX_TRACE_REQUESTS=1 — admission/dispatch spans, prefix
    match, prefill chunks, decode residency, stream + completion, and a
    postmortem bundle dump — must add ZERO collectives to the schedule
    (spans are host dicts in a preallocated ring; a collective here would
    couple request latency to peer health), and greedy outputs must be
    bit-identical to the same trace served with tracing off."""
    from .. import analysis

    def tracing_loop():
        import tempfile

        import jax
        import numpy as np

        from ..analysis import host_trace
        from ..generation import GenerationConfig
        from ..models import llama
        from ..serving import Engine, Request, Router
        from ..telemetry import flight
        from ..utils.environment import patch_environment

        config = llama.LlamaConfig.tiny(vocab_size=64, max_seq_len=64)
        params = llama.init(jax.random.PRNGKey(0), config)

        def mk_engine() -> Engine:
            return Engine(
                lambda p, t, c: llama.forward_with_cache(p, t, c, config),
                lambda b, m: llama.init_cache(config, b, m),
                params,
                GenerationConfig(
                    max_new_tokens=4, eos_token_id=None, pad_token_id=0
                ),
                slots=2,
                buckets=(8,),
                max_len=32,
                prefix_cache=True,
            )

        def trace_reqs() -> list[Request]:
            rng = np.random.RandomState(1)
            return [
                Request(prompt=rng.randint(1, 64, (6,)).astype(np.int32), rid=i)
                for i in range(4)
            ]

        def serve_once() -> dict[int, np.ndarray]:
            router = Router([mk_engine(), mk_engine()], threads=False)
            for r in trace_reqs():
                router.submit_request(r)
            out = {c.rid: c.tokens.copy() for c in router.join()}
            router.close()
            return out

        base = serve_once()  # tracing off: the bit-identity reference
        rec = host_trace._ACTIVE_RECORDER

        def n_collectives() -> int:
            if rec is None:
                return 0
            return sum(1 for e in rec.collective_events if e.kind != "dispatch")

        before = n_collectives()
        with patch_environment(ATX_TRACE_REQUESTS="1"):
            flight.reset_recorder()
            traced = serve_once()
            pm_dir = tempfile.mkdtemp(prefix="atx_lint_pm_")
            path = flight.dump_postmortem("lint_tracing", pm_dir)
            assert path is not None, "postmortem dump returned no path"
            bundle = flight.read_bundle(path)
            assert bundle["spans"], "flight recorder captured no spans"
        after = n_collectives()
        names = {e["name"] for e in flight.recorder().last()}
        for want in (
            "admission", "dispatch", "prefix_match", "prefill_chunk",
            "phase_decode", "stream", "complete",
        ):
            assert want in names, f"missing span {want!r}: {sorted(names)}"
        for rid, toks in base.items():
            assert np.array_equal(toks, traced[rid]), (
                f"rid {rid} diverged with ATX_TRACE_REQUESTS=1"
            )
        assert after == before, (
            f"request tracing added {after - before} collective(s)"
        )

    report = analysis.lint_host_loop(
        tracing_loop, processes=processes, target="tracing"
    )
    return (
        f"2-replica traced serve vs untraced bit-identity + postmortem "
        f"bundle, {processes} processes",
        report,
    )


MULTIHOST_SCENARIOS: dict[str, Callable[..., tuple[str, Any]]] = {
    "save_path": _mh_scenario_save_path,
    "preemption_exit": _mh_scenario_preemption_exit,
    "router_drain": _mh_scenario_router_drain,
    "router_recovery": _mh_scenario_router_recovery,
    "replicated_save": _mh_scenario_replicated_save,
    "elastic_restore": _mh_scenario_elastic_restore,
    "shrink": _mh_scenario_shrink,
    "telemetry": _mh_scenario_telemetry,
    "tracing": _mh_scenario_tracing,
}


def _examples_dir():
    from pathlib import Path

    return Path(__file__).resolve().parents[2] / "examples"


def resolve_targets(
    targets: list[str], multihost: bool = False
) -> tuple[list[str], list[str]]:
    """Map CLI targets (scenario names / example files / directories) to
    scenario names; second element is the unmatched remainder. Multi-host
    scenario names always resolve when given explicitly; ``multihost``
    adds them to the no-target default set."""
    known = {**SCENARIOS, **MULTIHOST_SCENARIOS}
    if not targets:
        names = list(SCENARIOS)
        if multihost:
            names += list(MULTIHOST_SCENARIOS)
        return names, []
    names: list[str] = []
    unmatched: list[str] = []
    for t in targets:
        stem = os.path.splitext(os.path.basename(t.rstrip("/")))[0]
        if t == "perf":
            names.extend(PERF_SCENARIOS)
        elif t == "memory":
            names.extend(MEMORY_SCENARIOS)
        elif t in known:
            names.append(t)
        elif os.path.isdir(t):
            found = [
                os.path.splitext(f)[0]
                for f in sorted(os.listdir(t))
                if os.path.splitext(f)[0] in known and f.endswith(".py")
            ]
            if found:
                names.extend(found)
            else:
                unmatched.append(t)
        elif stem in known:
            names.append(stem)
        else:
            unmatched.append(t)
    # de-dup, keep order
    seen: set[str] = set()
    names = [n for n in names if not (n in seen or seen.add(n))]
    return names, unmatched


def run(args: argparse.Namespace) -> int:
    if args.host_devices and "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.host_devices}"
            )

    from ..analysis import Severity, registered_rules

    if args.rules:
        for spec in registered_rules():
            print(f"{spec.rule_id} [{spec.severity}] ({spec.family}) {spec.summary}")
            if spec.fix_hint:
                print(f"    fix: {spec.fix_hint}")
        return 0
    if args.list:
        for name, builder in SCENARIOS.items():
            print(f"{name}: {builder.__doc__.splitlines()[0]}")
        for name, builder in MULTIHOST_SCENARIOS.items():
            print(f"{name} [multihost]: {builder.__doc__.splitlines()[0]}")
        return 0

    procs = int(args.multihost or 0)
    names, unmatched = resolve_targets(args.targets, multihost=procs >= 2)
    if unmatched:
        print(
            f"lint: no scenario registered for {unmatched} "
            f"(known: {', '.join(list(SCENARIOS) + list(MULTIHOST_SCENARIOS))}); "
            "register one in accelerate_tpu/commands/lint.py:SCENARIOS",
            file=sys.stderr,
        )
        return 2

    gate = Severity.parse(args.severity)
    show = Severity.parse(args.show)
    failed = False
    json_reports = []
    measured_series: dict[str, Any] = {}
    scenario_kw: dict[str, Any] = {}
    if getattr(args, "chip", None):
        scenario_kw["roofline_chip"] = args.chip
    for name in names:
        if name in MULTIHOST_SCENARIOS:
            desc, report = MULTIHOST_SCENARIOS[name](processes=max(procs, 2))
        elif procs >= 2:
            desc, report = SCENARIOS[name](processes=procs, **scenario_kw)
        else:
            desc, report = SCENARIOS[name](**scenario_kw)
        if args.budgets or args.write_budgets:
            from ..analysis import perf_budget

            measured_series[name] = perf_budget.extract_series(report)
        if report.filter(gate):
            failed = True
        if args.json_lines:
            for finding in report.filter(show):
                d = finding.to_dict()
                d["scenario"] = name
                d["target"] = report.target or name
                print(json.dumps(d, sort_keys=True))
        elif args.fmt == "json":
            d = report.to_dict()
            d["scenario"] = name
            d["description"] = desc
            json_reports.append(d)
        else:
            print(f"== {report.target or name} — {desc}")
            print(f"   {report.format(show)}".replace("\n", "\n   "))
    budget_failed = False
    if args.budgets:
        from ..analysis import perf_budget

        problems = perf_budget.check_budgets(
            perf_budget.load_budgets(args.budgets), measured_series
        )
        for problem in problems:
            print(f"lint budget: {problem}", file=sys.stderr)
        if problems:
            budget_failed = True
        else:
            print(
                f"lint budget: ratchet holds for "
                f"{len(perf_budget.load_budgets(args.budgets))} scenario(s)"
            )
    if args.write_budgets:
        from ..analysis import perf_budget

        series = {k: v for k, v in measured_series.items() if v}
        perf_budget.write_budgets(args.write_budgets, series)
        print(
            f"lint budget: wrote {args.write_budgets} "
            f"({len(series)} scenario(s))"
        )
    if args.json_lines:
        pass  # JSON-lines streams findings only; exit code carries the gate
    elif args.fmt == "json":
        print(json.dumps({"reports": json_reports}, indent=2))
    elif failed:
        print(f"\nlint: findings at/above severity '{gate}' — failing")
    else:
        print(f"\nlint: no findings at/above severity '{gate}'")
    return 1 if failed or budget_failed else 0
