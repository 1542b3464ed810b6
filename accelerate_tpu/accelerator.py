"""The `Accelerator` facade — the framework's single user-facing entry point.

TPU-native redesign of the reference `Accelerator` (`accelerator.py:175`,
3,769 LoC). The reference rewrites torch objects so an eager loop becomes
distributed; here "prepare" means **build mesh + shardings + one jitted train
step over sharded pytrees** (SURVEY.md §7 design stance). The reference's
training-loop choreography —

    with accelerator.accumulate(model):
        out = model(batch); accelerator.backward(loss)
        accelerator.clip_grad_norm_(...); optimizer.step(); scheduler.step()

— collapses into `state, metrics = train_step(state, batch)` where the step
internally: scans over microbatches (grad accumulation, `accelerator.py:1116`
`accumulate`), casts to the compute dtype (autocast, :1462-1473), clips by
global norm (`clip_grad_norm_` :2485), applies the optax update (optimizer
step + LR schedule), and lets GSPMD insert the gradient reductions that DDP's
C++ reducer performed (:1519-1544).

Capability parity index (reference `accelerator.py` line refs):
- prepare                      :1283  -> `prepare` / `prepare_data_loader` /
                                         `create_train_state` / `make_train_step`
- accumulate/no_sync           :1116  -> `gradient_accumulation_steps` (scan)
- backward                     :2357  -> inside the jitted step
- clip_grad_norm_              :2485  -> `max_grad_norm` / clipping in-step
- clip_grad_value_             :2523  -> `max_grad_value` elementwise clamp in-step
- gather/gather_for_metrics    :2569/:2601 -> `gather` / `gather_for_metrics`
- reduce/pad_across_processes  :2704/:2679 -> re-exported ops
- unwrap_model                 :2745  -> `unwrap` (identity on pytrees)
- save/load_state              :3106/:3272 -> checkpointing milestone
- autocast                     :3587  -> `MixedPrecisionPolicy`
- free_memory                  :3412  -> `free_memory`
- trigger flags                :2391  -> `set_trigger`/`check_trigger`
- join_uneven_inputs           :1161  -> not needed: even_batches wraparound
                                         keeps SPMD steps uniform by design
"""

from __future__ import annotations

import gc
import os
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .data.loader import DataLoader
from .ops import collectives as _ops
from .ops import fp8 as _fp8
from .parallel.mesh import (
    BATCH_AXES,
    TENSOR_AXIS,
    MeshConfig,
    batch_sharding,
    build_mesh,
    data_parallel_size,
    resize_mesh_config,
    topology_signature,
    use_mesh,
)
from .parallel.sharding import (
    ShardingStrategy,
    infer_opt_specs,
    infer_param_specs,
    shard_pytree,
    to_named_shardings,
)
from .state import AcceleratorState, GradientState, ProcessState
from .utils.dataclasses import (
    DataLoaderConfiguration,
    GradientAccumulationPlugin,
    MixedPrecisionPolicy,
    ProjectConfiguration,
)
from .utils.random import set_seed as _set_seed


def _warn_fp8_noop() -> None:
    """mixed_precision='fp8' only has an effect for models whose projections
    route through `matmul_einsum` (the in-repo model zoo does; arbitrary user
    models may not). Runs at trace time, so it fires once per compilation."""
    import warnings

    warnings.warn(
        "mixed_precision='fp8' had no effect: the traced loss_fn never routed "
        "a matmul through accelerate_tpu.models.layers.matmul_einsum, so the "
        "whole step ran in bf16. Use the in-repo model layers (or call "
        "matmul_einsum for your projections) to get real fp8 matmuls.",
        stacklevel=2,
    )


class NonFiniteGuardError(RuntimeError):
    """``ATX_NAN_GUARD`` ran out of patience: the training step produced a
    non-finite loss or gradients for ``ATX_NAN_GUARD_MAX_CONSECUTIVE``
    consecutive steps. Each bad step's optimizer update was *skipped* inside
    the compiled step (params/opt-state untouched), so the state this error
    leaves behind is the last finite one — checkpoint it, lower the LR /
    inspect the data, and resume. A budget-exceeded streak almost always
    means divergence, not a transient batch."""


_UNPINNED_WARNED: set[str] = set()


def _warn_unpinned_once(message: str) -> None:
    """Trace-time warning for the silent-fallback paths in the train step's
    output pinning (ADVICE r3: a skipped pin reintroduces the ZERO1
    recompile/layout drift with no signal). Once per distinct reason."""
    import warnings

    if message not in _UNPINNED_WARNED:
        _UNPINNED_WARNED.add(message)
        warnings.warn(message, stacklevel=3)


class DynamicLossScale(struct.PyTreeNode):
    """fp16 dynamic loss-scale state — the GradScaler analog (reference
    `utils/modeling.py:2054` `get_grad_scaler` + overflow-skip in
    `optimizer.py:162-176`), carried functionally inside :class:`TrainState`
    so the whole scaler lives in the compiled step.

    Semantics per step: grads are taken of ``loss * scale`` and unscaled;
    if any gradient is non-finite the parameter/optimizer update is skipped
    and ``scale *= backoff_factor``; after ``growth_interval`` consecutive
    finite steps ``scale *= growth_factor``.
    """

    scale: jax.Array  # f32 scalar
    growth_counter: jax.Array  # i32 scalar
    growth_factor: float = struct.field(pytree_node=False, default=2.0)
    backoff_factor: float = struct.field(pytree_node=False, default=0.5)
    growth_interval: int = struct.field(pytree_node=False, default=2000)

    @classmethod
    def create(cls, init_scale: float = 2.0**15, **kwargs: Any) -> "DynamicLossScale":
        return cls(
            scale=jnp.asarray(init_scale, jnp.float32),
            growth_counter=jnp.zeros((), jnp.int32),
            **kwargs,
        )


class TrainState(struct.PyTreeNode):
    """Functional train state: the pytree the jitted step transforms.

    Mirrors `flax.training.train_state.TrainState` in shape; owned by the
    framework so sharding/checkpoint logic controls its layout.
    ``loss_scale`` is None except under fp16 mixed precision (None is an
    empty pytree node, so every existing path is unaffected).
    """

    step: jax.Array
    params: Any
    opt_state: Any
    apply_fn: Callable = struct.field(pytree_node=False, default=None)
    tx: optax.GradientTransformation = struct.field(pytree_node=False, default=None)
    loss_scale: Any = None

    @classmethod
    def create(cls, *, params: Any, tx: optax.GradientTransformation, apply_fn: Callable | None = None) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            apply_fn=apply_fn,
            tx=tx,
        )


def _specs_equal(a: Any, b: Any) -> bool:
    """Leaf-wise PartitionSpec equality between two spec trees (is_leaf
    guard because PartitionSpec is tuple-like and would be flattened into
    its entries otherwise). Used to verify an elastic mesh resize preserves
    every leaf's layout."""
    is_spec = lambda x: isinstance(x, PartitionSpec)  # noqa: E731
    la = jax.tree_util.tree_flatten(a, is_leaf=is_spec)[0]
    lb = jax.tree_util.tree_flatten(b, is_leaf=is_spec)[0]
    return len(la) == len(lb) and all(x == y for x, y in zip(la, lb))


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


class Accelerator:
    """Single entry point: mesh + shardings + compiled SPMD train step."""

    def __init__(
        self,
        *,
        mixed_precision: str | None = None,  # None -> ATX_MIXED_PRECISION env or "no"
        gradient_accumulation_steps: int = 1,
        gradient_accumulation_plugin: GradientAccumulationPlugin | None = None,
        mesh_config: MeshConfig | None = None,
        strategy: Any = None,
        sharding_rules: Sequence[tuple[str, PartitionSpec]] = (),
        max_grad_norm: float | None = None,
        max_grad_value: float | None = None,
        loss_scale_config: dict[str, Any] | None = None,
        dataloader_config: DataLoaderConfiguration | None = None,
        project_config: ProjectConfiguration | None = None,
        project_dir: str | None = None,
        log_with: Any = None,
        seed: int | None = None,
    ) -> None:
        from .utils.dataclasses import TensorParallelPlugin

        if isinstance(strategy, TensorParallelPlugin) and (strategy.tp_size or 1) > 1:
            # The plugin's tp_size is a mesh request: build (or validate) a
            # mesh whose `tensor` axis matches it, the way the reference's TP
            # plugin sizes its device sub-group (`utils/dataclasses.py:1863`).
            if mesh_config is None and MeshConfig.from_env() is None:
                mesh_config = MeshConfig(tensor=strategy.tp_size)
        self.state = AcceleratorState(mesh_config=mesh_config, mixed_precision=mixed_precision)
        if (
            isinstance(strategy, TensorParallelPlugin)
            and (strategy.tp_size or 1) > 1
            and self.state.mesh.shape[TENSOR_AXIS] != strategy.tp_size
        ):
            raise ValueError(
                f"TensorParallelPlugin(tp_size={strategy.tp_size}) does not "
                f"match the active mesh's tensor axis "
                f"({self.state.mesh.shape[TENSOR_AXIS]}); size the mesh's "
                "`tensor` axis to tp_size (MeshConfig(tensor=...) / "
                "ATX_MESH_TENSOR)."
            )
        self.process_state = ProcessState()
        if gradient_accumulation_plugin is None:
            gradient_accumulation_plugin = GradientAccumulationPlugin(
                num_steps=gradient_accumulation_steps if gradient_accumulation_steps > 1 else None
            )
        self.gradient_state = GradientState(gradient_accumulation_plugin.num_steps)
        self.gradient_accumulation_plugin = gradient_accumulation_plugin
        self.policy = MixedPrecisionPolicy.from_precision(self.state.mixed_precision)
        if strategy is None:
            # Launcher env contract (ATX_SHARDING_STRATEGY) fallback.
            import os

            strategy = os.environ.get("ATX_SHARDING_STRATEGY") or None
            if strategy in ("DATA_PARALLEL",):
                strategy = None  # the default; avoid requiring rules
        self.strategy = ShardingStrategy.resolve(strategy, rules=tuple(sharding_rules))
        self.max_grad_norm = max_grad_norm
        self.max_grad_value = max_grad_value
        self._loss_scale_config = dict(loss_scale_config or {})
        self.dataloader_config = dataloader_config or DataLoaderConfiguration()
        # Launcher env contract fallbacks (`commands/launch.py build_child_env`
        # forwards the config file's tracker/project knobs as ATX_*), same
        # pattern as the mesh/strategy env reads.
        import os

        if project_dir is None and project_config is None:
            project_dir = os.environ.get("ATX_PROJECT_DIR") or None
        self.project_config = project_config or ProjectConfiguration(project_dir=project_dir)
        self.rng = _set_seed(seed) if seed is not None else jax.random.PRNGKey(0)
        self.trackers: list[Any] = []
        if log_with is None and os.environ.get("ATX_LOG_WITH"):
            log_with = [
                t.strip() for t in os.environ["ATX_LOG_WITH"].split(",") if t.strip()
            ]
        self.log_with = log_with
        # Preemption safety (resilience/preemption.py): trap SIGTERM so a
        # spot reclaim / maintenance notice becomes an emergency checkpoint
        # at the next step boundary instead of lost work. Opt out with
        # ATX_PREEMPTION_HANDLER=0 (the handler is main-thread-only and
        # idempotent, so repeated Accelerator constructions are fine).
        from .utils.environment import parse_flag_from_env

        if parse_flag_from_env("ATX_PREEMPTION_HANDLER", True):
            from . import resilience

            resilience.install_preemption_handler()
        # GCE maintenance-event poller (resilience/gce.py): opt-in via
        # ATX_GCE_PREEMPT_POLL_SECS — catches metadata preemption notices
        # that arrive before (or without) the SIGTERM.
        from . import resilience as _resilience

        self._gce_poller = _resilience.maintenance_poller_from_env()
        # Durable checkpoint replication (resilience/replicate.py): opt-in
        # via ATX_REPLICATE_URL — a background thread mirrors each committed
        # checkpoint into the object store; None when replication is off.
        self._replicator = _resilience.replicator_from_env()
        # Peer-health watchdog (resilience/health.py): opt-in via
        # ATX_HEALTH_BEAT_SECS — collective-free heartbeats through the
        # checkpoint root (or the replicate store) flag a dead peer in
        # seconds and route the survivors onto the emergency-save +
        # exit-75 elastic path. None when disabled.
        self._health = None
        try:
            from . import checkpointing as _ckpt

            _health_root = _ckpt.checkpoint_root(self)
        except Exception:
            _health_root = None
        self._health = _resilience.health_from_env(
            root=_health_root,
            store=self._replicator.store if self._replicator is not None else None,
            process_index=self.process_index,
            num_processes=self.num_processes,
        )
        if self._health is not None:
            self._health.start()
        # Shrink/grow-in-place (resilience/elastic.py): opt-in via
        # ATX_ELASTIC_SHRINK — on health escalation or a devices-file
        # retarget, survivors agree on a reduced topology and reshard live
        # state in memory at the next step entry instead of relaunching.
        self._elastic = _resilience.elastic_controller_from_env(
            root=_health_root,
            store=self._replicator.store if self._replicator is not None else None,
            health=self._health,
            process_index=self.process_index,
            num_processes=self.num_processes,
            host_devices=jax.local_device_count(),
            total_devices=self.mesh.devices.size,
        )
        self._topology_callbacks: list[Callable] = []
        self._mesh_epoch = 0
        self._elastic_timer: tuple[int, str, float] | None = None
        self._preemption_exit_started = False
        self._preemption_sync_calls = 0
        self._flag_tensor: jax.Array | None = None
        self._checkpoint_registry: list[Any] = []
        self._param_specs: Any = None
        self._opt_specs: Any = None
        self._opt_host_shardings: Any = None
        self._dataloaders: list[DataLoader] = []
        self._train_steps: dict[int, Callable] = {}

    # ----------------------------------------------------------- properties
    @property
    def mesh(self) -> Mesh:
        return self.state.mesh

    @property
    def num_processes(self) -> int:
        return self.process_state.num_processes

    @property
    def process_index(self) -> int:
        return self.process_state.process_index

    @property
    def is_main_process(self) -> bool:
        return self.process_state.is_main_process

    @property
    def is_local_main_process(self) -> bool:
        return self.process_state.is_local_main_process

    @property
    def is_last_process(self) -> bool:
        return self.process_state.is_last_process

    @property
    def device(self) -> jax.Device:
        return self.process_state.device

    @property
    def use_distributed(self) -> bool:
        return self.process_state.use_distributed

    @property
    def mixed_precision(self) -> str:
        return self.state.mixed_precision

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.gradient_state.num_steps

    @property
    def sync_gradients(self) -> bool:
        # Accumulation happens inside the compiled step; every outer step is a
        # sync step (reference `_do_sync`, accelerator.py:1090-1097, made moot).
        return True

    @property
    def data_parallel_size(self) -> int:
        return data_parallel_size(self.mesh)

    # ------------------------------------------------------------- process
    def print(self, *args: Any, **kwargs: Any) -> None:
        self.process_state.print(*args, **kwargs)

    def wait_for_everyone(self) -> None:
        self.process_state.wait_for_everyone()

    def split_between_processes(self, inputs: Any, apply_padding: bool = False):
        return self.process_state.split_between_processes(inputs, apply_padding)

    def on_main_process(self, f: Callable) -> Callable:
        return self.process_state.on_main_process(f)

    def on_local_main_process(self, f: Callable) -> Callable:
        return self.process_state.on_local_main_process(f)

    def main_process_first(self):
        return self.process_state.main_process_first()

    # -------------------------------------------------------------- prepare
    def prepare(self, *args: Any, lint: str | None = None) -> Any:
        """Polymorphic prepare (reference `prepare`, `accelerator.py:1283`).

        Dispatch per object type (`_prepare_one`, reference :1266-1281):
        `DataLoader` -> mesh-bound loader; `TrainState` -> sharded onto the
        mesh; optax `GradientTransformation` and schedules pass through
        (they live inside the jitted step). Returns objects in input order.

        ``lint`` runs the ahead-of-time sharding analyzer (ATX1xx family,
        docs/static_analysis.md) over each TrainState's planned specs
        BEFORE any buffer moves: ``"warn"`` surfaces findings as
        `AnalysisWarning`s, ``"error"`` raises `LintError` on
        error-severity findings (e.g. a spec axis missing from the mesh),
        ``"off"`` (default) skips. The ``ATX_LINT`` env var supplies the
        default so a launcher can turn it on fleet-wide.
        """
        mode = self._resolve_lint_mode(lint)
        prepared = tuple(self._prepare_one(a, lint=mode) for a in args)
        return prepared[0] if len(prepared) == 1 else prepared

    def _prepare_one(self, obj: Any, lint: str = "off") -> Any:
        if isinstance(obj, DataLoader):
            return self._prepare_data_loader_obj(obj)
        if isinstance(obj, TrainState):
            return self.prepare_train_state(obj, lint=lint)
        return obj

    @staticmethod
    def _resolve_lint_mode(lint: str | None) -> str:
        import os

        mode = lint if lint is not None else os.environ.get("ATX_LINT") or "off"
        if mode not in ("off", "warn", "error"):
            raise ValueError(
                f"lint={mode!r}: expected 'off', 'warn', or 'error' "
                "(or unset ATX_LINT)"
            )
        return mode

    def _dispatch_lint(self, report: Any, mode: str) -> None:
        """Route lint findings per mode: raise on errors under "error",
        everything else becomes an `AnalysisWarning`."""
        import warnings

        from .analysis import AnalysisWarning, LintError, Severity

        if mode == "error" and report.has_errors:
            raise LintError(report.findings)
        for finding in report.filter(Severity.WARNING):
            warnings.warn(finding.format(), AnalysisWarning, stacklevel=3)

    def _prepare_data_loader_obj(self, dl: DataLoader) -> DataLoader:
        dl._rebind(self.mesh, self.dataloader_config)
        self._dataloaders.append(dl)
        return dl

    def prepare_data_loader(
        self,
        dataset: Any,
        batch_size: int | None = None,
        *,
        shuffle: bool | None = None,
        seed: int | None = None,
        drop_last: bool | None = None,
        collate_fn: Callable | None = None,
        spec: PartitionSpec | None = None,
    ) -> DataLoader:
        """None for batch_size/shuffle/drop_last means "default" (1 / False /
        False) — or, when ``dataset`` is a torch DataLoader, "inherit from
        it"; explicit values always win over inherited ones."""
        from .data.torch_interop import is_torch_dataloader, unwrap_torch_dataloader

        if is_torch_dataloader(dataset):
            # Reference-style migration path: hand in the torch DataLoader,
            # get the framework loader over the same dataset back (the torch
            # sampler is replaced by the sharded seeded one, exactly as the
            # reference substitutes its BatchSamplerShard). A collate_fn
            # passed HERE receives raw torch samples; its output is
            # converted tensor->numpy.
            torch_cfg = unwrap_torch_dataloader(
                dataset, has_user_collate=collate_fn is not None
            )
            dataset = torch_cfg["dataset"]
            batch_size = batch_size if batch_size is not None else torch_cfg["batch_size"]
            shuffle = shuffle if shuffle is not None else torch_cfg["shuffle"]
            drop_last = drop_last if drop_last is not None else torch_cfg["drop_last"]
            seed = seed if seed is not None else torch_cfg["seed"]
            if collate_fn is not None:
                from .data.torch_interop import to_numpy as _to_np

                collate_fn = (lambda samples, _c=collate_fn: _to_np(_c(samples)))
            else:
                collate_fn = torch_cfg["collate_fn"]
        dl = DataLoader(
            dataset,
            batch_size if batch_size is not None else 1,
            shuffle=bool(shuffle),
            seed=seed if seed is not None else 0,
            drop_last=bool(drop_last),
            collate_fn=collate_fn,
            mesh=self.mesh,
            spec=spec,
            config=self.dataloader_config,
        )
        self._dataloaders.append(dl)
        return dl

    # ------------------------------------------------------- state creation
    def _resolve_specs(self, params_shapes: Any, tx: optax.GradientTransformation) -> tuple[Any, Any]:
        param_specs = infer_param_specs(params_shapes, self.mesh, self.strategy)
        opt_shapes = jax.eval_shape(tx.init, params_shapes)
        opt_specs = infer_opt_specs(opt_shapes, params_shapes, param_specs, self.mesh, self.strategy)
        self._param_specs, self._opt_specs = param_specs, opt_specs
        return param_specs, opt_specs

    def state_shardings(self, state_shapes: "TrainState") -> "TrainState":
        """TrainState-shaped pytree of NamedShardings (for jit out_shardings)."""
        replicated = NamedSharding(self.mesh, PartitionSpec())
        opt_sh = getattr(self, "_opt_host_shardings", None)
        return TrainState(
            step=replicated,
            params=to_named_shardings(self._param_specs, self.mesh),
            opt_state=opt_sh
            if opt_sh is not None
            else to_named_shardings(self._opt_specs, self.mesh),
            apply_fn=state_shapes.apply_fn,
            tx=state_shapes.tx,
            loss_scale=jax.tree.map(lambda _: replicated, state_shapes.loss_scale),
        )

    def _maybe_loss_scale(self) -> DynamicLossScale | None:
        """fp16 compute requires a dynamic loss scaler (fp16's 5-bit exponent
        underflows real gradients); bf16/fp32 need none. ``loss_scale_config``
        (init_scale / growth_factor / backoff_factor / growth_interval)
        overrides the GradScaler-equivalent defaults — e.g. a ds_config's
        fp16 block maps onto it (`utils/ds_config.py`)."""
        if self.policy.compute_dtype == jnp.float16:
            return jax.device_put(
                DynamicLossScale.create(**self._loss_scale_config),
                NamedSharding(self.mesh, PartitionSpec()),
            )
        return None

    def _offload_opt_placement(self, tx: Any, opt_shapes_fn: Callable, opt_sh: Any) -> Any:
        """Apply the offload_optimizer placement policy to the optimizer
        shardings: pinned-host float moments when the backend supports it
        (and the optimizer is offload-aware), a loud fallback otherwise.
        Records the host shardings for the train step's streaming path."""
        self._opt_host_shardings = None
        if getattr(self.strategy, "offload_optimizer_device", None) == "nvme":
            # The run configuration (e.g. a ds_config with
            # offload_optimizer.device='nvme') requested the DISK tier,
            # which rides the optimizer object — a plain optax optimizer
            # here would silently train with device-resident moments, the
            # exact downgrade the 'cpu' tier already refuses.
            from .parallel.disk_offload import DiskOffloadedAdamW

            if not isinstance(tx, DiskOffloadedAdamW):
                raise ValueError(
                    "offload_optimizer.device='nvme' was requested but the "
                    "optimizer is not disk-offloaded; use "
                    "disk_offloaded_adamw(..., offload_dir=<nvme_path>) (or "
                    "optax_from_deepspeed_config, which builds it from the "
                    "same ds_config) instead of a plain optax transformation."
                )
        if not self.strategy.offload_optimizer:
            return opt_sh
        from .parallel import host_offload as _ho

        if not _ho.host_offload_supported():
            _ho.warn_host_offload_unsupported()
            return opt_sh
        if not isinstance(tx, _ho.HostOffloadedAdamW):
            raise ValueError(
                "offload_optimizer requires an offload-aware optimizer: use "
                "accelerate_tpu.host_offloaded_adamw(...) instead of a plain "
                "optax transformation — the streamed update must know the "
                "optimizer's math (the DeepSpeedCPUAdam requirement, "
                "reference utils/deepspeed.py:29)."
            )
        # ZeRO-Offload analog: float moments live in pinned host RAM and
        # never materialize whole in HBM.
        opt_sh = _ho.host_opt_shardings(opt_shapes_fn(), opt_sh)
        self._opt_host_shardings = opt_sh
        return opt_sh

    def create_train_state(
        self,
        init_fn: Callable[[jax.Array], Any] | Any,
        tx: optax.GradientTransformation,
        *,
        apply_fn: Callable | None = None,
        rng: jax.Array | None = None,
    ) -> TrainState:
        """Build a sharded TrainState directly on the mesh.

        ``init_fn`` is either `(rng) -> params` (jit-compiled with sharded
        out-shardings so huge models initialize *already sharded*, never
        materializing unsharded on one device — the meta-device-init analog,
        reference `big_modeling.py:58`) or a concrete params pytree.
        """
        rng = rng if rng is not None else self.rng
        if callable(init_fn):
            params_shapes = jax.eval_shape(init_fn, rng)
            param_specs, opt_specs = self._resolve_specs(params_shapes, tx)
            param_sh = to_named_shardings(param_specs, self.mesh)
            params = jax.jit(init_fn, out_shardings=param_sh)(rng)
        else:
            params_shapes = jax.eval_shape(lambda: init_fn)
            param_specs, opt_specs = self._resolve_specs(params_shapes, tx)
            params = shard_pytree(init_fn, param_specs, self.mesh)
        if self.policy.param_dtype is not None:
            # Explicit master-param dtype (policy.param_dtype docstring):
            # cast float leaves; ints (embedding tables are float, token ids
            # never live in params, but quantized int8 leaves do) stay put.
            pd = self.policy.param_dtype
            params = jax.tree.map(
                lambda x: x.astype(pd)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                params,
            )
        opt_sh = self._offload_opt_placement(
            tx, lambda: jax.eval_shape(tx.init, params),
            to_named_shardings(opt_specs, self.mesh),
        )
        opt_state = jax.jit(tx.init, out_shardings=opt_sh)(params)
        # The step counter must be mesh-replicated like every other scalar in
        # the state: a single-device scalar here gives the first jitted step
        # a different input layout than every later one (one wasted compile).
        replicated = NamedSharding(self.mesh, PartitionSpec())
        return TrainState(
            step=jax.device_put(jnp.zeros((), jnp.int32), replicated),
            params=params,
            opt_state=opt_state,
            apply_fn=apply_fn,
            tx=tx,
            loss_scale=self._maybe_loss_scale(),
        )

    def prepare_train_state(self, state: TrainState, *, lint: str | None = None) -> TrainState:
        """Shard an existing (host or single-device) TrainState onto the mesh.

        ``lint`` ("off"|"warn"|"error", default from ``ATX_LINT``) runs the
        sharding analyzer over the planned specs first — a bad spec is
        caught here, before GiBs start moving, not three hours into a pod
        run (see `prepare`)."""
        from .parallel.host_offload import place_opt_state as _ho_place

        mode = self._resolve_lint_mode(lint)
        if mode != "off":
            from . import analysis

            report = analysis.lint_specs(
                jax.eval_shape(lambda: state.params),
                self.mesh,
                strategy=self.strategy,
                opt_shapes=jax.eval_shape(lambda: state.opt_state),
                target="prepare_train_state",
            )
            # ATX_LINT_PROCESSES=N (N >= 2) additionally proves the planned
            # specs are process-independent: the same inference replayed
            # under each simulated process_index must agree (ATX501).
            import os

            procs = int(os.environ.get("ATX_LINT_PROCESSES", "1") or "1")
            if procs >= 2:
                from .analysis import rules_multihost

                shapes = jax.eval_shape(lambda: state.params)
                report.findings.extend(
                    rules_multihost.spec_consistency_findings(
                        lambda: infer_param_specs(shapes, self.mesh, self.strategy),
                        procs,
                    )
                )
            self._dispatch_lint(report, mode)

        params_shapes = jax.eval_shape(lambda: state.params)
        param_specs, opt_specs = self._resolve_specs(params_shapes, state.tx)
        loss_scale = state.loss_scale
        if loss_scale is None:
            loss_scale = self._maybe_loss_scale()
        else:
            # A restored scaler may carry single-device layout; replicate it
            # like every other state scalar or the first step recompiles.
            loss_scale = jax.device_put(
                loss_scale, NamedSharding(self.mesh, PartitionSpec())
            )
        opt_sh = self._offload_opt_placement(
            state.tx, lambda: jax.eval_shape(lambda: state.opt_state),
            to_named_shardings(opt_specs, self.mesh),
        )
        return state.replace(
            step=jax.device_put(
                state.step, NamedSharding(self.mesh, PartitionSpec())
            ),
            params=shard_pytree(state.params, param_specs, self.mesh),
            # Chunked pooled placement (host-offloaded moments are the big
            # case: GiBs of fp32 headed for pinned host RAM).
            opt_state=_ho_place(state.opt_state, opt_sh),
            loss_scale=loss_scale,
        )

    def unwrap(self, state: TrainState) -> Any:
        """Reference `unwrap_model` (`accelerator.py:2745`): the raw params."""
        return state.params

    unwrap_model = unwrap

    # ------------------------------------------------------------ scheduler
    def prepare_scheduler(self, schedule: Callable[[Any], Any]) -> Callable[[Any], Any]:
        """Adapt an optax schedule to gradient accumulation (reference
        `AcceleratedScheduler`, `scheduler.py:62`).

        With ``adjust_scheduler=True`` (the plugin default) the reference
        advances the LR schedule once per *batch* even on non-sync
        accumulation steps, so a schedule denominated in batches completes
        on time. Optax schedules count optimizer updates — which advance
        ``num_steps``× slower under accumulation — so the returned schedule
        evaluates the original at ``count * num_steps``. The schedule you
        pass in must therefore be denominated in *microbatches* (reference
        batches): with ``total_updates`` optimizer steps planned that is
        ``total_updates * num_steps``, NOT ``len(loader) * epochs`` (a
        framework dataloader batch is the whole accumulation window). Pass
        the result as the ``learning_rate`` of your optax optimizer::

            microbatches = total_updates * accelerator.gradient_accumulation_steps
            sched = accelerator.prepare_scheduler(
                optax.cosine_decay_schedule(3e-4, decay_steps=microbatches))
            tx = optax.adamw(learning_rate=sched)

        With ``adjust_scheduler=False`` (or no accumulation) the schedule is
        returned unchanged.
        """
        accum = self.gradient_state.num_steps
        if accum <= 1 or not self.gradient_accumulation_plugin.adjust_scheduler:
            return schedule

        def adjusted(count):
            return schedule(count * accum)

        return adjusted

    # ----------------------------------------------------------- train step
    def make_train_step(
        self,
        loss_fn: Callable[..., Any],
        *,
        has_aux: bool = False,
        donate: bool = True,
        extra_metrics_fn: Callable[[Any, Any], dict[str, jax.Array]] | None = None,
    ) -> Callable[[TrainState, Any], tuple[TrainState, dict[str, jax.Array]]]:
        """Compile the full training step.

        ``loss_fn(params, batch, rng) -> loss`` (or ``(loss, aux)`` with
        ``has_aux``). The returned callable maps
        ``(state, batch) -> (state, metrics)`` and internally:

        1. splits the global batch into `gradient_accumulation_steps`
           microbatches and `lax.scan`s gradients (reference `accumulate`,
           `accelerator.py:1116`; DDP ``no_sync`` dance is unnecessary — one
           compiled step has exactly one gradient reduction);
        2. computes in `policy.compute_dtype` with fp32 master params
           (autocast analog, :1462-1473) — gradients come out fp32 because
           autodiff flows through the cast;
        3. clips by global norm when `max_grad_norm` is set (:2485);
        4. applies the optax update; LR schedules live in the optax chain
           (the `AcceleratedScheduler` skip-on-overflow logic is bf16-moot).
        """
        accum = self.gradient_state.num_steps
        policy = self.policy
        max_grad_norm = self.max_grad_norm
        max_grad_value = self.max_grad_value
        use_scaler = policy.compute_dtype == jnp.float16
        # Capture the planned specs NOW (create_train_state time), not at
        # trace time: a later create_train_state for a second model would
        # overwrite self._param_specs and this step would pin the wrong
        # layout (or crash on tree mismatch) when it finally traces.
        planned_param_specs = getattr(self, "_param_specs", None)
        planned_opt_specs = getattr(self, "_opt_specs", None)
        # Host-offloaded moments (create_train_state decided placement):
        # the step moves them host->HBM right before the update and back
        # after, all inside the jit so XLA overlaps the DMAs with compute.
        opt_host_shardings = getattr(self, "_opt_host_shardings", None)
        if opt_host_shardings is not None and use_scaler:
            raise ValueError(
                "offload_optimizer with fp16 dynamic loss scaling is not "
                "supported (the overflow-skip select would have to span "
                "memory spaces); use bf16 mixed precision."
            )
        # Non-finite training guard (opt-in, ATX_NAN_GUARD): the compiled
        # step skips the optimizer update via a pure lax.cond whenever the
        # loss or any gradient is non-finite — no host sync on the happy
        # path. The host side counts consecutive skips off the returned
        # metrics (drained only when .is_ready(), so dispatch stays async)
        # and aborts with NonFiniteGuardError after
        # ATX_NAN_GUARD_MAX_CONSECUTIVE (default 3) bad steps in a row.
        from .utils.environment import get_int_from_env, parse_flag_from_env

        nan_guard = parse_flag_from_env("ATX_NAN_GUARD", False)
        nan_guard_budget = max(
            1, get_int_from_env(("ATX_NAN_GUARD_MAX_CONSECUTIVE",), 3)
        )
        if nan_guard and opt_host_shardings is not None:
            raise ValueError(
                "ATX_NAN_GUARD with offload_optimizer is not supported (the "
                "skip-update cond would have to span memory spaces, like the "
                "fp16 overflow select); disable one of the two."
            )

        def _pin(tree: Any, spec_tree: Any) -> Any:
            """Constrain `tree` to its planned shardings; skipped when no
            plan exists or the structures disagree (a state this step was
            not planned for). The skip warns once — a silently unpinned
            output regresses the ZERO1 layout/recompile fix without any
            signal."""
            if spec_tree is None:
                _warn_unpinned_once(
                    "make_train_step has no planned shardings to pin outputs "
                    "to (create_train_state was not called on this "
                    "Accelerator); output layouts are left to the "
                    "partitioner, which may recompile or change the "
                    "strategy's memory story."
                )
                return tree
            is_spec = lambda x: isinstance(x, PartitionSpec)
            if jax.tree.structure(tree) != jax.tree.structure(spec_tree, is_leaf=is_spec):
                _warn_unpinned_once(
                    "make_train_step's planned shardings do not match the "
                    "state actually passed to the step (different model?); "
                    "outputs are left unpinned."
                )
                return tree
            return jax.tree.map(
                jax.lax.with_sharding_constraint,
                tree,
                to_named_shardings(spec_tree, self.mesh),
            )

        def compute_loss(params: Any, batch: Any, rng: jax.Array, scale: jax.Array):
            cparams = policy.cast_for_compute(params)
            cbatch = policy.cast_for_compute(batch)
            # Under fp8, the model traces with matmuls lowered to scaled-fp8
            # contractions (ops/fp8.py); the mode is read at trace time, so
            # the compiled step bakes it in.
            with _fp8.fp8_matmuls(policy.fp8):
                out = loss_fn(cparams, cbatch, rng)
                if policy.fp8 and _fp8.fp8_hits() == 0:
                    _warn_fp8_noop()
            if has_aux:
                loss, aux = out
            else:
                loss, aux = out, None
            loss = loss.astype(jnp.float32)
            # Differentiate the SCALED loss (fp16 grads underflow otherwise);
            # scale == 1.0 outside fp16, so this is the identity there.
            return loss * scale, (loss, aux)

        grad_fn = jax.value_and_grad(compute_loss, has_aux=True)

        def accumulated_grads(params, batch, rng, scale):
            """(grads, loss, reduced aux) — the one microbatch-accumulation
            pipeline, shared by the monolithic step and the disk-tier grad
            pass so the two cannot drift."""
            if accum > 1:
                def reshape(x):
                    b = x.shape[0]
                    if b % accum != 0:
                        raise ValueError(
                            f"Global batch size {b} is not divisible by "
                            f"gradient_accumulation_steps={accum}; adjust the "
                            "dataloader batch size or the accumulation steps."
                        )
                    return x.reshape((accum, b // accum) + x.shape[1:])

                microbatches = jax.tree.map(reshape, batch)

                def scan_body(carry, xs):
                    mb, mb_idx = xs
                    g_acc, l_acc = carry
                    # Distinct rng per microbatch: otherwise dropout masks are
                    # identical across the accumulation window.
                    (_, (loss, aux)), grads = grad_fn(
                        params, mb, jax.random.fold_in(rng, mb_idx), scale
                    )
                    g_acc = jax.tree.map(jnp.add, g_acc, grads)
                    return (g_acc, l_acc + loss), aux

                zero_grads = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), params
                )
                (grads, loss_sum), aux = jax.lax.scan(
                    scan_body,
                    (zero_grads, jnp.zeros((), jnp.float32)),
                    (microbatches, jnp.arange(accum)),
                )
                grads = jax.tree.map(lambda g: g / accum, grads)
                loss = loss_sum / accum
                # lax.scan stacked aux along the accumulation axis; reduce it
                # so extra_metrics_fn sees the same values regardless of the
                # accumulation setting: mean for float metrics, sum for
                # integer counters (a count over the full batch).
                if aux is not None:
                    aux = jax.tree.map(
                        lambda x: jnp.mean(x, axis=0)
                        if jnp.issubdtype(x.dtype, jnp.inexact)
                        else jnp.sum(x, axis=0),
                        aux,
                    )
                return grads, loss, aux
            (_, (loss, aux)), grads = grad_fn(params, batch, rng, scale)
            return grads, loss, aux

        def step_fn(state: TrainState, batch: Any) -> tuple[TrainState, dict[str, jax.Array]]:
            rng = jax.random.fold_in(self.rng, state.step)
            scale = state.loss_scale.scale if use_scaler else jnp.float32(1.0)
            grads, loss, aux = accumulated_grads(state.params, batch, rng, scale)

            # Loss math stays fp32 throughout; output_dtype only changes the
            # dtype the metric is *reported* in.
            metrics: dict[str, jax.Array] = {
                "loss": loss
                if policy.output_dtype is None
                else loss.astype(policy.output_dtype)
            }
            guard_finite = None
            if nan_guard and not use_scaler:
                # Raw loss + grads, BEFORE clipping: a clip can turn inf into
                # a large finite number and mask the divergence signal.
                guard_finite = jnp.isfinite(loss) & jnp.all(
                    jnp.stack(
                        [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)]
                    )
                )
            if use_scaler:
                grads = jax.tree.map(lambda g: g / scale, grads)
                finite = jnp.all(
                    jnp.stack(
                        [jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads)]
                    )
                )
                if nan_guard:
                    # The scaler's select already skips on non-finite grads;
                    # the guard adds the loss itself (a NaN loss with finite
                    # grads is still divergence) and the abort budget.
                    guard_finite = finite & jnp.isfinite(loss)
                # Zero non-finite grads so the (discarded) optimizer update
                # below computes on clean numbers either way.
                grads = jax.tree.map(
                    lambda g: jnp.where(finite, g, jnp.zeros_like(g)), grads
                )
            if max_grad_value is not None:
                # clip_grad_value_ analog (reference accelerator.py:2523):
                # elementwise clamp, applied BEFORE norm clipping like a
                # torch loop calling both would.
                grads = jax.tree.map(
                    lambda g: jnp.clip(g, -max_grad_value, max_grad_value), grads
                )
            grad_scale = None
            if max_grad_norm is not None:
                gnorm = global_norm(grads)
                clip = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                if opt_host_shardings is None:
                    grads = jax.tree.map(lambda g: g * clip, grads)
                else:
                    # Folding the clip into the streamed per-layer update
                    # avoids materializing a scaled copy of every gradient
                    # (measured: 6 GiB of fp32 HLO temps at 1.6B).
                    grad_scale = clip
                metrics["grad_norm"] = gnorm
            if opt_host_shardings is not None:
                # Layer-streamed offloaded update (host_offload module
                # docstring): moments stay pinned-host; one layer's slices
                # at a time round-trip through HBM inside a lax.scan.
                from .parallel.host_offload import streaming_adamw_update

                updates, new_opt_state = streaming_adamw_update(
                    state.tx,
                    grads,
                    state.opt_state,
                    state.params,
                    planned_param_specs,
                    self.mesh,
                    grad_scale=grad_scale,
                )
                new_params = optax.apply_updates(state.params, updates)
            elif nan_guard:
                # Guarded update: a pure lax.cond keeps the whole optimizer
                # update off the trace when the step is bad — params and
                # opt-state pass through IDENTICALLY (no 0-update applied,
                # so stateful transforms like Adam moments don't advance on
                # garbage). The predicate is a device scalar; no host sync.
                def _apply_update(operand):
                    g, p, o = operand
                    upd, new_o = state.tx.update(g, o, p)
                    return optax.apply_updates(p, upd), new_o

                def _skip_update(operand):
                    _, p, o = operand
                    return p, o

                new_params, new_opt_state = jax.lax.cond(
                    guard_finite,
                    _apply_update,
                    _skip_update,
                    (grads, state.params, state.opt_state),
                )
            else:
                updates, new_opt_state = state.tx.update(
                    grads, state.opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates)
            new_loss_scale = state.loss_scale
            if use_scaler:
                # Overflow: keep params/opt untouched, back the scale off.
                # Finite: apply, and grow the scale every `growth_interval`
                # consecutive finite steps (reference optimizer.py:162-176:
                # `scaler.step` skips on inf, `scaler.update` adjusts).
                keep_new = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(finite, n, o), new, old
                )
                new_params = keep_new(new_params, state.params)
                new_opt_state = keep_new(new_opt_state, state.opt_state)
                ls = state.loss_scale
                counter = jnp.where(finite, ls.growth_counter + 1, 0)
                grow = counter >= ls.growth_interval
                new_scale = jnp.where(
                    finite,
                    jnp.where(grow, scale * ls.growth_factor, scale),
                    scale * ls.backoff_factor,
                )
                new_loss_scale = ls.replace(
                    scale=new_scale, growth_counter=jnp.where(grow, 0, counter)
                )
                metrics["loss_scale"] = new_scale
                metrics["grads_finite"] = finite
            if nan_guard:
                metrics["nonfinite_skipped"] = (~guard_finite).astype(jnp.int32)
            # Pin the updated params/opt-state to their PLANNED shardings.
            # Without this, jit is free to return them in whatever layout the
            # partitioner found cheapest for this program (e.g. ZERO1's
            # sharded-update output params came back sharded instead of
            # replicated) — which silently changes the strategy's memory
            # story AND forces a recompile when the state round-trips into
            # the next step with a different input layout.
            new_params = _pin(new_params, planned_param_specs)
            if opt_host_shardings is not None:
                # Explicit host placement IS the output pinning here.
                new_opt_state = jax.tree.map(
                    lambda x, s: jax.device_put(x, s),
                    new_opt_state,
                    opt_host_shardings,
                )
            else:
                new_opt_state = _pin(new_opt_state, planned_opt_specs)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt_state,
                loss_scale=new_loss_scale,
            )
            if extra_metrics_fn is not None:
                metrics.update(extra_metrics_fn(new_state, aux))
            return new_state, metrics

        donate_args = (0,) if donate else ()
        jitted = jax.jit(step_fn, donate_argnums=donate_args)

        # ---- disk-tier optimizer offload (parallel/disk_offload.py): the
        # step splits into a compiled grad pass and a host-streamed update
        # against disk-resident moments, so it cannot ride the monolithic
        # jit above. Closures are built lazily on first use.
        _disk_jits: dict[str, Any] = {}

        def run_disk_step(state: TrainState, batch: Any):
            from .parallel.disk_offload import disk_streamed_update

            if use_scaler:
                raise ValueError(
                    "disk offload_optimizer with fp16 dynamic loss scaling "
                    "is not supported (the overflow-skip select would span "
                    "the host update); use bf16 mixed precision."
                )
            if nan_guard:
                raise ValueError(
                    "ATX_NAN_GUARD is not supported with disk-offloaded "
                    "optimizers (the update streams through the host outside "
                    "the compiled step, so there is no in-jit skip point); "
                    "disable one of the two."
                )
            if not all(
                l.is_fully_addressable for l in jax.tree.leaves(state.params)
            ):
                raise NotImplementedError(
                    "disk_offloaded_adamw streams grads through THIS host, so "
                    "it requires fully-addressable (single-process) params — "
                    "the DeepSpeed per-node NVMe-swap shape. For sharded "
                    "multi-process params use the pinned-host tier "
                    "(host_offloaded_adamw), whose update runs inside the "
                    "compiled SPMD program."
                )
            if "grad" not in _disk_jits:
                def grad_step(params, batch, step_idx):
                    rng = jax.random.fold_in(self.rng, step_idx)
                    grads, loss, aux = accumulated_grads(
                        params, batch, rng, jnp.float32(1.0)
                    )
                    metrics = {
                        "loss": loss
                        if policy.output_dtype is None
                        else loss.astype(policy.output_dtype)
                    }
                    if max_grad_value is not None:
                        grads = jax.tree.map(
                            lambda g: jnp.clip(g, -max_grad_value, max_grad_value),
                            grads,
                        )
                    gs = jnp.float32(1.0)
                    if max_grad_norm is not None:
                        gnorm = global_norm(grads)
                        gs = jnp.minimum(1.0, max_grad_norm / (gnorm + 1e-6))
                        metrics["grad_norm"] = gnorm
                    return grads, metrics, gs, aux

                _disk_jits["grad"] = jax.jit(grad_step)
                _disk_jits["apply"] = jax.jit(
                    lambda p, u: optax.apply_updates(p, u),
                    donate_argnums=(0,) if donate else (),
                )
            here = int(jax.device_get(state.step))
            if _disk_jits.get("next_step") != here:
                # First call, or the state's step jumped (a checkpoint was
                # restored mid-run): the memmaps are the optimizer
                # checkpoint, and pairing them with a state from any OTHER
                # step silently corrupts the bias correction (moments ahead
                # of the count). Steady-state steps skip the file read.
                # count() joins the overlapped flush from the previous step
                # first, so the guard judges completed moments.
                stored = state.tx.store.count()
                if stored is not None and stored != here:
                    raise ValueError(
                        f"disk-offloaded moments in {state.tx.store.dir!r} "
                        f"were last written at step {stored}, but the "
                        f"restored train state is at step {here}. Restore "
                        "the matching checkpoint, or point offload_dir at a "
                        "fresh directory to restart the optimizer."
                    )
            with use_mesh(self.mesh):
                grads, metrics, gs, aux = _disk_jits["grad"](
                    state.params, batch, state.step
                )
            count = here + 1
            _disk_jits["next_step"] = count
            grad_scale = (
                float(jax.device_get(gs)) if max_grad_norm is not None else None
            )
            updates = disk_streamed_update(
                state.tx, grads, state.params, count, grad_scale
            )
            del grads
            # Each update leaf lands directly in its param's sharding —
            # one flat device_put to the default device would commit the
            # whole tree to one chip on a multi-chip mesh. The transfer
            # engine streams the big stacked leaves in chunks from its
            # worker pool instead of serializing behind one Python-level
            # device_put per leaf.
            from .parallel.transfer import get_transfer_engine

            updates = get_transfer_engine().put_tree(
                updates, jax.tree.map(lambda p: p.sharding, state.params)
            ).result()
            with use_mesh(self.mesh):
                new_params = _disk_jits["apply"](state.params, updates)
            new_state = state.replace(
                step=state.step + 1,
                params=new_params,
                opt_state={"count": jnp.asarray(count, jnp.int32)},
            )
            if extra_metrics_fn is not None:
                metrics.update(extra_metrics_fn(new_state, aux))
            return new_state, metrics

        # NaN-guard host state: `pending` holds the nonfinite_skipped metric
        # of in-flight steps (device scalars, appended in dispatch order);
        # entries are folded into the consecutive-skip streak only once
        # .is_ready(), so the guard never blocks the async dispatch pipeline.
        _guard = {"pending": [], "streak": 0, "skipped_total": 0}

        def _drain_guard(block: bool = False) -> None:
            pending = _guard["pending"]
            while pending and (block or pending[0].is_ready()):
                skipped = int(jax.device_get(pending.pop(0)))
                _guard["skipped_total"] += skipped
                _guard["streak"] = _guard["streak"] + 1 if skipped else 0
                if _guard["streak"] >= nan_guard_budget:
                    from .telemetry import flight as _flight

                    _flight.dump_postmortem(
                        "nan_guard",
                        extra={
                            "streak": _guard["streak"],
                            "skipped_total": _guard["skipped_total"],
                            "budget": nan_guard_budget,
                        },
                    )
                    raise NonFiniteGuardError(
                        f"ATX_NAN_GUARD: {_guard['streak']} consecutive "
                        "training steps produced a non-finite loss or "
                        "gradients (budget ATX_NAN_GUARD_MAX_CONSECUTIVE="
                        f"{nan_guard_budget}; {_guard['skipped_total']} "
                        "skipped in total this run). Every bad step's "
                        "optimizer update was skipped, so the current state "
                        "is the last finite one — checkpoint it, then lower "
                        "the learning rate / inspect the input pipeline "
                        "before resuming."
                    )

        # Health-beat step hint: a host-side counter (seeded once from the
        # state, then incremented) so note_step never forces a device sync.
        _host_step = {"n": None}

        # ---- step telemetry (docs/observability.md). ATX_METRICS=0 removes
        # every hook; with it on (default) the hooks are host clocks + shape
        # math only — zero device syncs unless ATX_METRICS_SAMPLE_EVERY turns
        # the block_until_ready sampler on. Nothing here touches rng, step
        # math, or dispatch order, so losses are bit-identical either way.
        from . import telemetry as _telemetry
        from .utils import profiler as _profiler
        from .utils.environment import get_int_from_env as _get_int

        _stats: Any = None
        _stats_cell: dict[str, Any] = {"tokens": None, "abstract": None, "calls": 0}
        _metrics_log_every = 0
        _metrics_dir = ""
        if _telemetry.metrics_enabled():
            peak = _telemetry.peak_device_flops()
            peak_total = peak * jax.device_count() if peak else None

            def _flops_fn() -> float | None:
                abstract = _stats_cell["abstract"]
                if abstract is None:
                    return None
                compiled = lower(*abstract).compile()
                flops = _profiler.estimate_step_flops(compiled)
                return None if flops is None else flops * jax.device_count()

            _stats = _telemetry.StepStats(
                flops_fn=_flops_fn, peak_flops_total=peak_total
            )
            _metrics_log_every = _get_int(("ATX_METRICS_LOG_EVERY",), 0)
            _metrics_dir = os.environ.get("ATX_METRICS_DIR", "")

        def _stats_entry(state: TrainState, batch: Any) -> None:
            if _stats_cell["tokens"] is None:
                _stats_cell["tokens"] = _telemetry.tokens_in_batch(batch)
                if _stats.peak_flops_total:
                    # With each argument's own sharding: the MFU lowering is
                    # then the program the step call compiled, and its
                    # compile is a read of the persistent cache (13.8 s of
                    # recompile at step 2 otherwise, 2.8 B params on a v5e).
                    _stats_cell["abstract"] = jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(
                            jnp.shape(x),
                            jnp.result_type(x),
                            sharding=getattr(x, "sharding", None),
                        ),
                        (state, batch),
                    )
            _stats.on_entry(_stats_cell["tokens"])

        def _stats_dispatched(metrics: Any) -> None:
            _stats.on_dispatched(metrics, cache_size=jitted._cache_size())
            n = _stats_cell["calls"]
            if _metrics_log_every and n % _metrics_log_every == 0:
                if self.trackers:
                    self.log(_stats.latest(), step=n)
                if _metrics_dir:
                    _telemetry.write_snapshot(
                        _metrics_dir, process_index=self.process_index
                    )

        def run_step(state: TrainState, batch: Any):
            from . import resilience
            from .parallel.disk_offload import DiskOffloadedAdamW

            _stats_cell["calls"] += 1
            if _stats is not None:
                _stats_entry(state, batch)
            if nan_guard:
                _drain_guard()
                # Bound the undrained window so detection can't lag forever
                # behind a deep dispatch queue.
                if len(_guard["pending"]) > max(8, 2 * nan_guard_budget):
                    _drain_guard(block=True)
            if self._health is not None or self._elastic is not None:
                if _host_step["n"] is None:
                    _host_step["n"] = int(jax.device_get(state.step))
                else:
                    _host_step["n"] += 1
                if self._health is not None:
                    self._health.note_step(_host_step["n"])
            # Elastic shrink/grow check BEFORE the preemption boundary: a
            # successful in-place resize clears the health-escalated
            # preemption flag so the emergency-save + exit-75 machinery
            # below never fires; a failed one leaves the flag set and the
            # very next lines take the relaunch path as before.
            if self._elastic is not None:
                resized = self._maybe_elastic_resize(state, _host_step["n"])
                if resized is not None:
                    state = resized
            # Preemption boundary check at ENTRY, before any compute: the
            # input state is exactly the last completed step's output (whose
            # metrics the caller already has), so the emergency checkpoint
            # loses nothing and the resumed trajectory is bit-identical.
            # Multi-process, this is a COLLECTIVE (flag or-reduce): every
            # process participates every entry so the group agrees on the
            # exit step — one process acting on its local flag alone would
            # barrier against peers still in training-step collectives.
            self._maybe_emergency_exit(state)
            # Hang watchdog (ATX_WATCHDOG_SECS): heartbeat semantics — each
            # step ENTRY re-arms the countdown and it stays armed across the
            # call, because jax dispatches the compiled step asynchronously
            # (the call can return before the device work runs; a disarm
            # here would miss a wedged collective entirely). A wedge is
            # caught when the loop blocks fetching the step's metrics — or
            # wherever the process stalls — and no next step entry arrives
            # within the deadline. `end_training()` disarms.
            wd = resilience.watchdog_from_env()
            if wd is not None:
                wd.arm()
            if isinstance(state.tx, DiskOffloadedAdamW):
                new_state, metrics = run_disk_step(state, batch)
                if _stats is not None:
                    _stats_dispatched(None)
                return new_state, metrics
            # Trace (and run) under the ambient mesh so the model's
            # activation constraints (parallel.mesh.constrain_batch) bind
            # to this Accelerator's axes. `step_span` numbers the step in
            # any profiler capture of the process (a flag check when there
            # is none).
            with use_mesh(self.mesh), _telemetry.step_span(_stats_cell["calls"]):
                new_state, metrics = jitted(state, batch)
            if self._elastic_timer is not None:
                # First step after an in-place resize: block on its output
                # (once) so the reported escalation -> first-step wall clock
                # covers real compute, not an async dispatch.
                self._report_elastic_latency(new_state)
            if nan_guard:
                _guard["pending"].append(metrics["nonfinite_skipped"])
            if _stats is not None:
                _stats_dispatched(metrics)
            return new_state, metrics

        def lower(*args: Any, **kwargs: Any):
            with use_mesh(self.mesh):
                return jitted.lower(*args, **kwargs)

        # Keep the jit surface the HLO-verification tooling relies on.
        run_step.lower = lower
        run_step._cache_size = jitted._cache_size
        # Telemetry read side (None when ATX_METRICS=0): bench and the
        # tracker glue read EMA'd step timing from here.
        run_step.step_stats = _stats
        # NaN-guard introspection: counters for tests/metrics, and a blocking
        # drain so a loop's last steps are judged before it declares success.
        run_step._nan_guard = _guard if nan_guard else None
        run_step.drain_nan_guard = (
            (lambda: _drain_guard(block=True)) if nan_guard else (lambda: None)
        )
        self._train_steps[id(run_step)] = jitted
        return run_step

    def make_eval_step(
        self, fn: Callable[[Any, Any], Any]
    ) -> Callable[[TrainState, Any], Any]:
        """Compile an inference/eval step ``fn(params, batch) -> outputs`` with
        params cast to the compute dtype."""
        policy = self.policy

        def eval_fn(state: TrainState, batch: Any) -> Any:
            with _fp8.fp8_matmuls(policy.fp8):
                return fn(policy.cast_for_compute(state.params), batch)

        return jax.jit(eval_fn)

    # ----------------------------------------------------------- collectives
    def gather(self, tree: Any) -> Any:
        return _ops.gather(tree)

    def reduce(self, tree: Any, reduction: str = "mean") -> Any:
        return _ops.reduce(tree, reduction)

    def pad_across_processes(self, tree: Any, dim: int = 0, pad_index: int = 0, pad_first: bool = False) -> Any:
        return _ops.pad_across_processes(tree, dim=dim, pad_index=pad_index, pad_first=pad_first)

    def gather_for_metrics(self, tree: Any, use_gather_object: bool = False) -> Any:
        """Gather eval outputs, dropping the samples duplicated by the
        even-batches wraparound on the last batch (reference
        `gather_for_metrics`, `accelerator.py:2601-2672`)."""
        if use_gather_object:
            return _ops.gather_object(list(tree))
        data = self.gather(tree)
        try:
            remainder = self.gradient_state.remainder
            on_last = self.gradient_state.end_of_dataloader
        except Exception:
            return data
        if on_last and remainder and remainder > 0:
            data = _ops.slice_tensors(data, slice(0, remainder))
        return data

    # -------------------------------------------------------------- tracking
    def init_trackers(
        self,
        project_name: str,
        config: dict | None = None,
        init_kwargs: dict | None = None,
    ) -> None:
        """Instantiate the trackers selected by ``log_with`` (reference
        `accelerator.py:2804`). ``init_kwargs`` is keyed by tracker name."""
        from . import tracking

        init_kwargs = init_kwargs or {}
        logging_dir = self.project_config.logging_dir
        self.trackers = []
        for entry in tracking.filter_trackers(self.log_with, logging_dir):
            if isinstance(entry, tracking.GeneralTracker):
                tracker = entry
            else:
                # Constructors have global side effects (run creation, open
                # files): instantiate on the main process only, unless the
                # tracker opts in to per-process runs (reference wandb
                # `main_process_only = False`, `tracking.py:289`).
                if entry.main_process_only and not self.is_main_process:
                    continue
                kwargs = dict(init_kwargs.get(entry.name, {}))
                if entry.requires_logging_directory:
                    kwargs.setdefault("logging_dir", logging_dir)
                tracker = entry(project_name, **kwargs)
            self.trackers.append(tracker)
        if config is not None:
            for tracker in self.trackers:
                tracker.store_init_configuration(config)

    def get_tracker(self, name: str, unwrap: bool = False) -> Any:
        """Fetch one initialized tracker by name (reference
        `accelerator.py:2850`); ``unwrap`` returns the raw library object.

        On non-main processes (where main-only trackers were never
        instantiated) a blank no-op tracker is returned, so user code can
        call this unguarded everywhere (reference :2878-2881)."""
        from . import tracking

        for tracker in self.trackers:
            if tracker.name == name:
                return tracker.tracker if unwrap else tracker
        if not self.is_main_process:
            return tracking.GeneralTracker(_blank=True)
        raise ValueError(
            f"Tracker {name!r} not found; initialized: "
            f"{[t.name for t in self.trackers]} (did you call init_trackers?)"
        )

    def log(
        self,
        values: dict,
        step: int | None = None,
        log_kwargs: dict | None = None,
    ) -> None:
        """Log metrics to every tracker (reference `accelerator.py:2883`).

        Device arrays (e.g. the metrics dict a compiled train step returned)
        are synced to host scalars HERE, once, so trackers never touch jax.
        """
        if not self.trackers:
            # No device->host sync when nothing consumes the metrics — the
            # fetch would serialize dispatch on TPU.
            return
        log_kwargs = log_kwargs or {}
        host_values = {
            k: (float(v) if hasattr(v, "dtype") and getattr(v, "ndim", 1) == 0 else v)
            for k, v in values.items()
        }
        if step is not None and hasattr(step, "item"):
            step = int(step)
        for tracker in self.trackers:
            tracker.log(host_values, step=step, **log_kwargs.get(tracker.name, {}))

    def end_training(self) -> None:
        """Flush/close all trackers (reference `accelerator.py:2912`), join
        any in-flight async checkpoint writer, and stand down the hang
        watchdog (its heartbeat expects a steady stream of steps; post-
        training eval/export must not trip it)."""
        for tracker in self.trackers:
            tracker.finish()
        self.trackers = []
        from . import checkpointing, resilience, telemetry

        # Final telemetry snapshot so the shared metrics dir reflects the
        # run's last state even when the step cadence never hit the flush.
        metrics_dir = os.environ.get("ATX_METRICS_DIR", "")
        if metrics_dir and telemetry.metrics_enabled():
            try:
                telemetry.write_snapshot(
                    metrics_dir, process_index=self.process_index
                )
            except OSError:
                pass

        wd = resilience.watchdog_from_env()
        if wd is not None:
            wd.stop()
        if self._health is not None:
            self._health.stop()
        checkpointing.wait_for_checkpoint()
        self._ship_collective_log()
        if self._replicator is not None:
            # The final checkpoint just landed in the queue (async saves
            # joined above): give its upload the drain window, then stop.
            from .resilience import replicate as _replicate

            if not self._replicator.stop(_replicate.drain_secs_from_env()):
                _replicate.logger.warning(
                    "checkpoint replication queue did not drain before "
                    "end_training returned; the last checkpoint may not be "
                    "durable remotely (raise ATX_REPLICATE_DRAIN_SECS)"
                )

    # -------------------------------------------------------------- triggers
    def set_trigger(self) -> None:
        """Cooperative cross-process abort flag (reference
        `accelerator.py:2391-2448`), used for early stopping."""
        self._flag_tensor = jnp.ones((), jnp.int32)

    def check_trigger(self) -> bool:
        flag = self._flag_tensor if self._flag_tensor is not None else jnp.zeros((), jnp.int32)
        total = _ops.reduce({"flag": np.asarray(flag)}, "sum")["flag"]
        if int(total) > 0:
            self._flag_tensor = None
            return True
        return False

    # ---------------------------------------------------------------- memory
    def free_memory(self, *objects: Any) -> tuple:
        """Release references + device buffers (reference `free_memory`,
        `accelerator.py:3412`)."""
        self._train_steps.clear()
        objects = tuple(None for _ in objects)
        gc.collect()
        jax.clear_caches()
        return objects

    # ------------------------------------------------------------ resilience
    def preemption_requested(self) -> bool:
        """Has a SIGTERM / maintenance notice arrived? (The handler only
        sets a flag; poll this at step boundaries and checkpoint + exit with
        ``resilience.PREEMPTION_EXIT_CODE`` — or rely on the automatic hook
        in the step helper when ``automatic_checkpoint_naming`` is on.)"""
        from . import resilience

        return resilience.preemption_requested()

    def _preemption_agreed(self) -> bool:
        """Cross-process agreement on the preemption flag (the orbax-style
        multihost preemption sync). SIGTERM delivery and Python signal
        dispatch skew across hosts: acting on the LOCAL flag alone lets one
        process enter the collective emergency save while peers are still
        issuing training-step collectives (mismatched collectives → hang
        until the watchdog/KILL, emergency checkpoint lost), or lets
        processes enter one step apart and commit shards mixing step N and
        N+1. Every process or-reduces its flag at the same step entries, so
        all agree on the exit step before any of them starts the save.

        ``ATX_PREEMPTION_SYNC_STEPS=N`` (default 1) syncs every N entries —
        raising it trades up to N-1 steps of notice-to-checkpoint latency
        for fewer per-step host round-trips."""
        from . import resilience

        if self.num_processes == 1:
            return resilience.preemption_requested()
        from .utils.environment import get_int_from_env

        self._preemption_sync_calls += 1
        interval = max(1, get_int_from_env(("ATX_PREEMPTION_SYNC_STEPS",), 1))
        if self._preemption_sync_calls % interval:
            return False
        local = resilience.preemption_requested()
        total = _ops.reduce({"flag": np.asarray(int(local), np.int32)}, "sum")["flag"]
        if int(total) == 0:
            return False
        if not local:
            # Adopt the peers' notice so local polls (`preemption_requested`)
            # and the second-SIGTERM escalation see consistent state.
            resilience.request_preemption()
        return True

    def _maybe_emergency_exit(self, state: "TrainState") -> None:
        """The step helper's automatic preemption hook: once ALL processes
        agree a preemption notice is pending (`_preemption_agreed` — the
        collective runs at every step entry so the whole group exits at the
        same step), write a committed emergency checkpoint and raise
        ``SystemExit(PREEMPTION_EXIT_CODE)`` — the exit code the elastic
        loop in `commands/launch.py` resumes immediately without burning a
        ``--max_restarts`` attempt. The save only fires under
        ``automatic_checkpoint_naming`` (otherwise there is no agreed place
        to save; the loop polls `preemption_requested` itself — by the time
        the agreement collective returns True, the flag is set on every
        process, so such loops also act at one common step boundary)."""
        from . import resilience

        if not self._preemption_agreed():
            return
        if not self.project_config.automatic_checkpoint_naming:
            return
        if self._preemption_exit_started:  # re-entry (e.g. user caught it)
            from .telemetry import flight as _flight

            _flight.dump_postmortem("preemption_exit_75_reentry")
            raise SystemExit(resilience.PREEMPTION_EXIT_CODE)
        self._preemption_exit_started = True
        # The emergency save may legitimately exceed the per-step deadline;
        # the watchdog must not shoot it down mid-commit.
        wd = resilience.watchdog_from_env()
        if wd is not None:
            wd.stop()
        import sys as _sys

        _sys.stderr.write(
            "[accelerate_tpu] preemption requested: writing emergency "
            "checkpoint before exiting\n"
        )
        from . import checkpointing

        path = checkpointing.save_state(self, None, state, async_save=False)
        if self._replicator is not None:
            # The emergency checkpoint is only preemption-proof once it is
            # durable OFF this VM: flush the upload queue, bounded by
            # ATX_REPLICATE_DRAIN_SECS so a dead store cannot eat the whole
            # grace window (a SIGKILL mid-drain still leaves the local
            # commit + any fully-uploaded parts for the next attempt).
            from .resilience import replicate as _replicate

            drain_secs = _replicate.drain_secs_from_env()
            _sys.stderr.write(
                "[accelerate_tpu] flushing checkpoint replication queue "
                f"(up to {drain_secs:.0f}s) before preemption exit\n"
            )
            if not self._replicator.stop(drain_secs):
                _sys.stderr.write(
                    "[accelerate_tpu] replication queue did not drain in "
                    "time; the emergency checkpoint may not be durable "
                    "remotely (already-uploaded parts will be skipped on "
                    "the next attempt)\n"
                )
        # Post-mortem shipping: the collective log (when armed) rides out on
        # the same store before the VM disappears. Best-effort by design.
        self._ship_collective_log()
        _sys.stderr.write(
            f"[accelerate_tpu] emergency checkpoint committed at {path}; "
            f"exiting with code {resilience.PREEMPTION_EXIT_CODE} (elastic "
            "launchers resume without consuming a restart attempt)\n"
        )
        _sys.stderr.flush()
        # Black-box bundle (no-op unless ATX_POSTMORTEM_DIR): what the
        # process was doing when the preemption notice landed. After the
        # checkpoint commit, so a slow collector can't eat grace time.
        from .telemetry import flight as _flight

        _flight.dump_postmortem(
            "preemption_exit_75", extra={"checkpoint": str(path)}
        )
        raise SystemExit(resilience.PREEMPTION_EXIT_CODE)

    def _ship_collective_log(self) -> None:
        """Ship this process's collective log off-host (best effort).

        Fires only when ``ATX_COLLECTIVE_LOG=1`` recorded a log AND a
        replicate store is armed — the log is a post-mortem aid, so failures
        here must never mask the exit path that called us."""
        try:
            from .analysis import collective_log as _cl

            if not _cl.enabled():
                return
            store = self._replicator.store if self._replicator is not None else None
            if store is None:
                from .resilience import replicate as _replicate

                store = _replicate.store_from_env()
            if store is None:
                return
            _cl.ship_log(store, process_index=self.process_index)
        except Exception as e:
            import logging

            logging.getLogger(__name__).warning(
                "collective-log shipping failed (post-mortem aid only): %s", e
            )

    # ---------------------------------------------------- elastic shrink/grow
    def on_topology_change(
        self, callback: Callable[[dict, dict, Any], None]
    ) -> Callable:
        """Register ``callback(old_signature, new_signature, decision)`` to
        fire after an in-place shrink/grow (signatures from
        `parallel.mesh.topology_signature`). The hook is where user code
        re-prepares anything pinned to the old world — dataloader sharding,
        logging of the new topology, LR rescaling for the changed global
        batch. Exceptions are logged, never raised (the resize already
        committed). Returns the callback (usable as a decorator)."""
        self._topology_callbacks.append(callback)
        return callback

    def _maybe_elastic_resize(
        self, state: "TrainState", step_hint: int
    ) -> "TrainState | None":
        """Step-entry elastic poll: the resized TrainState when the group
        just shrank/grew in place, None otherwise. Every failure mode —
        agreement timeout/conflict, unsupported layout, reshard holes —
        degrades to the existing emergency-save + exit-75 relaunch path by
        setting the preemption flag and letting `_maybe_emergency_exit`
        (the very next check in `run_step`) take over."""
        import sys as _sys

        from . import resilience
        from .resilience import elastic as _elastic

        try:
            decision = self._elastic.check(int(step_hint))
        except _elastic.AgreementError as e:
            _sys.stderr.write(
                f"[atx elastic] topology agreement failed ({e}); falling "
                "back to emergency-save + relaunch\n"
            )
            _sys.stderr.flush()
            resilience.request_preemption()
            return None
        if decision is None:
            return None
        try:
            return self._apply_topology_decision(state, decision)
        except Exception as e:
            _sys.stderr.write(
                f"[atx elastic] in-place resize failed before completion "
                f"({type(e).__name__}: {e}); falling back to emergency-save "
                "+ relaunch\n"
            )
            _sys.stderr.flush()
            self._elastic.abandon()
            resilience.request_preemption()
            return None

    def _apply_topology_decision(
        self, state: "TrainState", decision: Any
    ) -> "TrainState":
        """Execute an agreed resize: snapshot live shards, rebuild the
        distributed runtime + mesh at the new size, reshard
        params/opt-state/step in memory, and swing the health/elastic
        rosters over. Raises on any problem BEFORE mutating accelerator
        state wherever possible (the `shrink.before_reshard` fault point
        marks that boundary); the caller maps failures to the relaunch
        path."""
        import sys as _sys
        import time as _time

        from . import checkpointing as _ckpt
        from . import resilience
        from .resilience.commit import fault_point

        esc_at = self._elastic.escalated_at
        t0 = _time.monotonic()
        if esc_at is None:
            esc_at = t0
        old_sig = topology_signature(self.mesh)
        old_devices = self.mesh.devices.size
        if getattr(self, "_opt_host_shardings", None) is not None:
            raise RuntimeError(
                "host-offloaded optimizer state cannot be resized in place "
                "yet (its pinned-host shardings are tied to the old mesh)"
            )
        fault_point("shrink.before_reshard")
        # 1. Snapshot every live leaf to host — ALL addressable shards, so
        #    replica copies cover slices whose replica-0 owner died. This is
        #    the last read of the old-mesh arrays.
        template: dict[str, Any] = {
            "step": state.step,
            "params": state.params,
            "opt_state": state.opt_state,
        }
        if state.loss_scale is not None:
            template["loss_scale"] = state.loss_scale
        snapshot = _ckpt.InMemoryShardSource.from_tree(template)
        live_step = int(jax.device_get(state.step))
        # 2. Real multi-host worlds re-initialize the distributed runtime at
        #    the reduced size (survivor ranks densify via decision.rank_of).
        #    Single-process simulated worlds skip this — the mesh rebuild
        #    below is the whole transition.
        if (
            self.process_state.num_processes > 1
            and decision.num_processes != self.process_state.num_processes
        ):
            new_rank = decision.rank_of(self.process_state.process_index)
            if new_rank is None:
                raise RuntimeError(
                    f"rank {self.process_state.process_index} is not in the "
                    f"agreed survivor set {decision.survivors}"
                )
            import os as _os

            from .state import maybe_initialize_jax_distributed

            self.process_state.destroy_process_group()
            _os.environ["ATX_NUM_PROCESSES"] = str(decision.num_processes)
            _os.environ["ATX_PROCESS_ID"] = str(new_rank)
            maybe_initialize_jax_distributed()
        # 3. Rebuild the mesh with the same parallelism layout at the new
        #    device count; per-leaf partition specs must come out unchanged
        #    (a layout flip would need a different jit program — relaunch).
        want = decision.num_devices
        devs = list(jax.devices())
        if len(devs) < want:
            raise RuntimeError(
                f"resize wants {want} devices but only {len(devs)} are "
                "visible"
            )
        cfg = resize_mesh_config(self.mesh, want, devices=devs[:want])
        new_mesh = build_mesh(cfg)
        old_param_specs = self._param_specs
        self.state.set_mesh(new_mesh)
        try:
            params_shapes = jax.eval_shape(lambda p: p, state.params)
            self._resolve_specs(params_shapes, state.tx)
            if old_param_specs is not None and not _specs_equal(
                old_param_specs, self._param_specs
            ):
                raise RuntimeError(
                    "parameter partition specs differ at the new world size "
                    "(a leaf stopped dividing evenly); in-place resize would "
                    "silently change layouts"
                )
            shardings = self.state_shardings(state)
            shard_tree: dict[str, Any] = {
                "step": shardings.step,
                "params": shardings.params,
                "opt_state": shardings.opt_state,
            }
            if state.loss_scale is not None:
                shard_tree["loss_scale"] = shardings.loss_scale
            # 4. In-memory reshard: live local shards first; the replicate
            #    store's newest SAME-STEP committed checkpoint only for
            #    slices nobody alive holds (ranged reads, not whole files).
            try:
                restored = _ckpt.reshard_arrays(template, shard_tree, [snapshot])
            except _ckpt.CheckpointShardCoverageError:
                store = (
                    self._replicator.store if self._replicator is not None else None
                )
                if store is None:
                    from .resilience import replicate as _replicate

                    store = _replicate.store_from_env()
                fallback = (
                    _ckpt.store_fallback_source(store, live_step)
                    if store is not None
                    else None
                )
                if fallback is None:
                    raise
                _sys.stderr.write(
                    "[atx elastic] live shards have holes; streaming missing "
                    f"slices from remote {fallback.name} (byte-range reads)\n"
                )
                restored = _ckpt.reshard_arrays(
                    template, shard_tree, [snapshot, fallback]
                )
        except BaseException:
            # The mesh swing is the one mutation before this point; undo it
            # so the relaunch fallback saves the emergency checkpoint under
            # the topology the live arrays actually have. Best-effort: in a
            # torn-down real multi-host world this can itself fail, and the
            # relaunch path recovers regardless.
            try:
                if len(devs) >= old_devices:
                    self.state.set_mesh(
                        build_mesh(
                            resize_mesh_config(
                                new_mesh, old_devices, devices=devs[:old_devices]
                            )
                        )
                    )
                    if old_param_specs is not None:
                        params_shapes = jax.eval_shape(lambda p: p, state.params)
                        self._resolve_specs(params_shapes, state.tx)
            except Exception:
                pass
            raise
        new_state = state.replace(
            step=restored["step"],
            params=restored["params"],
            opt_state=restored["opt_state"],
            loss_scale=restored.get("loss_scale", state.loss_scale),
        )
        # 5. Roster swing: the health monitor stops scanning (and retires
        #    the beats of) departed ranks; the controller arms the next
        #    epoch. A health-escalated preemption flag is now satisfied —
        #    clear it so the emergency-exit path doesn't fire.
        if self._health is not None:
            self._health.adopt_roster(decision.survivors)
        self._elastic.adopt(decision)
        resilience.clear_preemption()
        self._mesh_epoch += 1
        new_sig = topology_signature(new_mesh)
        for cb in self._topology_callbacks:
            try:
                cb(old_sig, new_sig, decision)
            except Exception as e:
                _sys.stderr.write(
                    f"[atx elastic] on_topology_change callback failed: {e}\n"
                )
        kind = "grow" if decision.num_devices > old_devices else "shrink"
        agree_secs = (self._elastic.last_transition or {}).get("agree_secs", 0.0)
        reshard_secs = _time.monotonic() - t0
        if self._elastic.last_transition is not None:
            self._elastic.last_transition["reshard_secs"] = reshard_secs
        _sys.stderr.write(
            f"[atx elastic] {kind} in place (epoch {decision.epoch}): "
            f"{old_sig['num_devices']} -> {decision.num_devices} devices, "
            f"{decision.num_processes} process(es) x "
            f"{decision.host_devices} device(s) at step {live_step}; "
            f"agreement {agree_secs:.3f}s, reshard {reshard_secs:.3f}s\n"
        )
        _sys.stderr.flush()
        self._elastic_timer = (decision.epoch, kind, esc_at)
        return new_state

    def _report_elastic_latency(self, new_state: "TrainState") -> None:
        """Log escalation -> first post-resize step wall clock (the ISSUE's
        reported metric) after blocking once on that step's output."""
        import sys as _sys
        import time as _time

        epoch, kind, esc_at = self._elastic_timer
        self._elastic_timer = None
        try:
            jax.block_until_ready(new_state.step)
        except Exception:  # pragma: no cover - reporting must not kill steps
            pass
        _sys.stderr.write(
            f"[atx elastic] epoch {epoch} {kind}: escalation -> first "
            f"post-{kind} step {_time.monotonic() - esc_at:.3f}s\n"
        )
        _sys.stderr.flush()

    # ------------------------------------------------------------ checkpoint
    def register_for_checkpointing(self, *objects: Any) -> None:
        """Attach arbitrary stateful objects (must expose state_dict /
        load_state_dict) to save_state/load_state (reference
        `accelerator.py:3550`)."""
        for obj in objects:
            if not (hasattr(obj, "state_dict") and hasattr(obj, "load_state_dict")):
                raise ValueError(
                    f"Object {obj!r} must define state_dict() and load_state_dict() "
                    "to be registered for checkpointing"
                )
            self._checkpoint_registry.append(obj)

    def save_state(self, output_dir: str, state: TrainState, **kwargs: Any) -> str:
        from . import checkpointing

        return checkpointing.save_state(self, output_dir, state, **kwargs)

    def load_state(
        self, input_dir: str | None, state: TrainState, **kwargs: Any
    ) -> TrainState:
        """Restore a checkpoint. ``load_state(None, state, resume="latest")``
        discovers the newest *committed* checkpoint under the automatic-
        naming root, verifies its manifest, and falls back to the previous
        committed one on corruption (docs/fault_tolerance.md)."""
        from . import checkpointing

        return checkpointing.load_state(self, input_dir, state, **kwargs)

    def save_model(self, params: Any, output_dir: str, **kwargs: Any) -> str:
        """Params-only inference checkpoint (reference `save_model`,
        `accelerator.py:3020`). Layout follows the FSDP plugin's
        ``state_dict_type``: FULL_STATE_DICT consolidates to one file,
        SHARDED_STATE_DICT keeps per-process shards."""
        from . import checkpointing

        kwargs.setdefault(
            "consolidate", self.strategy.fsdp.state_dict_type == "FULL_STATE_DICT"
        )
        return checkpointing.save_model(self, params, output_dir, **kwargs)

    # -------------------------------------------------------------- profiling
    def profile(self, profile_kwargs: Any = None):
        """Capture a `jax.profiler` trace of the enclosed block (reference
        `accelerator.profile()`, `accelerator.py:3614`). Trace files land in
        ``profile_kwargs.output_trace_dir`` or ``<logging_dir>/atx_profile``;
        open the directory with TensorBoard to see the device timeline.

        Run warmup steps before entering — compilation inside the context
        dominates the timeline otherwise.
        """
        from .utils import profiler as _profiler

        return _profiler.profile(
            profile_kwargs, logging_dir=self.project_config.logging_dir
        )

    # ---------------------------------------------------------------- misc
    def autocast(self):
        """Apply the dtype policy to ad-hoc computations OUTSIDE the compiled
        train/eval steps (reference `autocast`, `accelerator.py:3587`).

        JAX has no global op interception, so the context (a) activates the
        fp8 matmul mode when the policy is fp8 — any `matmul_einsum` traced
        inside lowers to scaled-fp8 contractions, exactly as in the compiled
        steps — and (b) yields the policy's cast function for the operands::

            with accelerator.autocast() as cast:
                out = model_fn(cast(params), batch)

        fp8 pitfall: the matmul mode is read at *trace* time and is not part
        of jit's cache key. A function you ``jax.jit`` yourself and first
        call inside this context bakes fp8 contractions into its cached
        executable (and keeps them outside the context); traced first
        outside, it never gets fp8. Either trace the function fresh per mode
        (e.g. pass a ``static_argnum`` flag derived from the policy) or keep
        fp8 work inside the Accelerator's own compiled steps, which close
        over the mode correctly.
        """
        import contextlib

        @contextlib.contextmanager
        def ctx():
            with _fp8.fp8_matmuls(self.policy.fp8):
                yield self.policy.cast_for_compute

        return ctx()

    def __repr__(self) -> str:
        return (
            f"Accelerator(mesh={dict(self.mesh.shape)}, "
            f"strategy={self.strategy.kind}, precision={self.mixed_precision!r}, "
            f"accum={self.gradient_accumulation_steps})"
        )
