"""Every public config field must be consumed (the honesty
contract): activation_checkpointing changes the compiled program but not the
math; state_dict_type drives the save_model layout; removed knobs are gone."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.parallel.sharding import ShardingStrategy
from accelerate_tpu.test_utils.training import regression_init
from accelerate_tpu.utils.dataclasses import DataLoaderConfiguration, FsdpPlugin


def test_model_level_remat_is_the_activation_checkpointing_path():
    # The FsdpPlugin deliberately has NO activation_checkpointing knob: remat
    # must be segmented per block inside the layer scan to cut peak memory,
    # so it lives on the model config. Assert the wiring is real: remat=True
    # changes the compiled program, numerics stay identical.
    from accelerate_tpu.models import llama

    config_plain = llama.LlamaConfig.tiny(remat=False)
    config_remat = llama.LlamaConfig.tiny(remat=True)
    params = llama.init(jax.random.PRNGKey(0), config_plain)
    tokens = jnp.zeros((2, 8), jnp.int32)

    def grads(config):
        def loss(p):
            return llama.loss_fn(p, {"input_ids": tokens}, config)

        return jax.grad(loss)(params)

    jaxpr_plain = str(jax.make_jaxpr(lambda: grads(config_plain))())
    jaxpr_remat = str(jax.make_jaxpr(lambda: grads(config_remat))())
    assert "remat" not in jaxpr_plain
    assert "remat" in jaxpr_remat
    g1, g2 = grads(config_plain), grads(config_remat)
    np.testing.assert_allclose(
        np.asarray(g1["embed"]), np.asarray(g2["embed"]), rtol=1e-5, atol=1e-6
    )


def test_state_dict_type_drives_save_model_layout(tmp_path):
    acc = Accelerator(seed=0, strategy=FsdpPlugin(state_dict_type="FULL_STATE_DICT"))
    state = acc.create_train_state(regression_init, optax.sgd(0.1))
    out = acc.save_model(state.params, str(tmp_path / "full"))
    assert out.endswith("model.npz") and os.path.isfile(out)

    acc2 = Accelerator(seed=0, strategy=FsdpPlugin(state_dict_type="SHARDED_STATE_DICT"))
    state2 = acc2.create_train_state(regression_init, optax.sgd(0.1))
    out2 = acc2.save_model(state2.params, str(tmp_path / "sharded"))
    assert os.path.isdir(out2)
    assert any(f.startswith("index_") for f in os.listdir(out2))


def test_invalid_state_dict_type_rejected():
    with pytest.raises(ValueError, match="state_dict_type"):
        FsdpPlugin(state_dict_type="NOT_A_THING")


def test_removed_knobs_are_gone():
    with pytest.raises(TypeError):
        FsdpPlugin(reshard_after_forward=False)
    with pytest.raises(TypeError):
        FsdpPlugin(cpu_offload=True)
    with pytest.raises(TypeError):
        FsdpPlugin(activation_checkpointing=True)
    with pytest.raises(TypeError):
        DataLoaderConfiguration(use_seedable_sampler=False)
    with pytest.raises(TypeError):
        DataLoaderConfiguration(non_blocking=False)
    with pytest.raises(TypeError):
        Accelerator(step_scheduler_with_optimizer=False)


def test_fsdp_plugin_as_strategy():
    strat = ShardingStrategy.resolve(FsdpPlugin(min_weight_size=1))
    assert strat.fsdp.min_weight_size == 1


def test_zero2_is_documented_alias_of_zero1():
    import optax

    from accelerate_tpu.state import AcceleratorState

    shardings = {}
    for kind in ("ZERO1", "ZERO2"):
        AcceleratorState._reset_state()
        acc = Accelerator(seed=0, strategy=kind)
        state = acc.create_train_state(
            lambda r: {"w": jax.random.normal(r, (2048, 64))}, optax.adam(1e-3)
        )
        moment = jax.tree.leaves(state.opt_state)[1]  # adam mu for w
        shardings[kind] = (str(moment.sharding.spec), str(state.params["w"].sharding.spec))
    assert shardings["ZERO1"] == shardings["ZERO2"]
    # and both actually shard the moment (params stay replicated)
    assert "data" in shardings["ZERO2"][0]
    assert shardings["ZERO2"][1] == "PartitionSpec()"


def test_prepare_scheduler_adjusts_for_accumulation():
    # Reference semantics (`scheduler.py:62`): with adjust_scheduler=True the
    # LR schedule advances per microbatch, so at optimizer update k it reads
    # schedule(k * num_steps).
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import GradientAccumulationPlugin

    sched = optax.linear_schedule(1.0, 0.0, transition_steps=100)

    AcceleratorState._reset_state()
    acc = Accelerator(seed=0, gradient_accumulation_steps=4)
    adjusted = acc.prepare_scheduler(sched)
    for k in (0, 5, 25):
        np.testing.assert_allclose(adjusted(k), sched(k * 4))

    # adjust_scheduler=False (or accum == 1) passes through unchanged.
    AcceleratorState._reset_state()
    acc = Accelerator(
        seed=0,
        gradient_accumulation_plugin=GradientAccumulationPlugin(
            num_steps=4, adjust_scheduler=False
        ),
    )
    assert acc.prepare_scheduler(sched) is sched
    AcceleratorState._reset_state()
    acc = Accelerator(seed=0)
    assert acc.prepare_scheduler(sched) is sched


def test_sync_with_dataloader_false_rejected():
    from accelerate_tpu.utils.dataclasses import GradientAccumulationPlugin

    with pytest.raises(ValueError, match="sync_with_dataloader"):
        GradientAccumulationPlugin(num_steps=2, sync_with_dataloader=False)


def test_tensor_parallel_plugin_wires_plan_and_mesh():
    """TensorParallelPlugin(tp_size, plan) must actually size the mesh and
    select the named rule-set (not sit decoratively next to string
    selection)."""
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import (
        ShardingStrategyType,
        TensorParallelPlugin,
    )

    AcceleratorState._reset_state()
    acc = Accelerator(seed=0, strategy=TensorParallelPlugin(tp_size=2, plan="llama"))
    assert acc.mesh.shape["tensor"] == 2
    assert acc.strategy.kind is ShardingStrategyType.TENSOR_PARALLEL
    assert len(acc.strategy.rules) > 0

    # Plugin and explicit rules together is ambiguous -> loud error.
    from jax.sharding import PartitionSpec

    with pytest.raises(ValueError, match="not both"):
        ShardingStrategy.resolve(
            TensorParallelPlugin(plan="llama"),
            rules=(("w", PartitionSpec("tensor")),),
        )
    # No plan and no rules -> loud error (TP with nothing sharded is a lie).
    with pytest.raises(ValueError, match="sharding rules"):
        ShardingStrategy.resolve(TensorParallelPlugin(tp_size=2))


def test_tensor_parallel_plugin_mesh_mismatch_rejected():
    from accelerate_tpu.parallel import MeshConfig
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import TensorParallelPlugin

    AcceleratorState._reset_state()
    with pytest.raises(ValueError, match="tensor axis"):
        Accelerator(
            seed=0,
            mesh_config=MeshConfig(tensor=4),
            strategy=TensorParallelPlugin(tp_size=2, plan="llama"),
        )
    AcceleratorState._reset_state()


def test_save_on_each_node_writes_shared_artifacts_per_process(
    monkeypatch, tmp_path
):
    """With save_on_each_node=True a non-zero rank must write the
    process-agnostic artifacts (metadata/dataloader states) too — per-node
    filesystems get a self-contained directory."""
    import accelerate_tpu.checkpointing as ckpt
    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import ProjectConfiguration

    AcceleratorState._reset_state()
    acc = Accelerator(
        seed=0,
        project_config=ProjectConfiguration(save_on_each_node=True),
    )
    state = acc.create_train_state(regression_init, optax.sgd(0.1))
    monkeypatch.setattr(ckpt.jax, "process_index", lambda: 1)
    out = acc.save_state(str(tmp_path / "ck"), state)
    assert os.path.isfile(os.path.join(out, "metadata.json"))
    assert os.path.isfile(os.path.join(out, "rng_state_1.json"))
    assert os.path.isfile(os.path.join(out, "dataloaders.json"))


def test_param_and_output_dtype_consumed():
    """MixedPrecisionPolicy.param_dtype / output_dtype: None leaves dtypes
    alone (the bf16-weights recipe depends on that); set explicitly, they
    drive master-param and reported-metric dtypes."""
    import jax.numpy as jnp

    from accelerate_tpu.state import AcceleratorState
    from accelerate_tpu.utils.dataclasses import MixedPrecisionPolicy

    AcceleratorState._reset_state()
    acc = Accelerator(seed=0)
    # None default: params keep their init dtype.
    state = acc.create_train_state(regression_init, optax.sgd(0.1))
    init_dtypes = {str(l.dtype) for l in jax.tree.leaves(state.params)}

    AcceleratorState._reset_state()
    acc2 = Accelerator(seed=0)
    acc2.policy = MixedPrecisionPolicy(
        param_dtype=jnp.bfloat16, output_dtype=jnp.bfloat16
    )
    state2 = acc2.create_train_state(regression_init, optax.sgd(0.1))
    assert all(
        l.dtype == jnp.bfloat16
        for l in jax.tree.leaves(state2.params)
        if jnp.issubdtype(l.dtype, jnp.floating)
    )
    assert init_dtypes != {"bfloat16"}  # the cast actually changed something

    from accelerate_tpu.test_utils.training import regression_loss

    step = acc2.make_train_step(regression_loss)
    batch = {"x": jnp.ones((4,)), "y": jnp.zeros((4,))}
    _, metrics = step(state2, batch)
    assert metrics["loss"].dtype == jnp.bfloat16
    AcceleratorState._reset_state()
