"""Compiled-HLO verification of the sharding strategies.

The strategy claims (`utils/dataclasses.py:54-59`, `parallel/sharding.py`)
are that GSPMD lowers each strategy's train step to the right collectives —
here each strategy's step is compiled on the 8-device CPU mesh and the
optimized HLO text plus output shardings are asserted directly, so a spec
typo that silently replicates a sharded array can never pass CI again.

Backend note: XLA:CPU expresses reduce-scatter as all-reduce+dynamic-slice
(or all-to-all) rather than a fused reduce-scatter op; the assertions accept
any of those spellings of the same semantics.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.heavy  # compile-heavy / subprocess lane

from accelerate_tpu.accelerator import Accelerator
from accelerate_tpu.parallel import MeshConfig
from accelerate_tpu.parallel.mesh import batch_sharding
from accelerate_tpu.parallel.tp import get_tp_plan
from accelerate_tpu.state import AcceleratorState

COLLECTIVES = r"(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)"


def _compiled(strategy, mesh_config, *, sharding_rules=()):
    AcceleratorState._reset_state()
    acc = Accelerator(
        seed=0, strategy=strategy, mesh_config=mesh_config, sharding_rules=sharding_rules
    )
    state = acc.create_train_state(
        lambda r: {
            "w1": jax.random.normal(r, (512, 512)),
            "w2": jax.random.normal(r, (512, 512)),
        },
        optax.adam(1e-3),
    )

    def loss(p, b, rng):
        h = jnp.tanh(b["x"] @ p["w1"])
        return jnp.mean((h @ p["w2"] - b["y"]) ** 2)

    step = acc.make_train_step(loss)
    batch = jax.device_put(
        {"x": np.ones((16, 512), np.float32), "y": np.ones((16, 512), np.float32)},
        batch_sharding(acc.mesh),
    )
    txt = step.lower(state, batch).compile().as_text()
    return acc, state, step, batch, txt


def _ops(txt):
    return set(re.findall(COLLECTIVES, txt))


def _reduce_scatter_equivalent(txt):
    """XLA:CPU spells reduce-scatter as all-reduce+dynamic-slice/all-to-all."""
    return (
        "reduce-scatter" in txt
        or ("all-reduce" in txt and "dynamic-slice" in txt)
        or "all-to-all" in txt
    )


class TestFSDP:
    def test_gathers_params_and_scatters_grads(self):
        acc, state, step, batch, txt = _compiled("FSDP", MeshConfig(data=1, fsdp=8))
        # ZeRO-3 signature: params gathered per use, gradients scattered back
        # to shards — never a bare data-parallel all-reduce alone.
        assert "all-gather" in txt, _ops(txt)
        assert _reduce_scatter_equivalent(txt), _ops(txt)
        # State arrays must STAY sharded through the step (no silent
        # replication — the memory story of FSDP).
        new_state, _ = step(state, batch)
        assert "fsdp" in str(new_state.params["w1"].sharding.spec)
        for leaf in jax.tree.leaves(new_state.opt_state):
            if leaf.shape == (512, 512):
                assert "fsdp" in str(leaf.sharding.spec)


class TestZero1:
    def test_shards_optimizer_update(self):
        acc, state, step, batch, txt = _compiled("ZERO1", MeshConfig(data=8))
        # ZeRO-1 signature: grads all-reduced, each device updates only its
        # OWN shard of the moments (dynamic-slice), new params reassembled
        # (all-gather). A fully-replicated update would show none of the
        # slice/gather structure.
        assert "all-reduce" in txt, _ops(txt)
        assert _reduce_scatter_equivalent(txt), _ops(txt)
        assert "all-gather" in txt, _ops(txt)
        new_state, _ = step(state, batch)
        # Params replicated (ZeRO-1 keeps full params), moments sharded.
        assert new_state.params["w1"].sharding.spec == jax.sharding.PartitionSpec()
        sharded_moments = [
            leaf
            for leaf in jax.tree.leaves(new_state.opt_state)
            if leaf.shape == (512, 512)
        ]
        assert sharded_moments
        for leaf in sharded_moments:
            assert "data" in str(leaf.sharding.spec)

    def test_zero2_compiles_to_the_same_program(self):
        # The ZERO2 alias claim (`utils/dataclasses.py:54-59`): identical
        # XLA program, asserted at the strongest possible level.
        *_, txt1 = _compiled("ZERO1", MeshConfig(data=8))
        *_, txt2 = _compiled("ZERO2", MeshConfig(data=8))

        def strip(t):
            # Drop source-location metadata (differs per trace site) and
            # whitespace; keep every op, shape, and sharding annotation.
            t = re.sub(r"metadata=\{[^}]*\}", "", t)
            t = re.sub(r"\{[^}]*file_name_id[^}]*\}", "", t)
            t = re.sub(r"#.*", "", t)
            return re.sub(r"\s+", " ", t)

        assert strip(txt1) == strip(txt2)


class TestTensorParallel:
    def test_activation_reductions_params_stay_sharded(self):
        from accelerate_tpu.models import llama

        AcceleratorState._reset_state()
        acc = Accelerator(
            seed=0,
            strategy="TENSOR_PARALLEL",
            mesh_config=MeshConfig(data=1, tensor=8),
            sharding_rules=get_tp_plan("llama"),
        )
        config = llama.LlamaConfig.tiny(num_heads=8, num_kv_heads=8)
        state = acc.create_train_state(
            lambda r: llama.init(r, config), optax.adam(1e-3)
        )
        step = acc.make_train_step(
            lambda p, b, r: llama.loss_fn(p, b, config)
        )
        batch = jax.device_put(
            {"input_ids": np.ones((8, 16), np.int32)}, batch_sharding(acc.mesh)
        )
        txt = step.lower(state, batch).compile().as_text()
        # Megatron signature: partial activations reduced (all-reduce /
        # reduce-scatter) — and the weights themselves never move.
        assert "all-reduce" in txt or "reduce-scatter" in txt, _ops(txt)
        new_state, _ = step(state, batch)
        wq = new_state.params["blocks"]["attn"]["wq"]
        assert "tensor" in str(wq.sharding.spec)
        # A TP weight must hold exactly 1/8 of the elements per device.
        assert wq.addressable_shards[0].data.size * 8 == wq.size


class TestHybrid:
    def test_data_and_fsdp_axes_compose(self):
        acc, state, step, batch, txt = _compiled("HYBRID", MeshConfig(data=2, fsdp=4))
        assert "all-gather" in txt, _ops(txt)
        assert _reduce_scatter_equivalent(txt), _ops(txt)
        new_state, _ = step(state, batch)
        assert "fsdp" in str(new_state.params["w1"].sharding.spec)


class TestCompileStability:
    @pytest.mark.parametrize(
        "strategy,mc",
        [
            ("FSDP", MeshConfig(data=1, fsdp=8)),
            ("ZERO1", MeshConfig(data=8)),
            ("HYBRID", MeshConfig(data=2, fsdp=4)),
        ],
    )
    def test_state_round_trip_does_not_recompile(self, strategy, mc):
        # The output-sharding constraint pins the state to its planned
        # layout; a second compile on the state round-trip means the
        # constraint and the input layout disagree.
        acc, state, step, batch, _ = _compiled(strategy, mc)
        for _ in range(3):
            state, _ = step(state, batch)
        assert step._cache_size() == 1


class TestSpmdWarningClean:
    """The dryrun's phases must compile without involuntary SPMD resharding.

    A multi-chip dry run once passed with repeated "[SPMD]
    Involuntary full rematerialization" warnings — the embed table's D dim
    was sharded over fsdp, colliding with the batch-over-(data,fsdp)
    activation constraint (fixed in `parallel/tp.py`; the plans now shard
    table ROWS over (tensor, fsdp)). These tests compile the same steps
    under fd-2 capture so the regression can never pass silently again;
    `__graft_entry__.dryrun_multichip` applies the same guard at driver time.
    """

    def _compile_family_step(self, family, mesh_config, **config_overrides):
        from __graft_entry__ import _fail_on_spmd_warnings
        from accelerate_tpu.models import gpt, llama, t5

        mod = {"llama": llama, "gpt": gpt, "t5": t5}[family]
        config = {
            "llama": llama.LlamaConfig,
            "gpt": gpt.GPTConfig,
            "t5": t5.T5Config,
        }[family].tiny(**config_overrides)
        batch = {"input_ids": jnp.zeros((8, 32), jnp.int32)}
        if family == "t5":
            batch["decoder_input_ids"] = jnp.zeros((8, 32), jnp.int32)
        with _fail_on_spmd_warnings():
            acc = Accelerator(
                seed=0,
                strategy="HYBRID",
                mesh_config=mesh_config,
                sharding_rules=get_tp_plan(family),
                mixed_precision="bf16",
            )
            state = acc.create_train_state(
                lambda r: mod.init(r, config), optax.adamw(1e-3)
            )
            step = acc.make_train_step(
                lambda p, b, r: mod.loss_fn(p, b, config, r)
            )
            step.lower(state, batch).compile()

    @pytest.mark.parametrize("family", ["llama", "gpt", "t5"])
    def test_hybrid_3d_step_compiles_warning_free(self, family):
        # Every plan whose embed sharding changed (llama/gpt/t5) compiles
        # clean on the 3-D mesh that used to trigger the rematerialization.
        self._compile_family_step(family, MeshConfig(data=2, fsdp=2, tensor=2))

    def test_sequence_expert_step_compiles_warning_free(self):
        self._compile_family_step(
            "llama",
            MeshConfig(data=2, sequence=2, expert=2),
            n_experts=2,
            attention_impl="ring",
        )

    def test_capture_detects_planted_warning(self):
        import os as _os

        from __graft_entry__ import _fail_on_spmd_warnings

        with pytest.raises(RuntimeError, match="SPMD partitioner warning"):
            with _fail_on_spmd_warnings():
                _os.write(
                    2,
                    b"W0000 00:00:00.0 0 spmd_partitioner.cc:652] [SPMD] "
                    b"Involuntary full rematerialization. (planted)\n",
                )
