"""Compile a train cell's step at its real size for a described v5e.

The TPU's compiler is installed where there is no chip: it compiles for a
`v5e:2x2` that is described, not attached, from shapes alone. What does not
lower, does not partition or does not fit 15.75 GiB is refused here, and
`memory_analysis()` says how much a device holds. Nothing runs: no time, no
result, and never a chip run. (`check_correct.py --compile`.)
"""

from __future__ import annotations

import json
import os
import re
import time

_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(?:-start)?\("
)


def main(name: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec

    # Entries written for a described chip cannot be read back without one.
    jax.config.update("jax_enable_compilation_cache", False)

    import accelerate_tpu as atx
    import accelerate_tpu.native.pallas.dispatch as dispatch
    import accelerate_tpu.ops.flash_attention as flash
    from accelerate_tpu.accelerator import TrainState
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.mesh import batch_sharding
    from accelerate_tpu.parallel.sharding import to_named_shardings

    from . import harness, program

    # `jax.default_backend()` is still the CPU here: steer the two places
    # that ask it onto their chip branch (the verify skill's recipe).
    flash._interpret_default = lambda: False
    dispatch._on_tpu = lambda: True

    entry, cell, config = harness.find_cell(harness.benchmark_file(), name)
    if "recipe" not in cell:
        raise SystemExit("bench: --compile rehearses train cells only (PERF.md, open questions)")
    recipe, traffic = cell["recipe"], cell["traffic"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    lcfg = program.llama_config(
        config, max_seq_len=traffic["seq_len"], remat=True, remat_policy=recipe["remat_policy"],
        attention_impl=recipe["attention_impl"], loss_chunk_size=recipe["loss_chunk_size"],
    )
    mesh, kwargs = dict(recipe.get("mesh", {})), {}
    if mesh:
        from accelerate_tpu.parallel.tp import get_tp_plan

        kwargs = {"sharding_rules": get_tp_plan(recipe["tp_plan"]), "strategy": recipe["strategy"]}
    acc = atx.Accelerator(
        mixed_precision=recipe["mixed_precision"], seed=0, max_grad_norm=recipe["max_grad_norm"],
        mesh_config=atx.MeshConfig(devices=list(topo.devices[: entry["chips"]]), **mesh), **kwargs,
    )
    tx = optax.adafactor(recipe["learning_rate"])
    params = jax.eval_shape(lambda r: program.init_bf16_params(r, lcfg), acc.rng)
    param_specs, opt_specs = acc._resolve_specs(params, tx)
    opt = jax.eval_shape(tx.init, params)
    replicated = NamedSharding(acc.mesh, PartitionSpec())

    def described(shape, sharding):
        return jax.ShapeDtypeStruct(shape.shape, shape.dtype, sharding=sharding)

    state = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
        params=jax.tree.map(described, params, to_named_shardings(param_specs, acc.mesh)),
        opt_state=jax.tree.map(described, opt, to_named_shardings(opt_specs, acc.mesh)),
        apply_fn=None, tx=tx, loss_scale=None,
    )
    step = acc.make_train_step(lambda p, b, r: llama.loss_fn(p, b, lcfg, r))
    batch = {
        "input_ids": jax.ShapeDtypeStruct(
            (traffic["batch_size"], traffic["seq_len"]), jnp.int32, sharding=batch_sharding(acc.mesh)
        )
    }
    t0 = time.time()
    compiled = step.lower(state, batch).compile()
    m = compiled.memory_analysis()
    text = compiled.as_text()
    gib = 2.0**30
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    print("COMPILE-REHEARSAL (described v5e, nothing ran) " + json.dumps({
        "workload": name, "layers": lcfg.n_layers, "batch": traffic["batch_size"],
        "seq_len": traffic["seq_len"], "chips": entry["chips"], "mesh": mesh,
        "compile_s": round(time.time() - t0, 1),
        "arguments_gib": m.argument_size_in_bytes / gib, "temporaries_gib": m.temp_size_in_bytes / gib,
        "per_device_total_gib": total / gib, "fits_15.75_gib": total / gib < 15.75,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "collectives": sorted(set(_COLLECTIVE.findall(text))),
    }))
    return 0
