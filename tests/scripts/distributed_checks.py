"""Driver script for REAL multi-process tests (jax.process_count() > 1).

Launched by tests/test_multiprocess.py via `accelerate-tpu launch
--num_processes N --host_devices K` on CPU — the analog of the reference's
subprocess-launched distributed scripts (`test_utils/scripts/test_script.py`,
driven from `tests/test_multigpu.py:50` with `accelerate launch`).

Modes:
- (default)   full check battery: identity, barriers, collectives, object
              channel, split_between_processes, end-to-end sharded training,
              multi-process checkpoint save/load.
- --mode mismatch   with ATX_DEBUG_MODE=1: feeds shape-mismatched inputs to a
              collective and asserts `verify_operation` catches it.

Every process must print its final OK line; the pytest wrapper asserts one
per rank plus exit code 0.
"""

import argparse
import os
import sys

# The launcher execs this file directly; put the repo root on the path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import jax
import jax.numpy as jnp
import optax

import accelerate_tpu as atx
from accelerate_tpu.ops import collectives as ops
from accelerate_tpu.state import ProcessState
from accelerate_tpu.test_utils.training import (
    RegressionDataset,
    regression_init,
    regression_loss,
)


def check_identity_and_barrier(ps: ProcessState) -> None:
    n_expected = int(os.environ["ATX_NUM_PROCESSES"])
    assert ps.num_processes == n_expected, (ps.num_processes, n_expected)
    assert ps.process_index == int(os.environ["ATX_PROCESS_ID"])
    assert jax.process_count() == n_expected
    assert ps.is_main_process == (ps.process_index == 0)
    ps.wait_for_everyone()


def check_collectives(ps: ProcessState) -> None:
    n, rank = ps.num_processes, ps.process_index

    g = ops.gather(np.full((2, 3), rank, np.float32))
    assert g.shape == (2 * n, 3), g.shape
    for r in range(n):
        assert (g[2 * r : 2 * r + 2] == r).all(), (r, g)

    r_sum = ops.reduce(np.float32([rank + 1.0]), "sum")
    assert float(r_sum[0]) == n * (n + 1) / 2

    r_mean = ops.reduce({"v": np.float32([2.0 * rank])}, "mean")
    assert float(r_mean["v"][0]) == float(np.mean([2.0 * i for i in range(n)]))

    b = ops.broadcast(
        np.arange(4, dtype=np.float32) * (1.0 if rank == 0 else -7.0)
    )
    assert (b == np.arange(4, dtype=np.float32)).all(), b

    b1 = ops.broadcast(np.full((3,), float(rank), np.float32), from_process=1)
    assert (b1 == 1.0).all(), b1

    padded = ops.pad_across_processes(np.ones((rank + 1, 2), np.float32))
    assert padded.shape == (n, 2), padded.shape


def check_object_channel(ps: ProcessState) -> None:
    n, rank = ps.num_processes, ps.process_index

    objs = ops.gather_object([{"rank": rank, "tag": f"p{rank}"}])
    assert [o["rank"] for o in objs] == list(range(n)), objs

    lst = ops.broadcast_object_list([f"root-payload-{rank}", rank * 10])
    assert lst == ["root-payload-0", 0], lst


def check_split_between_processes(ps: ProcessState) -> None:
    n, rank = ps.num_processes, ps.process_index
    items = list(range(2 * n + 1))
    with ps.split_between_processes(items) as chunk:
        local = list(chunk)
    sizes = ops.gather_object([len(local)])
    assert sum(sizes) == len(items), (sizes, items)
    flat = [x for part in ops.gather_object([local]) for x in part]
    assert flat == items, flat


def check_training_and_checkpoint(ps: ProcessState, ckpt_dir: str):
    acc = atx.Accelerator(seed=0)
    assert acc.num_processes == ps.num_processes
    state = acc.create_train_state(regression_init, optax.sgd(0.05))
    step = acc.make_train_step(regression_loss, donate=False)
    loader = acc.prepare_data_loader(RegressionDataset(length=64), batch_size=16)

    losses = []
    for epoch in range(4):
        for batch in loader:
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], (losses[0], losses[-1])

    # Params replicated under DP: every process must hold identical values.
    a_all = ops.gather_object([float(np.asarray(state.params["a"]))])
    assert max(a_all) - min(a_all) < 1e-6, a_all

    # Multi-process checkpoint round trip into one shared directory.
    acc.save_state(ckpt_dir, state)
    acc.wait_for_everyone()
    state2 = acc.create_train_state(regression_init, optax.sgd(0.05))
    state2 = acc.load_state(ckpt_dir, state2)
    assert int(state2.step) == int(state.step)
    np.testing.assert_allclose(
        np.asarray(state2.params["a"]), np.asarray(state.params["a"]), rtol=1e-6
    )
    gathered_metric = acc.gather(jnp.ones((2,)) * ps.process_index)
    assert gathered_metric.shape[0] >= ps.num_processes * 2
    return acc, state2


def check_dispatch_loader(ps: ProcessState) -> None:
    """dispatch_batches: rank 0 reads the dataset, other ranks receive each
    batch over the object channel (reference `DataLoaderDispatcher`,
    `data_loader.py:696`) — every rank must see identical global batches."""
    from accelerate_tpu.utils.dataclasses import DataLoaderConfiguration

    class MainOnlyDataset:
        """Readable only on rank 0 — proves no other rank touches the data."""

        def __len__(self) -> int:
            return 24

        def __getitem__(self, i: int) -> dict:
            if ps.process_index != 0:
                raise AssertionError("dataset read on a non-main process")
            return {"x": np.float32([i])}

    loader = atx.DataLoader(
        MainOnlyDataset(),
        batch_size=2,
        config=DataLoaderConfiguration(dispatch_batches=True, prefetch_size=0),
    )
    seen = []
    for batch in loader:
        x = batch["x"]
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # Each rank holds only its shards of the global batch.
            local = np.concatenate(
                [np.asarray(s.data).ravel() for s in x.addressable_shards]
            )
        else:
            local = np.asarray(x).ravel()
        seen.append(local.tolist())
    assert seen, "dispatch loader yielded nothing"
    # The union of every rank's shards per step must cover the whole dataset
    # exactly (dispatch delivered every sample to exactly one device slot,
    # modulo the even_batches wraparound duplicates).
    all_seen = ops.gather_object([seen])
    flat = [v for rank_seen in all_seen for step_vals in rank_seen for v in step_vals]
    expected = {float(i) for i in range(24)}
    assert set(flat) == expected, sorted(set(flat) ^ expected)
    assert len(flat) >= 24


def check_iterable_dispatch(ps: ProcessState) -> None:
    """Iterable datasets default to dispatch mode (reference
    `data_loader.py:1085-1089`): per-process streams may diverge, so rank 0's
    stream is authoritative. A rank-dependent stream proves it: every rank
    must observe rank 0's values. Then shard mode (explicit
    dispatch_batches=False) with ATX_DEBUG_MODE must catch the divergence."""
    from accelerate_tpu.ops.collectives import DistributedOperationException
    from accelerate_tpu.utils.dataclasses import DataLoaderConfiguration

    class DivergentStream:
        """Yields values offset by the process index — a stand-in for any
        unseeded/network-backed stream that differs per process."""

        def __iter__(self):
            base = ps.process_index * 1000
            for i in range(8):
                yield {"x": np.float32([base + i])}

    # Default config: dispatch_batches=None -> True for iterables.
    loader = atx.DataLoader(
        DivergentStream(), batch_size=2, config=DataLoaderConfiguration(prefetch_size=0)
    )
    got = []
    for batch in loader:
        x = batch["x"]
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            local = np.concatenate(
                [np.asarray(s.data).ravel() for s in x.addressable_shards]
            )
        else:
            local = np.asarray(x).ravel()
        got.extend(local.tolist())
    # Rank 0's stream is [0..7]; each rank holds its own SHARD of the global
    # batch, so no rank may see values >= 1000 (its own divergent stream) and
    # the union across ranks must reproduce rank 0's stream exactly.
    assert got and all(v < 1000 for v in got), got
    all_got = ops.gather_object([got])
    union = sorted(v for g in all_got for v in g)
    assert union == [float(i) for i in range(8)], union

    # Shard mode + debug: the first-batch digest check must fire on the
    # divergent stream with actionable guidance.
    old_debug = ps.debug
    ps.debug = True
    try:
        loader = atx.DataLoader(
            DivergentStream(),
            batch_size=2,
            config=DataLoaderConfiguration(dispatch_batches=False, prefetch_size=0),
        )
        try:
            next(iter(loader))
        except DistributedOperationException as e:
            assert "DIVERGE" in str(e)
        else:
            raise AssertionError("divergent shard-mode stream not detected")
    finally:
        ps.debug = old_debug
    ps.wait_for_everyone()


def check_gather_for_metrics(
    ps: ProcessState, acc: "atx.Accelerator", state: "atx.TrainState"
) -> None:
    """Ragged eval: the wraparound duplicates on the final global batch must
    be trimmed to exactly one prediction per dataset sample."""
    eval_step = acc.make_eval_step(lambda p, b: p["a"] * b["x"] + p["b"])
    total = 4 * ps.num_processes + 2  # ragged tail
    loader = acc.prepare_data_loader(
        RegressionDataset(length=total, seed=3), batch_size=4
    )
    preds = []
    for batch in loader:
        preds.append(np.asarray(acc.gather_for_metrics(eval_step(state, batch))))
    n_preds = int(np.concatenate(preds).shape[0])
    assert n_preds == total, (n_preds, total)


def run_sharded_mode(ps: ProcessState, kind: str, ckpt_dir: str) -> None:
    """The pod regime: FSDP / TP training where every
    param is a *global non-addressable* array spanning process boundaries,
    with per-host shard I/O in save_state/load_state and loss parity against
    a single-device reference run of the same math."""
    from accelerate_tpu.data.loader import _form_global_batch
    from accelerate_tpu.models import llama
    from accelerate_tpu.utils.dataclasses import FsdpPlugin

    n_proc = ps.num_processes
    n_dev = len(jax.devices())
    config = llama.LlamaConfig.tiny()
    if kind == "fsdp":
        # data axis across processes, fsdp within each host's 4 devices.
        acc = atx.Accelerator(
            seed=0,
            mesh_config=atx.MeshConfig(data=n_proc, fsdp=n_dev // n_proc),
            strategy=FsdpPlugin(min_weight_size=1),
        )
        want_axis = "fsdp"
    else:
        acc = atx.Accelerator(
            seed=0,
            mesh_config=atx.MeshConfig(data=n_dev // 2, tensor=2),
            strategy=atx.TensorParallelPlugin(tp_size=2, plan="llama"),
        )
        want_axis = "tensor"

    state = acc.create_train_state(
        lambda r: llama.init(r, config), optax.adamw(1e-2)
    )
    leaves = jax.tree.leaves(state.params)
    # Params must be true global arrays: no process holds all shards.
    assert any(not l.is_fully_addressable for l in leaves), kind
    assert any(want_axis in str(l.sharding.spec) for l in leaves), [
        str(l.sharding.spec) for l in leaves[:4]
    ]

    step = acc.make_train_step(
        lambda p, b, r: llama.loss_fn(p, b, config, r), donate=False
    )
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, config.vocab_size, size=(8, 16)).astype(np.int32)
    batch = _form_global_batch({"input_ids": tokens}, acc.mesh)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))

    # Loss parity: the same model + batch on ONE local device, plain optax.
    ref_params = llama.init(jax.random.PRNGKey(0), config)
    ref_tx = optax.adamw(1e-2)
    ref_opt = ref_tx.init(ref_params)
    ref_losses = []

    @jax.jit
    def ref_step(params, opt):
        def loss_fn(p):
            return llama.loss_fn(p, {"input_ids": jnp.asarray(tokens)}, config, None)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = ref_tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    for _ in range(5):
        ref_params, ref_opt, ref_loss = ref_step(ref_params, ref_opt)
        ref_losses.append(float(ref_loss))
    # Same seed/init + same global batch => identical trajectories modulo
    # reduction order. (create_train_state seeds with acc.rng == PRNGKey(0)
    # after seed=0 -> set_seed; both sides must start from the same init.)
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-3, atol=2e-4)

    # Sharded checkpoint round trip across process boundaries.
    acc.save_state(ckpt_dir, state)
    acc.wait_for_everyone()
    state2 = acc.create_train_state(
        lambda r: llama.init(r, config), optax.adamw(1e-2)
    )
    state2 = acc.load_state(ckpt_dir, state2)
    assert int(jax.device_get(state2.step)) == 5
    # Compare a sharded leaf by fetching each process's addressable shards
    # and checking them against the pre-save state.
    for l_old, l_new in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(state2.params)
    ):
        for s_old, s_new in zip(l_old.addressable_shards, l_new.addressable_shards):
            np.testing.assert_allclose(
                np.asarray(s_old.data), np.asarray(s_new.data), rtol=1e-6
            )
    # And the restored state trains on.
    state2, metrics = step(state2, batch)
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
    ps.wait_for_everyone()
    print(f"[proc {ps.process_index}] SHARDED {kind.upper()} OK", flush=True)


def run_longcontext_mode(ps: ProcessState, kind: str) -> None:
    """Sequence/expert parallelism with the axis SPANNING the process
    boundary: 2 processes × 4 devices with sequence=8 (the
    KV ring's ppermute hops cross hosts) or expert=8 (the MoE dispatch
    all-to-all crosses hosts), trained for 5 steps with loss parity against
    a single-device oracle of the same math — not just a finite-loss check."""
    from accelerate_tpu.data.loader import _form_global_batch
    from accelerate_tpu.models import llama
    from accelerate_tpu.parallel.tp import get_tp_plan

    n_dev = len(jax.devices())
    if kind == "ring":
        config = llama.LlamaConfig.tiny(attention_impl="ring")
        mesh_config = atx.MeshConfig(data=1, sequence=n_dev)
        span_axis = "sequence"
    else:
        config = llama.LlamaConfig.tiny(n_experts=n_dev, moe_top_k=2)
        mesh_config = atx.MeshConfig(data=1, expert=n_dev)
        span_axis = "expert"
    acc = atx.Accelerator(
        seed=0,
        mesh_config=mesh_config,
        strategy="HYBRID",
        sharding_rules=get_tp_plan("llama"),
    )
    # The parallel axis must genuinely cross the process boundary: one
    # axis GROUP contains devices owned by both processes.
    from accelerate_tpu.parallel.mesh import MESH_AXES

    axis_idx = MESH_AXES.index(span_axis)
    groups = np.moveaxis(acc.mesh.devices, axis_idx, -1).reshape(
        -1, acc.mesh.shape[span_axis]
    )
    owners = {d.process_index for d in groups[0]}
    assert len(owners) == ps.num_processes, (span_axis, owners)

    state = acc.create_train_state(
        lambda r: llama.init(r, config), optax.adamw(1e-2)
    )
    if kind == "moe":
        # Expert weights are global non-addressable arrays sharded over the
        # spanning axis.
        moe_leaf = state.params["blocks"]["moe"]["w_gate"]
        assert not moe_leaf.is_fully_addressable
        assert "expert" in str(moe_leaf.sharding.spec), moe_leaf.sharding.spec

    step = acc.make_train_step(
        lambda p, b, r: llama.loss_fn(p, b, config, r), donate=False
    )
    rng = np.random.RandomState(11)
    tokens = rng.randint(0, config.vocab_size, size=(8, 32)).astype(np.int32)
    batch = _form_global_batch({"input_ids": tokens}, acc.mesh)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))

    # Single-device oracle: same init/seed/batch; ring attention is exact,
    # so the oracle uses plain dot attention; the MoE math is identical.
    import dataclasses as _dc

    ref_config = (
        _dc.replace(config, attention_impl="dot") if kind == "ring" else config
    )
    ref_params = llama.init(jax.random.PRNGKey(0), ref_config)
    ref_tx = optax.adamw(1e-2)
    ref_opt = ref_tx.init(ref_params)

    @jax.jit
    def ref_step(params, opt):
        def loss_fn(p):
            return llama.loss_fn(
                p, {"input_ids": jnp.asarray(tokens)}, ref_config, None
            )

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt = ref_tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, loss

    ref_losses = []
    for _ in range(5):
        ref_params, ref_opt, ref_loss = ref_step(ref_params, ref_opt)
        ref_losses.append(float(ref_loss))
    np.testing.assert_allclose(losses, ref_losses, rtol=2e-3, atol=2e-4)
    ps.wait_for_everyone()
    print(f"[proc {ps.process_index}] LONGCTX {kind.upper()} OK", flush=True)


def run_mismatch_mode(ps: ProcessState) -> None:
    assert ps.debug, "mismatch mode requires ATX_DEBUG_MODE=1"
    shape = (2,) if ps.process_index == 0 else (3,)
    try:
        ops.gather(np.ones(shape, np.float32))
    except ops.DistributedOperationException as e:
        assert "Mismatch" in str(e)
        print(f"[proc {ps.process_index}] MISMATCH DETECTED OK", flush=True)
        return
    raise AssertionError("verify_operation failed to flag a shape mismatch")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--mode",
        default="all",
        choices=["all", "mismatch", "fsdp", "tp", "ring", "moe"],
    )
    parser.add_argument("--ckpt_dir", default="")
    args = parser.parse_args()

    ps = ProcessState()
    if args.mode == "mismatch":
        run_mismatch_mode(ps)
        return 0
    if args.mode in ("fsdp", "tp"):
        run_sharded_mode(ps, args.mode, args.ckpt_dir)
        return 0
    if args.mode in ("ring", "moe"):
        run_longcontext_mode(ps, args.mode)
        return 0

    check_identity_and_barrier(ps)
    check_collectives(ps)
    check_object_channel(ps)
    check_split_between_processes(ps)
    check_dispatch_loader(ps)
    check_iterable_dispatch(ps)
    if args.ckpt_dir:
        acc, trained_state = check_training_and_checkpoint(ps, args.ckpt_dir)
        check_gather_for_metrics(ps, acc, trained_state)
    ps.wait_for_everyone()
    print(f"[proc {ps.process_index}] ALL OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
