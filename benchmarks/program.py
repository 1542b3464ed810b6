"""The one place where the benchmark touches the program under test.

Everything the benchmark takes from `accelerate_tpu` comes through here: the
model family (`models/llama.py`, which runs both Mistral and Qwen2), the
trainer (`Accelerator`), the engine (`serving.Engine`) and the layout of
their parameters. The yardstick (traffic, reference, trace reduction,
metric arithmetic) imports none of it.
"""

from __future__ import annotations

import json
import os
from typing import Any

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)


def load_json(*parts: str) -> dict[str, Any]:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def jax_seed(seed: int) -> int:
    """``--seed`` may exceed 32 signed bits; JAX keys take what fits."""
    return int(seed) % (2**31 - 1)


def llama_config(config: dict[str, Any], **overrides: Any):
    """A published `config.json` (HF key names) as the program's
    `LlamaConfig`. Nothing is defaulted: a missing key is an error."""
    from accelerate_tpu.models.llama import LlamaConfig

    if config.get("sliding_window") and config.get("use_sliding_window", True):
        overrides.setdefault("sliding_window", config["sliding_window"])
    return LlamaConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"],
        head_dim=config.get("head_dim"),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        attn_bias=bool(config["program"]["qkv_bias"]),
        **overrides,
    )


def init_bf16_params(rng, lcfg):
    """The family's own init in bf16. It zeroes the q/k/v biases; a Qwen
    checkpoint's are not zero, and a zero bias would let a dropped bias pass
    the comparison with the reference, so they are drawn too."""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import llama

    k_init, k_bias = jax.random.split(rng)
    params = llama.init(k_init, lcfg, dtype=jnp.bfloat16)
    if lcfg.attn_bias:
        attn = params["blocks"]["attn"]
        for name, key in zip(("bq", "bk", "bv"), jax.random.split(k_bias, 3)):
            attn[name] = (0.1 * jax.random.normal(key, attn[name].shape)).astype(jnp.bfloat16)
    return params


def init_int8_params(rng, lcfg):
    """Params with int8 block weights (`utils.quantization` nodes), made on
    the device one layer at a time in one jitted call: the bf16 copy of the
    blocks, twice the int8 bytes, never exists. Embeddings, head and norms
    stay bf16, as `load_pretrained(quantize_bits=8)` leaves them. (A copy of
    `chip_smoke.init_int8_params`: the yardstick does not move when that
    file does.)"""
    import jax
    import jax.numpy as jnp

    from accelerate_tpu.models import llama
    from accelerate_tpu.utils.quantization import quantize_pytree

    def build(rng):
        k_top, k_blocks = jax.random.split(rng)
        top = {
            k: v for k, v in llama.init(k_top, lcfg, jnp.bfloat16).items() if k != "blocks"
        }

        def one_layer(key):
            return quantize_pytree(
                llama.init_block(key, lcfg, jnp.bfloat16), stack_dim_patterns=(("", 0),)
            )

        top["blocks"] = jax.lax.map(one_layer, jax.random.split(k_blocks, lcfg.n_layers))
        return top

    return jax.jit(build)(rng)


# ------------------------------------------------ parameters for the reference
def _plain(leaf):
    """A stored leaf as float32: an int8 node is values x scales."""
    import jax.numpy as jnp

    if isinstance(leaf, dict):
        (values,) = (v for k, v in leaf.items() if k != "scale")
        return values.astype(jnp.float32) * leaf["scale"].astype(jnp.float32)
    return leaf.astype(jnp.float32)


def reference_weights(params, lcfg, device):
    """`(get_layer, top)` for `reference.decoder.Decoder` from the program's
    parameter tree, gathered onto ``device``: heads flattened into the
    output axis, norm scales stored as ``g - 1`` turned back into ``g``,
    int8 nodes dequantized. One layer is cast at a time."""
    import jax
    from jax.sharding import SingleDeviceSharding

    here = SingleDeviceSharding(device)
    D = lcfg.d_model

    def layer(blocks, i):
        b = jax.tree.map(lambda a: a[i], blocks)
        attn = {k: _plain(v) for k, v in b["attn"].items()}
        out = {
            "input_layernorm": 1.0 + _plain(b["attn_norm"]),
            "post_attention_layernorm": 1.0 + _plain(b["mlp_norm"]),
            "q_proj": attn["wq"].reshape(D, -1),
            "k_proj": attn["wk"].reshape(D, -1),
            "v_proj": attn["wv"].reshape(D, -1),
            "o_proj": attn["wo"].reshape(-1, D),
            "gate_proj": _plain(b["mlp"]["w_gate"]),
            "up_proj": _plain(b["mlp"]["w_up"]),
            "down_proj": _plain(b["mlp"]["w_down"]),
        }
        if lcfg.attn_bias:
            for ours, theirs in (("bq", "q_bias"), ("bk", "k_bias"), ("bv", "v_bias")):
                out[theirs] = attn[ours].reshape(-1)
        return out

    blocks = params["blocks"]
    spread = len(params["embed"].devices()) > 1
    if not spread:
        layer_fn = jax.jit(layer)
        top = {
            "embed_tokens": params["embed"],
            "lm_head": params["lm_head"],
            "norm": 1.0 + _plain(params["final_norm"]),
        }
        return (lambda i: layer_fn(blocks, i)), top
    # Sharded over a mesh: gather on the mesh (replicated output), then
    # keep the first device's copy.
    from jax.sharding import NamedSharding, PartitionSpec

    everywhere = NamedSharding(params["embed"].sharding.mesh, PartitionSpec())
    layer_fn = jax.jit(layer, out_shardings=everywhere)
    top = {
        "embed_tokens": _IndexedOnto(params["embed"], everywhere, here),
        "lm_head": _IndexedOnto(params["lm_head"], everywhere, here),
        "norm": jax.device_put(1.0 + _plain(params["final_norm"]), here),
    }
    return (lambda i: jax.device_put(layer_fn(blocks, i), here)), top


def norm_scales(params) -> dict:
    """The program's norm weights on the host in float32, under the
    reference's names and shapes (`Decoder.loss_and_grad_norm`'s
    ``"norm_grads"``). Stored as ``g - 1``: a difference of two readings,
    which is all they are used for, is the same either way."""
    import numpy as np

    blocks = params["blocks"]
    return {
        "input_layernorm": np.asarray(blocks["attn_norm"], np.float32),
        "post_attention_layernorm": np.asarray(blocks["mlp_norm"], np.float32),
        "norm": np.asarray(params["final_norm"], np.float32),
    }


class _IndexedOnto:
    """A sharded table of the program's, read as ``table[index]`` onto one
    device: the reference takes rows of the embedding and column blocks of
    the head, never a whole gathered table."""

    def __init__(self, table, everywhere, here):
        self.table, self.everywhere, self.here, self._slicers = table, everywhere, here, {}

    def __getitem__(self, index):
        import jax

        if isinstance(index, tuple):  # static slices
            key = repr(index)
            if key not in self._slicers:
                self._slicers[key] = jax.jit(lambda t: t[index], out_shardings=self.everywhere)
            return jax.device_put(self._slicers[key](self.table), self.here)
        if "rows" not in self._slicers:
            self._slicers["rows"] = jax.jit(lambda t, i: t[i], out_shardings=self.everywhere)
        return jax.device_put(self._slicers["rows"](self.table, index), self.here)


# --------------------------------------------------------------- systems
def build_trainer(config: dict, cell: dict, seed: int, devices):
    """`Accelerator`, the train state for ``seed``, a maker of further
    states from other seeds, and the compiled-step callable for a train cell: bf16 weights + adafactor, flash attention, remat, chunked loss,
    gradient clipping (the recipe of the cell's file)."""
    import jax
    import optax

    import accelerate_tpu as atx
    from accelerate_tpu.models import llama
    from accelerate_tpu.state import AcceleratorState

    recipe = cell["recipe"]
    seq_len = cell["traffic"]["seq_len"]
    lcfg = llama_config(
        config,
        max_seq_len=seq_len,
        remat=True,
        remat_policy=recipe["remat_policy"],
        attention_impl=recipe["attention_impl"],
        loss_chunk_size=recipe["loss_chunk_size"],
    )
    mesh = dict(recipe.get("mesh", {}))
    kwargs = {}
    if mesh:
        from accelerate_tpu.parallel.tp import get_tp_plan

        kwargs = {"sharding_rules": get_tp_plan(recipe["tp_plan"]), "strategy": recipe["strategy"]}
    AcceleratorState._reset_state()
    acc = atx.Accelerator(
        mixed_precision=recipe["mixed_precision"],
        seed=jax_seed(seed),
        max_grad_norm=recipe["max_grad_norm"],
        mesh_config=atx.MeshConfig(devices=list(devices), **mesh),
        **kwargs,
    )
    if recipe["optimizer"] != "adafactor":
        raise ValueError(f"unknown optimizer {recipe['optimizer']!r}")
    tx = optax.adafactor(recipe["learning_rate"])

    def init_fn(rng):
        return init_bf16_params(rng, lcfg)

    def new_state(seed: int):
        return acc.create_train_state(init_fn, tx, rng=jax.random.PRNGKey(jax_seed(seed)))

    # The step takes its shardings from the plan `create_train_state`
    # makes, so the first state is made before the step is built.
    state = new_state(seed)
    step = acc.make_train_step(lambda p, b, r: llama.loss_fn(p, b, lcfg, r))
    return acc, state, new_state, step, lcfg


def build_engine(config: dict, cell: dict, seed: int, device):
    """`serving.Engine` as `atx serve` builds it, on int8 block weights made
    on ``device`` from the seed."""
    import jax

    from accelerate_tpu import serving
    from accelerate_tpu.generation import GenerationConfig
    from accelerate_tpu.models import llama
    from accelerate_tpu.ops.int8 import with_int8_compute

    deploy = cell["engine"]
    lcfg = llama_config(config, max_seq_len=deploy["max_len"])
    if deploy["weights"] != "int8":
        raise ValueError(f"unknown weight format {deploy['weights']!r}")
    with jax.default_device(device):
        params = init_int8_params(jax.random.PRNGKey(jax_seed(seed)), lcfg)
    apply_fn = with_int8_compute(lambda p, t, c: llama.forward_with_cache(p, t, c, lcfg))
    engine = serving.Engine(
        apply_fn,
        lambda batch, max_len: llama.init_cache(lcfg, batch, max_len),
        params,
        GenerationConfig(),  # greedy, no EOS: a request runs to its budget
        slots=deploy["slots"],
        buckets=tuple(deploy["buckets"]),
        max_len=deploy["max_len"],
        prefill_interleave=deploy["prefill_interleave"],
        decode_block=deploy["decode_block"],
    )
    return engine, params, lcfg


def jit_cache_sizes(*fns) -> int:
    """Programs compiled so far by these jitted callables."""
    return sum(f._cache_size() for f in fns)
