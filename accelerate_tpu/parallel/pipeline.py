"""Pipeline-parallel inference: GPipe microbatch schedule over a stage mesh.

Analog of the reference PP-inference subsystem (`inference.py:73-184`
`build_pipeline` / `prepare_pippy`, which wraps torch.distributed.pipelining:
split the model into stages, one device per stage, microbatches streamed
through). The TPU-native construction:

- stage parameters are a pytree with a leading ``[n_stages]`` axis (the
  scan-over-layers layout the in-repo models already use), sharded over a
  dedicated 1-D ``stage`` mesh — each device holds exactly its stage's
  weights;
- one `shard_map` program runs the classic GPipe schedule: at tick ``t``
  stage ``s`` processes microbatch ``t-s``; activations hop to the next
  stage via `ppermute` over ICI. ``M`` microbatches drain in ``M+S-1``
  ticks, so per-device idle time (the pipeline bubble) is ``(S-1)/(M+S-1)``;
- the last stage's outputs are collected into a buffer and replicated with
  a `psum` at the end, so callers see an ordinary ``[M*mb, ...]`` array.

Stages must be shape-homogeneous (stage output shape == stage input shape)
— true of transformer blocks, which is the case PP exists for. Embedding /
head layers run replicated outside the pipeline (they are a few percent of
FLOPs; the reference makes the same split, `inference.py:124-145`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

STAGE_AXIS = "stage"


def pipeline_mesh(n_stages: int, devices: Sequence[jax.Device] | None = None) -> Mesh:
    """A dedicated 1-D mesh for PP inference (separate from the training
    mesh: stage layout is an inference-serving topology choice)."""
    devices = list(devices) if devices is not None else jax.devices()
    if len(devices) < n_stages:
        raise ValueError(f"{n_stages} stages need {n_stages} devices, found {len(devices)}")
    return Mesh(np.asarray(devices[:n_stages]), (STAGE_AXIS,))


def split_stages(stacked: Any, n_stages: int) -> Any:
    """Reshape a scan-over-layers pytree ``[L, ...] -> [S, L/S, ...]`` so each
    pipeline stage owns a contiguous group of layers."""

    def reshape(x):
        L = x.shape[0]
        if L % n_stages != 0:
            raise ValueError(f"{L} layers do not divide into {n_stages} stages")
        return x.reshape((n_stages, L // n_stages) + x.shape[1:])

    return jax.tree.map(reshape, stacked)


def shard_stages(stage_params: Any, mesh: Mesh) -> Any:
    """Place the ``[S, ...]`` stage pytree so each device holds its stage."""
    sharding = NamedSharding(mesh, PartitionSpec(STAGE_AXIS))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), stage_params)


def build_pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    mesh: Mesh,
) -> Callable[[Any, jax.Array], jax.Array]:
    """Compile the GPipe schedule.

    ``stage_fn(stage_params, x) -> y`` runs ONE stage (e.g. a scan over that
    stage's transformer blocks); ``y.shape == x.shape``. The returned callable
    maps ``(stage_params [S, ...], microbatches [M, mb, ...]) -> [M, mb, ...]``.
    """
    n_stages = mesh.shape[STAGE_AXIS]

    def schedule(params_blk: Any, mb_all: jax.Array) -> jax.Array:
        params_local = jax.tree.map(lambda x: x[0], params_blk)
        s = jax.lax.axis_index(STAGE_AXIS)
        n_micro = mb_all.shape[0]
        ticks = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(t, carry):
            cur, out = carry
            # Stage 0 feeds fresh microbatches (clamped past the end — those
            # ticks produce garbage that is never collected); later stages
            # consume what ppermute delivered last tick.
            feed = jax.lax.dynamic_index_in_dim(
                mb_all, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False
            )
            inp = jnp.where(s == 0, feed, cur)
            y = stage_fn(params_local, inp)
            m_idx = t - (n_stages - 1)
            valid = (s == n_stages - 1) & (m_idx >= 0)
            collected = jax.lax.dynamic_update_index_in_dim(
                out, y.astype(out.dtype), jnp.clip(m_idx, 0, n_micro - 1), 0
            )
            out = jnp.where(valid, collected, out)
            # Cast back to the carry dtype: a stage computing in reduced
            # precision (bf16 out of fp32 in) must not change the loop carry
            # type between ticks.
            y = y.astype(cur.dtype)
            cur = jax.lax.ppermute(y, STAGE_AXIS, perm) if perm else y
            return cur, out

        # Mark the zero-init carries as device-varying over the stage axis:
        # the loop body writes stage-dependent values into them, and
        # shard_map's typing rejects an unvarying->varying carry.
        def _varying(x):
            return jax.lax.pcast(x, (STAGE_AXIS,), to="varying")

        cur0 = _varying(jnp.zeros(mb_all.shape[1:], mb_all.dtype))
        out0 = _varying(jnp.zeros_like(mb_all))
        _, out = jax.lax.fori_loop(0, ticks, tick, (cur0, out0))
        # Only the last stage holds real outputs; replicate to all.
        return jax.lax.psum(jnp.where(s == n_stages - 1, out, 0), STAGE_AXIS)

    from ..ops.in_jit import shard_map_over

    # check_vma=False: the stage-varying carries and the final psum are
    # deliberate.
    sharded = shard_map_over(
        schedule,
        mesh=mesh,
        in_specs=(PartitionSpec(STAGE_AXIS), PartitionSpec()),
        out_specs=PartitionSpec(),
        check_vma=False,
    )
    return jax.jit(sharded)


class Pipeline:
    """User-facing PP runner (reference `prepare_pippy`, `inference.py:124`).

    >>> pipe = Pipeline(stage_fn, n_stages=4)
    >>> params = pipe.prepare(stacked_layer_params)   # [L,...] -> sharded [S,L/S,...]
    >>> y = pipe(params, x, microbatch_size=8)        # x: [B, ...]
    """

    def __init__(
        self,
        stage_fn: Callable[[Any, jax.Array], jax.Array],
        n_stages: int,
        devices: Sequence[jax.Device] | None = None,
    ) -> None:
        self.mesh = pipeline_mesh(n_stages, devices)
        self.n_stages = n_stages
        self._forward = build_pipeline(stage_fn, self.mesh)

    def prepare(self, stacked_layers: Any) -> Any:
        return shard_stages(split_stages(stacked_layers, self.n_stages), self.mesh)

    def __call__(self, stage_params: Any, x: jax.Array, *, microbatch_size: int) -> jax.Array:
        B = x.shape[0]
        if B % microbatch_size != 0:
            raise ValueError(
                f"Batch {B} is not divisible by microbatch_size {microbatch_size}"
            )
        m = B // microbatch_size
        mb = x.reshape((m, microbatch_size) + x.shape[1:])
        out = self._forward(stage_params, mb)
        return out.reshape((B,) + out.shape[2:])


def llama_pipeline(
    params: Any,
    config: Any,
    n_stages: int,
    devices: Sequence[jax.Device] | None = None,
) -> tuple[Pipeline, Any, Callable[[jax.Array, int], jax.Array]]:
    """Wire a Llama checkpoint into a pipeline: blocks are staged; embedding,
    final norm and head run replicated around it.

    Returns ``(pipe, stage_params, forward)`` with
    ``forward(tokens [B,S], microbatch_size) -> logits [B,S,V]``.
    """
    from ..models import llama as _llama
    from ..models.layers import rms_norm

    # _rope_tables honours config.rope_scaling (Llama-3.1-style checkpoints
    # would otherwise silently run plain RoPE through the pipeline path).
    cos, sin = _llama._rope_tables(config)

    def stage_fn(stage_blocks: Any, x: jax.Array) -> jax.Array:
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        mask = (
            _llama._window_mask(None, positions, S, config.sliding_window)
            if getattr(config, "sliding_window", None) is not None
            else None
        )
        body = partial(
            _llama.block_forward,
            config=config,
            cos=cos,
            sin=sin,
            positions=positions,
            mask=mask,
        )

        def scan_body(carry, block):
            new_x, _aux = body(block, carry)  # MoE aux unused at inference
            return new_x, None

        x, _ = jax.lax.scan(scan_body, x, stage_blocks)
        return x

    pipe = Pipeline(stage_fn, n_stages, devices)
    stage_params = pipe.prepare(params["blocks"])
    embed = params["embed"]
    final_norm = params["final_norm"]
    head = embed.T if config.tie_embeddings else params["lm_head"]

    def forward(tokens: jax.Array, microbatch_size: int) -> jax.Array:
        x = embed[tokens]
        x = pipe(stage_params, x, microbatch_size=microbatch_size)
        x = rms_norm(x, final_norm, config.norm_eps)
        return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))

    return pipe, stage_params, forward
