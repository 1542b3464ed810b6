"""On the chip: the two readings behind `chip_smoke.ARGMAX_RTOL` and
`ARGMAX_MEAN_RTOL`, through `chip_smoke.serve_phase`'s own raise at its own
config, a seed a run.

    chiprun -- python3 perf/smoke_argmax_limit.py sound 0 1 2 3
    chiprun -- python3 perf/smoke_argmax_limit.py sliced 0 1 2 3
    chiprun -- python3 perf/smoke_argmax_limit.py fp8 0 1 2 3

- ``sound``: the engine as shipped, the limits lifted so that the worst of the
  request's 128 tokens and their mean are read, not judged;
- ``sliced``: the same with `prefill_attn` forced off (a chunk's attention
  sliced out of the stack, the lowering from before `flash_prefill`);
- ``fp8``: the control, one precision down, held to the limits as they stand:
  every matrix of the ENGINE's weights on float8_e4m3's grid (the blocks'
  int8 values rounded to 4 significant bits where they lie, their scales
  kept; embeddings and head cast to float8_e4m3 and back), `Generator` and
  the cache-free forward on the weights as made. Two copies of the weights do
  not fit the chip, so the rounded ones are deleted after the engine has
  served and the others are made again from the same key, in the dict
  `serve_phase` holds, before its first reference runs. The blocks' rounding
  is integer arithmetic and the two casts are a program each: inside one
  program XLA drops a convert to float8 and back.

One JSON line a seed: the readings, or ``refused`` with `serve_phase`'s
message. About 15 s a seed with a warm compile cache. Refuses to run without
a TPU.
"""

import gc
import json
import os
import sys

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from accelerate_tpu import generation  # noqa: E402
from accelerate_tpu.models import llama  # noqa: E402
from accelerate_tpu.native.pallas import force_kernels  # noqa: E402
from accelerate_tpu.state import configure_compile_cache  # noqa: E402


def to_fp8_grid(params):
    """int8 leaves onto e4m3's grid: 16-31 in steps of 2, 32-63 of 4, 64 and
    over of 8 (ties away from zero, 120 the largest)."""

    def leaf(w):
        if w.dtype != jnp.int8:
            return w
        m = jnp.abs(w.astype(jnp.int32))
        step = jnp.where(m >= 64, 8, jnp.where(m >= 32, 4, jnp.where(m >= 16, 2, 1)))
        rounded = jnp.minimum((m + step // 2) // step * step, 120)
        return (jnp.sign(w).astype(jnp.int32) * rounded).astype(jnp.int8)

    return jax.tree.map(leaf, params)


def engine_on_fp8_references_on_int8() -> None:
    real_init, held = chip_smoke.init_int8_params, {}
    round_where_they_lie = jax.jit(to_fp8_grid, donate_argnums=0)

    def coarse_init(rng, config):
        held["args"] = (rng, config)
        out = round_where_they_lie(real_init(rng, config))
        for name in ("embed", "lm_head"):
            if name in out:
                out[name] = out[name].astype(jnp.float8_e4m3fn).astype(out[name].dtype)
        return out

    class RestoringGenerator(generation.Generator):
        def __call__(self, params, *args, **kwargs):
            for leaf in jax.tree.leaves(params):
                leaf.delete()
            fresh = real_init(*held["args"])
            params.clear()
            params.update(fresh)
            return super().__call__(params, *args, **kwargs)

    chip_smoke.init_int8_params = coarse_init
    generation.Generator = RestoringGenerator


def main() -> int:
    mode, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]]
    if mode not in ("sound", "sliced", "fp8"):
        raise SystemExit(__doc__)
    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU: this script reads the chip or nothing")
    configure_compile_cache()
    if mode == "fp8":
        engine_on_fp8_references_on_int8()
    else:
        chip_smoke.ARGMAX_RTOL = chip_smoke.ARGMAX_MEAN_RTOL = 1.0
    config = llama.LlamaConfig.llama3_8b(max_seq_len=chip_smoke.SERVE_ENGINE["max_len"])
    for seed in seeds:
        line = {"mode": mode, "seed": seed, "limits": [chip_smoke.ARGMAX_RTOL, chip_smoke.ARGMAX_MEAN_RTOL]}
        try:
            with force_kernels("off" if mode == "sliced" else "on", "prefill_attn"):
                out = chip_smoke.serve_phase(
                    config, requests=chip_smoke.SERVE_REQUESTS,
                    engine_kwargs=chip_smoke.SERVE_ENGINE, seed=seed,
                )
            line.update(
                worst=out["worst_short_of_top_logit"], mean=out["mean_short_of_top_logit"],
                equal_to_generator=out["equal_to_generator"],
            )
        except RuntimeError as e:
            line["refused"] = str(e)
        print(json.dumps(line), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
