"""Iteration-level continuous-batching serving engine.

The fixed-batch `generation.Generator` serves OFFLINE workloads well (one
batch in, one batch out) but wastes the chip under traffic: every request
pads to the longest prompt in its batch, the batch decodes until its LAST
row finishes, and new arrivals wait for the whole batch to drain. Decode
is bandwidth-bound on the weights, so a full batch of 8 costs little more
per step than a batch of 1: keeping the decode batch full is the lever.

This engine applies the Orca iteration-level-scheduling idea in its
XLA-native form (the vLLM slot/page design reduced to what a TPU actually
needs — static shapes):

- the KV cache is a fixed pool of ``slots`` (batch rows of one
  slot-batched family cache); requests are admitted into free slots and
  evicted on EOS / token budget, so the compiled decode step never sees a
  shape change as traffic comes and goes;
- per-slot length cursors ride the family cache contract
  (``cache['length']`` as a (B,) vector, `models/layers.py:cache_write`) —
  the cursors live on the HOST (the scheduler knows them deterministically)
  and are shipped as a tiny (N,) int32 each step, which keeps the device
  step pure and the whole engine replayable;
- prefill is **bucketed and chunked**: prompts are split into chunks, each
  padded to one of a small static set of bucket lengths, and each chunk is
  computed on a single slot's cache ROW (`models/layers.py:cache_slot_view`
  / `cache_slot_write`, slot index traced) — so prefill compiles at most
  once per bucket (validated by the ATX302 drift checker in tests) and a
  long prompt never stalls in-flight decodes: chunks interleave with decode
  steps at a configurable ratio;
- one jitted decode step runs over the FULL slot batch every time (free
  slots compute garbage that is never read — the price of static shapes);
  greedy outputs are bit-identical to solo `generate()` per request
  (tested), because masked-out cache positions contribute exactly zero to
  the fp32 softmax.

Knobs: ``ATX_SERVE_SLOTS`` / ``ATX_SERVE_BUCKETS`` (comma-separated bucket
lengths) set the defaults; see docs/serving.md for sizing guidance and when
the plain `Generator` is still the right tool.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import resilience
from .. import telemetry as _telemetry
from ..telemetry import flight as _flight
from ..generation import GenerationConfig, warp_logits
from ..models.layers import (
    cache_slot_copy,
    cache_slot_view,
    cache_slot_write,
    is_state_leaf,
    record_attention_paths,
    record_head_rows,
    record_step_counts,
)
from ..native.pallas.decode_attention import rows_fetched
from ..ops.int8 import record_weight_paths
from ..ops.moe import MOE_COUNTS
from ..utils.environment import (
    get_int_from_env,
    get_str_from_env,
    parse_flag_from_env,
)
from .fetch_stalls import FetchStalls, fetch_key
from .prefix_cache import PrefixCache

__all__ = [
    "Engine",
    "Request",
    "Completion",
    "poisson_trace",
    "shared_prefix_trace",
    "default_buckets",
]

logger = logging.getLogger(__name__)

ApplyFn = Callable[[Any, jax.Array, Any], tuple[jax.Array, Any]]

_DEFAULT_BUCKETS = (32, 64, 128, 256)


def default_buckets() -> tuple[int, ...]:
    """Prefill bucket lengths from ``ATX_SERVE_BUCKETS`` (comma-separated,
    e.g. ``"16,64,256"``), else the built-in (32, 64, 128, 256)."""
    raw = get_str_from_env(("ATX_SERVE_BUCKETS",), "")
    if not raw:
        return _DEFAULT_BUCKETS
    try:
        buckets = tuple(sorted({int(x) for x in raw.split(",") if x.strip()}))
    except ValueError:
        raise ValueError(
            f"ATX_SERVE_BUCKETS={raw!r}: expected comma-separated ints"
        ) from None
    if not buckets or buckets[0] <= 0:
        raise ValueError(f"ATX_SERVE_BUCKETS={raw!r}: buckets must be positive")
    return buckets


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival`` is seconds relative to the trace
    start (used by `Engine.serve(realtime=True)`); ``seed``
    drives the per-request sampling stream, so a request's tokens don't
    depend on which other requests share the batch. ``max_new_tokens=None``
    falls back to the engine config's budget; ``stop_sequences`` are
    multi-token stop strings matched HOST-side against the emitted tail
    (the device step never sees them — no recompiles per stop set).
    ``priority`` is the request's admission class for `serving.Router`
    (lower = more important, default 1; the engine itself ignores it):
    under EDF scheduling a lower class is dispatched first at equal
    deadlines and is the last to be shed under overload."""

    prompt: np.ndarray
    max_new_tokens: int | None = None
    rid: int = -1
    seed: int = 0
    arrival: float | None = None
    stream: Callable[[int, int, str | None], None] | None = None
    stop_sequences: Sequence[Sequence[int]] | None = None
    # Deadline in seconds from submission, enforced by `serving.Router`
    # (the engine itself never expires a request): on expiry the request
    # is cancelled mid-queue or mid-decode with finish_reason="cancelled".
    timeout: float | None = None
    priority: int = 1


@dataclasses.dataclass
class Completion:
    """A finished request. ``tokens`` is (max_new_tokens,) int32 padded with
    ``pad_token_id`` after EOS — the exact layout solo `generate()` emits
    for the generated region, so bit-identity checks are a slice compare.
    Timestamps are absolute `time.perf_counter()` values, in the order a
    request lives them: ``submitted_at`` <= ``admitted_at`` (a slot) <=
    ``prefill_started_at`` (its first prefill chunk: the wait in between is
    the prefill queue) <= ``first_token_at`` <= ``finished_at``; 0.0 for a
    stage a cancelled request never reached. ``finish_reason``
    is ``"eos"`` / ``"stop"`` (a stop sequence matched; its tokens stay in
    ``tokens``) / ``"length"`` (budget exhausted) / ``"cancelled"``
    (`Engine.cancel` — deadline expiry or caller cancellation; ``tokens``
    holds whatever was generated before the cancel) / ``"failed"``
    (`serving.Router` only: replica deaths exhausted the retry budget) /
    ``"shed"`` (`serving.Router` only: evicted from the admission queue
    under overload to make room for a higher-priority request). ``slot`` is
    the slot the request finished in (-1 for one that was cancelled):
    `Engine.slot_state` reads what it left there."""

    rid: int
    prompt: np.ndarray
    tokens: np.ndarray
    n_new: int
    text: str | None
    submitted_at: float
    first_token_at: float
    finished_at: float
    finish_reason: str = "length"
    admitted_at: float = 0.0
    prefill_started_at: float = 0.0
    slot: int = -1


class _Slot:
    __slots__ = (
        "req", "chunks", "cursor", "n_new", "last_token", "out",
        "first_token_at", "decoding", "pending_copy",
        "t_admit", "t_prefill0", "occ_sum", "occ_n",
    )

    def __init__(
        self, req: Request, chunks: list, pad: int, *, matched: int = 0,
        pending_copy=None,
    ) -> None:
        self.req = req
        self.chunks = chunks  # [(padded (1, bucket) np.int32, real_len), ...]
        # KV positions written & committed so far. A prefix-cache hit
        # starts the cursor at the match boundary; the pinned source node
        # in ``pending_copy`` is copied into the slot row right before the
        # slot's first prefill chunk (same device order: copy, then chunk).
        self.cursor = matched
        self.pending_copy = pending_copy  # (CacheNode, matched) | None
        self.n_new = 0
        self.last_token = 0
        self.out = np.full((req.max_new_tokens,), pad, np.int32)
        self.first_token_at = 0.0
        self.decoding = False
        # Admission and first prefill-chunk dispatch, always stamped (the
        # `request` record). Tracing residuals (ATX_TRACE_REQUESTS=1):
        # decode-residency accumulators (sum of batch occupancy over
        # resident iterations) — plain float/int adds in the decode loop,
        # emitted as ONE span at completion.
        self.t_admit = time.perf_counter()
        self.t_prefill0 = 0.0
        self.occ_sum = 0
        self.occ_n = 0


def _weight_path_counts(paths: list[str]) -> tuple[int, int, int]:
    return paths.count("in_place"), paths.count("sliced"), paths.count("act_quantized_once")


class Engine:
    """Continuous-batching engine over a family cached forward.

    ``apply_fn(params, tokens, cache) -> (logits, cache)`` and
    ``init_cache_fn(batch, max_len) -> cache`` follow the model-family
    cache contract (e.g. `models/llama.py:forward_with_cache` /
    ``init_cache``); every family cache whose non-``length`` leaves are
    layer-stacked ``(L, B, T, ...)`` buffers works (bf16/fp32/int8). A
    family with two kinds of layer keeps one set of leaves for each, of
    different ``L`` and ``T``: leaves shorter than ``max_len`` are rings that
    hold a sliding window's rows (`models/layers.py:cache_write_stacked`).
    Slot bookkeeping by cursor is the same; a prefill chunk then tells the
    forward how many of its rows are real (``cache['valid']``), and the
    prefix cache is off: its copies go "at the same sequence offset", which
    a ring does not have (``stats['prefix_cache_off_for_ring']``; asking for
    it explicitly is a ValueError).

    A prefill chunk's cache also carries ``cache['logits_at']``, a (B,)
    int32 row index into the chunk: the row the engine samples (the chunk's
    last real row) on a prompt's last chunk, -1 on every other chunk, whose
    token is never read. A forward that honours it projects that row alone
    through its final norm and vocabulary head and returns (B, 1, vocab)
    logits, and for -1 does not run the head at all
    (`models/layers.py:head_logits`); one that ignores it returns every row
    and stays correct: the engine takes the row it samples from either.
    ``stats['prefill_head_rows']`` counts the rows the head projected. Decode
    steps never carry the key: every slot samples.

    A family with recurrent layers keeps **state leaves** beside its rows
    (`models/layers.py:is_state_leaf`: named ``state`` / ``state_<what>``,
    laid out ``(L, B, ...)`` with no row axis). A state has no cursor to hide
    behind, so the engine tells the forward what the cursor told it for
    rows: a prefill chunk carries ``cache['valid']`` (a bucket's pad tail
    advances no state), a decode step ``cache['decoding']`` (the (B,) mask of
    the decoding slots: free and mid-prefill slots keep their state), and
    the first chunk of a request (cursor 0) zeroes its slot's states inside
    the chunk's own program. The prefix cache is off for such a cache too:
    a state at a block boundary is not kept anywhere
    (``stats['prefix_cache_off_for_state']``; asking for it is a ValueError).

    ``max_len`` is the per-slot KV capacity (prompt + new tokens must fit);
    defaults to ``2 * max(buckets)``. ``prefill_interleave`` is the number
    of decode steps granted between two prefill chunks while both kinds of
    work are pending (1 = strict alternation; 0 = prefill-first, which
    stalls in-flight decodes for the whole prompt — the fixed-batch
    behaviour this engine exists to avoid).

    ``prefix_cache`` (default on; ``ATX_SERVE_PREFIX_CACHE=0`` disables)
    retains committed prompt-prefix KV in a dedicated device pool and
    serves future requests' shared prefixes by device-to-device copy
    instead of prefill (docs/serving.md). ``prefix_cache_mib``
    (``ATX_SERVE_PREFIX_CACHE_MIB``, default 64) is the pool's byte
    budget; ``prefix_cache_rows`` overrides the derived row count
    directly (tests / exact sizing). Greedy outputs are bit-identical
    with the cache on or off.

    **Thread ownership**: an Engine is NOT thread-safe. Exactly one thread
    may drive it — every `submit`/`submit_request`/`step`/`cancel`/`serve`
    call must come from that same thread (the host-side scheduler state
    and the device dispatch order both assume a single driver). The
    multi-replica `serving.Router` honours this by giving each replica
    engine its own dedicated thread and forwarding submissions and
    cancellations through a per-replica inbox.
    """

    def __init__(
        self,
        apply_fn: ApplyFn,
        init_cache_fn: Callable[[int, int], Any],
        params: Any,
        config: GenerationConfig | None = None,
        *,
        slots: int | None = None,
        buckets: Sequence[int] | None = None,
        max_len: int | None = None,
        prefill_interleave: int = 1,
        decode_block: int = 1,
        detokenize: Callable[[Sequence[int]], str] | None = None,
        prefix_cache: bool | None = None,
        prefix_cache_mib: float | None = None,
        prefix_cache_rows: int | None = None,
    ) -> None:
        self.config = config or GenerationConfig()
        self.n_slots = (
            slots if slots is not None else get_int_from_env(("ATX_SERVE_SLOTS",), 8)
        )
        if self.n_slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.n_slots}")
        self.buckets = tuple(sorted(set(buckets))) if buckets else default_buckets()
        if self.buckets[0] <= 0:
            raise ValueError(f"buckets must be positive, got {self.buckets}")
        self.max_len = max_len if max_len is not None else 2 * self.buckets[-1]
        self.prefill_interleave = prefill_interleave
        # Decode steps dispatched per host sync. 1 = fetch every token
        # (lowest admission/eviction latency); >1 chains steps on device and
        # fetches their tokens in one device_get — the per-step round trip
        # amortizes away (the speculative.py host-loop design). A slot that
        # hits EOS mid-block zombie-decodes to the block end; its post-EOS
        # tokens are discarded, so outputs still match solo generate()'s
        # truncation exactly (tested).
        self.decode_block = max(1, decode_block)
        self.detokenize = detokenize
        self.params = params
        cache = init_cache_fn(self.n_slots, self.max_len)
        kv = {k: v for k, v in cache.items() if k != "length"}
        # The engine lives where its weights live: a caller that wants
        # replica i on chip i commits the params there (`atx serve
        # --replicas`) and the KV pools follow. Host (numpy) weights, or
        # weights spread over several devices, leave the default device.
        leaf = next(iter(jax.tree.leaves(params)), None)
        placed = leaf.devices() if isinstance(leaf, jax.Array) else ()
        self._device = next(iter(placed)) if len(placed) == 1 else jax.devices()[0]
        # Commit the slot pool: every decode / prefill output inherits this
        # placement, so the jit signatures (which key on argument
        # committedness) stay IDENTICAL from the first call on — one compile
        # for decode, one per prefill bucket.
        self._kv = jax.device_put(kv, self._device)
        # Recurrent states, told apart by name: they have no row axis, and
        # nothing below that reads a leaf's rows may look at them.
        self._state_names = tuple(sorted(k for k in kv if is_state_leaf(k)))
        rows_of = [v for k, v in kv.items() if k not in self._state_names]
        # Layers that keep a state: what one decode step's live slots count by.
        self._state_layers = max((int(kv[k].shape[0]) for k in self._state_names), default=0)
        # Rows of the shortest leaf where it is shorter than a slot: a ring.
        shortest = min((int(v.shape[2]) for v in jax.tree.leaves(rows_of)), default=self.max_len)
        self._ring_len = shortest if shortest < self.max_len else 0
        # Bytes of one cached token by the rows of its leaf (the widest leaf
        # of that length: K or V, not their scales): what sizes the decode
        # kernel's blocks, for `_kv_rows_fetched`.
        self._kv_row_bytes: dict[int, int] = {}
        for v in jax.tree.leaves(rows_of):
            rows, width = int(v.shape[2]), int(v.shape[3]) * np.dtype(v.dtype).itemsize
            self._kv_row_bytes[rows] = max(self._kv_row_bytes.get(rows, 0), width)
        config_ = self.config
        eos, pad = config_.eos_token_id, config_.pad_token_id

        def _sample(logits, seed, n):
            # Token n of a request draws from fold_in(PRNGKey(seed), n):
            # stateless, so the stream is reproducible regardless of batch
            # composition (solo replay gives the same tokens).
            if not config_.do_sample:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), n)
            return jax.random.categorical(key, warp_logits(logits, config_)).astype(
                jnp.int32
            )

        def decode_fn(params, tokens, lengths, kv, seeds, steps, *decoding):
            """One token for every slot. Free/mid-prefill slots compute too
            (static shapes) — their write lands at their cursor, a position
            the next prefill chunk fully overwrites, and their output is
            dropped by the host scheduler. A cache with state leaves brings
            one more operand, the (N,) mask of the decoding slots: the
            forward leaves every other slot's state as it is.

            The T=1 attention inside ``apply_fn`` routes through the
            `flash-decode Pallas kernel <native/pallas/decode_attention.py>`
            where `dispatch.kernel_mode` allows it (on a TPU, or under
            ``force_kernels``; read at trace time): split-K over the slot KV
            cache, masked by each row's cursor, int8 KV dequantized in-kernel."""
            with (
                record_attention_paths() as paths,
                record_step_counts() as counts,
                record_weight_paths() as weights,
            ):
                cache = dict(kv, length=lengths)
                if decoding:
                    (cache["decoding"],) = decoding
                logits, new = apply_fn(params, tokens[:, None], cache)
            # Trace time: which attention lowering this program compiled to,
            # and how its quantized contractions get their weights.
            self.stats["decode_in_place"] = int(
                bool(paths) and all(p == "in_place" for p in paths)
            )
            self._weight_paths["decode"] = _weight_path_counts(weights)
            nxt = jax.vmap(_sample)(logits[:, -1, :], seeds, steps)
            # What the forward counted (an expert layer's routing; nothing
            # for a dense model) leaves with the tokens: one fetch a step.
            return (nxt, counts), {k: new[k] for k in kv}

        def prefill_fn(params, tokens, kv, slot, cursor, sample_pos, seed, last):
            """One bucket-padded prompt chunk into slot row ``slot`` at
            ``cursor``. Pad-tail KV lands at positions >= the row's real
            cursor — never attended before decode overwrites it. The
            returned token (sampled at ``sample_pos``, the chunk's last
            REAL position) is only meaningful on a prompt's final chunk
            (``last``): on any other the forward is asked for no logits."""
            row = cache_slot_view(kv, slot)
            for name in self._state_names:
                # A request's first chunk starts from no state, whatever the
                # slot's last occupant left: zeroed here, in this program.
                row[name] = jnp.where(cursor == 0, jnp.zeros_like(row[name]), row[name])
            cache = dict(row, length=cursor, logits_at=jnp.where(last, sample_pos, -1)[None])
            if self._ring_len or self._state_names:
                # In a ring the pad tail would land on rows still in the
                # window; a state would advance over it.
                cache["valid"] = sample_pos + 1
            with (
                record_attention_paths() as paths,
                record_weight_paths() as weights,
                record_head_rows() as head,
            ):
                logits, new = apply_fn(params, tokens, cache)
            S = tokens.shape[1]
            self._weight_paths[S] = _weight_path_counts(weights)
            self._attention_paths[S] = (paths.count("in_place"), paths.count("sliced"))
            # Rows the head projects on a prompt's last chunk and on any
            # other: every row, every time, where the forward ignored the key.
            self._head_rows[S] = (sum(head), 0) if head else (S, S)
            kv = cache_slot_write(kv, {k: new[k] for k in row}, slot)
            logits = logits[0]
            if logits.shape[0] > 1:  # the forward returned every row
                logits = jnp.take_along_axis(logits, sample_pos[None, None], axis=0)
            tok = _sample(logits[0], seed, jnp.zeros((), jnp.int32))
            return tok, kv

        # Per program ("decode", or a prefill bucket's rows), as traced: how
        # many of its quantized contractions read their weight stack in
        # place, how many were handed a slice, and how many took their rows
        # as one row tile of int8 activations quantized once.
        self._weight_paths: dict[Any, tuple[int, int, int]] = {}
        # Per prefill bucket, as traced: how many layers' attention over the
        # cache a kernel computes on the stack where it lies (up to the
        # cursor), and how many slice their layer out (a ring's included).
        self._attention_paths: dict[int, tuple[int, int]] = {}
        # Per prefill bucket, as traced: rows the vocabulary head projects on
        # a prompt's last chunk, and on any other chunk.
        self._head_rows: dict[int, tuple[int, int]] = {}
        self._decode_fn = decode_fn
        self._prefill_fn = prefill_fn
        self._decode = jax.jit(decode_fn, donate_argnums=(3,))
        self._prefill = jax.jit(prefill_fn, donate_argnums=(2,))

        # Prefix cache: a dedicated pool of KV rows (same leaf layout as the
        # slot pool) indexed by a host-side radix tree. Hit/promotion copies
        # go through ONE jitted cache_slot_copy whose chunk length is a
        # static drawn from the bucket set (slots/cursor traced), so its jit
        # cache is bounded by 2 x len(buckets) — hit copies (dst = slot kv)
        # and promotions (dst = pool) have different dst/src shapes when the
        # pool row count differs from the slot count.
        # Per-engine wrapper (not cache_slot_copy itself): jit caches key on
        # the function object, so a shared callee would pool compile counts
        # across engines and make prefix_copy_compiles meaningless.
        def copy_fn(dst, src, dst_slot, src_slot, start, length: int):
            return cache_slot_copy(dst, src, dst_slot, src_slot, start, length)

        self._copy_fn = copy_fn
        self._copy = jax.jit(copy_fn, static_argnums=(5,), donate_argnums=(0,))
        self.copy_signatures: list[int] = []  # chunk length per issued copy
        enabled = (
            parse_flag_from_env("ATX_SERVE_PREFIX_CACHE", True)
            if prefix_cache is None
            else prefix_cache
        )
        if self._ring_len:
            if prefix_cache:
                raise ValueError(
                    f"this family's cache has ring leaves ({self._ring_len} rows for slots "
                    f"of {self.max_len}): the prefix cache copies rows at the same sequence "
                    "offset, which a ring does not have; run with prefix_cache off"
                )
            if enabled:
                logger.info(
                    "prefix cache off: the cache has ring leaves of %d rows", self._ring_len
                )
            enabled = False
        if self._state_names:
            if prefix_cache:
                raise ValueError(
                    f"this family's cache has state leaves {self._state_names}: the prefix "
                    "cache copies rows, and a recurrent state at a prefix's end is kept "
                    "nowhere; run with prefix_cache off"
                )
            if enabled:
                logger.info("prefix cache off: the cache has state leaves %s", self._state_names)
            enabled = False
        self.prefix_cache: PrefixCache | None = None
        self._pool: Any = None
        if enabled:
            rows = prefix_cache_rows
            if rows is None:
                mib = (
                    prefix_cache_mib
                    if prefix_cache_mib is not None
                    else get_int_from_env(("ATX_SERVE_PREFIX_CACHE_MIB",), 64)
                )
                row_bytes = sum(
                    int(np.prod(v.shape)) * v.dtype.itemsize
                    for v in jax.tree.leaves(kv)
                ) // self.n_slots
                rows = int(mib * 2**20 // max(row_bytes, 1))
            rows = min(rows, 1024)  # bound host tree bookkeeping
            if rows >= 1:
                pool = init_cache_fn(rows, self.max_len)
                self._pool = jax.device_put(
                    {k: v for k, v in pool.items() if k != "length"}, self._device
                )
                self.prefix_cache = PrefixCache(rows, self.buckets, self.max_len)

        # Static capacity guard (ATX_SERVE_CAPACITY_CHECK, default "warn"):
        # weights + slot pool + prefix pool are all committed by this point,
        # so a config that cannot fit the chip is known *now*, not at the
        # first burst of traffic. docs/serving.md#capacity-planner.
        from ..analysis.capacity import check_engine_capacity

        check_engine_capacity(self)

        self._queue: deque[Request] = deque()
        self._slots: list[_Slot | None] = [None] * self.n_slots
        self._free: deque[int] = deque(range(self.n_slots))
        self._prefill_order: deque[int] = deque()  # slots with pending chunks
        self._decode_credit = 0
        self._next_rid = 0
        self.prefill_signatures: list[int] = []  # bucket length per issued chunk
        # Counters live on the telemetry registry (docs/observability.md):
        # this dict-shaped view keeps every historical `stats[...]` use and
        # snapshot working while `/metrics` reads the same series — one
        # source of truth. Keys: decode_slot_steps sums active rows over
        # decode steps; prefill_tokens_saved counts prompt tokens served by
        # KV copy instead of prefill compute; decode_in_place is 1 when the
        # traced decode program's attention reads the stacked cache in place
        # (the flash-decode kernel) and 0 when it slices a layer out.
        self.stats = _telemetry.StatsView(
            "serve",
            (
                "admitted",
                "completed",
                "prefill_chunks",
                "decode_steps",
                "decode_slot_steps",
                "prompt_tokens",
                "prefix_hits",
                "prefill_tokens_saved",
                "prefix_copy_chunks",
                "prefix_promotions",
                "cancelled",
                "decode_in_place",
                # Quantized contractions of the programs dispatched (decode
                # steps and prefill chunks) whose int8 weights the kernel
                # read out of the layer stack where it lies, and those handed
                # one layer's matrix sliced out of it (a copy a layer).
                "weights_in_place",
                "weights_sliced",
                # Of those, the contractions whose rows the kernel took as one
                # row tile of int8 activations quantized once, in XLA: those
                # it would otherwise split into several row tiles (a 1024-row
                # chunk's seven; none at 512 rows or fewer).
                "int8_act_quantized_once",
                # Layers of the prefill chunks dispatched whose attention
                # over the cache the flash-prefill kernel computed on the
                # stack where it lies, and those that sliced their layer out
                # and scored every row of the slot (a ring layer's chunk too).
                "prefill_attn_in_place",
                "prefill_attn_sliced",
                # Rows of the prefill chunks dispatched that the vocabulary
                # head projected: one on a prompt's last chunk, none on the
                # others, where the forward honours ``cache['logits_at']``.
                "prefill_head_rows",
                "prefix_cache_off_for_ring",
                # Rows of KV a decode step had to read, summed over the
                # decoding slots and the steps: per full-length layer, and per
                # ring layer (capped at the ring's rows).
                "kv_rows_live_full",
                "kv_rows_live_window",
                # Rows of KV a decode step's attention copied out of one
                # such layer, summed over every slot (free and mid-prefill
                # ones too) and the steps: whole blocks up to each cursor
                # under the flash-decode kernel, every row of every slot
                # under the sliced lowering.
                "kv_rows_fetched_full",
                "kv_rows_fetched_window",
                "prefix_cache_off_for_state",
                # Recurrent states (a cache with state leaves; 0 otherwise).
                # At every decode step: decoding slots x layers that keep a
                # state, and the (slot, layer) states the step's kernel read
                # and wrote (the forward's own count: `gdn_decode` visits the
                # decoding slots, the XLA lowering selects over all of them).
                "state_slots_live",
                "state_slots_touched",
                # At every prefill chunk: its real rows and its bucket's rows,
                # which the chunkwise form ran over.
                "state_rows_real",
                "state_rows_padded",
                "state_resets",  # first chunks: a slot's states zeroed
                # Fetches that waited over 1.5 x the usual wait for the same
                # work (`fetch_stalls.FetchStalls`), and the ms they waited
                # beyond it.
                "stalled_fetches",
                "stalled_fetch_ms",
                *MOE_COUNTS,  # the expert layer's, summed over layers and decode steps
            ),
            label="engine",
            gauges=("decode_in_place", "prefix_cache_off_for_ring", "prefix_cache_off_for_state"),
        )
        self.stats["prefix_cache_off_for_ring"] = int(bool(self._ring_len))
        self.stats["prefix_cache_off_for_state"] = int(bool(self._state_names))
        _labels = ("engine",)
        self._tel_labels = self.stats.labels
        self._h_queue_wait = _telemetry.histogram(
            "serve_queue_wait_ms",
            "submit -> first prefill chunk (a slot and then the prefill queue)",
            labels=_labels,
        )
        self._h_prefill_ms = _telemetry.histogram(
            "serve_prefill_step_ms", "wall per prefill scheduler step",
            labels=_labels,
        )
        self._h_decode_ms = _telemetry.histogram(
            "serve_decode_step_ms",
            "wall per decode scheduler step (includes the token fetch sync)",
            labels=_labels,
        )
        self._h_ttft = _telemetry.histogram(
            "serve_ttft_ms", "engine submit -> first token", labels=_labels
        )
        self._h_e2e = _telemetry.histogram(
            "serve_e2e_ms", "engine submit -> completion", labels=_labels
        )
        self._c_tokens = _telemetry.counter(
            "serve_generated_tokens", "tokens emitted", labels=_labels
        )
        self.actions: list[str] = []  # "prefill" / "decode", for tests/traces
        # Request-scoped tracing (telemetry/flight.py), snapshotted ONCE so
        # the decode inner loop pays zero cost while off. Spans time the
        # HOST dispatch only — recording never adds a device sync, so
        # greedy outputs are bit-identical with tracing on or off.
        self._trace = _flight.trace_requests_enabled()
        # What the next fetch waits on: the programs dispatched since the
        # last one (a prefill chunk by its bucket, "decode", "copy").
        self._waiting_on: dict[int | str, int] = {}
        self._fetch_seq = 0
        self._stalls = FetchStalls()

    # ------------------------------------------------------------- submit
    def submit(
        self,
        prompt: Any,
        max_new_tokens: int | None = None,
        *,
        seed: int = 0,
        stream: Callable[[int, int, str | None], None] | None = None,
        arrival: float | None = None,
        stop_sequences: Sequence[Sequence[int]] | None = None,
    ) -> int:
        """Queue one request; returns its request id. ``stream`` is called
        as ``stream(rid, token_id, text)`` for every generated token (text
        is the detokenized piece when the engine has a detokenizer).
        ``max_new_tokens`` overrides the engine config's budget per
        request; ``stop_sequences`` end the request early when the emitted
        tail matches any of the token sequences (host-side — see
        `Request`)."""
        req = Request(
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=max_new_tokens,
            seed=seed,
            arrival=arrival,
            stream=stream,
            stop_sequences=stop_sequences,
        )
        return self.submit_request(req)

    def validate_request(self, req: Request) -> Request:
        """Resolve per-request defaults and validate against this engine's
        capacity WITHOUT queueing anything (raises ValueError on a request
        that could never run here). `submit_request` calls this; the
        multi-replica Router calls it at admission so a bad request is
        rejected at the front door instead of killing a replica thread."""
        if req.max_new_tokens is None:
            req.max_new_tokens = self.config.max_new_tokens
        if req.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {req.max_new_tokens}")
        if req.stop_sequences is not None:
            req.stop_sequences = tuple(
                tuple(int(t) for t in seq) for seq in req.stop_sequences
            )
            if any(len(seq) == 0 for seq in req.stop_sequences):
                raise ValueError("empty stop sequence")
        S = int(req.prompt.shape[0])
        if S < 1:
            raise ValueError("empty prompt")
        if S + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({S}) + max_new_tokens ({req.max_new_tokens}) exceeds "
                f"the engine's per-slot KV capacity max_len={self.max_len}"
            )
        # Bucket-padding fit: every prefill chunk writes a full BUCKET of KV
        # positions (pad tail included), so the padded plan — not just the
        # raw prompt — must fit max_len. Validate here, at submit time, so
        # an oversized prompt raises a clear error instead of the padded
        # final chunk's clamped cache write corrupting committed KV deep
        # inside the prefill path.
        self._chunk_plan(req.prompt)
        return req

    def submit_request(self, req: Request) -> int:
        self.validate_request(req)
        if req.rid < 0:
            req.rid = self._next_rid
        self._next_rid = max(self._next_rid, req.rid) + 1
        req.submitted_at = time.perf_counter()  # type: ignore[attr-defined]
        self._queue.append(req)
        return req.rid

    # ------------------------------------------------------------- cancel
    def cancel(self, rid: int) -> Completion | None:
        """Cancel a queued or in-flight request. Returns a `Completion`
        with ``finish_reason="cancelled"`` carrying whatever tokens were
        generated before the cancel (none for a still-queued request), or
        None when ``rid`` is unknown or already finished. Must be called
        from the engine-owning thread, between `step` calls (the Router's
        per-replica inbox serializes this). The cancelled slot's committed
        prefix is NOT promoted to the prefix cache — a partial request is
        a poor reuse candidate and the slot is recycled immediately."""
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                self.stats["cancelled"] += 1
                return self._cancelled_completion(
                    req,
                    np.full((req.max_new_tokens,), self.config.pad_token_id, np.int32),
                    0,
                )
        for slot_id, slot in enumerate(self._slots):
            if slot is not None and slot.req.rid == rid:
                if slot.pending_copy is not None:
                    self.prefix_cache.release(slot.pending_copy[0])
                    slot.pending_copy = None
                try:
                    self._prefill_order.remove(slot_id)
                except ValueError:
                    pass  # already decoding
                # The slot's partial KV is garbage to the next occupant:
                # its first prefill chunk overwrites from cursor 0 (the
                # same free-slot invariant every eviction relies on).
                self._slots[slot_id] = None
                self._free.append(slot_id)
                self.stats["cancelled"] += 1
                return self._cancelled_completion(
                    slot.req, slot.out, slot.n_new, slot
                )
        return None

    def _cancelled_completion(
        self, req: Request, tokens: np.ndarray, n_new: int, slot: _Slot | None = None
    ) -> Completion:
        completion = Completion(
            rid=req.rid,
            prompt=req.prompt,
            tokens=tokens,
            n_new=n_new,
            text=self.detokenize(tokens[:n_new].tolist()) if self.detokenize else None,
            submitted_at=getattr(req, "submitted_at", 0.0),
            first_token_at=slot.first_token_at if slot else 0.0,
            finished_at=time.perf_counter(),
            finish_reason="cancelled",
            admitted_at=slot.t_admit if slot else 0.0,
            prefill_started_at=slot.t_prefill0 if slot else 0.0,
        )
        self._record_request(completion)
        return completion

    def _record_request(self, c: Completion) -> None:
        """One `request` record per completion in the process's flight
        recorder, tracing on or off: the black box holds the last requests
        when a process dies, and `latency_summary` reads its exact samples
        from them."""
        _flight.record_span(
            "request",
            rid=c.rid,
            t0=c.submitted_at,
            t1=c.finished_at,
            engine=self.stats.instance,
            admitted_at=c.admitted_at,
            prefill_started_at=c.prefill_started_at,
            first_token_at=c.first_token_at,
            finish_reason=c.finish_reason,
            prompt_tokens=len(c.prompt),
            new_tokens=c.n_new,
        )

    def abort_inflight(self) -> list[Completion]:
        """Cancel EVERYTHING queued or in a slot, returning the cancelled
        completions. Leaves the engine idle with every slot free — used to
        sanitize an engine between chaos episodes and before a re-admission
        probe replays the canary on a quarantined replica (whatever the
        fault left mid-flight must not contaminate the probe)."""
        rids = [req.rid for req in self._queue]
        rids += [s.req.rid for s in self._slots if s is not None]
        out = []
        for rid in rids:
            c = self.cancel(rid)
            if c is not None:
                out.append(c)
        return out

    # ---------------------------------------------------------- scheduler
    def slot_state(self, slot: int) -> dict[str, np.ndarray]:
        """The state leaves of one slot, on the host, each ``(layers, ...)``:
        what the slot's last occupant left (a finished request's states stay
        until the next admission's first chunk zeroes them). Empty for a
        cache without state leaves."""
        return {n: np.asarray(self._kv[n][:, slot]) for n in self._state_names}

    @property
    def busy(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    def _chunk_plan(
        self, prompt: np.ndarray, start: int = 0
    ) -> list[tuple[np.ndarray, int]]:
        """Bucket-padded prefill chunks for ``prompt[start:]`` (``start`` is
        the prefix-cache match boundary — 0 when there's no hit)."""
        chunks = []
        pos, S = start, len(prompt)
        while pos < S:
            rem = S - pos
            if rem > self.buckets[-1]:
                bucket = self.buckets[-1]
            else:
                bucket = min(b for b in self.buckets if b >= rem)
            real = min(rem, bucket)
            if pos + bucket > self.max_len:
                raise ValueError(
                    f"prompt length {S}: the prefill chunk covering positions "
                    f"[{pos}, {pos + bucket}) (bucket {bucket}, buckets "
                    f"{self.buckets}) pads past the per-slot KV capacity "
                    f"max_len={self.max_len}; raise max_len or add a bucket "
                    f"<= {self.max_len - pos} so bucket-padded prefill fits"
                )
            buf = np.full((1, bucket), self.config.pad_token_id, np.int32)
            buf[0, :real] = prompt[pos : pos + real]
            chunks.append((buf, real))
            pos += real
        return chunks

    def _admit(self) -> None:
        while self._queue and self._free:
            req = self._queue.popleft()
            slot_id = self._free.popleft()
            node, matched = None, 0
            if self.prefix_cache is not None:
                # Cap the match one token short of the prompt: the final
                # prefill chunk must forward at least one real token to
                # produce the first sampling logits. The returned node is
                # pinned until the copy dispatch in _prefill_step — LRU
                # eviction cannot recycle its row in between, however many
                # promotions other slots' completions trigger first.
                node, matched = self.prefix_cache.match(
                    req.prompt, limit=len(req.prompt) - 1, rid=req.rid
                )
            try:
                chunks = self._chunk_plan(req.prompt, start=matched)
            except ValueError:
                # The match-shifted plan can pad past max_len even when the
                # start=0 plan (validated at submit) fits — a hit is an
                # optimization, never a requirement, so fall back to a full
                # prefill rather than rejecting the request.
                self.prefix_cache.release(node)
                node, matched = None, 0
                chunks = self._chunk_plan(req.prompt)
            self._slots[slot_id] = _Slot(
                req,
                chunks,
                self.config.pad_token_id,
                matched=matched,
                pending_copy=(node, matched) if node is not None else None,
            )
            self._prefill_order.append(slot_id)
            self.stats["admitted"] += 1
            self.stats["prompt_tokens"] += len(req.prompt)
            if matched:
                self.stats["prefix_hits"] += 1
                self.stats["prefill_tokens_saved"] += matched
            if self._trace:
                _flight.record_span(
                    "admit",
                    rid=req.rid,
                    slot=slot_id,
                    prefix_hit=bool(matched),
                    prefix_matched=int(matched),
                    prompt_tokens=len(req.prompt),
                )

    def step(self) -> list[Completion]:
        """One scheduler iteration: admit what fits, then run EITHER one
        prefill chunk OR one decode step over the slot batch (prefill and
        decode alternate per ``prefill_interleave`` when both are pending).
        Returns the requests that finished this iteration."""
        # Engine-level chaos injection point (test_utils/faults.py): a
        # cheap env-membership check when no fault is armed.
        resilience.fault_point("engine.step")
        # Which kind of step this is, known before admission so that the
        # step's span covers it: a request admitted now is never decoding
        # yet and always has a prefill chunk pending (a prefix match stops
        # one token short of the prompt).
        decoding = [i for i, s in enumerate(self._slots) if s is not None and s.decoding]
        will_prefill = self._prefill_order or (self._queue and self._free)
        if will_prefill and (not decoding or self._decode_credit <= 0):
            self._decode_credit = self.prefill_interleave
            self.actions.append("prefill")
            t0 = time.perf_counter()
            with _telemetry.span("serve_prefill"):
                with _telemetry.span("serve_admit"):
                    self._admit()
                out = self._prefill_step()
            self._h_prefill_ms.observe(
                (time.perf_counter() - t0) * 1e3, **self._tel_labels
            )
            return out
        if decoding:
            self._decode_credit -= 1
            self.actions.append("decode")
            t0 = time.perf_counter()
            with _telemetry.span("serve_decode"):
                with _telemetry.span("serve_admit"):
                    self._admit()
                out = self._decode_step(decoding)
            self._h_decode_ms.observe(
                (time.perf_counter() - t0) * 1e3, **self._tel_labels
            )
            return out
        return []

    def run_until_idle(self) -> list[Completion]:
        out: list[Completion] = []
        while self.busy:
            out.extend(self.step())
        return out

    def serve(
        self, requests: Iterable[Request], *, realtime: bool = False
    ) -> list[Completion]:
        """Drive a whole trace. ``realtime=True`` honours each request's
        ``arrival`` offset on the wall clock (idle gaps are slept through)
        — the latency-measuring mode; otherwise requests are submitted in
        arrival order as fast as the engine drains them."""
        reqs = sorted(requests, key=lambda r: (r.arrival or 0.0))
        out: list[Completion] = []
        t0 = time.perf_counter()
        i = 0
        while i < len(reqs) or self.busy:
            if i < len(reqs):
                now = time.perf_counter() - t0
                while i < len(reqs) and (
                    not realtime or (reqs[i].arrival or 0.0) <= now
                ):
                    self.submit_request(reqs[i])
                    i += 1
                if realtime and not self.busy and i < len(reqs):
                    time.sleep(
                        max((reqs[i].arrival or 0.0) - (time.perf_counter() - t0), 0.0)
                    )
                    continue
            out.extend(self.step())
        return out

    # ------------------------------------------------------------ actions
    def _prefill_step(self) -> list[Completion]:
        slot_id = self._prefill_order[0]
        slot = self._slots[slot_id]
        buf, real = slot.chunks.pop(0)
        last = not slot.chunks
        if slot.t_prefill0 == 0.0:
            slot.t_prefill0 = time.perf_counter()
            submitted = getattr(slot.req, "submitted_at", 0.0)
            if submitted:
                self._h_queue_wait.observe(
                    (slot.t_prefill0 - submitted) * 1e3, **self._tel_labels
                )
        with _telemetry.span(
            "serve_dispatch", bucket=int(buf.shape[1]), slot=slot_id, rid=slot.req.rid
        ):
            if slot.pending_copy is not None:
                self._copy_prefix(slot_id, slot)
            t_chunk0 = 0.0
            compiles_before = 0
            if self._trace:
                t_chunk0 = time.perf_counter()
                compiles_before = self._prefill._cache_size()
            tok, self._kv = self._prefill(
                self.params,
                buf,
                self._kv,
                np.int32(slot_id),
                np.int32(slot.cursor),
                np.int32(real - 1),
                np.uint32(slot.req.seed),
                np.bool_(last),
            )
            if self._state_names:
                self.stats["state_resets"] += slot.cursor == 0
                self.stats["state_rows_real"] += real
                self.stats["state_rows_padded"] += buf.shape[1]
            slot.cursor += real
            self._dispatched(int(buf.shape[1]))
            self.stats["prefill_chunks"] += 1
            self._count_paths(buf.shape[1])
            self.stats["prefill_head_rows"] += self._head_rows[buf.shape[1]][0 if last else 1]
            self.prefill_signatures.append(buf.shape[1])
            if self._trace:
                _flight.record_span(
                    "prefill_chunk",
                    rid=slot.req.rid,
                    t0=t_chunk0,
                    bucket=int(buf.shape[1]),
                    tokens=int(real),
                    compile_miss=self._prefill._cache_size() > compiles_before,
                )
        if not last:
            return []  # more prompt to go; tok was a throwaway
        first = int(self._fetch(tok))
        with _telemetry.span("serve_emit"):
            self._prefill_order.popleft()
            slot.first_token_at = time.perf_counter()
            slot.decoding = True
            return self._emit(slot_id, first)

    def _copy_prefix(self, slot_id: int, slot: _Slot) -> None:
        """Prefix-cache hit: copy the matched KV span out of the pool into
        this slot's row, chunked at bucket lengths (static per chunk — the
        jit cache stays bounded by the bucket set). The copies are
        dispatched BEFORE this slot's first prefill chunk, so in device
        order the chunk's attention over [0, cursor) reads committed prefix
        KV, never the pool row's future state."""
        node, matched = slot.pending_copy
        t_copy0 = time.perf_counter() if self._trace else 0.0
        off = 0
        n_copy = 0
        for ln in self.prefix_cache.chunks(matched):
            self._kv = self._copy(
                self._kv, self._pool,
                np.int32(slot_id), np.int32(node.row), np.int32(off), ln,
            )
            self._dispatched("copy")
            self.copy_signatures.append(ln)
            self.stats["prefix_copy_chunks"] += 1
            off += ln
            n_copy += 1
        self.prefix_cache.release(node)
        slot.pending_copy = None
        if self._trace:
            # Dispatch time only — the copies are async on device.
            _flight.record_span(
                "prefix_copy",
                rid=slot.req.rid,
                t0=t_copy0,
                tokens=int(matched),
                chunks=n_copy,
            )

    def _decode_step(self, decoding: list[int]) -> list[Completion]:
        # Block dispatch: chain up to decode_block steps on device, bounded
        # by the smallest remaining budget (so no step past a known budget
        # eviction), then fetch all their tokens in ONE sync. Interleave
        # granularity wins while prefill work is pending: block = 1.
        block = min(self.decode_block, *(
            self._slots[i].req.max_new_tokens - self._slots[i].n_new
            for i in decoding
        ))
        if self._prefill_order:
            block = 1
        with _telemetry.span("serve_dispatch", resident=len(decoding), block=block):
            lengths = np.zeros((self.n_slots,), np.int32)
            seeds = np.zeros((self.n_slots,), np.uint32)
            steps = np.zeros((self.n_slots,), np.int32)
            tokens: Any = np.zeros((self.n_slots,), np.int32)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue  # free slot: garbage write at 0, overwritten by
                    # the next admission's first prefill chunk
                # Mid-prefill slots ride along too: their cursor points at
                # the next chunk's start, so the row's garbage write lands
                # exactly where that chunk will overwrite it — never on
                # committed KV.
                tokens[i] = s.last_token
                lengths[i] = s.cursor
                seeds[i] = s.req.seed
                steps[i] = s.n_new
            if self._trace:
                # Residency accounting: two attribute adds per resident slot
                # — no per-iteration span, no allocation, nothing device-side.
                occ = len(decoding)
                for i in decoding:
                    s = self._slots[i]
                    s.occ_sum += occ * block
                    s.occ_n += block
            fetched = []
            # Commit the seed tokens to the cache's device so the chained
            # calls (whose token input is the previous step's committed
            # OUTPUT) share one jit signature with the first — otherwise the
            # decode step silently compiles twice (committed vs uncommitted
            # int32 (N,)).
            tokens = jax.device_put(tokens, self._device)
            extra = ()
            if self._state_names:
                mask = np.zeros((self.n_slots,), bool)
                mask[decoding] = True
                extra = (mask,)
            for _ in range(block):
                # The dispatch gets its own copies of the cursors: the
                # transfer is asynchronous and can alias numpy memory, so it
                # may still be reading a host buffer when the lines below
                # advance it in place.
                (tokens, counts), self._kv = self._decode(
                    self.params, tokens, lengths.copy(), self._kv, seeds, steps.copy(), *extra
                )
                fetched.append((tokens, counts))
                self._dispatched("decode")
                self._count_paths("decode")
                attended = lengths + 1  # what the attention is handed, slot by slot
                lengths[decoding] += 1
                steps[decoding] += 1
                if self._state_names:
                    self.stats["state_slots_live"] += len(decoding) * self._state_layers
                self.stats["kv_rows_live_full"] += int(attended[decoding].sum())
                self.stats["kv_rows_fetched_full"] += self._kv_rows_fetched(attended, self.max_len)
                if self._ring_len:
                    attended = np.minimum(attended, self._ring_len)
                    self.stats["kv_rows_live_window"] += int(attended[decoding].sum())
                    self.stats["kv_rows_fetched_window"] += self._kv_rows_fetched(
                        attended, self._ring_len
                    )
        host = self._fetch(fetched)
        out: list[Completion] = []
        with _telemetry.span("serve_emit"):
            self.stats["decode_steps"] += block
            self.stats["decode_slot_steps"] += block * len(decoding)
            for _, counts in host:
                for name, value in counts.items():
                    self.stats[name] += int(value)
            for nxt, _ in host:
                for i in decoding:
                    slot = self._slots[i]
                    if slot is None or not slot.decoding:
                        continue  # finished mid-block: later tokens are zombies
                    slot.cursor += 1
                    out.extend(self._emit(i, int(nxt[i])))
        return out

    def _dispatched(self, program: int | str) -> None:
        """Note one program dispatched for the next fetch to wait on."""
        self._waiting_on[program] = self._waiting_on.get(program, 0) + 1

    def _fetch(self, pending: Any) -> Any:
        """Bring ``pending`` to the host: the one place a step waits for the
        device, which by then has to finish every program dispatched since
        the last fetch. The ``serve_fetch`` span names the fetch (``seq``)
        and what it waits on (``chunks``, ``decodes``). A wait the stall
        rule flags adds to ``stalled_fetches`` / ``stalled_fetch_ms`` and
        leaves one ``fetch_stall`` record in the flight recorder, with the
        process's CPU time over the wait: about none, the process sat
        blocked; about the wait, some thread of it (the runtime's too) was
        busy."""
        waiting_on, self._waiting_on = self._waiting_on, {}
        self._fetch_seq += 1
        seq = self._fetch_seq
        decodes = waiting_on.get("decode", 0)
        chunks = sum(waiting_on.values()) - decodes - waiting_on.get("copy", 0)
        with _telemetry.span("serve_fetch", seq=seq, chunks=chunks, decodes=decodes):
            c0 = time.process_time()
            t0 = time.perf_counter()
            host = jax.device_get(pending)
            t1 = time.perf_counter()
        usual = self._stalls.judge(frozenset(waiting_on.items()), t1 - t0)
        if usual is not None:
            cpu_ms = (time.process_time() - c0) * 1e3
            self.stats["stalled_fetches"] += 1
            self.stats["stalled_fetch_ms"] += (t1 - t0 - usual) * 1e3
            _flight.record_span(
                "fetch_stall",
                t0=t0,
                t1=t1,
                engine=self.stats.instance,
                seq=seq,
                key=fetch_key(waiting_on),
                usual_ms=usual * 1e3,
                wait_ms=(t1 - t0) * 1e3,
                cpu_ms=cpu_ms,
            )
        return host

    def _kv_rows_fetched(self, attended: np.ndarray, rows: int) -> int:
        """Rows one decode step's attention copies out of a layer whose
        leaves hold ``rows`` a slot: the kernel's own arithmetic where the
        traced program reads the cache in place, else every row."""
        if rows not in self._kv_row_bytes:
            return 0  # a cache of states only keeps no rows
        if not self.stats["decode_in_place"]:
            return attended.size * rows
        return rows_fetched(attended, rows, self._kv_row_bytes[rows])

    def _count_paths(self, program: Any) -> None:
        """One dispatch of ``program``: add what its trace recorded."""
        in_place, sliced, quantized_once = self._weight_paths.get(program, (0, 0, 0))
        self.stats["weights_in_place"] += in_place
        self.stats["weights_sliced"] += sliced
        self.stats["int8_act_quantized_once"] += quantized_once
        in_place, sliced = self._attention_paths.get(program, (0, 0))  # prefill buckets only
        self.stats["prefill_attn_in_place"] += in_place
        self.stats["prefill_attn_sliced"] += sliced

    def _emit(self, slot_id: int, tok: int) -> list[Completion]:
        """Record one generated token for a slot; finish/evict on EOS, a
        stop-sequence match, or budget exhaustion."""
        slot = self._slots[slot_id]
        req = slot.req
        slot.out[slot.n_new] = tok
        slot.n_new += 1
        slot.last_token = tok
        if req.stream is not None:
            piece = self.detokenize([tok]) if self.detokenize else None
            req.stream(req.rid, tok, piece)
        eos_hit = (
            self.config.eos_token_id is not None and tok == self.config.eos_token_id
        )
        stop_hit = False
        if req.stop_sequences and not eos_hit:
            for seq in req.stop_sequences:
                n = len(seq)
                if n <= slot.n_new and slot.out[slot.n_new - n : slot.n_new].tolist() == list(seq):
                    stop_hit = True
                    break
        if not eos_hit and not stop_hit and slot.n_new < req.max_new_tokens:
            return []
        t_decode_end = time.perf_counter() if self._trace else 0.0
        completion = Completion(
            rid=req.rid,
            prompt=req.prompt,
            tokens=slot.out,
            n_new=slot.n_new,
            text=self.detokenize(slot.out[: slot.n_new].tolist())
            if self.detokenize
            else None,
            submitted_at=getattr(req, "submitted_at", 0.0),
            first_token_at=slot.first_token_at,
            finished_at=time.perf_counter(),
            finish_reason="eos" if eos_hit else ("stop" if stop_hit else "length"),
            admitted_at=slot.t_admit,
            prefill_started_at=slot.t_prefill0,
            slot=slot_id,
        )
        self._record_request(completion)
        if self._trace:
            # Contiguous phase spans — queue / prefill / decode / emit tile
            # [submitted_at, finished_at] exactly, so the `atx trace`
            # attribution table sums to the request's e2e by construction.
            # A router stamps its admission time on the request so queue
            # time spent BEFORE engine dispatch is attributed too (the
            # `complete` span's e2e starts at router admission).
            submitted = (
                getattr(req, "router_submitted_at", 0.0)
                or getattr(req, "submitted_at", 0.0)
                or slot.t_prefill0
            )
            t_p0 = slot.t_prefill0 or submitted
            t_first = slot.first_token_at or t_p0
            _flight.record_span("phase_queue", rid=req.rid, t0=submitted, t1=t_p0)
            _flight.record_span("phase_prefill", rid=req.rid, t0=t_p0, t1=t_first)
            _flight.record_span(
                "phase_decode",
                rid=req.rid,
                t0=t_first,
                t1=t_decode_end,
                iterations=slot.occ_n,
                tokens=slot.n_new,
                occupancy=round(
                    slot.occ_sum / max(slot.occ_n * self.n_slots, 1), 4
                ),
            )
            _flight.record_span(
                "phase_emit",
                rid=req.rid,
                t0=t_decode_end,
                t1=completion.finished_at,
                finish_reason=completion.finish_reason,
            )
        if self.prefix_cache is not None:
            self._promote(slot_id, slot)
        self._slots[slot_id] = None  # evict: the slot is immediately reusable
        self._free.append(slot_id)
        self.stats["completed"] += 1
        self._c_tokens.inc(slot.n_new, **self._tel_labels)
        submitted = completion.submitted_at
        if submitted:
            if completion.first_token_at:
                self._h_ttft.observe(
                    (completion.first_token_at - submitted) * 1e3,
                    **self._tel_labels,
                )
            self._h_e2e.observe(
                (completion.finished_at - submitted) * 1e3, **self._tel_labels
            )
        return [completion]

    def _promote(self, slot_id: int, slot: _Slot) -> None:
        """Offer an evicted slot's committed prefix to the cache: the
        chunk-aligned front of [0, cursor) — the prompt plus every
        generated token whose KV has been committed (all but the last, so
        multi-turn follow-ups hit past the original prompt). The copies
        read the slot row BEFORE any later admission overwrites it (host
        dispatch order is device order), and a dedup/full-pool insert
        returns None, in which case promotion is just skipped — hits are
        an optimization, never a correctness dependency."""
        committed = slot.cursor
        cached_len = self.prefix_cache.aligned(committed)
        if cached_len <= 0:
            return
        tokens = slot.req.prompt
        if cached_len > len(tokens):
            tokens = np.concatenate([tokens, slot.out[: cached_len - len(tokens)]])
        else:
            tokens = tokens[:cached_len]
        row = self.prefix_cache.insert(tokens)
        if row is None:
            return
        off = 0
        for ln in self.prefix_cache.chunks(cached_len):
            self._pool = self._copy(
                self._pool, self._kv,
                np.int32(row), np.int32(slot_id), np.int32(off), ln,
            )
            self._dispatched("copy")
            self.copy_signatures.append(ln)
            self.stats["prefix_copy_chunks"] += 1
            off += ln
        self.stats["prefix_promotions"] += 1

    # ------------------------------------------------------------ metrics
    def latency_summary(self) -> dict:
        """Exact request-latency percentiles (ms, None until the first
        completion) over the `request` records this engine has in the flight
        recorder's ring: submit -> completion and submit -> first token of
        the requests that ran to their end (a caller whose requests were due
        earlier adds its own lateness). The numbers behind `atx serve`'s
        ``serve_p50_ms`` / ``serve_ttft_p50_ms``; `/metrics` exports the
        same samples as fixed-bucket histograms."""
        mine = [
            r for r in _flight.recorder().last()
            if r["name"] == "request"
            and r["attrs"]["engine"] == self.stats.instance
            and r["attrs"]["finish_reason"] != "cancelled"
        ]
        if not mine:
            return dict.fromkeys(
                ("p50_ms", "p99_ms", "ttft_p50_ms", "ttft_p99_ms", "mean_ms")
            )
        e2e = np.array([r["t1"] - r["t0"] for r in mine]) * 1e3
        ttft = np.array([r["attrs"]["first_token_at"] - r["t0"] for r in mine]) * 1e3
        return {
            "p50_ms": float(np.percentile(e2e, 50)),
            "p99_ms": float(np.percentile(e2e, 99)),
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p99_ms": float(np.percentile(ttft, 99)),
            "mean_ms": float(e2e.mean()),
        }

    def prefix_metrics(self) -> dict:
        """Prefix-cache counters in reporting shape (`atx serve` JSON).
        ``prefill_saved_frac`` is the fraction of
        all admitted prompt tokens that were served by KV copy instead of
        prefill compute — the headline number for shared-prefix traffic."""
        if self.prefix_cache is None:
            return {"prefix_cache": 0}
        pc = self.prefix_cache
        return {
            "prefix_cache": 1,
            "prefix_rows": pc.n_rows,
            "prefix_rows_used": pc.used_rows,
            "prefix_hit_rate": round(
                self.stats["prefix_hits"] / max(pc.stats["lookups"], 1), 3
            ),
            "prefill_tokens_saved": self.stats["prefill_tokens_saved"],
            "prefill_saved_frac": round(
                self.stats["prefill_tokens_saved"]
                / max(self.stats["prompt_tokens"], 1),
                3,
            ),
            "prefix_promotions": self.stats["prefix_promotions"],
            "prefix_evictions": pc.stats["evictions"],
            "prefix_copy_compiles": self._copy._cache_size(),
        }

    # --------------------------------------------------------------- lint
    def abstract_decode_args(self) -> tuple:
        """ShapeDtypeStructs matching one decode-step call — feed to
        `analysis.lint_step(engine._decode_fn, *engine.abstract_decode_args(),
        donate_argnums=(3,))` (the `atx lint serving` scenario and the
        smoke-serve lane gate on its error findings)."""
        sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        vec = lambda dt: jax.ShapeDtypeStruct((self.n_slots,), dt)
        return (
            jax.tree.map(sds, self.params),
            vec(np.int32),
            vec(np.int32),
            jax.tree.map(sds, self._kv),
            vec(np.uint32),
            vec(np.int32),
            *((vec(np.bool_),) if self._state_names else ()),
        )

    def copy_fn_for_bucket(self, bucket: int):
        """The prefix-copy computation at one static chunk length, for
        linting: `analysis.lint_step(engine.copy_fn_for_bucket(b),
        *engine.abstract_copy_args(), donate_argnums=(0,))` — the `atx
        lint serving` scenario runs it alongside the decode step."""
        return lambda dst, src, dst_slot, src_slot, start: self._copy_fn(
            dst, src, dst_slot, src_slot, start, bucket
        )

    def abstract_copy_args(self) -> tuple:
        """ShapeDtypeStructs matching one hit-direction prefix-copy call
        (dst = the slot kv pool, src = the prefix pool); pairs with
        `copy_fn_for_bucket`. Requires the prefix cache to be enabled."""
        if self._pool is None:
            raise RuntimeError("prefix cache is disabled on this engine")
        sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
        return (
            jax.tree.map(sds, self._kv),
            jax.tree.map(sds, self._pool),
            scalar(np.int32),
            scalar(np.int32),
            scalar(np.int32),
        )


def poisson_trace(
    n: int,
    rate: float,
    *,
    vocab_size: int,
    prompt_lens: tuple[int, int] = (8, 96),
    new_tokens: tuple[int, int] = (8, 48),
    seed: int = 0,
    stop_sequences: Sequence[Sequence[int]] | None = None,
) -> list[Request]:
    """Synthetic mixed-length request trace with Poisson arrivals at
    ``rate`` requests/sec — the `atx serve` workload shape.
    ``stop_sequences`` (if given) is attached to every request."""
    rng = np.random.RandomState(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    reqs = []
    for i in range(n):
        S = int(rng.randint(prompt_lens[0], prompt_lens[1] + 1))
        reqs.append(
            Request(
                prompt=rng.randint(0, vocab_size, (S,)).astype(np.int32),
                max_new_tokens=int(rng.randint(new_tokens[0], new_tokens[1] + 1)),
                rid=i,
                seed=i,
                arrival=float(arrivals[i]),
                stop_sequences=stop_sequences,
            )
        )
    return reqs


def shared_prefix_trace(
    n: int,
    rate: float,
    *,
    vocab_size: int,
    n_prefixes: int = 2,
    prefix_len: int = 64,
    tail_lens: tuple[int, int] = (4, 24),
    new_tokens: tuple[int, int] = (4, 16),
    seed: int = 0,
    stop_sequences: Sequence[Sequence[int]] | None = None,
) -> list[Request]:
    """Poisson trace where every prompt is one of ``n_prefixes`` shared
    system prompts (``prefix_len`` tokens) plus a unique tail — the
    workload shape automatic prefix caching targets. With the cache on,
    hit-rate approaches ``(n - n_prefixes) / n`` once each prefix has been
    promoted; make ``prefix_len`` a sum of bucket lengths so the whole
    prefix is reusable (docs/serving.md)."""
    rng = np.random.RandomState(seed)
    prefixes = [
        rng.randint(0, vocab_size, (prefix_len,)).astype(np.int32)
        for _ in range(n_prefixes)
    ]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    reqs = []
    for i in range(n):
        tail = rng.randint(
            0, vocab_size, (int(rng.randint(tail_lens[0], tail_lens[1] + 1)),)
        ).astype(np.int32)
        reqs.append(
            Request(
                prompt=np.concatenate([prefixes[i % n_prefixes], tail]),
                max_new_tokens=int(rng.randint(new_tokens[0], new_tokens[1] + 1)),
                rid=i,
                seed=i,
                arrival=float(arrivals[i]),
                stop_sequences=stop_sequences,
            )
        )
    return reqs
