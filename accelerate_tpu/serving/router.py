"""Multi-replica serving front-end: routing, admission, drain, failover.

One `Engine` saturates one device group; this module is the fleet layer
above it, following the front-end/engine split of production LLM servers
(Orca's request-level scheduler over execution engines; SGLang's
cache-aware routing, which the PR-6 radix prefix cache was built to
exploit). A `Router` owns a bounded admission queue and fans requests out
to N engine **replicas** — locally each replica is an `Engine` over its
own device subset; on a pod the same abstraction covers
one-engine-per-host. Five mechanics:

- **Prefix-affinity + least-loaded routing** — a host-side
  `AffinityIndex` over recently dispatched prompts steers a request
  sharing a cached prefix to the replica that owns that prefix KV
  (maximizing per-replica prefix-cache hit rate), falling back to the
  least-loaded replica. ``affinity_min_tokens`` (default: the smallest
  prefill bucket — shorter matches can't be cache-aligned anyway) and
  ``affinity_max_imbalance`` (how many extra in-flight requests affinity
  may pile onto one replica before balance wins) set the trade-off;
  ``affinity="least-loaded"`` disables steering entirely.
- **Admission scheduling & backpressure** — the queue of
  accepted-but-undispatched requests is bounded (``queue_depth``, env
  ``ATX_SERVE_QUEUE_DEPTH``, default 4x total fleet slots) and, by
  default (``scheduling="edf"``), dispatched earliest-deadline-first
  within priority classes (`Request.priority`, lower = more important;
  requests without deadlines order after deadlined peers, FIFO within a
  class — so a homogeneous trace reproduces the old FIFO order exactly).
  Under overload a full queue *sheds*: an arriving request of a strictly
  more important class evicts the newest queued request of the least
  important class (``finish_reason="shed"``, `router_shed_total{class}`)
  instead of being rejected; arrivals that don't outrank anyone still get
  `QueueFullError`. Requests whose deadline is already infeasible given
  the observed service time and the work ahead of them are rejected at
  the front door (`DeadlineInfeasibleError`,
  `router_deadline_infeasible_total`) once the e2e histogram has data.
  Per-request deadlines (`Request.timeout` seconds) still cancel
  mid-queue or mid-decode with ``finish_reason="cancelled"``;
  `Router.cancel` does the same on demand. ``scheduling="fifo"`` restores
  strict arrival order with reject-only overload behaviour.
- **Graceful drain** — every `poll` reads
  ``resilience.preemption_requested()`` (SIGTERM / the GCE maintenance
  poller); when set, the router stops admitting (`RouterDraining`),
  finishes everything already accepted, and the caller exits with
  ``resilience.PREEMPTION_EXIT_CODE`` (75) so an elastic launcher resumes
  it (`atx serve --replicas` does exactly this).
- **Replica failover, probation & re-admission** — a replica whose
  thread raises (including `test_utils.faults` injection at the
  ``router.replica<i>.step`` crash points) or wedges (per-replica
  `resilience.Watchdog` on step-entry heartbeats; ``watchdog_secs`` /
  ``ATX_SERVE_REPLICA_WATCHDOG_SECS``) is **quarantined**: its in-flight
  requests are re-dispatched to healthy replicas (up to ``max_retries``
  attempts, then ``finish_reason="failed"``), metered by a fleet-wide
  **retry budget** (token bucket: ``ATX_SERVE_RETRY_BUDGET`` capacity,
  ``ATX_SERVE_RETRY_REFILL_PER_SEC`` refill) so a sick fleet degrades to
  visible ``failed`` completions instead of a retry storm. With
  ``readmit_secs`` / ``ATX_SERVE_READMIT_SECS`` set, quarantine is not
  forever: after a capped-exponential + jittered backoff the replica is
  **probed** — a canary request recorded from real traffic is replayed
  directly on the idle quarantined engine and must reproduce the healthy
  fleet's tokens bit-for-bit (greedy determinism makes this exact) — and
  on success re-admitted under **probation** (dispatch capped to one
  in-flight request until ``ATX_SERVE_PROBATION_COMPLETIONS`` clean
  completions). A probe failure (or a wedged engine) rebuilds the
  replica from ``engine_factory`` (fresh engine, same weights) when one
  is provided. On quarantine the dead replica's hottest committed
  prefix-cache entries (HOST-side token ids) are **migrated**: re-seeded
  into a surviving replica by internal warm-up prefills (KV is
  re-prefilled, never copied cross-device) and the `AffinityIndex`
  retargeted so the family's future traffic steers at the warm survivor.
  Greedy outputs stay bit-identical to a solo `Engine` regardless of
  routing, retries, replica death, or re-admission: tokens are a pure
  function of (prompt, seed, config, params), so a retry is a replay —
  and per-ticket stream dedup delivers each token's callback exactly
  once even when an attempt died mid-decode.
- **Aggregate observability** — `Router.metrics()` snapshots fleet
  counters (queue depth/peak, rejects, retries, cancels, drains,
  TTFT/e2e p50/p99) plus per-replica occupancy, prefix hit rate, and
  quarantine state; `atx serve` merges it into its one-line JSON.

Execution modes:

- ``threads=True`` (default): each replica engine runs on its OWN
  dedicated thread (the one-thread-per-engine ownership rule in
  `engine.py`), pumping submissions/cancellations from a per-replica
  inbox; the caller's thread runs only router logic (`poll`/`serve`).
- ``threads=False``: replicas are pumped inline on the caller's thread,
  round-robin, one step per replica per `poll` — fully deterministic, no
  thread scheduling in the dispatch order. This is the mode the `atx
  lint router_drain` scenario replays through `analysis.lint_host_loop`
  and the mode bit-identity tests use; wedge detection (a stuck step
  would stall the caller itself) needs ``threads=True``.

Replicas must be identically configured (same ``buckets`` / ``max_len``
/ generation config): admission validates against replica 0 and failover
replays on any healthy replica, so a request must fit all of them.
See docs/serving.md ("Multi-replica routing & drain").
"""

from __future__ import annotations

import os
import queue
import random
import threading
import time
from collections import deque
from typing import Any, Callable, Iterable, Sequence

import numpy as np

# Package-attribute access (not by-value import): `analysis.host_trace`
# patches `resilience.preemption_requested` and `resilience.fault_point`
# on the package during lint replay, so the router must read them through
# the package or the router_drain scenario would dodge the simulation.
from .. import resilience
from .. import telemetry as _telemetry
from ..telemetry import flight as _flight
from ..utils.environment import get_int_from_env
from .engine import Completion, Engine, Request

__all__ = [
    "Router",
    "AffinityIndex",
    "QueueFullError",
    "RouterDraining",
    "DeadlineInfeasibleError",
    "NoHealthyReplicaError",
]

# Internal warm-up requests (prefix-cache migration) ride the normal
# dispatch path at a priority no user class should ever use: they fill
# idle capacity, never displace traffic, and are first to be shed.
_INTERNAL_PRIORITY = 1_000_000


class QueueFullError(RuntimeError):
    """Admission queue at ``queue_depth``: the request was REJECTED (never
    queued). Callers retry with backoff or shed load — the visible
    backpressure signal (`stats["rejects"]` counts these)."""


class DeadlineInfeasibleError(QueueFullError):
    """The request's deadline cannot be met given the observed service
    time and the queue ahead of it — rejected at admission so the caller
    can fail over instead of burning fleet time on a doomed request.
    Subclasses `QueueFullError` so overload-aware callers (retry with
    backoff / shed) handle both the same way."""


class RouterDraining(RuntimeError):
    """The router is draining (preemption or `Router.drain`): no new
    admissions; everything already accepted still completes."""


class NoHealthyReplicaError(RuntimeError):
    """Every replica is quarantined while requests are still outstanding —
    the fleet cannot make progress."""


class AffinityIndex:
    """Host-side index of recently dispatched prompts per replica.

    The router can't see inside each replica's device-resident prefix
    cache, so it keeps its own LRU record of (prompt, replica) pairs at
    dispatch time and scores candidates by longest shared prefix — the
    same signal the per-engine radix tree keys on, approximated at the
    fleet level. Bounded at ``cap`` entries (drop-oldest) so lookup cost
    stays a few hundred short vector compares per admission."""

    def __init__(self, cap: int = 512) -> None:
        self.cap = cap
        self._entries: deque[tuple[np.ndarray, int]] = deque()

    def insert(self, prompt: np.ndarray, replica: int) -> None:
        self._entries.append((np.asarray(prompt, np.int32), int(replica)))
        while len(self._entries) > self.cap:
            self._entries.popleft()

    def remove_replica(self, replica: int) -> None:
        """Forget a quarantined replica — its cached KV is unreachable, so
        steering traffic at it would be pure imbalance."""
        self._entries = deque((p, r) for p, r in self._entries if r != replica)

    def retarget(self, replica: int, target: int) -> int:
        """Re-point a quarantined replica's entries at ``target`` — the
        survivor its hot prefixes were migrated to — so the prefix
        families keep steering at warm KV instead of being forgotten.
        Returns how many entries moved."""
        moved = 0
        for i, (p, r) in enumerate(self._entries):
            if r == replica:
                self._entries[i] = (p, int(target))
                moved += 1
        return moved

    def best(self, prompt: np.ndarray) -> dict[int, int]:
        """Longest shared-prefix length per replica for ``prompt``."""
        prompt = np.asarray(prompt, np.int32)
        best: dict[int, int] = {}
        for toks, r in self._entries:
            n = min(len(toks), len(prompt))
            if n <= best.get(r, 0):
                continue  # can't beat this replica's current best
            neq = np.nonzero(toks[:n] != prompt[:n])[0]
            m = int(neq[0]) if len(neq) else n
            if m > best.get(r, 0):
                best[r] = m
        return best


class _Ticket:
    """Router-side bookkeeping for one accepted request."""

    __slots__ = (
        "req", "user_stream", "submitted_at", "deadline", "replica",
        "attempts", "generation", "streamed", "cancel_sent", "done",
        "seq", "internal",
    )

    def __init__(self, req: Request, seq: int = 0) -> None:
        self.req = req
        self.user_stream = req.stream
        self.submitted_at = time.perf_counter()
        self.deadline = (
            self.submitted_at + req.timeout if req.timeout is not None else None
        )
        self.replica: int | None = None
        self.attempts = 0
        # Bumped at every (re)dispatch and at resolution: a stream callback
        # from a superseded attempt (a quarantined replica's thread still
        # unwinding) sees a stale generation and drops itself.
        self.generation = 0
        self.streamed = 0  # tokens delivered to the user stream so far
        self.cancel_sent = False
        self.done = False
        # Admission sequence number: the EDF tiebreak (FIFO within a
        # class) — retries keep their original seq so age order survives
        # a re-dispatch, exactly like the old appendleft requeue.
        self.seq = seq
        # Internal tickets (prefix-cache migration warm-ups) bypass the
        # admission bound and are invisible to callers: no completion
        # surfaced, no latency observed, not counted as submissions.
        self.internal = False


class _Replica:
    """One engine + (in threads mode) its dedicated driver thread.

    The engine is single-threaded by contract; ALL interaction crosses a
    locked inbox of ``("submit", Request)`` / ``("cancel", rid)`` /
    ``("stop",)`` messages, applied between `step` calls by `pump` — which
    is the same code path the thread loop and the inline mode run, so the
    two modes differ only in who calls it."""

    def __init__(
        self,
        id: int,
        engine: Engine,
        router: "Router",
        *,
        watchdog_secs: float | None = None,
    ) -> None:
        self.id = id
        self.engine = engine
        self.router = router
        self.inbox: deque = deque()
        self.inbox_lock = threading.Lock()
        self.wake = threading.Event()
        self.thread: threading.Thread | None = None
        self.dead = False  # router-side quarantine flag (router thread only)
        self.error: str | None = None
        self.wedged = threading.Event()
        self.inflight: set[int] = set()  # rids dispatched here (router thread)
        self.dispatched = 0
        self.completed = 0
        self._stopping = False
        self._watchdog_secs = watchdog_secs
        # Re-admission state (router thread only): when the router has
        # readmit enabled, a quarantine schedules a probe at ``probe_at``;
        # a readmitted replica serves under probation (dispatch capped to
        # one in-flight) until ``probation_left`` clean completions.
        self.quarantines = 0
        self.probe_at: float | None = None
        self.probation_left = 0
        self.rebuilds = 0
        self.watchdog: resilience.Watchdog | None = None
        if watchdog_secs:
            # The abort seam turns the watchdog's process-kill into a
            # per-replica quarantine: the fleet survives one wedged engine.
            self.watchdog = resilience.Watchdog(
                watchdog_secs,
                first_deadline_secs=watchdog_secs * 10.0,  # compile headroom
                abort=self._wedge,
            )

    def _wedge(self) -> None:
        self.wedged.set()
        self.router._results.put((
            "down", self.id,
            f"wedged: step exceeded its {self.watchdog.deadline:.1f}s "
            "deadline (ATX_SERVE_REPLICA_WATCHDOG_SECS)",
        ))

    def send(self, msg: tuple) -> None:
        with self.inbox_lock:
            self.inbox.append(msg)
        self.wake.set()

    def pump(self) -> list[Completion]:
        """Apply queued messages, then run at most one engine step. Runs on
        the replica thread (threads mode) or the caller (inline mode)."""
        out: list[Completion] = []
        with self.inbox_lock:
            msgs = list(self.inbox)
            self.inbox.clear()
        for msg in msgs:
            if msg[0] == "submit":
                self.engine.submit_request(msg[1])
            elif msg[0] == "cancel":
                c = self.engine.cancel(msg[1])
                if c is not None:
                    out.append(c)
            elif msg[0] == "stop":
                self._stopping = True
        if self.engine.busy:
            if self.watchdog is not None:
                self.watchdog.arm()
            resilience.fault_point(f"router.replica{self.id}.step")
            out.extend(self.engine.step())
            if self.watchdog is not None:
                self.watchdog.disarm()
        return out

    def start(self) -> None:
        self.thread = threading.Thread(
            target=self._run, name=f"atx-replica{self.id}", daemon=True
        )
        self.thread.start()

    def _run(self) -> None:
        try:
            while True:
                for c in self.pump():
                    self.router._results.put(("done", self.id, c))
                if self._stopping and not self.engine.busy and not self.inbox:
                    return
                if not self.engine.busy and not self.inbox:
                    self.wake.wait(0.002)
                    self.wake.clear()
        except BaseException as e:  # any replica death is a quarantine event
            self.router._results.put(
                ("down", self.id, f"{type(e).__name__}: {e}")
            )
        finally:
            if self.watchdog is not None:
                self.watchdog.stop()

    def respawn(self) -> None:
        """Bring a quarantined replica back after a successful probe:
        fresh liveness state, fresh watchdog, and (threads mode) a fresh
        driver thread. The old thread is guaranteed gone or permanently
        parked (a wedged replica is only respawned after an engine
        rebuild), so single-thread engine ownership is preserved."""
        if self.watchdog is not None:
            self.watchdog.stop()
        with self.inbox_lock:
            self.inbox.clear()
        self.dead = False
        self.error = None
        self.wedged = threading.Event()
        self.wake = threading.Event()
        self._stopping = False
        self.probe_at = None
        self.watchdog = None
        if self._watchdog_secs:
            self.watchdog = resilience.Watchdog(
                self._watchdog_secs,
                first_deadline_secs=self._watchdog_secs * 10.0,
                abort=self._wedge,
            )
        if self.router.threads:
            self.start()


def _pct(xs: list[float], q: float) -> float | None:
    if not xs:
        return None
    s = sorted(xs)
    return round(s[min(len(s) - 1, int(q * len(s)))], 2)


def _hq(hist: Any, q: float, labels: dict) -> float | None:
    """Histogram-estimated percentile, rounded like the old exact `_pct`
    (None until data) so `metrics()` keeps its field contract."""
    value = hist.quantile(q, **labels)
    return None if value is None else round(value, 2)


class Router:
    """Bounded-admission front-end over N `Engine` replicas (module
    docstring has the full design). Typical use::

        with Router([engine_a, engine_b]) as router:
            completions = router.serve(trace, realtime=True)

    or incrementally: `submit`/`submit_request` -> `poll` (one tick) ->
    `pop_completions`, with `join` to run everything outstanding down.
    All Router methods must be called from ONE thread (the replicas have
    their own); completions come back in finish order with
    ``submitted_at`` rewritten to router admission time, so TTFT/e2e
    latencies include queueing delay."""

    def __init__(
        self,
        engines: Sequence[Engine],
        *,
        queue_depth: int | None = None,
        affinity: str = "prefix",
        affinity_min_tokens: int | None = None,
        affinity_max_imbalance: int | None = None,
        max_retries: int = 2,
        watchdog_secs: float | None = None,
        threads: bool = True,
        scheduling: str = "edf",
        readmit_secs: float | None = None,
        probation_completions: int | None = None,
        retry_budget: int | None = None,
        retry_refill_per_sec: float | None = None,
        migrate_prefixes: int | None = None,
        engine_factory: Callable[[int], Engine] | None = None,
    ) -> None:
        engines = list(engines)
        if not engines:
            raise ValueError("Router needs at least one engine replica")
        ref = engines[0]
        for i, e in enumerate(engines[1:], start=1):
            if e.buckets != ref.buckets or e.max_len != ref.max_len:
                raise ValueError(
                    "replicas must be identically configured (admission "
                    "validates against replica 0 and failover replays on any "
                    f"healthy replica): replica {i} has buckets={e.buckets} "
                    f"max_len={e.max_len}, replica 0 has buckets="
                    f"{ref.buckets} max_len={ref.max_len}"
                )
        self._ref = ref
        self.threads = threads
        if queue_depth is None:
            queue_depth = get_int_from_env(
                ("ATX_SERVE_QUEUE_DEPTH",), 4 * sum(e.n_slots for e in engines)
            )
        if queue_depth < 1:
            raise ValueError(f"queue_depth must be >= 1, got {queue_depth}")
        self.queue_depth = queue_depth
        if affinity not in ("prefix", "least-loaded"):
            raise ValueError(
                f"affinity must be 'prefix' or 'least-loaded', got {affinity!r}"
            )
        self.affinity = affinity
        self.affinity_min_tokens = (
            affinity_min_tokens
            if affinity_min_tokens is not None
            else ref.buckets[0]
        )
        self.affinity_max_imbalance = (
            affinity_max_imbalance
            if affinity_max_imbalance is not None
            else max(1, ref.n_slots - 1)
        )
        self.max_retries = max_retries
        if scheduling not in ("edf", "fifo"):
            raise ValueError(
                f"scheduling must be 'edf' or 'fifo', got {scheduling!r}"
            )
        self.scheduling = scheduling
        # Re-admission: None/<=0 disables (a quarantined replica stays
        # dead forever — the pre-PR-14 behaviour, and what the fail-stop
        # tests rely on). Env: ATX_SERVE_READMIT_SECS.
        if readmit_secs is None:
            raw = os.environ.get("ATX_SERVE_READMIT_SECS", "")
            try:
                readmit_secs = float(raw) if raw else None
            except ValueError:
                readmit_secs = None
        if readmit_secs is not None and readmit_secs <= 0:
            readmit_secs = None
        self.readmit_secs = readmit_secs
        self.probation_completions = (
            probation_completions
            if probation_completions is not None
            else get_int_from_env(("ATX_SERVE_PROBATION_COMPLETIONS",), 3)
        )
        # Fleet-wide failover retry budget (token bucket). Capacity < 0
        # means unlimited (the pre-PR-14 behaviour).
        self.retry_budget = (
            retry_budget
            if retry_budget is not None
            else get_int_from_env(("ATX_SERVE_RETRY_BUDGET",), 16)
        )
        if retry_refill_per_sec is None:
            raw = os.environ.get("ATX_SERVE_RETRY_REFILL_PER_SEC", "")
            try:
                retry_refill_per_sec = float(raw) if raw else 1.0
            except ValueError:
                retry_refill_per_sec = 1.0
        self.retry_refill_per_sec = max(0.0, retry_refill_per_sec)
        self._retry_tokens = float(max(self.retry_budget, 0))
        self._retry_refill_at = time.perf_counter()
        self.migrate_prefixes = (
            migrate_prefixes
            if migrate_prefixes is not None
            else get_int_from_env(("ATX_SERVE_MIGRATE_PREFIXES",), 4)
        )
        self.engine_factory = engine_factory
        # Probe-backoff jitter only perturbs WHEN a probe runs, never what
        # any request computes, so a fixed seed keeps runs comparable.
        self._rng = random.Random(0xA7C)
        # Canary recorded from real traffic: (prompt, seed, ref_tokens, k).
        # A probe replays it on the quarantined engine and the first k
        # tokens must match bit-for-bit (greedy determinism).
        self._canary: tuple[np.ndarray, int, np.ndarray, int] | None = None
        if watchdog_secs is None:
            raw = os.environ.get("ATX_SERVE_REPLICA_WATCHDOG_SECS", "")
            try:
                watchdog_secs = float(raw) if raw else None
            except ValueError:
                watchdog_secs = None
        if watchdog_secs is not None and watchdog_secs <= 0:
            watchdog_secs = None
        self.replicas = [
            # Inline mode gets no watchdog: a wedged step stalls the caller
            # itself, so there is nobody left to act on the firing.
            _Replica(i, e, self, watchdog_secs=watchdog_secs if threads else None)
            for i, e in enumerate(engines)
        ]
        self._affinity = AffinityIndex()
        self._results: queue.Queue = queue.Queue()
        self._pending: deque[_Ticket] = deque()  # accepted, not yet dispatched
        self._tickets: dict[int, _Ticket] = {}
        self._completions: list[Completion] = []
        self._next_rid = 0
        self._next_seq = 0
        self._outstanding = 0
        self._draining = False
        self.drain_reason: str | None = None
        self._classes_seen: set[int] = set()
        self._shed_by_class: dict[int, int] = {}
        self._migrated_prefixes = 0
        # Latency recording + counters live on the telemetry registry
        # (docs/observability.md): fixed-bucket histograms replace the old
        # unbounded p50/p99 lists, and `metrics()` reads its percentiles
        # from the same series the `/metrics` endpoint exports.
        self._tel_labels = {"router": _telemetry.views._next_instance()}
        _labels = ("router",)
        self._h_ttft = _telemetry.histogram(
            "router_ttft_ms", "admission -> first token", labels=_labels
        )
        self._h_e2e = _telemetry.histogram(
            "router_e2e_ms", "admission -> completion", labels=_labels
        )
        self._h_queue_wait = _telemetry.histogram(
            "router_queue_wait_ms", "admission -> replica dispatch",
            labels=_labels,
        )
        self._g_queue = _telemetry.gauge(
            "router_queue_depth", "pending admissions", labels=_labels
        )
        # Self-healing / overload series (ISSUE names keep the Prometheus
        # `_total` suffix convention for monotone counters).
        self._c_shed = _telemetry.counter(
            "router_shed_total",
            "requests evicted from the admission queue under overload",
            labels=("router", "class"),
        )
        self._c_readmit = _telemetry.counter(
            "router_readmissions_total",
            "quarantined replicas probed healthy and re-admitted",
            labels=_labels,
        )
        self._c_probe_fail = _telemetry.counter(
            "router_probe_failures_total",
            "re-admission probes that failed (canary mismatch or error)",
            labels=_labels,
        )
        self._c_retry_exhausted = _telemetry.counter(
            "router_retry_budget_exhausted_total",
            "failover retries denied by the fleet retry budget",
            labels=_labels,
        )
        self._c_infeasible = _telemetry.counter(
            "router_deadline_infeasible_total",
            "requests rejected at admission: deadline unmeetable",
            labels=_labels,
        )
        self._c_migrated = _telemetry.counter(
            "router_migrated_prefixes_total",
            "hot prefix-cache entries re-seeded into survivors on quarantine",
            labels=_labels,
        )
        self._h_class_ttft = _telemetry.histogram(
            "router_class_ttft_ms", "admission -> first token, per class",
            labels=("router", "class"),
        )
        self._h_class_e2e = _telemetry.histogram(
            "router_class_e2e_ms", "admission -> completion, per class",
            labels=("router", "class"),
        )
        # Request-scoped tracing flag, snapshotted once (the engines do
        # the same): admission/dispatch/stream spans cost zero when off.
        self._trace = _flight.trace_requests_enabled()
        self.stats = _telemetry.StatsView(
            "router",
            (
                "submitted",
                "rejects",
                "drain_rejected",
                "dispatched",
                "completed",
                "retries",
                "cancelled",
                "failed",
                "replicas_lost",
                "queue_peak",
            ),
            label="router",
            instance=self._tel_labels["router"],
            gauges=("queue_peak",),
        )
        if threads:
            for r in self.replicas:
                r.start()

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
        timeout: float | None = None,
        priority: int = 1,
    ) -> int:
        """Admit one request; returns its fleet-global request id. Raises
        `QueueFullError` when the admission queue is at ``queue_depth``
        (unless this request outranks a queued one, which is then shed),
        `DeadlineInfeasibleError` when ``timeout`` is unmeetable, and
        `RouterDraining` once drain has started. ``timeout`` is the
        request's deadline in seconds from now; ``priority`` its class
        (lower = more important)."""
        return self.submit_request(
            Request(
                prompt=np.asarray(prompt, np.int32).reshape(-1),
                max_new_tokens=max_new_tokens,
                seed=seed,
                arrival=arrival,
                stream=stream,
                stop_sequences=stop_sequences,
                timeout=timeout,
                priority=priority,
            )
        )

    def _public_pending(self) -> int:
        """Queued tickets that count against ``queue_depth`` (internal
        migration warm-ups don't — they must never cause user rejects)."""
        return sum(1 for t in self._pending if not t.internal)

    def submit_request(self, req: Request) -> int:
        if self._draining:
            self.stats["drain_rejected"] += 1
            if self._trace:
                _flight.record_span(
                    "admission", rid=req.rid, decision="drain_rejected",
                    cause=str(self.drain_reason),
                )
            raise RouterDraining(
                f"router is draining ({self.drain_reason}): "
                "not admitting new requests"
            )
        if self._public_pending() >= self.queue_depth:
            # Priority shedding (EDF mode): an arrival that strictly
            # outranks the least important queued class evicts that
            # class's newest ticket instead of being rejected.
            if not (self.scheduling == "edf" and self._shed_for(req)):
                self.stats["rejects"] += 1
                if self._trace:
                    _flight.record_span(
                        "admission", rid=req.rid, decision="rejected",
                        cause="queue_full", pending=self._public_pending(),
                    )
                raise QueueFullError(
                    f"admission queue full ({self._public_pending()}/"
                    f"{self.queue_depth} pending; ATX_SERVE_QUEUE_DEPTH raises "
                    "the bound) — retry with backoff"
                )
        # Validate at the front door (engine capacity, bucket-padded plan
        # fit) so a bad request raises HERE, not inside a replica thread.
        self._ref.validate_request(req)
        if self.scheduling == "edf" and self._deadline_infeasible(req):
            self._c_infeasible.inc(**self._tel_labels)
            if self._trace:
                _flight.record_span(
                    "admission", rid=req.rid, decision="rejected",
                    cause="deadline_infeasible",
                )
            raise DeadlineInfeasibleError(
                f"deadline {req.timeout:.3f}s is infeasible given observed "
                "service time and the queue ahead — rejected at admission"
            )
        if req.rid < 0:
            req.rid = self._next_rid
        self._next_rid = max(self._next_rid, req.rid) + 1
        t = _Ticket(req, seq=self._next_seq)
        self._next_seq += 1
        self._tickets[req.rid] = t
        self._pending.append(t)
        self._outstanding += 1
        self._classes_seen.add(int(req.priority))
        if self._trace:
            # The EDF key the dispatcher will sort this ticket by — the
            # scheduling decision, captured at the moment it was made.
            _flight.record_span(
                "admission", rid=req.rid, decision="accepted",
                priority=int(req.priority),
                deadline_ms=(
                    round(req.timeout * 1e3, 3)
                    if req.timeout is not None else None
                ),
                seq=t.seq,
            )
        self.stats["submitted"] += 1
        self.stats["queue_peak"] = max(
            self.stats["queue_peak"], self._public_pending()
        )
        self._g_queue.set(self._public_pending(), **self._tel_labels)
        return req.rid

    def _shed_for(self, req: Request) -> bool:
        """Make room for ``req`` by shedding the newest queued ticket of
        the least important class, IF ``req`` strictly outranks it.
        (Internal warm-ups don't count against the bound, so shedding
        them can't make room — only real tickets are candidates.)"""
        victims = [t for t in self._pending if not t.done and not t.internal]
        if not victims:
            return False
        worst = max(t.req.priority for t in victims)
        if int(req.priority) >= worst:
            return False
        victim = max(
            (t for t in victims if t.req.priority == worst),
            key=lambda t: t.seq,
        )
        self._pending.remove(victim)
        cls = int(victim.req.priority)
        if self._trace:
            _flight.record_span(
                "admission", rid=victim.req.rid, decision="shed",
                cause=f"displaced_by_class_{int(req.priority)}",
            )
        self._c_shed.inc(**{**self._tel_labels, "class": str(cls)})
        self._shed_by_class[cls] = self._shed_by_class.get(cls, 0) + 1
        c = self._local_cancel_completion(victim)
        c.finish_reason = "shed"
        self._resolve(victim, c)
        return True

    def _deadline_infeasible(self, req: Request) -> bool:
        """Admission-time feasibility: estimated finish = now + observed
        service time x (1 + work ahead / fleet slots). Conservative only
        once the e2e histogram has >= 5 samples (a cold router admits
        everything — there is nothing to estimate from)."""
        if req.timeout is None:
            return False
        labels = self._tel_labels
        if self._h_e2e.count(**labels) < 5:
            return False
        e2e = self._h_e2e.mean(**labels)
        if not e2e:
            return False
        queue_wait = self._h_queue_wait.mean(**labels) or 0.0
        service_ms = e2e - queue_wait
        if service_ms <= 0.0:
            service_ms = e2e
        slots = sum(
            r.engine.n_slots for r in self.replicas if not r.dead
        ) or 1
        key = (int(req.priority), time.perf_counter() + req.timeout, self._next_seq)
        ahead = sum(
            1
            for t in self._pending
            if not t.done and self._order_key(t) <= key
        )
        est_ms = service_ms * (1.0 + ahead / slots)
        return est_ms > req.timeout * 1000.0

    def _internal_submit(self, req: Request) -> None:
        """Queue a router-internal warm-up request (prefix migration):
        bypasses the admission bound and drain, surfaces no completion,
        but counts against ``_outstanding`` so `join` finishes it."""
        self._ref.validate_request(req)
        req.rid = self._next_rid
        self._next_rid += 1
        t = _Ticket(req, seq=self._next_seq)
        self._next_seq += 1
        t.internal = True
        self._tickets[req.rid] = t
        self._pending.append(t)
        self._outstanding += 1

    # ------------------------------------------------------------- cancel
    def cancel(self, rid: int) -> bool:
        """Cancel an accepted request (queued or dispatched). The
        ``finish_reason="cancelled"`` completion surfaces through the
        normal `poll`/`join` path; returns False for unknown/finished
        rids."""
        t = self._tickets.get(rid)
        if t is None or t.done:
            return False
        self._cancel_ticket(t)
        return True

    def _cancel_ticket(self, t: _Ticket) -> None:
        if t.replica is None:
            self._pending.remove(t)
            self._resolve(t, self._local_cancel_completion(t))
        elif not t.cancel_sent:
            t.cancel_sent = True
            self.replicas[t.replica].send(("cancel", t.req.rid))

    def _local_cancel_completion(self, t: _Ticket) -> Completion:
        return self._ref._cancelled_completion(
            t.req,
            np.full(
                (t.req.max_new_tokens,), self._ref.config.pad_token_id, np.int32
            ),
            0,
            0.0,
        )

    # -------------------------------------------------------------- drain
    def drain(self, reason: str = "manual") -> None:
        """Flip to drain mode: stop admitting (`RouterDraining`), let
        everything already accepted finish. `poll` calls this with
        ``reason="preemption"`` when `resilience.preemption_requested()`
        goes high; `atx serve` then exits 75 after `join` so the elastic
        launcher resumes the process."""
        if not self._draining:
            self._draining = True
            self.drain_reason = reason

    @property
    def draining(self) -> bool:
        return self._draining

    # --------------------------------------------------------------- tick
    def poll(self, timeout: float = 0.0) -> None:
        """One router tick: poll the preemption flag, quarantine dead
        replicas, expire deadlines, dispatch what fits, ingest results
        (blocking up to ``timeout`` seconds for the first one in threads
        mode)."""
        if not self._draining and resilience.preemption_requested():
            self.drain("preemption")
        if self.threads:
            self._check_threads()
        self._refill_retry_budget()
        self._maybe_readmit()
        self._check_deadlines()
        self._dispatch()
        if self.threads:
            self._pump_results(timeout)
        else:
            worked = self._pump_inline()
            if not worked and timeout > 0:
                time.sleep(timeout)
        # Quarantine/ingest may have freed slots or requeued orphans.
        self._dispatch()

    def _check_threads(self) -> None:
        for r in self.replicas:
            if (
                not r.dead
                and not r._stopping
                and r.thread is not None
                and not r.thread.is_alive()
            ):
                self._quarantine(r.id, r.error or "replica thread exited")

    def _check_deadlines(self) -> None:
        now = time.perf_counter()
        for t in list(self._pending):
            if t.deadline is not None and now >= t.deadline:
                self._pending.remove(t)
                self._resolve(t, self._local_cancel_completion(t))
        for r in self.replicas:
            if r.dead:
                continue
            for rid in list(r.inflight):
                t = self._tickets.get(rid)
                if (
                    t is not None
                    and not t.done
                    and not t.cancel_sent
                    and t.deadline is not None
                    and now >= t.deadline
                ):
                    t.cancel_sent = True
                    r.send(("cancel", rid))

    def _order_key(self, t: _Ticket) -> tuple:
        """EDF dispatch order: priority class first (lower = more
        important), earliest absolute deadline within a class (no deadline
        sorts last), admission seq as the FIFO tiebreak."""
        return (
            int(t.req.priority),
            t.deadline if t.deadline is not None else float("inf"),
            t.seq,
        )

    def _dispatch(self) -> None:
        # EDF: the best-ranked pending ticket dispatches first; FIFO mode
        # keeps the old strict head-only order. Either way a ticket that
        # can't place (no replica capacity) stops dispatch — capacity is
        # request-agnostic, so nothing behind it could place either.
        while self._pending:
            if self.scheduling == "edf":
                t = min(self._pending, key=self._order_key)
            else:
                t = self._pending[0]
            r = self._pick_replica(t.req)
            if r is None:
                return
            self._pending.remove(t)
            self._dispatch_to(t, r)

    def _replica_capacity(self, r: _Replica) -> int:
        # Probation: a freshly re-admitted replica gets one request at a
        # time until it proves itself with clean completions.
        return 1 if r.probation_left > 0 else r.engine.n_slots

    def _pick_replica(self, req: Request) -> _Replica | None:
        cands = [
            r
            for r in self.replicas
            if not r.dead and len(r.inflight) < self._replica_capacity(r)
        ]
        if not cands:
            return None
        least = min(cands, key=lambda r: (len(r.inflight), r.id))
        if self.affinity == "prefix":
            matches = self._affinity.best(req.prompt)
            best, best_m = None, 0
            for r in cands:
                m = matches.get(r.id, 0)
                if m >= self.affinity_min_tokens and m > best_m:
                    best, best_m = r, m
            if (
                best is not None
                and len(best.inflight) - len(least.inflight)
                <= self.affinity_max_imbalance
            ):
                return best
        return least

    def _dispatch_to(self, t: _Ticket, r: _Replica) -> None:
        t.replica = r.id
        t.attempts += 1
        t.generation += 1
        t.cancel_sent = False
        t.req.stream = self._make_stream(t)
        if self._trace and not t.internal:
            # The engine's phase_queue span starts here, not at engine
            # dispatch, so router queue wait lands in the attribution.
            t.req.router_submitted_at = t.submitted_at  # type: ignore[attr-defined]
        r.inflight.add(t.req.rid)
        r.dispatched += 1
        if not t.internal:
            self.stats["dispatched"] += 1
            if self._trace:
                # attempts > 1 marks a failover re-dispatch: a retried
                # request's trace shows BOTH the failed and replayed
                # dispatch (exactly-once tests key on this).
                _flight.record_span(
                    "dispatch", rid=t.req.rid, replica=r.id,
                    attempt=t.attempts, retry=t.attempts > 1,
                )
            self._h_queue_wait.observe(
                (time.perf_counter() - t.submitted_at) * 1e3, **self._tel_labels
            )
        self._g_queue.set(self._public_pending(), **self._tel_labels)
        if self.affinity == "prefix":
            # Record at dispatch (not completion) so a burst of same-prefix
            # requests steers together from the second one on.
            self._affinity.insert(t.req.prompt, r.id)
        r.send(("submit", t.req))

    def _make_stream(
        self, t: _Ticket
    ) -> Callable[[int, int, str | None], None]:
        """Exactly-once stream delivery across retries: greedy determinism
        means a retried attempt replays the identical token sequence, so
        the wrapper skips the ``t.streamed`` tokens the dead attempt
        already delivered and drops callbacks from superseded attempts
        (generation mismatch) entirely."""
        gen = t.generation
        count = 0
        trace = self._trace and not t.internal

        def stream(rid: int, tok: int, text: str | None) -> None:
            nonlocal count
            count += 1
            if t.generation != gen:
                return  # superseded attempt still unwinding
            if count > t.streamed:
                t.streamed = count
                if trace:
                    # Recorded only on actual delivery — a replayed
                    # attempt's deduplicated tokens leave no span, so a
                    # trace counts each streamed token exactly once.
                    _flight.record_span("stream", rid=rid, index=count)
                if t.user_stream is not None:
                    t.user_stream(rid, tok, text)

        return stream

    def _pump_results(self, timeout: float) -> None:
        block = timeout
        while True:
            try:
                kind, rid, payload = (
                    self._results.get(timeout=block)
                    if block > 0
                    else self._results.get_nowait()
                )
            except queue.Empty:
                return
            block = 0.0
            if kind == "done":
                self._ingest(rid, payload)
            else:
                self._quarantine(rid, payload)

    def _pump_inline(self) -> bool:
        worked = False
        for r in self.replicas:  # fixed order: deterministic replay
            if r.dead:
                continue
            try:
                completions = r.pump()
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:
                self._quarantine(r.id, f"{type(e).__name__}: {e}")
                worked = True
                continue
            for c in completions:
                self._ingest(r.id, c)
            worked = worked or bool(completions) or r.engine.busy
        return worked

    def _ingest(self, replica_id: int, c: Completion) -> None:
        t = self._tickets.get(c.rid)
        if t is None or t.done or t.replica != replica_id:
            return  # stale: resolved elsewhere or reassigned after quarantine
        r = self.replicas[replica_id]
        r.completed += 1
        if r.probation_left > 0 and c.finish_reason not in ("cancelled", "failed"):
            r.probation_left -= 1  # one clean completion toward full share
        self._resolve(t, c)

    def _resolve(self, t: _Ticket, c: Completion) -> None:
        t.done = True
        t.generation += 1  # silence any attempt still unwinding
        if t.replica is not None:
            self.replicas[t.replica].inflight.discard(t.req.rid)
            t.replica = None
        if t.internal:
            # Migration warm-up: no caller to surface it to. A successful
            # prefill means the survivor's radix cache now holds the path.
            if c.finish_reason not in ("cancelled", "failed", "shed"):
                self._migrated_prefixes += 1
                self._c_migrated.inc(**self._tel_labels)
            self._outstanding -= 1
            return
        # Router admission time, so latency includes queueing delay.
        c.submitted_at = t.submitted_at
        if c.finish_reason == "cancelled":
            self.stats["cancelled"] += 1
        if c.finish_reason not in ("cancelled", "failed", "shed"):
            cls_labels = {
                **self._tel_labels, "class": str(int(t.req.priority)),
            }
            if c.first_token_at:
                ttft_ms = (c.first_token_at - t.submitted_at) * 1000.0
                self._h_ttft.observe(ttft_ms, **self._tel_labels)
                self._h_class_ttft.observe(ttft_ms, **cls_labels)
            e2e_ms = (c.finished_at - t.submitted_at) * 1000.0
            self._h_e2e.observe(e2e_ms, **self._tel_labels)
            self._h_class_e2e.observe(e2e_ms, **cls_labels)
            if (
                self._canary is None
                and c.finish_reason in ("eos", "length")
                and c.n_new > 0
                and t.req.stop_sequences is None
            ):
                # Record the probe canary from real traffic: replaying
                # this prompt/seed must reproduce these first k tokens on
                # ANY healthy replica (greedy determinism).
                k = min(4, int(c.n_new))
                self._canary = (
                    t.req.prompt.copy(), int(t.req.seed),
                    c.tokens[:k].copy(), k,
                )
        if self._trace:
            _flight.record_span(
                "complete", rid=c.rid, t0=t.submitted_at, t1=c.finished_at,
                finish_reason=c.finish_reason, n_new=int(c.n_new),
                attempts=t.attempts,
            )
        self.stats["completed"] += 1
        self._outstanding -= 1
        self._completions.append(c)

    def _quarantine(self, replica_id: int, reason: str) -> None:
        r = self.replicas[replica_id]
        if r.dead:
            return
        r.dead = True
        r.error = reason
        self.stats["replicas_lost"] += 1
        if self._trace:
            _flight.record_span(
                "quarantine", rid=-1, replica=replica_id, cause=reason,
                inflight=len(r.inflight),
            )
        # Black-box dump: the flight recorder's last-N spans at the moment
        # a replica died (no-op unless ATX_POSTMORTEM_DIR is set).
        _flight.dump_postmortem(
            f"quarantine_replica{replica_id}",
            extra={"replica": replica_id, "reason": reason,
                   "inflight": sorted(r.inflight)},
        )
        # Prefix-cache migration: re-seed the dead replica's hottest
        # committed radix paths into a survivor (host token ids only — the
        # warm-up PREFILLS there; KV bytes never cross devices) and
        # re-point its affinity entries at that survivor so the families
        # keep steering at warm KV.
        survivors = [x for x in self.replicas if not x.dead]
        migrated = 0
        if survivors and not self._draining:
            migrated = self._migrate_prefix_cache(r)
        if survivors and migrated:
            target = min(survivors, key=lambda x: (len(x.inflight), x.id))
            self._affinity.retarget(replica_id, target.id)
        else:
            self._affinity.remove_replica(replica_id)
        orphans = [
            self._tickets[rid]
            for rid in sorted(r.inflight)
            if rid in self._tickets
        ]
        r.inflight.clear()
        # Retries jump the queue (appendleft, original order preserved):
        # they already waited once, and FIFO age order stays intact. (In
        # EDF mode the kept original seq achieves the same thing.) Each
        # retry costs a token from the fleet-wide budget — a sick fleet
        # runs out and degrades to visible ``failed`` completions instead
        # of a retry storm.
        for t in reversed(orphans):
            if t.done:
                continue
            t.replica = None
            t.generation += 1
            if t.attempts > self.max_retries:
                self.stats["failed"] += 1
                fc = self._local_cancel_completion(t)
                fc.finish_reason = "failed"
                self._resolve(t, fc)
                continue
            if self.retry_budget >= 0:
                if self._retry_tokens < 1.0:
                    self._c_retry_exhausted.inc(**self._tel_labels)
                    self.stats["failed"] += 1
                    fc = self._local_cancel_completion(t)
                    fc.finish_reason = "failed"
                    self._resolve(t, fc)
                    continue
                self._retry_tokens -= 1.0
            self.stats["retries"] += 1
            self._pending.appendleft(t)
        if self.readmit_secs is not None:
            self._schedule_probe(r)

    def _migrate_prefix_cache(self, r: _Replica) -> int:
        """Queue internal warm-up prefills of the dead replica's hottest
        cached prefixes. Best-effort: any failure just skips the entry."""
        if self.migrate_prefixes <= 0 or r.engine.prefix_cache is None:
            return 0
        try:
            paths = r.engine.prefix_cache.hot_entries(self.migrate_prefixes)
        except Exception:
            return 0
        n = 0
        for toks in paths:
            if len(toks) < 1 or len(toks) + 1 > self._ref.max_len:
                continue
            try:
                self._internal_submit(
                    Request(
                        prompt=np.asarray(toks, np.int32),
                        max_new_tokens=1,
                        seed=0,
                        priority=_INTERNAL_PRIORITY,
                    )
                )
            except ValueError:
                continue  # e.g. bucket-padded plan doesn't fit — skip
            n += 1
        return n

    # --------------------------------------------------- retry budget
    def _refill_retry_budget(self) -> None:
        now = time.perf_counter()
        if self.retry_budget < 0:
            self._retry_refill_at = now
            return
        dt = now - self._retry_refill_at
        self._retry_refill_at = now
        self._retry_tokens = min(
            float(self.retry_budget),
            self._retry_tokens + dt * self.retry_refill_per_sec,
        )

    # ------------------------------------------------- probation & probe
    def _schedule_probe(self, r: _Replica) -> None:
        """Capped-exponential + jittered backoff before the next probe."""
        r.quarantines += 1
        base = self.readmit_secs * (2.0 ** (r.quarantines - 1))
        backoff = min(base, max(self.readmit_secs, 60.0))
        r.probe_at = time.perf_counter() + backoff * (
            1.0 + 0.1 * self._rng.random()
        )

    def _maybe_readmit(self) -> None:
        if self.readmit_secs is None:
            return
        now = time.perf_counter()
        for r in self.replicas:
            if r.dead and r.probe_at is not None and now >= r.probe_at:
                self._probe(r)

    def _probe(self, r: _Replica) -> None:
        """Health-check a quarantined replica from the router thread (the
        old driver thread is gone — it raised — or permanently parked — it
        wedged; either way nothing else touches the engine, so a direct
        canary run preserves single-thread ownership). On success the
        replica re-enters dispatch under probation; on failure the engine
        is rebuilt from ``engine_factory`` (when available) and re-probed
        once, else the backoff doubles."""
        r.probe_at = None
        ok = False
        if r.wedged.is_set():
            # A wedged engine may have been interrupted mid-step (an
            # arbitrary stall, not just the pre-step fault hook): its
            # device state is not trustworthy. Only a rebuild recovers it.
            if self.engine_factory is None:
                self._c_probe_fail.inc(**self._tel_labels)
                return  # permanently quarantined (join() may fail the fleet)
            self._rebuild(r)
            ok = self._canary_ok(r.engine)
            if not ok:
                self._c_probe_fail.inc(**self._tel_labels)
        else:
            ok = self._canary_ok(r.engine)
            if not ok:
                self._c_probe_fail.inc(**self._tel_labels)
                if self.engine_factory is not None:
                    self._rebuild(r)
                    ok = self._canary_ok(r.engine)
                    if not ok:
                        self._c_probe_fail.inc(**self._tel_labels)
        if ok:
            self._readmit(r)
        else:
            self._schedule_probe(r)

    def _rebuild(self, r: _Replica) -> None:
        r.engine = self.engine_factory(r.id)
        r.rebuilds += 1
        r.wedged = threading.Event()

    def _canary_ok(self, engine: Engine) -> bool:
        """Replay the recorded canary directly on ``engine``; healthy
        means bit-identical first-k tokens (or, before any traffic has
        recorded a canary, simply completing a synthetic request)."""
        try:
            engine.abort_inflight()  # whatever the fault left mid-flight
            if self._canary is not None:
                prompt, seed, ref, k = self._canary
                req = Request(
                    prompt=prompt.copy(), max_new_tokens=k, seed=seed
                )
            else:
                ref, k = None, 0
                req = Request(
                    prompt=np.asarray(
                        [int(self._ref.config.pad_token_id)], np.int32
                    ),
                    max_new_tokens=2,
                    seed=0,
                )
            rid = engine.submit_request(req)
            for _ in range(10_000):
                for c in engine.step():
                    if c.rid != rid:
                        continue  # stale orphan unwound by abort_inflight
                    if ref is not None:
                        return bool(np.array_equal(c.tokens[:k], ref))
                    return c.finish_reason in ("eos", "length", "stop")
                if not engine.busy:
                    return False
            engine.abort_inflight()  # step cap hit: leave the engine idle
            return False
        except Exception:
            try:
                engine.abort_inflight()
            except Exception:
                pass
            return False

    def _readmit(self, r: _Replica) -> None:
        r.respawn()
        r.probation_left = max(0, self.probation_completions)
        self._c_readmit.inc(**self._tel_labels)

    # ---------------------------------------------------------- lifecycle
    def pop_completions(self) -> list[Completion]:
        out, self._completions = self._completions, []
        return out

    def join(self, timeout: float | None = None) -> list[Completion]:
        """Run until every accepted request resolves; returns completions
        gathered since the last pop, in finish order. Raises
        `NoHealthyReplicaError` when the whole fleet is quarantined with
        work outstanding, `TimeoutError` past ``timeout`` seconds."""
        t0 = time.perf_counter()
        while self._outstanding > 0:
            if all(r.dead for r in self.replicas) and not any(
                # With re-admission enabled a fully-dead fleet can still
                # recover: keep polling while any probe is scheduled
                # (``timeout`` still bounds the wait).
                r.probe_at is not None
                for r in self.replicas
            ):
                errors = "; ".join(
                    f"replica {r.id}: {r.error}" for r in self.replicas
                )
                raise NoHealthyReplicaError(
                    f"{self._outstanding} request(s) outstanding with every "
                    f"replica quarantined ({errors})"
                )
            if timeout is not None and time.perf_counter() - t0 > timeout:
                raise TimeoutError(
                    f"router join timed out after {timeout}s with "
                    f"{self._outstanding} request(s) outstanding"
                )
            self.poll(0.002 if self.threads else 0.0)
        return self.pop_completions()

    def serve(
        self, requests: Iterable[Request], *, realtime: bool = False
    ) -> list[Completion]:
        """Drive a whole trace through the fleet (the `Engine.serve`
        contract at router level). ``realtime=True`` honours arrival
        offsets and REJECTS on a full queue (the latency-measuring mode);
        otherwise submission blocks on backpressure so every request is
        eventually admitted. Drain (preemption or `drain()`) stops
        admissions mid-trace — unsubmitted requests are counted in
        ``stats["drain_rejected"]`` — then everything accepted runs to
        completion, preserving the exit-75 resume contract."""
        reqs = sorted(requests, key=lambda r: (r.arrival or 0.0))
        t0 = time.perf_counter()
        i = 0
        while i < len(reqs):
            if self._draining:
                self.stats["drain_rejected"] += len(reqs) - i
                break
            if realtime and (reqs[i].arrival or 0.0) > time.perf_counter() - t0:
                self.poll(0.002)
                continue
            if not realtime and self._public_pending() >= self.queue_depth:
                self.poll(0.002)  # backpressure: wait for queue space
                continue
            try:
                self.submit_request(reqs[i])
            except QueueFullError:
                pass  # realtime: visible reject, request is shed
            except RouterDraining:
                continue  # top of loop accounts the rest as drain_rejected
            i += 1
        return self.join()

    def close(self) -> None:
        """Stop replica threads and watchdogs. Wedged threads (blocked
        inside a stuck step) are daemons and are left behind."""
        if self.threads:
            for r in self.replicas:
                if r.thread is not None:
                    r.send(("stop",))
            for r in self.replicas:
                if r.thread is not None and not r.wedged.is_set():
                    r.thread.join(timeout=5.0)
        for r in self.replicas:
            if r.watchdog is not None:
                r.watchdog.stop()

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # ------------------------------------------------------------ metrics
    def metrics(self) -> dict:
        """Point-in-time fleet snapshot: router counters, latency
        percentiles (ms, None until data), and one dict per replica —
        the payload `atx serve` flattens into its JSON line."""
        per = []
        for r in self.replicas:
            es = r.engine.stats
            pm = r.engine.prefix_metrics()
            per.append(
                {
                    "replica": r.id,
                    "dispatched": r.dispatched,
                    "completed": r.completed,
                    "inflight": len(r.inflight),
                    "occupancy": round(
                        es["decode_slot_steps"]
                        / max(es["decode_steps"] * r.engine.n_slots, 1),
                        3,
                    ),
                    "prefix_hit_rate": pm.get("prefix_hit_rate", 0.0),
                    "quarantined": int(r.dead),
                    "wedged": int(r.wedged.is_set()),
                    "probation": r.probation_left,
                    "quarantines": r.quarantines,
                    "rebuilds": r.rebuilds,
                    "error": r.error,
                }
            )
        labels = self._tel_labels
        per_class = {}
        for cls in sorted(self._classes_seen):
            cl = {**labels, "class": str(cls)}
            per_class[str(cls)] = {
                "completed": self._h_class_e2e.count(**cl),
                "ttft_p50_ms": _hq(self._h_class_ttft, 0.50, cl),
                "e2e_p50_ms": _hq(self._h_class_e2e, 0.50, cl),
                "e2e_p99_ms": _hq(self._h_class_e2e, 0.99, cl),
                "shed": self._shed_by_class.get(cls, 0),
            }
        m: dict = dict(self.stats)
        m.update(
            replicas=len(self.replicas),
            replicas_alive=sum(1 for r in self.replicas if not r.dead),
            queue_depth=self._public_pending(),
            queue_capacity=self.queue_depth,
            draining=int(self._draining),
            drain_reason=self.drain_reason,
            scheduling=self.scheduling,
            shed=sum(self._shed_by_class.values()),
            shed_by_class={str(k): v for k, v in sorted(self._shed_by_class.items())},
            deadline_infeasible=int(self._c_infeasible.value(**labels)),
            readmissions=int(self._c_readmit.value(**labels)),
            probe_failures=int(self._c_probe_fail.value(**labels)),
            retry_budget_exhausted=int(self._c_retry_exhausted.value(**labels)),
            retry_tokens=(
                round(self._retry_tokens, 2) if self.retry_budget >= 0 else None
            ),
            migrated_prefixes=self._migrated_prefixes,
            per_class=per_class,
            ttft_p50_ms=_hq(self._h_ttft, 0.50, self._tel_labels),
            ttft_p99_ms=_hq(self._h_ttft, 0.99, self._tel_labels),
            e2e_p50_ms=_hq(self._h_e2e, 0.50, self._tel_labels),
            e2e_p99_ms=_hq(self._h_e2e, 0.99, self._tel_labels),
            per_replica=per,
        )
        return m
