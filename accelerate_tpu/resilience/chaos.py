"""Seeded chaos campaigns over the serving fleet and replication path.

`atx chaos` drives N *episodes*; each episode derives a deterministic
sub-seed, samples a `test_utils.faults.FaultSchedule` over one
subsystem's registered crash points (`faults.active_points`), runs a
small seeded workload under that fault env, and asserts the invariants
that hold the whole stack together:

- **exactly-once**: every admitted request resolves exactly once, and a
  stream callback delivers each token once across failover replays;
- **bit-identity**: greedy outputs match a solo engine token-for-token
  (references computed OUTSIDE the fault env, memoized across episodes);
- **drain**: the preemption flag flips the router to draining on the
  next tick and admissions are refused (the exit-75 contract; the
  subprocess episode checks the literal exit code);
- **no lost committed checkpoint**: a replication fault never yields a
  torn remote commit, and a clean retry converges to a restorable one.

Violations are *collected*, not raised, so a campaign always completes
and reports: one JSON line per episode (schedule, violations, detail)
plus a summary carrying a SHA-256 digest over all sampled schedules —
two runs with the same ``--seed`` produce the same digest, which is the
replay contract (re-run a failing seed, get the same fault assignment).

Episode subsystems rotate through ``kinds``: ``router`` (raise/delay at
``router.replica<i>.step`` — quarantine, probation re-admission, prefix
migration), ``engine`` (raise/delay at ``engine.step``), ``replication``
(raise/delay at ``replicate.*`` with a differential second checkpoint).
``subprocess_episodes=True`` appends the two out-of-process episodes:
kill -9 (exit 137) mid-replication followed by a clean converge, and a
SIGTERM drain of a threaded router that must exit 75. The subprocess
workers live in this module's ``__main__``.

Everything serving-related is imported lazily inside functions:
``serving.engine`` imports the ``resilience`` package for its fault
hooks, so a module-level import here would be circular.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
import time
from typing import Sequence

import numpy as np

from ..telemetry import flight as _flight
from ..test_utils import faults
from ..utils.environment import patch_environment
from . import commit as _commit
from . import preemption as _preemption
from . import replicate as _replicate

__all__ = ["run_campaign", "EPISODE_KINDS"]

EPISODE_KINDS = ("router", "engine", "replication")

_POINTS = {
    "router": ("router.replica0.step", "router.replica1.step"),
    "engine": ("engine.step",),
    "replication": ("replicate.part_uploaded", "replicate.before_marker"),
}
# Inline episodes only inject raise/delay: a hang would park the inline
# caller itself and a kill would take the campaign process down — those
# two kinds belong to the subprocess episodes.
_INLINE_KINDS = ("raise", "delay")
_DELAY_SECS = "0.05"

_VOCAB = 61


class _Fleet:
    """Two pooled replica engines + a solo reference engine, built once
    per campaign (XLA compilation dominates episode cost) and sanitized
    between episodes with `Engine.abort_inflight`. Solo greedy outputs
    are memoized by ``(prompt, budget, seed)`` — engine outputs are
    batching-independent, so the memo IS the per-request ground truth."""

    def __init__(self) -> None:
        import jax

        from .. import serving
        from ..generation import GenerationConfig
        from ..models import llama

        cfg = llama.LlamaConfig.tiny(
            vocab_size=_VOCAB, max_seq_len=256, num_heads=4, num_kv_heads=2
        )
        params = llama.init(jax.random.PRNGKey(1), cfg)

        def apply(p, t, c):
            return llama.forward_with_cache(p, t, c, cfg)

        def init_cache(b, m):
            return llama.init_cache(cfg, b, m)

        def mk_engine(slots: int = 2, prefix_cache: bool = True):
            return serving.Engine(
                apply, init_cache, params, GenerationConfig(),
                slots=slots, buckets=(8,), max_len=96,
                prefix_cache=prefix_cache,
            )

        self.mk_engine = mk_engine
        self.engines = [mk_engine(), mk_engine()]
        self._solo = mk_engine(slots=1, prefix_cache=False)
        self._memo: dict = {}

    def solo(self, prompt: np.ndarray, max_new: int, seed: int) -> np.ndarray:
        key = (prompt.tobytes(), int(max_new), int(seed))
        if key not in self._memo:
            self._solo.submit(np.asarray(prompt, np.int32), max_new, seed=seed)
            (c,) = self._solo.run_until_idle()
            self._memo[key] = c.tokens
        return self._memo[key]

    def sanitize(self) -> None:
        for eng in self.engines:
            eng.abort_inflight()


def _episode_seed(seed: int, episode: int) -> int:
    return seed * 100_003 + episode


def _trace(rng: random.Random, n: int, stream) -> list:
    from .. import serving

    reqs = []
    for i in range(n):
        prompt = np.asarray(
            [rng.randrange(_VOCAB) for _ in range(rng.randint(3, 24))], np.int32
        )
        reqs.append(
            serving.Request(
                prompt=prompt,
                max_new_tokens=rng.randint(2, 5),
                rid=i,
                seed=i,
                priority=rng.choice((0, 1, 2)),
                stream=stream,
            )
        )
    return reqs


def _serving_episode(fleet: _Fleet, kind: str, ep_seed: int) -> dict:
    """One router/engine episode: seeded trace through a fresh 2-replica
    inline Router (re-admission armed) under a sampled fault env."""
    from .. import serving

    rng = random.Random(ep_seed)
    streamed: dict[int, list[int]] = {}

    def stream(rid, tok, text):
        streamed.setdefault(rid, []).append(int(tok))

    reqs = _trace(rng, rng.randint(4, 6), stream)
    refs = {r.rid: fleet.solo(r.prompt, r.max_new_tokens, r.rid) for r in reqs}

    schedule = faults.FaultSchedule(
        ep_seed, points=_POINTS[kind], kinds=_INLINE_KINDS
    )
    env = dict(schedule.env())
    env[faults.DELAY_SECS_ENV] = _DELAY_SECS

    violations: list[str] = []
    faults._reset_counters()
    fleet.sanitize()
    router = None
    try:
        with patch_environment(**env):
            router = serving.Router(
                fleet.engines,
                threads=False,
                readmit_secs=0.01,
                probation_completions=2,
                engine_factory=lambda _replica: fleet.mk_engine(),
            )
            completions = router.serve(reqs)
            # Drain invariant: preemption flips the router on the next tick
            # and admissions are refused from then on.
            _preemption.request_preemption()
            router.poll()
            if not (router.draining and router.drain_reason == "preemption"):
                violations.append("drain: preemption flag did not drain")
            try:
                router.submit(np.arange(4, dtype=np.int32), 1)
                violations.append("drain: admission accepted while draining")
            except serving.RouterDraining:
                pass
    finally:
        if router is not None:
            router.close()
        _preemption.clear_preemption()
        faults._reset_counters()

    outs = {c.rid: c for c in completions}
    if sorted(outs) != sorted(r.rid for r in reqs):
        violations.append(
            f"exactly-once: resolved rids {sorted(outs)} != submitted "
            f"{sorted(r.rid for r in reqs)}"
        )
    for c in completions:
        if c.finish_reason in ("cancelled", "failed", "shed"):
            continue
        if not np.array_equal(c.tokens, refs.get(c.rid)):
            violations.append(f"bit-identity: rid {c.rid} diverged from solo")
        want = [int(t) for t in c.tokens[: c.n_new]]
        if streamed.get(c.rid, []) != want:
            violations.append(
                f"exactly-once-stream: rid {c.rid} streamed "
                f"{streamed.get(c.rid, [])} vs tokens {want}"
            )
    m = router.metrics()
    return {
        "schedule": schedule.describe(),
        "violations": violations,
        "detail": {
            "requests": len(reqs),
            "completed": len(completions),
            "replicas_lost": m["replicas_lost"],
            "retries": m["retries"],
            "readmissions": m["readmissions"],
            "migrated_prefixes": m["migrated_prefixes"],
        },
    }


def _make_checkpoint(root: str, name: str, step: int, files: dict) -> str:
    d = os.path.join(root, name)
    os.makedirs(d, exist_ok=True)
    for rel, data in files.items():
        with open(os.path.join(d, rel), "wb") as f:
            f.write(data)
    _commit.write_manifest(d, 0, sorted(files), step=step)
    _commit.write_aggregate_manifest(d)
    with open(os.path.join(d, _commit.COMMIT_MARKER), "w") as f:
        json.dump({"version": 1, "step": step, "num_processes": 1}, f)
    return d


def _ckpt_files(rng: random.Random, n: int = 4) -> dict:
    return {
        f"part_{i}.bin": bytes([rng.randrange(256)]) * rng.randint(64, 256)
        for i in range(n)
    }


def _replication_episode(ep_seed: int) -> dict:
    """One replication episode: replicate a committed checkpoint into a
    local store under a sampled fault env, then converge cleanly — the
    remote commit marker must never exist in a torn state, and the clean
    retry must yield a restorable checkpoint. A second checkpoint sharing
    shards with the first exercises the differential (server-side copy)
    path under the same invariants."""
    rng = random.Random(ep_seed)
    violations: list[str] = []
    schedule = faults.FaultSchedule(
        ep_seed, points=_POINTS["replication"], kinds=_INLINE_KINDS
    )
    env = dict(schedule.env())
    env[faults.DELAY_SECS_ENV] = _DELAY_SECS
    detail: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        store = _replicate.LocalObjectStore(os.path.join(tmp, "store"))
        files0 = _ckpt_files(rng)
        d0 = _make_checkpoint(tmp, "checkpoint_0", 1, files0)
        rep = _replicate.Replicator(store, retries=0, timeout_secs=60)
        faults._reset_counters()
        with patch_environment(**env):
            rep.enqueue(d0)
            rep.drain(60)
        faults._reset_counters()
        marker0 = f"checkpoint_0/{_commit.COMMIT_MARKER}"
        if rep.failures and store.exists(marker0):
            violations.append(
                "torn commit: replication failed but the remote COMMIT "
                "marker exists"
            )
        # Clean converge: re-enqueue with no fault env. Remote commits are
        # final, so a previously successful upload is a no-op here.
        rep.enqueue(d0)
        rep.drain(60)
        if not store.exists(marker0):
            violations.append(
                f"lost checkpoint: clean retry did not commit "
                f"({rep.last_error})"
            )
        # Differential follow-up: half the shards unchanged.
        files1 = dict(files0)
        for rel in sorted(files1)[: len(files1) // 2]:
            files1[rel] = bytes([rng.randrange(256)]) * rng.randint(64, 256)
        d1 = _make_checkpoint(tmp, "checkpoint_1", 2, files1)
        rep.enqueue(d1)
        rep.drain(60)
        if not store.exists(f"checkpoint_1/{_commit.COMMIT_MARKER}"):
            violations.append("differential checkpoint did not commit")
        restored = _replicate.restore_latest(store, os.path.join(tmp, "restored"))
        if restored is None:
            violations.append("restore_latest found nothing restorable")
        else:
            problems = _commit.verify_checkpoint(restored)
            if problems:
                violations.append(f"restored checkpoint corrupt: {problems}")
        detail = {
            "failures": rep.failures,
            "parts_uploaded": rep.parts_uploaded,
            "parts_skipped": rep.parts_skipped,
            "parts_unchanged": rep.parts_unchanged,
            "restored": bool(restored),
        }
    return {
        "schedule": schedule.describe(),
        "violations": violations,
        "detail": detail,
    }


def _kill_episode(ep_seed: int) -> dict:
    """Out-of-process kill -9 analog: a subprocess worker replicating a
    committed checkpoint dies at ``replicate.part_uploaded`` with exit
    137; the remote must be uncommitted, and an in-process clean retry
    must converge to a restorable checkpoint."""
    rng = random.Random(ep_seed)
    violations: list[str] = []
    detail: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        store_dir = os.path.join(tmp, "store")
        d0 = _make_checkpoint(tmp, "checkpoint_0", 1, _ckpt_files(rng))
        point = f"replicate.part_uploaded@{rng.randint(1, 3)}"
        proc = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.resilience.chaos",
             "replicate", d0, store_dir],
            env=dict(faults.kill_env(point), JAX_PLATFORMS="cpu"),
            capture_output=True,
            text=True,
            timeout=300,
        )
        if proc.returncode != faults.KILL_EXIT_CODE:
            violations.append(
                f"kill worker exited {proc.returncode}, expected "
                f"{faults.KILL_EXIT_CODE}: {proc.stdout[-500:]} "
                f"{proc.stderr[-500:]}"
            )
        store = _replicate.LocalObjectStore(store_dir)
        marker = f"checkpoint_0/{_commit.COMMIT_MARKER}"
        if store.exists(marker):
            violations.append("torn commit: marker exists after kill -9")
        rep = _replicate.Replicator(store, retries=0, timeout_secs=60)
        rep.enqueue(d0)
        rep.drain(60)
        if not store.exists(marker):
            violations.append("lost checkpoint: retry after kill did not commit")
        restored = _replicate.restore_latest(store, os.path.join(tmp, "restored"))
        if restored is None or _commit.verify_checkpoint(restored):
            violations.append("restore after kill retry failed verification")
        detail = {
            "kill_point": point,
            "worker_rc": proc.returncode,
            "parts_resumed": rep.parts_skipped,
        }
    return {
        "schedule": {"seed": ep_seed, "assignments": {"kill": point}},
        "violations": violations,
        "detail": detail,
    }


def _drain_episode(ep_seed: int) -> dict:
    """Out-of-process SIGTERM drain: a threaded 2-replica router worker
    must finish in-flight work, self-check bit-identity, and exit with
    ``PREEMPTION_EXIT_CODE`` (75) — the elastic-launcher resume contract."""
    violations: list[str] = []
    proc = subprocess.Popen(
        [sys.executable, "-m", "accelerate_tpu.resilience.chaos", "serve-drain"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )
    tail = ""
    try:
        deadline = time.time() + 300
        for line in proc.stdout:
            tail += line
            if "SERVING" in line:
                break
            if time.time() > deadline:
                break
        if proc.poll() is not None:
            violations.append(f"drain worker exited early: {tail[-500:]}")
        else:
            time.sleep(0.5)  # let requests reach mid-decode
            proc.send_signal(signal.SIGTERM)
            tail += proc.stdout.read()
            rc = proc.wait(timeout=180)
            if rc != _preemption.PREEMPTION_EXIT_CODE:
                violations.append(
                    f"drain worker exited {rc}, expected "
                    f"{_preemption.PREEMPTION_EXIT_CODE}: {tail[-500:]}"
                )
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    return {
        "schedule": {"seed": ep_seed, "assignments": {"sigterm": "serve-drain"}},
        "violations": violations,
        "detail": {"rc": proc.returncode},
    }


def run_campaign(
    *,
    episodes: int = 20,
    seed: int | None = None,
    kinds: Sequence[str] = EPISODE_KINDS,
    report_path: str | None = None,
    subprocess_episodes: bool = False,
) -> dict:
    """Run a seeded chaos campaign and return the summary dict.

    ``episodes`` inline episodes rotate through ``kinds``;
    ``subprocess_episodes`` appends the kill-137 and SIGTERM-drain-75
    episodes. ``report_path`` gets one JSON line per episode. The summary
    ``digest`` is a SHA-256 over every sampled schedule — equal seeds
    produce equal digests (and equal fault assignments), which is what
    makes a failing campaign replayable."""
    if seed is None:
        try:
            seed = int(os.environ.get(faults.FAULT_SEED_ENV, "") or 0)
        except ValueError:
            seed = 0
    kinds = tuple(kinds)
    unknown = [k for k in kinds if k not in EPISODE_KINDS]
    if unknown:
        raise ValueError(
            f"unknown episode kinds {unknown}; choose from {EPISODE_KINDS}"
        )
    fleet = _Fleet() if any(k in ("router", "engine") for k in kinds) else None
    records: list[dict] = []
    for e in range(episodes):
        kind = kinds[e % len(kinds)]
        ep_seed = _episode_seed(seed, e)
        try:
            if kind == "replication":
                rec = _replication_episode(ep_seed)
            else:
                rec = _serving_episode(fleet, kind, ep_seed)
        except Exception as exc:  # an escaped exception IS a violation
            rec = {
                "schedule": faults.FaultSchedule(
                    ep_seed, points=_POINTS[kind], kinds=_INLINE_KINDS
                ).describe(),
                "violations": [f"episode crashed: {type(exc).__name__}: {exc}"],
                "detail": {},
            }
        rec.update(episode=e, kind=kind, seed=ep_seed, ok=not rec["violations"])
        _attach_postmortem(rec)
        records.append(rec)
    if subprocess_episodes:
        for kind, fn in (("replication-kill", _kill_episode),
                         ("serve-drain", _drain_episode)):
            ep_seed = _episode_seed(seed, len(records))
            rec = fn(ep_seed)
            rec.update(
                episode=len(records), kind=kind, seed=ep_seed,
                ok=not rec["violations"],
            )
            _attach_postmortem(rec)
            records.append(rec)
    digest = hashlib.sha256(
        json.dumps([r["schedule"] for r in records], sort_keys=True).encode()
    ).hexdigest()
    if report_path:
        with open(report_path, "w") as f:
            for rec in records:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
    violations = [v for r in records for v in r["violations"]]
    return {
        "episodes": len(records),
        "seed": seed,
        "kinds": list(kinds),
        "ok": not violations,
        "violations": violations,
        "faulted_episodes": sum(
            1 for r in records if r["schedule"].get("assignments")
        ),
        "postmortems": [r["postmortem"] for r in records if "postmortem" in r],
        "digest": digest,
        "report_path": report_path,
    }


def _attach_postmortem(rec: dict) -> None:
    """Dump a flight-recorder bundle for a violating episode and attach
    its path to the record AND every violation string, so the triage
    trail leads straight from the campaign summary to the black box
    (`atx trace <bundle>`). No-op when the episode is clean or
    ``ATX_POSTMORTEM_DIR`` is unset."""
    if not rec["violations"]:
        return
    bundle = _flight.dump_postmortem(
        f"chaos_episode{rec.get('episode', '')}_{rec.get('kind', '')}",
        extra={"violations": rec["violations"], "schedule": rec["schedule"]},
    )
    if bundle:
        rec["postmortem"] = bundle
        rec["violations"] = [
            f"{v} [postmortem: {bundle}]" for v in rec["violations"]
        ]


# ----------------------------------------------------------- worker roles
def _replicate_worker(directory: str, store_url: str) -> int:
    rep = _replicate.Replicator(
        _replicate.store_for_url(store_url), retries=0, timeout_secs=60
    )
    rep.enqueue(directory)
    ok = rep.drain(60)
    return 0 if ok and not rep.failures else 3


def _serve_drain_worker() -> int:
    from .. import serving

    fleet = _Fleet()
    _preemption.install_preemption_handler()
    router = serving.Router(
        fleet.engines, engine_factory=lambda _replica: fleet.mk_engine()
    )
    rng = random.Random(0)
    refs: dict[int, np.ndarray] = {}

    def submit_one() -> None:
        prompt = np.asarray(
            [rng.randrange(_VOCAB) for _ in range(7)], np.int32
        )
        seed = rng.randrange(2**31 - 1)
        try:
            rid = router.submit(prompt, 4, seed=seed)
        except (serving.RouterDraining, serving.QueueFullError):
            return
        refs[rid] = fleet.solo(prompt, 4, seed)

    for _ in range(4):  # compile both replicas before announcing
        submit_one()
    router.join()
    print("SERVING", flush=True)
    deadline = time.time() + 120.0
    while not router.draining:
        if time.time() > deadline:
            print("no SIGTERM within 120s", flush=True)
            return 1
        if len(router._pending) < router.queue_depth:
            submit_one()
        router.poll(0.002)
    completions = router.pop_completions() + router.join()
    admitted_after_drain = 0
    try:
        router.submit(np.arange(7, dtype=np.int32), 4)
        admitted_after_drain = 1
    except serving.RouterDraining:
        pass
    router.close()
    mismatches = sum(
        1 for c in completions if not np.array_equal(c.tokens, refs[c.rid])
    )
    print(
        json.dumps(
            {
                "completions": len(completions),
                "mismatches": mismatches,
                "admitted_after_drain": admitted_after_drain,
                "drain_reason": router.drain_reason,
            }
        ),
        flush=True,
    )
    if mismatches or admitted_after_drain or not completions:
        return 1
    if router.drain_reason == "preemption":
        return _preemption.PREEMPTION_EXIT_CODE
    return 1


def _main(argv: Sequence[str]) -> int:
    if not argv:
        print("usage: chaos {replicate <dir> <store_url> | serve-drain}",
              file=sys.stderr)
        return 2
    if argv[0] == "replicate" and len(argv) == 3:
        return _replicate_worker(argv[1], argv[2])
    if argv[0] == "serve-drain":
        return _serve_drain_worker()
    print(f"unknown chaos worker role {argv!r}", file=sys.stderr)
    return 2


if __name__ == "__main__":  # pragma: no cover - subprocess entry
    sys.exit(_main(sys.argv[1:]))
