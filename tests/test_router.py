"""Multi-replica serving front-end (`accelerate_tpu/serving/router.py`).

The router-level invariants under test — the ISSUE-8 acceptance matrix:

- greedy outputs through a 2-replica `Router` are BIT-IDENTICAL to a solo
  engine, in both execution modes, and stay bit-identical when a replica
  is killed mid-decode and its in-flight requests fail over (a retry is a
  replay; stream callbacks still fire exactly once per token);
- admission control is visible: a full queue raises `QueueFullError`,
  deadlines cancel mid-queue AND mid-decode with
  ``finish_reason="cancelled"``;
- prefix-affinity steering lands shared-prefix requests on the replica
  that owns the cached KV (hit-rate strictly above pure least-loaded on
  the same trace);
- the preemption flag drains gracefully (stop admitting, finish in-flight)
  and a real SIGTERM drives the subprocess driver to exit 75;
- a wedged replica (hang fault + per-replica watchdog) is quarantined
  without taking the fleet down.

`make smoke-router` runs this file plus the `atx lint router_drain`
multi-host replay.
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from accelerate_tpu import resilience, serving
from accelerate_tpu.generation import GenerationConfig
from accelerate_tpu.models import llama
from accelerate_tpu.serving import (
    AffinityIndex,
    DeadlineInfeasibleError,
    NoHealthyReplicaError,
    QueueFullError,
    Router,
    RouterDraining,
)
from accelerate_tpu.test_utils import faults
from accelerate_tpu.utils.environment import patch_environment

CFG = llama.LlamaConfig.tiny(vocab_size=61, max_seq_len=256, num_heads=4, num_kv_heads=2)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPTS = os.path.join(REPO_ROOT, "tests", "scripts")


@pytest.fixture(scope="module")
def params():
    return llama.init(jax.random.PRNGKey(1), CFG)


def _apply(p, t, c):
    return llama.forward_with_cache(p, t, c, CFG)


def _init_cache(b, m):
    return llama.init_cache(CFG, b, m)


def _engine(params, config=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("buckets", (8,))
    kw.setdefault("max_len", 96)
    kw.setdefault("prefix_cache", False)
    return serving.Engine(_apply, _init_cache, params, config or GenerationConfig(), **kw)


@pytest.fixture(scope="module")
def solo(params):
    """Solo reference: one engine, one request at a time. Engine outputs
    are batching-independent (PR-3), so this IS the `generate()` answer."""
    eng = _engine(params, slots=1)

    def run(prompt, max_new, seed=0):
        eng.submit(np.asarray(prompt, np.int32), max_new, seed=seed)
        (c,) = eng.run_until_idle()
        return c.tokens

    return run


@pytest.fixture(autouse=True)
def _clean_fault_state():
    resilience.clear_preemption()
    faults._reset_counters()
    yield
    resilience.clear_preemption()
    faults._reset_counters()


def _mixed_requests(n, *, seed=0, max_prompt=30, budgets=(3, 6)):
    rng = np.random.RandomState(seed)
    return [
        serving.Request(
            prompt=rng.randint(0, 61, (int(rng.randint(3, max_prompt + 1)),)).astype(np.int32),
            max_new_tokens=int(rng.choice(budgets)),
            rid=i,
            seed=i,
        )
        for i in range(n)
    ]


def _assert_matches_solo(solo, reqs, completions, *, skip_reasons=()):
    outs = {c.rid: c for c in completions}
    assert set(outs) == {r.rid for r in reqs}
    for r in reqs:
        c = outs[r.rid]
        if c.finish_reason in skip_reasons:
            continue
        np.testing.assert_array_equal(
            c.tokens, solo(r.prompt, r.max_new_tokens, seed=r.seed),
            err_msg=f"rid {r.rid} diverged from solo engine",
        )


class TestBitIdentity:
    @pytest.mark.parametrize("threads", [False, True], ids=["inline", "threads"])
    def test_two_replicas_match_solo(self, params, solo, threads):
        reqs = _mixed_requests(8)
        with Router([_engine(params), _engine(params)], threads=threads) as router:
            completions = router.serve(reqs)
        _assert_matches_solo(solo, reqs, completions)
        m = router.metrics()
        assert m["completed"] == 8 and m["replicas_alive"] == 2
        # Both replicas actually served traffic — this was a fleet run.
        assert all(p["dispatched"] > 0 for p in m["per_replica"])

    def test_replica_kill_mid_decode_failover_bit_identical(self, params, solo):
        """Replica 0's thread dies on its 3rd step (mid-decode for whatever
        it holds); in-flight requests re-dispatch to replica 1 and every
        output still matches solo."""
        reqs = _mixed_requests(8, seed=1)
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@3"):
            with Router([_engine(params), _engine(params)]) as router:
                completions = router.serve(reqs)
        _assert_matches_solo(solo, reqs, completions)
        m = router.metrics()
        assert m["replicas_lost"] == 1 and m["retries"] >= 1
        assert m["per_replica"][0]["quarantined"] == 1
        assert "FaultInjected" in m["per_replica"][0]["error"]

    def test_failover_streams_each_token_exactly_once(self, params, solo):
        """A retried attempt replays the same tokens; the per-ticket stream
        wrapper must deliver each token ONCE across attempts."""
        streamed: dict[int, list[int]] = {}

        def stream(rid, tok, text):
            streamed.setdefault(rid, []).append(int(tok))

        reqs = [
            serving.Request(
                prompt=(np.arange(10, dtype=np.int32) * (i + 3)) % 61,
                max_new_tokens=8,
                rid=i,
                seed=i,
                stream=stream,
            )
            for i in range(4)
        ]
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@4"):
            with Router([_engine(params), _engine(params)]) as router:
                completions = router.serve(reqs)
        assert router.stats["replicas_lost"] == 1
        _assert_matches_solo(solo, reqs, completions)
        for c in completions:
            assert streamed[c.rid] == [int(t) for t in c.tokens[: c.n_new]], (
                f"rid {c.rid}: stream delivered {streamed[c.rid]} vs "
                f"tokens {c.tokens[: c.n_new]}"
            )

    def test_heterogeneous_replicas_rejected(self, params):
        with pytest.raises(ValueError, match="identically configured"):
            Router(
                [_engine(params), _engine(params, buckets=(16,))],
                threads=False,
            )


class TestAffinity:
    def test_affinity_index_prefix_scoring(self):
        idx = AffinityIndex(cap=3)
        a = np.arange(16, dtype=np.int32)
        b = np.concatenate([a[:8], 60 - np.arange(8)]).astype(np.int32)
        idx.insert(a, 0)
        idx.insert(b, 1)
        best = idx.best(a)
        assert best[0] == 16 and best[1] == 8
        idx.remove_replica(0)
        assert 0 not in idx.best(a)
        # cap is drop-oldest
        for i in range(5):
            idx.insert(np.full((4,), i, np.int32), 1)
        assert len(idx._entries) == 3

    def test_prefix_affinity_beats_least_loaded_on_hit_rate(self, params):
        """Two prefix families, two replicas. After a warm round places one
        family per replica, affinity keeps steering each family home (KV
        cache hits); pure least-loaded crosses them (misses). Inline mode:
        fully deterministic placement."""
        rng = np.random.RandomState(7)
        pa = rng.randint(0, 61, (16,)).astype(np.int32)
        pb = rng.randint(0, 61, (16,)).astype(np.int32)

        def family_reqs(rid0):
            tails = [rng.randint(0, 61, (4,)).astype(np.int32) for _ in range(4)]
            return (
                [np.concatenate([pa, t]) for t in tails[:2]],
                [np.concatenate([pb, t]) for t in tails[2:]],
            )

        hits = {}
        for policy in ("prefix", "least-loaded"):
            engines = [
                _engine(params, prefix_cache=True),
                _engine(params, prefix_cache=True),
            ]
            router = Router(engines, affinity=policy, threads=False)
            (a1, a2), (b1, b2) = family_reqs(0)
            # Warm round: A and B in flight together land on different
            # replicas under least-loaded (the affinity seed placement).
            router.submit(a1, 4, seed=0)
            router.submit(b1, 4, seed=1)
            router.join()
            # Second round, B first: least-loaded sends B to replica 0 (A's
            # home) on the id tiebreak; affinity sends each family home.
            router.submit(b2, 4, seed=2)
            router.submit(a2, 4, seed=3)
            router.join()
            router.close()
            hits[policy] = sum(e.stats["prefix_hits"] for e in engines)
        assert hits["prefix"] > hits["least-loaded"], hits

    def test_affinity_imbalance_cap_restores_balance(self, params):
        """With affinity_max_imbalance=0, steering loses whenever the
        preferred replica is busier — the pathological hot-replica pileup
        can't happen."""
        prefix = np.arange(16, dtype=np.int32)
        reqs = [
            serving.Request(
                prompt=np.concatenate([prefix, np.full((2,), 50 + i, np.int32)]),
                max_new_tokens=3, rid=i, seed=i,
            )
            for i in range(2)
        ]
        with Router(
            [_engine(params), _engine(params)],
            threads=False,
            affinity_max_imbalance=0,
        ) as router:
            for r in reqs:
                router.submit_request(r)
            router.poll()  # dispatch both before anything finishes
            placed = [len(rep.inflight) for rep in router.replicas]
            assert placed == [1, 1], placed  # steering denied, balance wins
            router.join()


class TestAdmissionControl:
    def test_queue_full_rejects_visibly(self, params):
        with Router([_engine(params, slots=1)], queue_depth=2, threads=False) as router:
            router.submit(np.arange(5, dtype=np.int32), 3, seed=0)
            router.submit(np.arange(5, dtype=np.int32), 3, seed=1)
            with pytest.raises(QueueFullError, match="admission queue full"):
                router.submit(np.arange(5, dtype=np.int32), 3, seed=2)
            assert router.stats["rejects"] == 1
            assert len(router.join()) == 2  # accepted work is unaffected

    def test_oversized_request_rejected_at_the_front_door(self, params):
        """A prompt whose bucket-padded prefill plan exceeds max_len raises
        at submit — never inside a replica thread."""
        with Router(
            [_engine(params, buckets=(16,), max_len=42)], threads=False
        ) as router:
            # 36 + 6 fits raw, but the padded plan is 3 x 16 = 48 > 42.
            with pytest.raises(ValueError, match="bucket-padded"):
                router.submit(np.arange(36, dtype=np.int32) % 61, 6)
            assert router.stats["submitted"] == 0
            router.submit(np.arange(8, dtype=np.int32), 4)
            assert len(router.join()) == 1

    def test_deadline_cancels_mid_queue(self, params, solo):
        """Requests stuck behind a blocker past their deadline resolve as
        cancelled with zero tokens; the blocker itself is untouched."""
        with Router([_engine(params, slots=1)], threads=False) as router:
            blocker = np.arange(7, dtype=np.int32)
            router.submit(blocker, 8, seed=0)
            router.poll()  # blocker occupies the only slot
            rids = [
                router.submit(np.arange(5, dtype=np.int32), 4, seed=s, timeout=0.0)
                for s in (1, 2)
            ]
            out = {c.rid: c for c in router.join()}
            for rid in rids:
                assert out[rid].finish_reason == "cancelled"
                assert out[rid].n_new == 0
            assert router.stats["cancelled"] == 2
            np.testing.assert_array_equal(out[0].tokens, solo(blocker, 8, seed=0))

    def test_deadline_cancels_mid_decode(self, params):
        eng = _engine(params, slots=1)
        with Router([eng], threads=False) as router:
            # Warm the compile caches so the timed request's steps are fast.
            router.submit(np.arange(6, dtype=np.int32), 2, seed=9)
            router.join()
            rid = router.submit(
                np.arange(6, dtype=np.int32), 85, seed=0, timeout=0.05
            )
            # First poll checks deadlines BEFORE dispatching, so the fresh
            # request always dispatches here; the sleep then lapses its
            # deadline while it sits mid-decode in the slot.
            router.poll()
            assert router.stats["dispatched"] == 2
            time.sleep(0.08)
            (c,) = [c for c in router.join() if c.rid == rid]
            assert c.finish_reason == "cancelled" and c.n_new < 85
            assert eng.stats["cancelled"] == 1  # cancel reached the ENGINE

    def test_cancel_api(self, params):
        with Router([_engine(params, slots=1)], threads=False) as router:
            router.submit(np.arange(6, dtype=np.int32), 6, seed=0)
            rid = router.submit(np.arange(6, dtype=np.int32), 6, seed=1)
            assert router.cancel(rid) is True
            assert router.cancel(rid) is False  # already resolved
            assert router.cancel(999) is False
            out = {c.rid: c for c in router.join()}
            assert out[rid].finish_reason == "cancelled"


class TestDrainAndFailover:
    def test_preemption_flag_drains_and_finishes_inflight(self, params, solo):
        reqs = _mixed_requests(4, seed=3)
        with Router([_engine(params), _engine(params)], threads=False) as router:
            for r in reqs:
                router.submit_request(r)
            resilience.request_preemption()
            router.poll()
            assert router.draining and router.drain_reason == "preemption"
            with pytest.raises(RouterDraining):
                router.submit(np.arange(5, dtype=np.int32), 2)
            completions = router.join()
        _assert_matches_solo(solo, reqs, completions)
        assert router.stats["drain_rejected"] == 1

    def test_serve_accounts_drain_rejected_remainder(self, params):
        reqs = _mixed_requests(8, seed=4)

        def drain_on_first_token(rid, tok, text):
            router.drain("manual")

        reqs[0].stream = drain_on_first_token
        router = Router([_engine(params, slots=1)], queue_depth=2, threads=False)
        completions = router.serve(reqs)
        router.close()
        assert router.draining and router.drain_reason == "manual"
        # Everything accepted before the drain finished; the rest never ran.
        assert len(completions) + router.stats["drain_rejected"] == 8
        assert router.stats["drain_rejected"] >= 1

    def test_retry_budget_exhausted_marks_failed(self, params):
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step"):
            with Router(
                [_engine(params)], max_retries=0, threads=False
            ) as router:
                router.submit(np.arange(6, dtype=np.int32), 4)
                (c,) = router.join()
        assert c.finish_reason == "failed"
        assert router.stats["failed"] == 1 and router.stats["replicas_lost"] == 1

    def test_no_healthy_replica_raises(self, params):
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step"):
            with Router([_engine(params)], threads=False) as router:
                router.submit(np.arange(6, dtype=np.int32), 4)
                with pytest.raises(NoHealthyReplicaError):
                    router.join()

    def test_wedged_replica_quarantined_by_watchdog(self, params, solo):
        """Replica 0 hangs inside its first busy step; the per-replica
        watchdog fires, the router quarantines it, and replica 1 finishes
        everything bit-identically. threads mode only — inline, a stuck
        step would stall the caller itself."""
        reqs = _mixed_requests(4, seed=5)
        engines = [_engine(params), _engine(params)]
        for eng in engines:
            # Compile every shape OUTSIDE the router so no legitimate step
            # (a multi-second compile) outlives the short watchdog deadline.
            eng.submit(np.arange(20, dtype=np.int32), 2, seed=90)
            eng.submit(np.arange(5, dtype=np.int32), 2, seed=91)
            eng.run_until_idle()
        with patch_environment(ATX_FAULT_HANG_AT="router.replica0.step@1"):
            with Router(engines, watchdog_secs=0.1) as router:
                for r in reqs:
                    router.submit_request(r)
                completions = router.join(timeout=60.0)
        _assert_matches_solo(solo, reqs, completions)
        m = router.metrics()
        assert m["per_replica"][0]["wedged"] == 1
        assert m["per_replica"][0]["quarantined"] == 1
        assert "wedged" in m["per_replica"][0]["error"]
        assert m["replicas_alive"] == 1

    def test_sigterm_drains_and_exits_75(self, tmp_path):
        """End-to-end resume contract: the driver serves a 2-replica router,
        the parent SIGTERMs it mid-stream, it drains (finishes in-flight,
        admits nothing), self-checks bit-identity vs a solo engine, and
        exits PREEMPTION_EXIT_CODE."""
        out_path = tmp_path / "drain.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(SCRIPTS, "router_drain.py"), str(out_path)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        try:
            deadline = time.time() + 180
            for line in proc.stdout:
                if "SERVING" in line:
                    break
                assert time.time() < deadline, "driver never started serving"
            else:
                pytest.fail(f"driver exited early: rc={proc.wait()}")
            time.sleep(0.5)  # let some requests reach mid-decode
            proc.send_signal(signal.SIGTERM)
            tail = proc.stdout.read()
            rc = proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
        assert rc == resilience.PREEMPTION_EXIT_CODE, f"rc={rc}\n{tail}"
        report = json.loads(out_path.read_text())
        assert report["drain_reason"] == "preemption"
        assert report["completions"] > 0
        assert report["mismatches"] == 0
        assert report["admitted_after_drain"] == 0


class TestAcceptanceMatrix:
    def test_shared_prefix_kill_reject_drain(self, params, solo):
        """The ISSUE-8 acceptance run in one trace: shared-prefix requests
        through 2 replicas with a mid-trace replica kill, a visible
        queue-full reject, and a preemption drain — every accepted request
        completes bit-identical to solo."""
        rng = np.random.RandomState(11)
        prefix = rng.randint(0, 61, (16,)).astype(np.int32)
        reqs = [
            serving.Request(
                prompt=np.concatenate([prefix, rng.randint(0, 61, (4,)).astype(np.int32)]),
                max_new_tokens=4,
                rid=i,
                seed=i,
            )
            for i in range(10)
        ]
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@2"):
            router = Router(
                [
                    _engine(params, prefix_cache=True),
                    _engine(params, prefix_cache=True),
                ],
                queue_depth=3,
                threads=False,
            )
            accepted, rejected = [], 0
            for i, r in enumerate(reqs):
                if i == 8:
                    resilience.request_preemption()
                    router.poll()  # the tick that notices and flips to drain
                # Submissions outpace the poll rate on purpose: the queue
                # fills to queue_depth and the overflow reject is VISIBLE
                # (dispatch only happens inside poll).
                while True:
                    try:
                        router.submit_request(r)
                        accepted.append(r)
                        break
                    except QueueFullError:
                        rejected += 1
                        router.poll()  # back off one tick and retry
                    except RouterDraining:
                        break
            completions = router.join()
            router.close()
        assert rejected >= 1 and router.stats["rejects"] >= 1
        assert router.stats["replicas_lost"] == 1
        assert router.draining and router.drain_reason == "preemption"
        assert len(accepted) == 8  # the two post-drain submissions refused
        _assert_matches_solo(solo, accepted, completions)


class TestEDFScheduling:
    def test_edf_orders_by_deadline_within_class(self, params):
        """Same class, reverse-deadline submission order: dispatch (and so
        completion, on one slot) runs tightest-deadline-first."""
        with Router([_engine(params, slots=1)], threads=False) as router:
            router.submit(np.arange(6, dtype=np.int32), 6, seed=0)  # blocker
            router.poll()  # blocker owns the only slot
            r_loose = router.submit(np.arange(5, dtype=np.int32), 2, seed=1, timeout=30.0)
            r_mid = router.submit(np.arange(5, dtype=np.int32), 2, seed=2, timeout=20.0)
            r_tight = router.submit(np.arange(5, dtype=np.int32), 2, seed=3, timeout=10.0)
            out = {c.rid: c for c in router.join()}
        assert (
            out[r_tight].finished_at
            < out[r_mid].finished_at
            < out[r_loose].finished_at
        ), {r: out[r].finished_at for r in (r_tight, r_mid, r_loose)}
        assert all(c.finish_reason in ("eos", "length") for c in out.values())

    def test_edf_priority_class_overtakes_fifo_does_not(self, params):
        """The EDF-vs-FIFO acceptance proxy: a priority-0 arrival behind
        two queued priority-2 requests is served FIRST under EDF (its
        deadline odds improve at the background class's expense) and LAST
        under fifo (arrival order, the pre-PR-14 behaviour)."""
        order = {}
        for scheduling in ("edf", "fifo"):
            with Router(
                [_engine(params, slots=1)], threads=False, scheduling=scheduling
            ) as router:
                router.submit(np.arange(6, dtype=np.int32), 6, seed=0)
                router.poll()
                lo = [
                    router.submit(
                        np.arange(5, dtype=np.int32), 2, seed=s, priority=2
                    )
                    for s in (1, 2)
                ]
                hi = router.submit(
                    np.arange(5, dtype=np.int32), 2, seed=3, priority=0
                )
                out = {c.rid: c for c in router.join()}
            order[scheduling] = out[hi].finished_at < min(
                out[r].finished_at for r in lo
            )
        assert order == {"edf": True, "fifo": False}

    def test_priority_shed_on_full_queue(self, params):
        """A full queue rejects same-or-lower classes but SHEDS the newest
        ticket of the least important class for a strictly higher one; the
        victim resolves visibly with ``finish_reason="shed"``."""
        with Router(
            [_engine(params, slots=1)], queue_depth=2, threads=False
        ) as router:
            router.submit(np.arange(6, dtype=np.int32), 6, seed=0)
            router.poll()  # blocker out of the queue, into the slot
            lo1 = router.submit(np.arange(5, dtype=np.int32), 2, seed=1, priority=2)
            lo2 = router.submit(np.arange(5, dtype=np.int32), 2, seed=2, priority=2)
            with pytest.raises(QueueFullError):  # equal class: no shed
                router.submit(np.arange(5, dtype=np.int32), 2, seed=3, priority=2)
            hi = router.submit(np.arange(5, dtype=np.int32), 2, seed=4, priority=0)
            out = {c.rid: c for c in router.join()}
        assert out[lo2].finish_reason == "shed" and out[lo2].n_new == 0
        assert out[lo1].finish_reason in ("eos", "length")
        assert out[hi].finish_reason in ("eos", "length")
        m = router.metrics()
        assert m["shed"] == 1 and m["shed_by_class"] == {"2": 1}
        assert m["rejects"] == 1
        assert m["per_class"]["2"]["shed"] == 1

    def test_fifo_never_sheds(self, params):
        with Router(
            [_engine(params, slots=1)], queue_depth=2, threads=False,
            scheduling="fifo",
        ) as router:
            router.submit(np.arange(6, dtype=np.int32), 6, seed=0)
            router.poll()
            router.submit(np.arange(5, dtype=np.int32), 2, seed=1, priority=2)
            router.submit(np.arange(5, dtype=np.int32), 2, seed=2, priority=2)
            with pytest.raises(QueueFullError):
                router.submit(np.arange(5, dtype=np.int32), 2, seed=3, priority=0)
            assert len(router.join()) == 3
        assert router.metrics()["shed"] == 0

    def test_deadline_infeasible_rejected_at_admission(self, params):
        """Once the e2e histogram is warm (>= 5 samples), a deadline the
        observed service time cannot meet raises at submit instead of
        burning a slot on work that will be cancelled anyway."""
        with Router([_engine(params, slots=1)], threads=False) as router:
            for s in range(5):  # warm the service-time estimate
                router.submit(np.arange(6, dtype=np.int32), 2, seed=s)
                router.join()
            router.submit(np.arange(6, dtype=np.int32), 30, seed=9)
            router.poll()
            with pytest.raises(DeadlineInfeasibleError):
                router.submit(
                    np.arange(5, dtype=np.int32), 4, seed=10, timeout=0.0005
                )
            assert router.metrics()["deadline_infeasible"] == 1
            rid = router.submit(  # a generous deadline is still admitted
                np.arange(5, dtype=np.int32), 2, seed=11, timeout=60.0
            )
            out = {c.rid: c for c in router.join()}
        assert out[rid].finish_reason in ("eos", "length")
        assert isinstance(
            DeadlineInfeasibleError("x"), QueueFullError
        )  # callers catching QueueFullError keep working


class TestSelfHealing:
    def test_quarantine_probe_readmit_bit_identical(self, params, solo):
        """The tentpole cycle: replica 0 dies mid-trace, failover finishes
        the batch bit-identically, the probe replays the canary after
        ``readmit_secs`` and re-admits the replica under probation — and
        the readmitted replica serves NEW traffic bit-identically too."""
        reqs = _mixed_requests(8, seed=21)
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@3"):
            with Router(
                [_engine(params), _engine(params)],
                threads=False,
                readmit_secs=0.01,
                probation_completions=2,
                engine_factory=lambda: _engine(params),
            ) as router:
                completions = router.serve(reqs)
                deadline = time.time() + 30.0
                while router.metrics()["readmissions"] < 1:
                    assert time.time() < deadline, "no re-admission within 30s"
                    router.poll(0.002)
                m = router.metrics()
                assert m["replicas_alive"] == 2
                assert m["per_replica"][0]["quarantines"] == 1
                d0 = m["per_replica"][0]["dispatched"]
                reqs2 = _mixed_requests(6, seed=22)
                for r in reqs2:
                    r.rid += 100
                completions2 = router.serve(reqs2)
        _assert_matches_solo(solo, reqs, completions)
        _assert_matches_solo(solo, reqs2, completions2)
        m = router.metrics()
        assert m["replicas_lost"] == 1 and m["readmissions"] == 1
        assert m["per_replica"][0]["dispatched"] > d0  # probation lifted
        assert m["per_replica"][0]["probation"] == 0

    def test_probation_caps_inflight_to_one(self, params):
        """A just-readmitted replica takes at most ONE in-flight request
        until it clears probation; the healthy replica absorbs the rest."""
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@1"):
            with Router(
                [_engine(params), _engine(params)],
                threads=False,
                readmit_secs=0.005,
                probation_completions=8,
                engine_factory=lambda: _engine(params),
            ) as router:
                router.submit(np.arange(6, dtype=np.int32), 3, seed=0)
                router.join()
                deadline = time.time() + 30.0
                while router.metrics()["readmissions"] < 1:
                    assert time.time() < deadline, "no re-admission within 30s"
                    router.poll(0.002)
                for s in range(4):
                    router.submit(np.arange(8, dtype=np.int32), 3, seed=s)
                router.poll()  # one dispatch pass while all four are queued
                placed = [len(rep.inflight) for rep in router.replicas]
                assert placed[0] <= 1, placed  # probation cap
                router.join()

    def test_readmit_disabled_by_default_stays_fail_stop(self, params):
        """Without ``readmit_secs`` a quarantined replica never comes back
        — the pre-PR-14 fail-stop contract the failover tests pin."""
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@1"):
            with Router(
                [_engine(params), _engine(params)], threads=False
            ) as router:
                router.submit(np.arange(6, dtype=np.int32), 3, seed=0)
                router.join()
                for _ in range(50):
                    router.poll(0.001)
                m = router.metrics()
        assert m["replicas_alive"] == 1 and m["readmissions"] == 0

    def test_retry_budget_exhaustion_fails_fast(self, params):
        """With a zero fleet retry budget the orphaned request fails
        instead of replaying — the retry-storm brake — and the exhaustion
        is visible in telemetry."""
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@1"):
            with Router(
                [_engine(params), _engine(params)],
                threads=False,
                retry_budget=0,
                retry_refill_per_sec=0.0,
            ) as router:
                router.submit(np.arange(6, dtype=np.int32), 4, seed=0)
                (c,) = router.join()
        assert c.finish_reason == "failed"
        m = router.metrics()
        assert m["retry_budget_exhausted"] == 1 and m["retry_tokens"] == 0
        assert m["replicas_alive"] == 1

    def test_retry_budget_token_absorbs_one_failover(self, params, solo):
        prompt = np.arange(6, dtype=np.int32)
        with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@1"):
            with Router(
                [_engine(params), _engine(params)],
                threads=False,
                retry_budget=1,
                retry_refill_per_sec=0.0,
            ) as router:
                router.submit(prompt, 4, seed=0)
                (c,) = router.join()
        assert c.finish_reason in ("eos", "length")
        np.testing.assert_array_equal(c.tokens, solo(prompt, 4, seed=0))
        m = router.metrics()
        assert m["retries"] == 1 and m["retry_budget_exhausted"] == 0
        assert m["retry_tokens"] == 0

    def test_prefix_migration_reseeds_survivor(self, params):
        """Quarantining the replica that owns a hot prefix re-prefills that
        prefix into the survivor (host token ids only — KV never crosses
        devices) and retargets affinity, so follow-up family traffic hits
        the survivor's cache immediately."""
        rng = np.random.RandomState(13)
        prefix = rng.randint(0, 61, (16,)).astype(np.int32)

        def fam():
            return np.concatenate(
                [prefix, rng.randint(0, 61, (4,)).astype(np.int32)]
            )

        engines = [
            _engine(params, prefix_cache=True),
            _engine(params, prefix_cache=True),
        ]
        with Router(engines, threads=False) as router:
            router.submit(fam(), 3, seed=0)  # warms family A onto replica 0
            router.join()
            with patch_environment(ATX_FAULT_RAISE_AT="router.replica0.step@1"):
                router.submit(fam(), 3, seed=1)
                router.join()
            m = router.metrics()
            assert m["replicas_lost"] == 1
            assert m["migrated_prefixes"] >= 1, m
            hits0 = engines[1].stats["prefix_hits"]
            router.submit(fam(), 3, seed=2)
            router.join()
            assert engines[1].stats["prefix_hits"] > hits0


class TestServeCLIFlags:
    def test_replicas_spread_over_local_devices(self, params, monkeypatch):
        """`atx serve --replicas 4` on a four-device host: replica i's
        weights, slot KV pool and prefix pool all live on device i — not
        four engines stacked on device 0."""
        from accelerate_tpu.commands import serve as serve_cmd

        four = jax.local_devices()[:4]
        monkeypatch.setattr(jax, "local_devices", lambda: four)
        engines = [
            _engine(serve_cmd.replica_params(params, i), prefix_cache=True)
            for i in range(5)
        ]
        placed = [
            {d for pool in (e._kv, e._pool, e.params)
             for leaf in jax.tree.leaves(pool) for d in leaf.devices()}
            for e in engines
        ]
        assert placed[:4] == [{d} for d in four]
        assert placed[4] == {four[0]}  # i mod n
        # ...and an engine still serves from where it was put.
        engines[2].submit(np.arange(5, dtype=np.int32), 3)
        (c,) = engines[2].run_until_idle()
        assert c.n_new == 3
        assert next(iter(engines[2]._kv.values())).devices() == {four[2]}

    def test_parser_accepts_router_flags(self):
        import argparse

        from accelerate_tpu.commands import serve as serve_cmd

        parser = argparse.ArgumentParser()
        serve_cmd.register(parser.add_subparsers())
        args = parser.parse_args(
            ["serve", "--replicas", "2", "--queue-depth", "7",
             "--affinity", "least-loaded"]
        )
        assert args.replicas == 2
        assert args.queue_depth == 7
        assert args.affinity == "least-loaded"
        with pytest.raises(SystemExit):
            parser.parse_args(["serve", "--affinity", "random"])

    @pytest.mark.slow
    def test_cli_two_replicas_emits_router_json(self, capsys):
        from accelerate_tpu.commands.cli import main as cli_main

        rc = cli_main(
            ["serve", "--model", "llama-tiny", "--replicas", "2",
             "--slots", "2", "--buckets", "8", "--requests", "6",
             "--rate", "64", "--prompt-lens", "4:8", "--new-tokens", "2:4"]
        )
        assert rc == 0
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["serve_router_replicas"] == 2
        assert out["serve_router_completed"] == 6
        assert out["serve_router_replicas_alive"] == 2
        assert len(out["serve_router_occupancy"]) == 2
        assert out["serve_router_draining"] == 0
