# Test lanes (the reference splits CI the same way, Makefile:25-60).
#
#   make test        - fast lane: skips tests marked `heavy` (< ~5 min)
#   make test-heavy  - ONLY the heavy lane (compile-heavy, subprocess launches)
#   make test-all    - everything
#
# The heavy marker lives on whole files (attention kernels, model-zoo
# forward parity, HF interop, HLO verification, examples, CLI/multiprocess
# launches, checkpointing); `pytest tests/ --heavy` is the raw invocation.

.PHONY: test test-heavy test-all smoke-transfer smoke-serve smoke-router smoke-resilience smoke-replication smoke-elastic smoke-shrink smoke-kernels smoke-telemetry smoke-chaos smoke-trace lint-graph lint-multihost lint-perf lint-memory

test:
	python -m pytest tests/ -q

# Fast CPU smoke over the transfer-engine code paths (tiny arrays, no TPU):
# the engine unit tests plus the disk-offload overlap/sentinel integration.
smoke-transfer:
	JAX_PLATFORMS=cpu python -m pytest tests/test_transfer.py tests/test_disk_offload.py -q -m 'not slow'

# CPU smoke for the continuous-batching serving engine (docs/serving.md):
# tiny model, a 16-request Poisson trace that must fully complete with
# outputs bit-identical to solo generate, a shared-system-prompt trace
# that must show prefix_hit_rate > 0 with >= 50% of prompt tokens served
# from the radix prefix cache AND stay bit-identical to the cache-off
# engine (tests/test_serving.py, tests/test_prefix_cache.py), plus `atx
# lint` over the engine's real decode step and the prefix-copy kernel —
# error-severity findings fail the lane.
smoke-serve:
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py tests/test_prefix_cache.py tests/test_generation.py -q -m 'not slow'
	JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli lint serving --severity error

# CPU smoke for the multi-replica serving front-end (docs/serving.md,
# "Multi-replica routing & drain"): 2-replica greedy outputs bit-identical
# to a solo engine — including under an injected replica kill mid-decode
# and a wedge caught by the per-replica watchdog — plus visible
# queue-full rejects, deadline cancels mid-queue and mid-decode, and the
# SIGTERM drain -> exit 75 subprocess contract; then the router_drain
# host-loop replay under 2 simulated processes (error findings fail).
smoke-router:
	JAX_PLATFORMS=cpu python -m pytest tests/test_router.py -q -m 'not slow'
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint router_drain --multihost 2 \
		--severity error

# Ahead-of-time step lint over the examples/ entry points (no training, no
# weights): fails on any error-severity finding (docs/static_analysis.md).
# The 8 simulated host devices give the sharding/collective rules a real
# mesh to check against.
lint-graph:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint examples --severity error

# Static performance lint + budget ratchet (ATX6xx, docs/performance.md
# "perf campaign"): the example train steps plus the 1.64B llama2b
# config are compiled abstractly, the roofline rules run at error
# severity, and the ATX601 series (static MFU bound, exposed-comms bytes,
# padding-waste fraction) are checked against the committed
# perf/budgets.json — any regression past tolerance fails the lane.
# Rated at v5e so the series are TPU-shaped even on the CPU container.
lint-perf:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint perf --severity error \
		--chip v5e --budgets perf/budgets.json

# Static memory lint + budget ratchet (ATX7xx, docs/static_analysis.md):
# the perf scenarios plus the serving engine get the compiled-HLO HBM
# timeline (peak live bytes vs the chip's HBM — ATX702 fires on a static
# OOM) and the serving capacity planner (ATX706), with the peak_hbm_mib /
# serve_static_max_slots series ratcheted against perf/budgets.json.
# Rated at v5e so the series are TPU-shaped even on the CPU container.
lint-memory:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint memory --severity error \
		--chip v5e --budgets perf/budgets.json

# Multi-host SPMD-consistency lint (ATX5xx, docs/static_analysis.md): the
# example train steps are re-traced under 2 simulated processes (divergent
# jitted collectives fail), and the host-side save / preemption-exit loops
# are replayed process-by-process so a collective-schedule divergence — the
# kind that hangs a real pod — fails here instead.
lint-multihost:
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint --multihost 2 \
		nlp_example lm_example cv_example save_path preemption_exit \
		--severity error

# CPU resilience lane (docs/fault_tolerance.md): fault-injected save/load
# roundtrips (truncate / bit-flip / kill-9 mid-save must never lose the last
# committed checkpoint), the SIGTERM-resume bit-identity subprocess smoke,
# and the hang-watchdog abort smoke.
smoke-resilience:
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q -m 'not slow'

# CPU replication lane (docs/fault_tolerance.md, "Checkpoint replication &
# remote restore"): LocalObjectStore round-trip (save -> background upload
# -> delete local root -> restore-from-remote, bit-identical), the
# fault-injection subset (kill -9 mid-upload resumes skipping completed
# parts; transient-error backoff bounded + jittered), then the
# replicated_save host-loop replay under 2 simulated processes proving
# replication adds NO collectives (error findings fail).
smoke-replication:
	JAX_PLATFORMS=cpu python -m pytest tests/test_replication.py -q -m 'not slow'
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint replicated_save --multihost 2 \
		--severity error

# CPU elastic-resume lane (docs/fault_tolerance.md, "Elastic resume &
# resharding restore"): reshard-on-restore round trips (save under an
# 8-device FSDP mesh, restore bit-identical under 4 and 2 — optimizer
# moments included), peer-shard fetch from the object store with manifest
# verification (corrupt bytes rejected, kill -9 mid-fetch leaves the
# checkpoint untouched), the peer-health watchdog, the ATX_NAN_GUARD
# skip/abort budget, and the 8-dev -> SIGTERM -> 4-dev resume subprocess
# acceptance; then the elastic_restore host-loop replay under 2 simulated
# processes proving the restore path adds NO collectives (error findings
# fail).
smoke-elastic:
	JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py -q -m 'not slow'
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint elastic_restore --multihost 2 \
		--severity error

# CPU shrink-in-place lane (docs/fault_tolerance.md, "Shrink/grow in
# place"): the live-resize acceptance — an 8-rank (simulated) run loses 2
# peers mid-training, survivors agree and reshard IN PLACE (no relaunch),
# and post-shrink losses + Adam moments + step match a never-interrupted
# 6-device reference; grow-back; kill -9 / agreement-timeout mid-shrink
# degrading to the exit-75 relaunch with the prior commit intact; ranged
# object-store reads; then the shrink host-loop replay under 2 simulated
# processes proving escalate -> agree -> reshard -> resume adds NO
# collectives (error findings fail).
smoke-shrink:
	JAX_PLATFORMS=cpu python -m pytest tests/test_shrink.py -q -m 'not slow'
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint shrink --multihost 2 \
		--severity error

# CPU kernel-tier lane (docs/performance.md, "Pallas kernel tier"):
# interpret-mode parity of every Pallas kernel against its exact fallback
# lowering (flash-decode attention incl. GQA/ragged cursors/int8 KV,
# int8/fp8 fused matmul fwd+bwd, fused AdamW), dispatch-knob resolution,
# and `atx lint kernels` over the kernel-enabled decode + train steps
# (error-severity ATX findings fail the lane).
smoke-kernels:
	JAX_PLATFORMS=cpu python -m pytest tests/test_kernels.py -q -m 'not slow'
	JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli lint kernels --severity error

# CPU telemetry lane (docs/observability.md): registry/histogram/span unit
# tests incl. the zero-device-sync and bit-identity gates, a 16-request
# `atx serve --metrics-port` run scraped live mid-trace with the Prometheus
# text cross-checked against the JSON summary, and the telemetry host-loop
# replay under 2 simulated processes proving metrics + snapshot export add
# NO collectives (error findings fail).
smoke-telemetry:
	JAX_PLATFORMS=cpu python -m pytest tests/test_telemetry.py -q -m 'not slow'
	JAX_PLATFORMS=cpu python tests/scripts/serve_scrape.py
	JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli lint telemetry --multihost 2 \
		--severity error

# CPU chaos lane (docs/fault_tolerance.md, "Chaos campaigns"): the
# FaultSchedule seed-replay + campaign-digest unit tests, a fixed-seed
# 12-episode inline campaign over router/engine/replication (exactly-once,
# bit-identity, drain, no-torn-commit — any violation exits 1), and the
# router_recovery host-loop replay under 2 simulated processes proving
# quarantine -> probe -> re-admit -> prefix migration adds NO collectives
# (error findings fail).
smoke-chaos:
	JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py -q -m 'not slow'
	JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli chaos \
		--episodes 12 --seed 0 --no-subprocess-episodes
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m accelerate_tpu.commands.cli lint router_recovery --multihost 2 \
		--severity error

# CPU tracing lane (docs/observability.md, "Request tracing & the flight
# recorder"): flight-recorder ring / postmortem-bundle
# unit tests incl. the exactly-once-through-failover and SystemExit-flush
# subprocess gates, a 16-request Poisson trace served twice proving
# ATX_TRACE_REQUESTS=1 is bit-identical to =0 with `atx trace --check
# 0.05` passing on both the bundle and the live JSONL dir (phase spans
# must sum to each request's e2e within 5%), and the tracing host-loop
# replay under 2 simulated processes proving span recording + the bundle
# dump add NO collectives (error findings fail).
smoke-trace:
	JAX_PLATFORMS=cpu python -m pytest tests/test_trace.py -q -m 'not slow'
	JAX_PLATFORMS=cpu python tests/scripts/trace_smoke.py
	JAX_PLATFORMS=cpu python -m accelerate_tpu.commands.cli lint tracing --multihost 2 \
		--severity error

test-heavy:
	python -m pytest tests/ -q -m heavy

test-all: lint-graph lint-multihost lint-perf lint-memory smoke-serve smoke-router smoke-resilience smoke-replication smoke-elastic smoke-shrink smoke-kernels smoke-telemetry smoke-chaos smoke-trace
	python -m pytest tests/ -q --heavy
