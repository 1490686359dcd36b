PYTHON ?= python

.PHONY: check test hooks chaos chaos-serve metrics \
	regress mesh paged paged-kernel fleet-mr aot slo governor history \
	analyze fleetscope servescope deploy elastic replay memscope

# Full commit gate: the whole test suite (CPU). The chip gate is
# `python chip_smoke.py`, run on the TPU through the chip tool.
check: test

test:
	$(PYTHON) -m pytest tests/ -x -q

# Deterministic fault-injection suite (docs/fleet_robustness.md) under
# three pinned chaos seeds — pinned so every configured fault fires
# within the toy run (see tests/test_fleet_chaos.py).
chaos:
	for seed in 1 3 5; do \
		echo "== chaos seed $$seed"; \
		VELES_TPU_CHAOS_SEED=$$seed JAX_PLATFORMS=cpu \
			$(PYTHON) -m pytest tests/test_fleet_chaos.py \
			-m chaos -q || exit 1; \
	done

# Serving chaos suite (docs/serving_robustness.md): breaker recovery,
# deadline expiry, admission control, hostile clients — under the same
# three pinned seeds (see tests/test_serving_chaos.py).
chaos-serve:
	for seed in 1 3 5; do \
		echo "== chaos-serve seed $$seed"; \
		VELES_TPU_CHAOS_SEED=$$seed JAX_PLATFORMS=cpu \
			$(PYTHON) -m pytest tests/test_serving_chaos.py \
			-m chaos_serve -q || exit 1; \
	done

# Mesh/sharding correctness suite (docs/sharded_serving.md) on the
# 8-device virtual CPU platform: reshard schedule exactness + byte
# accounting, sharded slot-engine bit-identity (incl. mid-flight joins
# and the int8-KV tier), dispatch-count/recompile-storm guards, and
# the train-dp -> reshard -> serve-tp composite — sharding correctness
# proven in CI without TPUs.
mesh:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_reshard.py \
		tests/test_mesh_serving.py -m mesh -q

# Paged-KV serving suite (docs/paged_kv.md): page-pool bit-identity vs
# the dense engine and greedy generate() (bf16 + int8-KV, single-chip
# and the 8-device CPU mesh), shared-prefix tail/hit admissions with
# divergence, cancel/eviction page accounting, the pool-aware admission
# gate's no-deadlock invariant, and the dispatch-economy /
# zero-recompile-storm bound for the paged programs.
paged:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_paged.py \
		-m paged -q

# Fused paged-attention kernel suite (docs/paged_kv.md "The fused
# kernel"): kernel-vs-gather token bit-identity through the real
# serving engine via Pallas interpret mode (bf16 + int8-KV, mid-flight
# joins, tail/hit admissions), the ragged admission path's per-row
# masking + exact page allocation, the rule's fallback matrix
# (platform / mesh / the test seam), tile_pad waste
# accounting with span/page overshoot pinned 0, and the warmed-sweep
# zero-retrace guard. (The interpret-mode composites ride the `slow`
# marker so tier-1 keeps its timeout margin; this target runs them.)
paged-kernel:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest \
		tests/test_paged_kernel.py tests/test_tpu_lowering.py \
		-m paged_kernel -q

# Compiler-visible fleet aggregation suite (docs/compiler_fleet.md):
# the mapreduce primitives (f32 bit-exact vs psum, bf16/int8 quantized
# all-reduce tiers with error bounds + convergence parity), the
# instrumented fleet_train_step, and the control-plane fleet's
# bit-identity vs the single-process fused step on the 8-device CPU
# mesh — clean AND under the chaos harness (death/zombie/duplicate
# with the rollback protocol).
fleet-mr:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_mapreduce.py \
		tests/test_fleet_chaos.py -m fleet_mr -q

# Observability suite standalone (docs/observability.md): registry
# concurrency + exposition format, the disabled-path overhead guard
# (shared null-span identity, zero registry mutations — observability
# must never silently tax the serving hot path), trace export, and
# the end-to-end serving/fleet trace-propagation acceptance tests.
metrics:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_observe.py -q

# Artifact-proof regression sentinel (docs/observability.md): the
# sentinel suite — the loader's truncated-tail recovery over
# tests/fixtures/bench_truncated_tail.json, and the seeded-regression
# fixture that proves the gate exits NONZERO on a real regression. CI
# runs this on every push.
regress:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_regress.py -q

# Request-truth ledger + SLO suite (docs/observability.md): the
# bounded per-request ledger's stage-waterfall invariants, SLO
# burn-rate window math + per-tenant labels, the /debug/requests +
# fleet-piggyback round trip, AOT dispatch attribution, and the chaos
# acceptance — a seeded slow-step run burns budget and its autopsy
# names the stall stage.
slo:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_reqledger.py \
		-m slo -q

# Closed-loop serving governor suite (docs/serving_robustness.md):
# hysteresis-band/cooldown state-machine determinism (at most one tier
# transition per cooldown window), the priced Retry-After helper on
# every 429/503 surface, per-tenant SLO gauge retirement, and the
# chaos acceptance — under each seeded burn-inducing profile (latency
# ramp, pool-exhaustion flood, compile storm) the governor converges
# to a stable degraded tier with a PINNED transition count, every
# demoted request's ledger row names its tier, and full fidelity
# restores with burn < 1.0 after the fault clears.
governor:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_governor.py \
		-m governor -q

# Metric flight recorder suite (docs/observability.md): ring/series-cap
# bounds and counter-rate math, the threshold/slope/drop anomaly
# predicates on synthetic series, incident-artifact schema + atomic
# write discipline + leading-indicator math, the /debug/history round
# trip, fleet slave-labeled history piggyback, sparkline cells, the
# `observe incident` CLI on saved and live payloads, and the
# governor-reads-history acceptance (control and autopsy trends share
# one store). The chaos-driven end-to-end cases ride the `slow` marker
# so tier-1 keeps its timeout margin.
history:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_history.py \
		-m history -q

# Invariant gate (docs/static_analysis.md): the AST rule engine over
# the package — flight-recorder lock discipline, retrace hazards,
# donation safety, the thread-shared-state census and the Prometheus
# metric grammar — gating on NEW findings only (the committed baseline
# suppresses triaged ones; exit 1 = new violation, 2 = unreadable
# file), then the analyzer's own suite: every rule proven live on a
# seeded-violation fixture + the clean negative control + the baseline
# round trip + the CLI exit-code matrix.
analyze:
	JAX_PLATFORMS=cpu $(PYTHON) -m veles_tpu analyze veles_tpu/ \
		--baseline analyze_baseline.json
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_analyze.py \
		-m analyze -q

# Fleet goodput observatory suite (docs/observability.md "Fleet
# timeline + goodput"): span-summary shipping on update frames with
# hostile-row ingestion caps, NTP-style clock alignment proven within
# its own reported uncertainty (incl. the chaos frame-delay profile),
# the goodput decomposition + ledger wasted-work accounting, the
# persistent-straggler detector + fleet incident artifact, the
# multi-process Chrome exporter, and the chaos slow-slave acceptance —
# `observe fleet-trace` on a real loopback fleet deterministically
# names the injected straggler and emits a Perfetto-loadable merged
# trace with connected issue->do_job->apply chains. (The e2e also
# carries the `slow` marker so tier-1 keeps its timeout margin; this
# target runs it.)
fleetscope:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_fleetscope.py \
		-m fleetscope -q

# Serving goodput observatory suite (docs/observability.md "Serving
# goodput + slot timeline"): the lock-free per-dispatch accounting
# ring, EXACT per-cause token-waste math against the real dense and
# paged engines (bucket pad, duplicate rows, span/page overshoot,
# dead slots, lag-tail discards), the wall decomposition, the per-slot
# occupancy timeline + `observe serve-trace` Perfetto assembly (saved
# and --live), /debug/serve + the /debug/ index, and the chaos
# waste-profile acceptance — a seeded injection must land an incident
# artifact naming EXACTLY the injected dominant cause. (The e2e
# carries the `slow` marker so tier-1 keeps its timeout margin; this
# target runs it.)
servescope:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_servescope.py \
		-m servescope -q

# Zero-downtime deploy suite (docs/zero_downtime.md): the live weight
# hot-swap seam (outputs change, rollback restores bit-identically,
# poisoned checkpoints refused with the old weights still serving,
# zero 5xx across the swap window), the blue-green rollback
# predicate's edge cases under an explicit clock (idle-green no
# verdict, blue-baseline suppression, breach-streak + dwell
# hysteresis), torn/tampered executable-cache entries refused loudly
# once and repaired, and the chaos acceptances — a seeded bad-green
# ramp auto-rolls back naming the leading indicator in the incident
# artifact with zero shed and blue streams bit-identical; a clean
# green promotes. (The engine-booting chaos cases ride the `slow`
# marker so tier-1 keeps its timeout margin; this target runs them.)
deploy:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_deploy.py \
		-m deploy -q

# Elastic replicated serving suite (docs/elastic_serving.md): the
# consistent-hash affinity ring's stability under replica churn (zero
# foreign keys remap), pressure spill, the per-request lease's
# exactly-once delivery fence (half-stream failover, hedged
# double-delivery discard, Retry-After-priced backoff), the honest
# all-down 503, the real transport's half-stream EOF verdict, the
# control plane's leave-one-out collapse detector + ledger-visible
# lifecycle actuations (drain/retire/dead/adopt, min_active
# suppression, cooldown), the incident artifact naming the replica,
# and the kill -9 chaos acceptance — N same-seed subprocess replicas,
# one killed mid-traffic, every request completing through failover
# bit-identically with zero non-retryable 5xx. (The subprocess
# acceptance rides the `slow` marker so tier-1 keeps its timeout
# margin; this target runs it.)
elastic:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_router.py \
		-m elastic -q

# Traffic record-replay + capacity-cliff suite (docs/traffic_replay.md):
# the anonymized trace schema round trip (salted tenant hashes, no
# prompt text, sha256 sidecar refusal), lossy-trace stamping off the
# ledger's loss counters, bit-identical seeded warp plans, the
# open-loop replayer's shed/error bookkeeping, the capacity
# controller's escalate-then-backoff loop on a scripted endpoint, the
# recorded-traffic chaos profile, and the live acceptance — `observe
# record --live` then `observe capacity --live` escalates warp until
# the SLO burns and the report names the first-breaching series. (The
# live-endpoint acceptances ride the `slow` marker so tier-1 keeps its
# timeout margin; this target runs them.)
replay:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_replay.py \
		-m replay -q

# Per-owner HBM attribution suite (docs/memscope.md): weakref'd
# byte-accountants + GC-as-unregister, the reconciliation contract
# (exported owner rows cover the device total with owner="untagged"
# as the published residue), lifecycle-edge leak verdicts + their
# flight-recorder incident artifacts with the LEAK_EXEMPT carve-outs,
# the headroom-forecast slope math, the governor's memory-frac CPU
# fallback + headroom_guard_s actuator, the veles_hbm_* /
# veles_device_memory_limit_bytes families, /debug/memory, the real
# serving engine's owner registrations, and the chaos acceptance — a
# seeded retained-pool injection must land an incident artifact
# naming kv_pool. (The engine-booting acceptances ride the `slow`
# marker so tier-1 keeps its timeout margin; this target runs them.)
memscope:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_memscope.py \
		-m memscope -q

# AOT compiled-program artifact suite (docs/aot_artifacts.md): bundle
# build/load bit-identity (dense + paged, bf16 + int8-KV, the 8-device
# CPU mesh, one fused train step), the compatibility-gate rejection
# matrix (schema/jax/jaxlib/fingerprint/mesh each refused by name), the
# zero-retrace serving warmup (veles_xla_compiles_total pinned flat),
# deterministic package bytes, and the forge 422-on-tamper upload path.
aot:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_aot.py \
		-m aot -q

# Install the pre-commit test gate into .git/hooks.
hooks:
	printf '#!/bin/sh\nmake -C "$$(git rev-parse --show-toplevel)" check\n' \
		> "$$(git rev-parse --git-path hooks)/pre-commit"
	chmod +x "$$(git rev-parse --git-path hooks)/pre-commit"
