.PHONY: test test-fast test-engine test-e2e native clean verify analyze chaos scale lockdep

test:
	python -m pytest tests/ -q

# Canonical tier-1 gate: the EXACT command from ROADMAP.md ("Tier-1
# verify"), so builders and CI invoke one entrypoint instead of
# re-typing (and drifting from) the driver's command line.
# bash, not sh: the command uses PIPESTATUS.
verify: SHELL := /bin/bash
verify:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# <2min signal on WARM caches (XLA compile + import caches). The first
# run on a cold box pays one-time jax/XLA warmup and can take ~10min on
# a single-core machine — that's cache fill, not test time; re-runs are
# fast. The full suite remains the merge gate.
test-fast:
	python -m pytest tests/ -q -m fast

# Project-native static analysis (docs/ANALYSIS.md): event-loop safety,
# state-machine conformance, config/metric drift. Also enforced inside
# tier-1 via tests/analysis/test_codebase_clean.py — this target is the
# fast direct entrypoint (~1s).
analyze:
	python -m gpustack_tpu.analysis

# Seeded chaos against the in-process cluster (docs/RESILIENCE.md): one
# schedule per fault class (worker kill, heartbeat blackhole, RPC
# delay/drop, engine crash mid-STARTING, server restart, the
# multi-server ha-failover class: leader kill/hang + lease expiry over
# a shared DB, kv-handoff aborts, the kv-directory staleness class
# (a poisoned fleet KV directory entry must degrade to a counted cold
# route, never a stall — docs/KV_CACHE.md "Fleet KV fabric"), the
# noisy-neighbor tenant flood with its fairness invariant —
# docs/TENANCY.md — and the fleet-scale classes: acquire-storm (8-way
# lease storms) and rolling-server-restart, both multi-server); exits
# nonzero on any invariant violation or failed convergence. Same seed
# ⇒ same schedule, so failures are replayable.
# Narrow with CLASSES (e.g. `make chaos CLASSES=kv-directory`).
CLASSES ?= all
SEED ?= 1
chaos:
	JAX_PLATFORMS=cpu python -m gpustack_tpu.testing.chaos --classes $(CLASSES) --seed $(SEED)

# Chaos under the runtime lockdep monitor (docs/ANALYSIS.md "Runtime
# lockdep"): every threading.Lock/RLock/Condition the cluster
# constructs is acquisition-order- and hold-time-tracked; the observed
# edges merge with the analyzer's static lock graph and any cycle (an
# ABBA deadlock some interleaving can reach, even if this run never
# hung) or over-threshold hold fails the class. Narrow with
# LOCKDEP_CLASSES (default: worker-kill, the densest thread mesh).
LOCKDEP_CLASSES ?= worker-kill
lockdep:
	JAX_PLATFORMS=cpu python -m gpustack_tpu.testing.chaos --classes $(LOCKDEP_CLASSES) --seed $(SEED) --lockdep

# Slow scheduler-at-scale suites (docs/RESILIENCE.md "Scale &
# crash-consistency"): the 1000+-worker fleet suite (reconcile-pass
# latency SLOs, sub-linear DB write rate query-counted 100-vs-1000,
# O(events) watch fan-out across a multi-server cluster, zero
# invariant violations) plus the 300-worker smoke. Width override:
# GPUSTACK_TPU_SCALE_WORKERS=200 make scale
scale:
	JAX_PLATFORMS=cpu python -m pytest tests/e2e/test_fleet_scale.py tests/e2e/test_scale_smoke.py tests/e2e/test_scale_chaos.py -q

test-engine:
	python -m pytest tests/ -q -m engine

test-e2e:
	python -m pytest tests/ -q -m e2e

native:
	$(MAKE) -C native

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
