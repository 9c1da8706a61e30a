# Developer entry points. The native decoder has its own Makefile
# (native/Makefile, `make native`); everything here is pure Python.

PYTHON ?= python

.PHONY: lint test native stamps trace ragged multichip chaos \
	dct benchdiff pages races shard

# Static analysis: pipeline graph checker over every shipped config,
# hot-path AST lint over rnb_tpu/, telemetry schema checker — no JAX
# device, no dataset. Rule catalog: README.md "Static analysis".
lint:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/rnb_lint.py

# Tier-1 gate (same selection ROADMAP.md pins): fast tests on the
# forced 8-virtual-device CPU backend, the benchmark harness's own
# (tests/harness collects benchmarks/tests) among them.
test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow' \
	  --continue-on-collection-errors -p no:cacheprovider

# Generated telemetry-schema reference (the registries rnb-lint
# enforces).
stamps:
	$(PYTHON) scripts/parse_utils.py --stamps

# Tiny traced end-to-end run + structural validation of the exported
# Chrome trace (README "Observability"): writes logs/<job>/trace.json
# ready for ui.perfetto.dev and prints the phase attribution.
trace:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/trace_demo.py

# Tiny ragged-dispatch A/B end-to-end (README "Ragged dispatch"):
# bucketed vs same-seed ragged arm, asserting one compiled shape,
# zero computed pad rows, pad_rows_eliminated == the bucketed arm's
# pad_rows, and parse_utils --check green on both.
ragged:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/ragged_demo.py

# Replica scale-out A/B (README "Scale-out"): the two shipped
# rnb-scaleout arms under one seeded saturating workload, asserting
# >= 2.5x videos/s at 4 replicas, zero host-hop bytes on every
# device-resident edge, and parse_utils --check green (including the
# planner's predicted-vs-traced occupancy comparison).
multichip:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/multichip_demo.py

# Intra-stage sharding A/B (README "Intra-stage sharding"): the
# weight-gathered shard_map forward at degrees 2/4 asserted BITWISE
# identical to the unsharded stage with one compiled signature per
# arm, the degree-1 launch rejected under an HBM budget degree 2
# satisfies, a same-seed d1-vs-d2 run_benchmark A/B with
# parse_utils --check green on both arms, and the planner's joint plan
# validated against the executed arms.
shard:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/shard_demo.py

# Replica-loss chaos gate (README "Self-healing & chaos"): seeded
# mid-stream kill of 1 of 4 replica lanes on the shipped chaos arm,
# asserting every request terminates exactly once (completed /
# dead-lettered / shed), the dead lane is evicted with its queued work
# redispatched onto healthy siblings, the selector never routes to it
# after circuit-open, and parse_utils --check is green including the
# Health:/Deadline:/Hedge: invariants. Exit 0 = containment holds.
chaos:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/chaos_demo.py

# DCT-domain ingest gate (README "DCT-domain ingest"): same-seed
# yuv420-vs-dct A/B over a generated 112x112 MJPEG dataset, asserting
# logit parity through the fused on-device IDCT, one compiled shape on
# the dct network stage, host->device bytes/frame <= 0.5x the yuv420
# arm, and parse_utils --check green on both arms.
dct:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/dct_demo.py

# Perf-trajectory check: diff MULTICHIP_CONFIGS.json against the
# committed MULTICHIP_BASELINE.json floor with a per-cell tolerance;
# non-zero exit on any regression (ratify a reviewed new floor with
# `python scripts/bench_diff.py --update`).
benchdiff:
	$(PYTHON) scripts/bench_diff.py

# Paged-memory gate (README "Paged memory"): bit-parity of paged
# clip-cache hits and feature-page hits against the uncached forward
# through real reduced stages, then a same-seed Zipf A/B (blob-cache
# arm vs paged + feature-pages arm) asserting zero host memcpy bytes
# on the hit path (gather rows == clip-cache hit rows), feature pages
# serving repeat traffic, zero-transfer emissions counted, the Pages:
# ledger footing (allocs == frees + live) and parse_utils --check
# green on both arms.
pages:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/pages_demo.py

# Lock-discipline gate (README "Concurrency contracts"): the shipped
# chaos arm re-run with the runtime lock-order witness armed
# (lint.lock_witness) — every core lock records its acquisition-order
# edges — asserting zero witnessed violations (no inversion, no
# release-without-hold, no *_locked breach), every observed edge
# present in the static RNB-C lock-order graph, and the Locks: ledger
# footing under parse_utils --check. Exit 0 = the declared
# concurrency contracts hold under fire.
races:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/races_demo.py

native:
	$(MAKE) -C native
