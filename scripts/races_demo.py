#!/usr/bin/env python
"""``make races``: the lock-discipline contract, witnessed at runtime.

Takes the SHIPPED chaos arm (configs/rnb-scaleout-r4-chaos.json — the
nastiest concurrency workload in the tree: 4 replica lanes, hedged
re-dispatch, a seeded mid-stream lane wedge-then-kill, eviction and
queue redispatch all racing one another) and re-runs it with the
runtime lock-order witness armed (``lint: {lock_witness: true}``), so
every core lock (cache, pager, staging, health, hedge) is a
recording WitnessLock. Then asserts the discipline the static
RNB-C analyzer declares:

* **zero witnessed violations** — no lock-order inversion, no
  release-without-hold, no ``*_locked`` method reached without its
  lock — across the whole chaotic run;
* **observed ⊆ declared**: every runtime acquisition-order edge is in
  the static RNB-C004 lock-order graph (an edge the analyzer cannot
  see would be an undeclared cross-class lock dependency — exactly
  the kind that becomes a deadlock two PRs later);
* the ``Locks:`` ledger foots — tracked/acquires/edges/violations
  match the ``Lock edges:`` JSON detail line, checked by
  ``parse_utils --check`` alongside every other invariant (the chaos
  run must also still pass its containment checks);
* the witness saw real traffic: > 0 locks tracked, > 0 acquisitions,
  and the BenchmarkResult mirror fields agree with the log.

Exit 0 = the declared concurrency contracts hold under fire. ~30 s
with a warm XLA compile cache; no dataset, no native decoder.
"""

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_"
                                 "device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CONFIG = "configs/rnb-scaleout-r4-chaos.json"
NUM_VIDEOS = 12


def main() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")

    from rnb_tpu.benchmark import run_benchmark
    from rnb_tpu.analysis.concurrency import static_lock_order_edges
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import parse_utils

    with open(os.path.join(REPO, CONFIG)) as f:
        config = json.load(f)
    config["lint"] = {"lock_witness": True}

    failures = []
    with tempfile.TemporaryDirectory(prefix="rnb-races-") as tmp:
        armed = os.path.join(tmp, "rnb-scaleout-r4-chaos-witness.json")
        with open(armed, "w") as f:
            json.dump(config, f)
        res = run_benchmark(armed, mean_interval_ms=0,
                            num_videos=NUM_VIDEOS, queue_size=64,
                            log_base=tmp, print_progress=False,
                            seed=17)
        if res.termination_flag != 0:
            failures.append("witnessed chaos run terminated with "
                            "flag %d" % res.termination_flag)

        # parse_utils --check: the full invariant battery, now
        # including _check_locks (ledger footing + observed-edge
        # subset against the static graph)
        problems, parse_failed = parse_utils.check_job_detail(
            res.log_dir)
        for problem in problems:
            failures.append("--check (%s): %s"
                            % ("parse" if parse_failed else "invariant",
                               problem))

        print("races arm: %d witnessed lock(s), %d acquisition(s), "
              "%d order edge(s), %d violation(s); %d completed / "
              "%d dead-lettered / %d shed of %d requests"
              % (res.locks_tracked, res.locks_acquires,
                 res.locks_edges, res.locks_violations,
                 res.num_completed, res.num_failed, res.num_shed,
                 NUM_VIDEOS))

        # the headline: zero violations under the nastiest workload
        if res.locks_violations != 0:
            failures.append("lock witness recorded %d violation(s)"
                            % res.locks_violations)
        # and the witness genuinely watched the run
        if res.locks_tracked < 1 or res.locks_acquires < 1:
            failures.append(
                "witness saw no traffic (tracked=%d acquires=%d) — "
                "the config arm did not enable it"
                % (res.locks_tracked, res.locks_acquires))

        # observed ⊆ declared, re-asserted here against the meta line
        # (parse_utils already checks it; this keeps the gate honest
        # if the parser's import guard ever silently disables it)
        meta = parse_utils.parse_meta(res.log_dir)
        detail = meta.get("lock_edge_detail")
        if detail is None:
            failures.append("log-meta has no Lock edges: line")
        else:
            observed = {tuple(e) for e in detail.get("edges", [])}
            declared = static_lock_order_edges()
            undeclared = observed - declared
            if undeclared:
                failures.append(
                    "runtime lock-order edge(s) missing from the "
                    "static RNB-C graph: %s"
                    % sorted(undeclared))
            if detail.get("violations"):
                failures.append("Lock edges: detail carries "
                                "violations: %s"
                                % detail["violations"][:5])
            # the ledger line and result fields mirror one another
            if meta.get("locks_violations") != res.locks_violations \
                    or meta.get("locks_edges") != res.locks_edges:
                failures.append(
                    "Locks: line (%r edges, %r violations) disagrees "
                    "with the result (%d edges, %d violations)"
                    % (meta.get("locks_edges"),
                       meta.get("locks_violations"),
                       res.locks_edges, res.locks_violations))

        # the witness must not have broken containment
        terminated = res.num_completed + res.num_failed + res.num_shed
        if terminated != NUM_VIDEOS:
            failures.append(
                "%d of %d requests terminated under the witness — "
                "exactly-once must survive instrumentation"
                % (terminated, NUM_VIDEOS))

    for failure in failures:
        print("FAIL: %s" % failure, file=sys.stderr)
    if failures:
        return 1
    print("make races: OK — zero lock-discipline violations; every "
          "observed edge is declared in the static graph")
    return 0


if __name__ == "__main__":
    sys.exit(main())
