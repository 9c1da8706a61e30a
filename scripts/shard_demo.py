#!/usr/bin/env python
"""``make shard``: the intra-stage sharding A/B, asserted end-to-end.

Drives a reduced-geometry R(2+1)D stage through the whole shard
contract on the 8-virtual-device CPU backend:

* **bit parity** — the weight-gathered sharded forward (degrees 2 and
  4) produces logits BITWISE identical to the unsharded forward on the
  same pool, with exactly ONE compiled signature per arm;
* **the feasibility gate** — with an HBM budget pinned between the
  degree-1 and degree-2 per-device projections, the degree-1 launch is
  REJECTED (the honest "does not fit" failure) while degree 2 runs;
* **end-to-end arms** — a same-seed d1-vs-d2 ``run_benchmark`` A/B
  (both arms whole-pool apply: only structurally identical programs
  are bitwise-comparable), each passing ``parse_utils --check``
  including the Shard: footing and trace-nesting invariants. Both
  arms carry the scale-out demo's deterministic fault-plan latency
  injection emulating a device-bound stage: on this 1-host-core
  cpu-virtual harness the ring's k full-width compute replicas
  SERIALIZE (real TPU members run them in parallel — that wall-clock
  invariance is physically impossible to demonstrate here), so
  without the injection the A/B ratio measures a harness artifact,
  not the collective tax the model predicts;
* **the planner closes its loop** — the d2 arm's measured-cost joint
  plan keeps the budget-bound degree-2 ring, the d1 arm's plan sees no
  reason to shard.

Exit 0 = everything holds. A couple of minutes on a cold XLA cache;
no dataset, no native decoder required (synthetic video ids).
"""

import json
import os
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_"
                                 "device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

LS = [1, 1, 1, 1]
NUM_CLASSES = 8
NUM_VIDEOS = 12


def _arm_config(shard):
    """One reduced benchmark arm; `shard` is the runner's shard key."""
    return {
        "video_path_iterator":
            "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
        "trace": {"enabled": True, "sample_hz": 20},
        "placement": {"mode": "plan"},
        "ragged": {"enabled": True, "pool_rows": 1},
        # emulated device-bound network stage (the rnb-scaleout
        # methodology): the injection dominates the reduced net's
        # host compute, so the A/B ratio measures the collective tax
        # — the one thing the cpu twin CAN measure — instead of the
        # serialized-full-width-compute harness artifact
        "fault_plan": {"faults": [
            {"kind": "latency", "step": 1, "probability": 1.0,
             "ms": 4000}]},
        "pipeline": [
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
             "queue_groups": [{"devices": [0], "out_queues": [0]}],
             "num_shared_tensors": 30, "max_clips": 1,
             "consecutive_frames": 2,
             "num_clips_population": [1], "weights": [1],
             "fuse": 1, "num_warmups": 1},
            {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
             "queue_groups": [{"devices": shard["ring"],
                               "in_queue": 0}],
             "start_index": 1, "end_index": 5,
             "num_classes": NUM_CLASSES, "layer_sizes": LS,
             "max_rows": 1, "consecutive_frames": 2, "num_warmups": 1,
             # whole-pool apply on BOTH arms: the shard contract
             "ragged_chunk_rows": 0,
             "shard": shard["key"]},
        ],
    }


def main() -> int:
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from rnb_tpu.benchmark import run_benchmark
    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    from rnb_tpu.parallel.shardplan import projected_device_mb
    from rnb_tpu.stage import PaddedBatch
    from rnb_tpu.telemetry import TimeCard
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import parse_utils

    failures = []
    dev = jax.devices()[0]
    net = dict(start_index=1, end_index=5, num_classes=NUM_CLASSES,
               layer_sizes=tuple(LS), max_rows=3,
               consecutive_frames=2, num_warmups=1,
               pixel_path="yuv420")

    # -- 1. bit parity + one compiled signature per arm ---------------
    from rnb_tpu.ops.yuv import packed_frame_bytes
    pool = np.random.RandomState(17).randint(
        0, 256, (3, 2, packed_frame_bytes(112, 112)), np.uint8)
    base = R2P1DRunner(dev, **net)
    (want,), _, _ = base((PaddedBatch(jnp.asarray(pool), 3),), None,
                         TimeCard(0))
    want = np.asarray(want.data)
    for degree in (2, 4):
        arm = R2P1DRunner(dev, shard_degree=degree, **net)
        arm.bind_shard_step(1)
        (got,), _, _ = arm((PaddedBatch(jnp.asarray(pool), 3),), None,
                           TimeCard(1))
        if not np.array_equal(np.asarray(got.data), want):
            failures.append("degree-%d logits are not bitwise the "
                            "unsharded forward's" % degree)
        arm.compiles.freeze()
        arm((PaddedBatch(jnp.asarray(pool), 3),), None, TimeCard(2))
        snap = arm.compiles.snapshot()
        if snap["warmup"] != 1 or snap["steady_new"] != 0:
            failures.append(
                "degree-%d arm compiled %d warmup / %d steady "
                "signature(s); the contract is exactly one"
                % (degree, snap["warmup"], snap["steady_new"]))
        print("degree %d: bitwise parity %s, signatures %d+%d"
              % (degree, "OK" if np.array_equal(
                     np.asarray(got.data), want) else "BROKEN",
                 snap["warmup"], snap["steady_new"]))

    # -- 2. the feasibility gate: budget between the d1/d2 projections
    stats = R2P1DRunner(
        dev, shard_degree=2,
        **dict(net, num_warmups=0, ragged=True,
               ragged_pool_rows=3)).shard_stats
    rep, sh = stats["replicated_bytes"], stats["sharded_bytes"]
    pool_b = stats["pool_bytes"]
    d1_mb = projected_device_mb(rep, sh, pool_b, 1)
    d2_mb = projected_device_mb(rep, sh, pool_b, 2)
    budget = round((d1_mb + d2_mb) / 2.0, 3)
    print("projection: %.3f MiB at d1, %.3f at d2 — budget %.3f"
          % (d1_mb, d2_mb, budget))
    try:
        R2P1DRunner(dev, shard_degree=1, shard_hbm_budget_mb=budget,
                    **dict(net, num_warmups=0, ragged=True,
                           ragged_pool_rows=3))
        failures.append("degree-1 launch fit a %.3f MiB budget its "
                        "projection (%.3f MiB) exceeds" % (budget,
                                                           d1_mb))
    except ValueError as e:
        if "shard launch rejected" not in str(e):
            raise
        print("degree-1 launch rejected under the budget, as claimed")

    # -- 3. the benchmark A/B: d1 vs d2, same seed --------------------
    arms = {
        "d1": _arm_config({"ring": [1], "key": {"degree": 1}}),
        "d2": _arm_config({"ring": [1, 2],
                           "key": {"degree": 2,
                                   "hbm_budget_mb": budget}}),
    }
    results = {}
    with tempfile.TemporaryDirectory(prefix="rnb-shard-") as tmp:
        for arm, cfg in arms.items():
            path = os.path.join(tmp, "rnb-shard-%s.json" % arm)
            with open(path, "w") as f:
                json.dump(cfg, f)
            res = run_benchmark(path, mean_interval_ms=0,
                                num_videos=NUM_VIDEOS, queue_size=64,
                                log_base=tmp, print_progress=False,
                                seed=17)
            results[arm] = res
            if res.termination_flag != 0:
                failures.append("%s arm terminated with flag %d"
                                % (arm, res.termination_flag))
                continue
            for problem in parse_utils.check_job(res.log_dir):
                failures.append("%s --check: %s" % (arm, problem))
            print("%s: %.3f videos/s — shard steps=%d max_degree=%d "
                  "gathers=%d collective_us=%d"
                  % (arm, res.throughput_vps, res.shard_steps,
                     res.shard_max_degree, res.shard_gathers,
                     res.shard_collective_us))

        d1, d2 = results["d1"], results["d2"]
        if d1.shard_max_degree != 1 or d1.shard_gathers != 0:
            failures.append("d1 arm accounting: degree %d / %d "
                            "gather(s); wanted 1 / 0"
                            % (d1.shard_max_degree, d1.shard_gathers))
        if d2.shard_max_degree != 2 or d2.shard_gathers <= 0:
            failures.append("d2 arm accounting: degree %d / %d "
                            "gather(s); wanted 2 / > 0"
                            % (d2.shard_max_degree, d2.shard_gathers))

        # -- 4. the planner closes its loop ---------------------------
        p1 = d1.placement.get("plan", {}).get("step1", {})
        p2 = d2.placement.get("plan", {}).get("step1", {})
        if p2.get("shard_degree") != 2:
            failures.append(
                "d2 arm's joint plan names degree %r for step 1; its "
                "budget-bound floor is 2" % (p2.get("shard_degree"),))
        if p1.get("shard_degree") != 1:
            failures.append(
                "d1 arm's joint plan names degree %r for step 1; "
                "nothing binds it above 1" % (p1.get("shard_degree"),))

    for failure in failures:
        print("FAIL: %s" % failure)
    if failures:
        return 1
    print("OK — sharded forward bitwise-identical at degrees 2 and 4 "
          "(one signature per arm), degree-1 launch rejected under "
          "the %.1f MiB budget, both A/B arms --check green, planner "
          "consistent with the measured arms" % budget)
    return 0


if __name__ == "__main__":
    sys.exit(main())
