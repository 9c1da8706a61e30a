"""Execute EVERY shipped pipeline config end-to-end on the virtual mesh.

Runs each ``configs/*.json`` through ``run_benchmark`` on the
8-virtual-device CPU backend (bulk mode, a handful of videos from the
committed-layout y4m dataset) and records one result row per config in
``MULTICHIP_CONFIGS.json``. tests/test_shipped_configs.py then asserts
every shipped config has an ``ok`` row — so a config can no longer sit
in the tree without ever having executed (the reference shipped
config/r2p1d-segment.json broken for years; its sanity_check only
parsed).

    python scripts/run_shipped_configs.py [--videos 8] [--only glob]
"""

import argparse
import glob
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT_PATH = os.path.join(REPO, "MULTICHIP_CONFIGS.json")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--videos", type=int, default=8)
    parser.add_argument("--queue-size", type=int, default=64)
    parser.add_argument("--only", default=None,
                        help="basename glob to restrict the sweep")
    args = parser.parse_args()

    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")  # a cpu-virtual sweep
    os.environ.setdefault("RNB_TPU_DATA_ROOT",
                          os.path.join(REPO, "data", "bench_y4m"))

    from rnb_tpu.benchmark import run_benchmark

    paths = sorted(glob.glob(os.path.join(REPO, "configs", "*.json")))
    if args.only:
        import fnmatch
        paths = [p for p in paths
                 if fnmatch.fnmatch(os.path.basename(p), args.only)]
    rows = []
    for path in paths:
        name = os.path.relpath(path, REPO)
        t0 = time.time()
        row = {"config": name, "n_devices": 8, "platform": "cpu",
               "num_videos": args.videos, "mean_interval_ms": 0}
        try:
            with tempfile.TemporaryDirectory() as tmp:
                res = run_benchmark(path, mean_interval_ms=0,
                                    num_videos=args.videos,
                                    queue_size=args.queue_size,
                                    log_base=tmp, print_progress=False)
            row["termination_flag"] = int(res.termination_flag)
            row["wall_s"] = round(time.time() - t0, 3)
            row["videos_per_sec"] = round(res.throughput_vps, 3)
            row["ok"] = int(res.termination_flag) == 0
        except Exception as e:  # noqa: BLE001 - recorded, not hidden
            row["ok"] = False
            row["error"] = "%s: %s" % (type(e).__name__, e)
            row["wall_s"] = round(time.time() - t0, 3)
        rows.append(row)
        print("%-45s ok=%-5s wall=%6.1fs %s"
              % (name, row["ok"], row["wall_s"],
                 row.get("error", "")), flush=True)

    if args.only is not None and os.path.exists(OUT_PATH):
        # merge a partial sweep into the existing artifact by config
        # name (e.g. one newly added config without re-running all);
        # rows for configs no longer on disk are dropped so a stale
        # failure can't poison all_ok forever
        with open(OUT_PATH) as f:
            result = json.load(f)
        shipped = {os.path.relpath(p, REPO)
                   for p in glob.glob(os.path.join(REPO, "configs",
                                                   "*.json"))}
        by_name = {r["config"]: r for r in result.get("configs", [])
                   if r.get("config") in shipped}
        by_name.update({r["config"]: r for r in rows})
        result["configs"] = [by_name[k] for k in sorted(by_name)]
    else:
        result = {"n_devices": 8, "platform": "cpu-virtual",
                  "configs": rows}
    result["generated"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime())
    result["all_ok"] = all(r["ok"] for r in result["configs"])
    with open(OUT_PATH, "w") as f:
        json.dump(result, f, indent=1)
    print("wrote %s (all_ok=%s)" % (OUT_PATH, result["all_ok"]))
    return 0 if result["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
