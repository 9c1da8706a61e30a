"""Poisson throughput-latency frontier: p50/p99 vs offered load.

The reference's methodology decomposed latency per mean-interval
setting (reference scripts/latency_summary.py:29-76, README.md
example at mi=90). This sweep drives the fused flagship configs at a
range of Poisson mean intervals — one fresh bench.py process per cell
(same isolation rule as bench_matrix.py) — and renders the frontier:
offered load (1000/mi requests/s) vs measured throughput and p50/p99.

    python scripts/latency_frontier.py          # TPU
    RNB_BENCH_PLATFORM=cpu RNB_FRONTIER_VIDEOS=40 ...  # smoke

Artifacts: FRONTIER.json (full bench rows) and frontier.png
(p50/p99 curves per config) under RNB_FRONTIER_OUT (default repo
root). This parent only orchestrates and never imports JAX.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIGS = ("configs/rnb-fused-yuv.json",
           "configs/rnb-fused-yuv-mid.json",
           "configs/rnb-fused-yuv-big.json")
#: mean intervals (ms): 3 ms ~ 333 req/s offered (near the observed
#: Poisson ceiling), 9 ms ~ 111 req/s (comfortably feasible)
INTERVALS = (3, 4, 6, 9)


# one fresh bench.py process per cell — same runner as the matrix, so
# env handling / JSON parsing / bench_rc diagnostics stay in one place
from bench_matrix import run_cell  # noqa: E402


def render_plot(rows, out_path):
    import matplotlib
    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, (ax50, ax99) = plt.subplots(1, 2, figsize=(11, 4.5),
                                     sharex=True)
    for config in CONFIGS:
        pts = [(1000.0 / r["mean_interval_ms"], r.get("p50_ms"),
                r.get("p99_ms"))
               for r in rows
               if r.get("config") == config and r.get("p50_ms")
               is not None]
        if not pts:
            continue
        pts.sort()
        label = os.path.basename(config).replace(".json", "")
        ax50.plot([p[0] for p in pts], [p[1] for p in pts],
                  marker="o", label=label)
        ax99.plot([p[0] for p in pts], [p[2] for p in pts],
                  marker="o", label=label)
    for ax, title in ((ax50, "p50"), (ax99, "p99")):
        ax.set_xlabel("offered load (requests/s)")
        ax.set_ylabel("latency (ms)")
        ax.set_title("%s end-to-end latency vs offered load" % title)
        ax.legend()
        ax.grid(True, alpha=0.3)
    fig.tight_layout()
    fig.savefig(out_path, dpi=120)


def main() -> int:
    videos = int(os.environ.get("RNB_FRONTIER_VIDEOS", "3000"))
    out_dir = os.environ.get("RNB_FRONTIER_OUT", REPO)
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for config in CONFIGS:
        for mi in INTERVALS:
            print("frontier: %s mi=%d videos=%d ..."
                  % (config, mi, videos), file=sys.stderr)
            t0 = time.time()
            row = run_cell(config, mi, videos)
            row.setdefault("config", config)
            row.setdefault("mean_interval_ms", mi)
            row["cell_wall_s"] = round(time.time() - t0, 1)
            rows.append(row)
            print("frontier:   -> %s" % json.dumps(row),
                  file=sys.stderr)
    artifact = {"rows": rows, "videos": videos,
                "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
                "isolation": "one fresh bench.py process per cell"}
    with open(os.path.join(out_dir, "FRONTIER.json"), "w") as f:
        json.dump(artifact, f, indent=2)
    try:
        render_plot(rows, os.path.join(out_dir, "frontier.png"))
    except Exception as e:  # plot is a bonus; rows are the artifact
        print("frontier: plot failed: %s" % e, file=sys.stderr)
    print("frontier: wrote FRONTIER.json (+ frontier.png)",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
