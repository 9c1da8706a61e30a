"""One run of one cell: ``python3 benchmarks/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

Set-up (counted in ``setup_s``, from this process's start to the start
of the measured window): the family's build (``benchmarks/families/``;
for R(2+1)D ``make -C native``, a no-op once built), JAX under the
configuration's ``runtime_env``, the cell's request files (made once
per checkout), weights from ``--seed``, the run's pipeline
configuration, the program's own warm-up of the cell's buckets inside
``run_benchmark``, and the mix's ramp. Then the window of ``--seconds``:
requests are released by :mod:`benchmarks.traffic`, the program serves
them, and everything reported is computed from stamps and counters
that fall inside the window. After it: the drain, the peak memory, and
the family's check of the serving applier's logits against its float32
reference.

What is particular to a family of models (its inputs, weights,
reference, operation count) is in the family file the configuration
names; nothing here knows a model.

The last line of standard output is the result object. Without an
accelerator (or with fewer chips than the cell asks for) the run exits
non-zero and prints no result; ``--platform cpu`` is the builder's dry
run of the control flow, asked for by name, and its result says "cpu"
and carries no rate against a peak.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def say(msg: str) -> None:
    print("[bench] %.1fs %s" % (time.time() - T_PROCESS, msg),
          file=sys.stderr, flush=True)


class Watcher(threading.Thread):
    """Marks the window on the host: process CPU seconds at its start
    and end and, in a traced run, the profiler over its first
    ``trace_s`` seconds. Waits on the schedule's first release."""

    def __init__(self, schedule, trace_dir, trace_s: float):
        super().__init__(name="bench-watcher", daemon=True)
        self.schedule = schedule
        self.trace_dir = trace_dir
        self.trace_s = float(trace_s)
        self.cpu = [None, None]
        self.trace_span = None
        self.error = None

    def run(self) -> None:
        from benchmarks.traffic import sleep_until
        try:
            self.schedule.started.wait()
            start, end = self.schedule.window
            sleep_until(start)
            self.cpu[0] = time.process_time()
            if self.trace_dir is not None:
                import jax
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=options)
                t0 = time.time()
                sleep_until(min(t0 + self.trace_s, end))
                t1 = time.time()
                jax.profiler.stop_trace()
                self.trace_span = (t0, t1)
            sleep_until(end)
            self.cpu[1] = time.process_time()
        except Exception as e:  # surfaced by the main thread
            self.error = e


def device_memory_peak(device) -> int:
    """Peak bytes of one device's memory that were taken: live buffers
    (``peak_bytes_in_use``: weights, inputs, outputs) plus the region
    the runtime reserves for the loaded programs' temporaries
    (``peak_bytes_reserved``). On a TPU the two are disjoint — the free
    block is the limit less both — and the reserved region is as large
    as the largest program's temporaries (PERF.md, PR 23: 6.17 GB
    reserved beside 0.40 GB in use after one 48-row dispatch, where the
    compiler's memory analysis says 5.76 GiB of temporaries). A backend
    that reports no statistics (the CPU) gives 0."""
    stats = device.memory_stats()
    if not stats:
        return 0
    return int(stats.get("peak_bytes_in_use", 0)) \
        + int(stats.get("peak_bytes_reserved", 0))


def derive_pipeline_config(config: dict, ckpt_path: str, out_dir: str):
    """The configuration file's ``pipeline_config`` as run: the weights'
    path on the steps that load them is the one run-time addition."""
    pipeline = json.loads(json.dumps(config["pipeline_config"]))
    for idx in config.get("weights_steps", []):
        pipeline["pipeline"][idx]["ckpt_path"] = ckpt_path
    path = os.path.join(out_dir, "pipeline.json")
    with open(path, "w") as f:
        json.dump(pipeline, f, indent=1)
    return path, pipeline


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--platform", choices=("tpu", "cpu"),
                        default="tpu", help="'cpu' is the builder's dry "
                        "run; it has to be asked for by name")
    parser.add_argument("--out", default=None, help="run directory "
                        "(default logs/benchmarks/<workload> in the "
                        "checkout)")
    parser.add_argument("--manifest", default=None,
                        help="another BENCHMARK.json (tests)")
    args = parser.parse_args(argv)

    from benchmarks import manifest as manifest_mod
    manifest = manifest_mod.load(args.manifest or manifest_mod.MANIFEST)
    bench_root = os.path.dirname(os.path.abspath(
        args.manifest or manifest_mod.MANIFEST))
    cell = manifest_mod.cell(manifest, args.workload)
    chips = int(cell["chips"])
    config = manifest_mod.load_config_file(manifest, cell["config"],
                                           bench_root)
    family = manifest_mod.load_family(
        config["family"], manifest_mod.subdir(bench_root, "families"))

    # children that never touch JAX come first
    family.build(REPO)
    if args.platform == "cpu" and chips > 1:
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=%d" % chips)
    # the deployment's settings of the accelerator runtime, which reads
    # them once, when JAX starts it (PERF.md, PR 23: the host buffer
    # it pins at start-up was the unsteady part of set-up)
    for key, value in config.get("runtime_env", {}).items():
        os.environ[key] = str(value)

    import jax
    import numpy as np
    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    found = devices[0].platform
    if found != args.platform:
        print("benchmarks/run.py: found platform %r, not %r; a cell is "
              "measured on the chip (--platform cpu is the dry run)"
              % (found, args.platform), file=sys.stderr)
        return 3
    if len(devices) < chips:
        print("benchmarks/run.py: cell %s needs %d chips, JAX found %d"
              % (args.workload, chips, len(devices)), file=sys.stderr)
        return 3
    device_kind = devices[0].device_kind
    from benchmarks import peaks
    peak = None
    if found == "tpu":
        peak = peaks.peak_for(device_kind)["bf16_flops_per_s"]

    from benchmarks import facts as facts_mod, stamps, traffic
    from rnb_tpu.benchmark import enable_compilation_cache, run_benchmark
    cache_dir = enable_compilation_cache()
    say("platform=%s kind=%s devices=%d compile_cache=%s"
        % (found, device_kind, len(devices), cache_dir))

    out_dir = os.path.abspath(args.out or os.path.join(
        REPO, "logs", "benchmarks", args.workload))
    os.makedirs(out_dir, exist_ok=True)
    inputs = family.prepare_inputs(
        config, os.path.join(REPO, "data", "benchmarks"))
    shorts, longs = inputs["short_files"], inputs["long_files"]
    os.environ["RNB_TPU_DATA_ROOT"] = inputs["data_root"]
    say("dataset %s: %d short, %d long names"
        % (inputs["data_root"], len(shorts), len(longs)))

    ckpt_path, weights = family.make_weights(
        config, args.seed, os.path.join(REPO, "checkpoints", "benchmarks",
                                        cell["config"]))
    config_path, pipeline = derive_pipeline_config(config, ckpt_path,
                                                   out_dir)
    say("weights from seed -> %s" % ckpt_path)

    mix = traffic.load_mix(cell["traffic"],
                           manifest_mod.subdir(bench_root, "traffic"))
    schedule = traffic.build_schedule(
        mix, args.seed, args.seconds, chips, shorts, longs,
        inputs["rows_of"],
        capacity_hint=config.get("capacity_videos_per_chip_s"))
    traffic.ACTIVE = schedule
    trace_dir = os.path.join(out_dir, "xplane") if args.trace else None
    if trace_dir is not None:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    watcher = Watcher(schedule, trace_dir, mix.get("trace_s", 10.0))
    watcher.start()
    say("schedule: %d requests (%s), ramp %.1fs, window %.1fs"
        % (len(schedule), schedule.process, schedule.ramp_s,
           schedule.seconds))

    result = run_benchmark(config_path=config_path, mean_interval_ms=0,
                           num_videos=len(schedule), log_base=out_dir,
                           print_progress=False, seed=args.seed,
                           job_id="run")
    traffic.ACTIVE = None
    watcher.join(timeout=60.0)
    if watcher.is_alive() or watcher.error is not None:
        raise RuntimeError("the window's watcher did not finish: %r"
                           % (watcher.error,))
    setup_s = schedule.window[0] - T_PROCESS
    used = devices[:chips]
    memory_peak = max(device_memory_peak(d) for d in used)
    say("served: total %.1fs, completed %d failed %d shed %d flag %d"
        % (result.total_time_s, result.num_completed, result.num_failed,
           result.num_shed, result.termination_flag))

    finish, instance = stamps.match_requests(
        stamps.read_tables(result.log_dir), schedule.sent)
    trace_facts = None
    if trace_dir is not None:
        from benchmarks import xplane
        t0, t1 = watcher.trace_span
        trace_facts = xplane.TraceFacts(xplane.find_xplane(trace_dir),
                                        window_s=t1 - t0)
        trace_facts.host_span = (t0, t1)
    facts = facts_mod.RunFacts(
        schedule=schedule, finish=finish, instance=instance, result=result,
        chips=chips, device_kind=device_kind, platform=found,
        config=config, family=family,
        flops_per_row=family.flops_per_row(config),
        peak_flops_per_s=peak,
        window_cpu_s=watcher.cpu[1] - watcher.cpu[0],
        memory_peak_bytes=memory_peak,
        wire_bytes_per_row=family.wire_bytes_per_row(config, pipeline),
        trace=trace_facts)

    # -- correct ---------------------------------------------------------
    problems = []
    if result.termination_flag != 0:
        problems.append("termination flag %d" % result.termination_flag)
    steady_new = sum(sig.get("steady_new", 0)
                     for sig in result.compile_signatures.values())
    if steady_new:
        problems.append("%d compilation(s) inside the run" % steady_new)
    if np.isnan(schedule.sent).any():
        problems.append("%d scheduled request(s) were never sent"
                        % int(np.isnan(schedule.sent).sum()))
    backlog = None
    if schedule.process == "backlog":
        backlog = traffic.backlog_room(
            len(schedule), int((finish < schedule.window[1]).sum()),
            float(mix["arrivals"].get("min_left_share", 0.05)),
            facts.videos_per_s())
        say("backlog: %s" % backlog)
        emptied = traffic.backlog_problem(backlog)
        if emptied:
            problems.append(emptied)
    logits = family.check_outputs(config, pipeline, weights, ckpt_path,
                                  args.seed, inputs, devices, result)
    if not logits["ok"]:
        problems.append("logits against the float32 reference: %s" % logits)
    say("logits vs reference: %s" % logits)
    for problem in problems:
        say("NOT CORRECT: " + problem)

    # -- metrics ---------------------------------------------------------
    metrics = {}
    if args.trace:
        for entry in manifest_mod.metrics_for(manifest, "per_layer",
                                              args.workload):
            module = manifest_mod.load_layer_metric(
                entry["name"],
                manifest_mod.subdir(bench_root, "layer_metrics"))
            value = module.read(facts)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
    else:
        readers = {"videos_per_s": facts.videos_per_s,
                   "setup_s": lambda: setup_s}
        for entry in manifest_mod.metrics_for(manifest, "end_to_end",
                                              args.workload):
            value = readers[entry["name"]]()
            if value is not None:
                metrics[entry["name"]] = {"value": float(value),
                                          "unit": entry["unit"]}
    device = {"platform": found, "kind": device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    line = {"correct": not problems, "attempted": facts.attempted(),
            "failed": facts.failed(), "metrics": metrics, "device": device}
    if trace_facts is not None:
        device["busy_s"] = trace_facts.mean_busy_s
        device["window_s"] = trace_facts.window_s
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace_facts.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in trace_facts.gaps]}
    halves = {}
    if schedule.process != "backlog":
        # a growing queue shows as a second half slower than the first
        mid = schedule.window[0] + schedule.seconds / 2
        done = ~np.isnan(finish) & facts.due_in_window
        for name, mask in (("first", facts.due_epoch < mid),
                           ("second", facts.due_epoch >= mid)):
            values = (finish - facts.due_epoch)[done & mask] * 1e3
            if len(values):
                halves["latency_p50_ms_%s_half" % name] = \
                    stamps.percentile(values, 50.0)
    # finishes in 5 s bins from the window's start to the end of the
    # drain: a stalled or slowed stretch of a run shows as a thin bin
    done_at = finish[~np.isnan(finish)] - schedule.window[0]
    bins = np.bincount(np.clip(done_at // 5.0, 0, None).astype(int))
    line["notes"] = {"setup_s": setup_s, "problems": problems, **halves,
                     "finished_by_5s": [int(n) for n in bins],
                     "requests": len(schedule),
                     "run_total_s": result.total_time_s,
                     "warmup_s": result.warmup_s,
                     "wall_s": time.time() - T_PROCESS}
    if backlog is not None:
        line["notes"]["backlog"] = backlog
    if args.trace:
        from benchmarks import hostspans
        reduced = hostspans.notes_of(facts)
        if reduced is not None:
            line["notes"]["hostspans"] = reduced
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
