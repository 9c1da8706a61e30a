"""What the benchmark (``benchmarks/``) reads from the program, held in
one place: the ``BenchmarkResult`` attributes it names, the signatures
it calls, the meta lines and stamp columns its readers parse, and the
root keys the configuration no longer takes. One tiny CPU run, shared
by the cases; one case a fact."""

import inspect
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import parse_utils  # noqa: E402

from benchmarks import stamps  # noqa: E402
from rnb_tpu import benchmark  # noqa: E402
from rnb_tpu.config import ConfigError, parse_config  # noqa: E402

CONFIG = {
    "video_path_iterator":
        "rnb_tpu.models.r2p1d.model.R2P1DVideoPathIterator",
    "handoff": {"mode": "device"},
    "pipeline": [
        {"model": "rnb_tpu.models.r2p1d.model.R2P1DFusingLoader",
         "queue_groups": [{"devices": [0], "out_queues": [0]}],
         "num_shared_tensors": 10, "fuse": 1, "max_clips": 2,
         "num_clips_population": [2], "weights": [1],
         "consecutive_frames": 2, "num_warmups": 1},
        {"model": "rnb_tpu.models.r2p1d.model.R2P1DRunner",
         "queue_groups": [{"devices": [1], "in_queue": 0}],
         "start_index": 1, "end_index": 5, "num_classes": 8,
         "layer_sizes": [1, 1, 1, 1], "max_rows": 2,
         "consecutive_frames": 2, "num_warmups": 1},
    ],
}

#: the attributes ``benchmarks/`` reads off the result, with their types
RESULT_ATTRIBUTES = [
    ("log_dir", str), ("termination_flag", int), ("total_time_s", float),
    ("num_completed", int), ("num_failed", int), ("num_shed", int),
    ("pad_emissions", int), ("tokens_valid", int), ("experts_held", int),
    ("experts_max_per_expert", int), ("compile_signatures", dict),
    ("warmup_s", dict),
]

#: the keyword arguments ``benchmarks/run.py`` passes
RUN_ARGUMENTS = ["config_path", "mean_interval_ms", "num_videos",
                 "log_base", "print_progress", "seed", "job_id"]

#: the meta lines the benchmark's readers parse, with a key each leaves
#: in ``parse_utils.parse_meta``'s dict
META_LINES = [("Padding:", "pad_emissions"),
              ("Compiles:", "compile_signatures"),
              ("Handoff:", "handoff_edges"), ("Faults:", "num_failed")]

REMOVED_ROOT_KEYS = ["metrics", "devobs", "critpath", "whatif", "operator",
                     "netedge"]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("contract")
    path = os.path.join(str(tmp), "contract.json")
    with open(path, "w") as f:
        json.dump(CONFIG, f)
    return benchmark.run_benchmark(
        config_path=path, mean_interval_ms=0, num_videos=6,
        log_base=os.path.join(str(tmp), "logs"), print_progress=False,
        seed=3, job_id="contract")


@pytest.mark.parametrize("name,kind", RESULT_ATTRIBUTES)
def test_result_attribute(run, name, kind):
    assert isinstance(getattr(run, name), kind)


def test_the_run_completed(run):
    assert run.termination_flag == 0
    assert run.num_completed == 6 and run.num_failed == run.num_shed == 0
    assert os.path.basename(run.log_dir) == "contract"
    assert all(set(sig) >= {"warmup", "steady_new"}
               for sig in run.compile_signatures.values())


@pytest.mark.parametrize("argument", RUN_ARGUMENTS)
def test_run_benchmark_takes(argument):
    assert argument in inspect.signature(benchmark.run_benchmark).parameters


def test_enable_compilation_cache_takes_nothing_and_names_a_directory():
    assert not inspect.signature(
        benchmark.enable_compilation_cache).parameters
    assert isinstance(benchmark.enable_compilation_cache(), str)


@pytest.mark.parametrize("prefix,key", META_LINES)
def test_meta_line_is_written_and_parses(run, prefix, key):
    with open(os.path.join(run.log_dir, "log-meta.txt")) as f:
        assert any(line.startswith(prefix) for line in f)
    assert key in parse_utils.parse_meta(run.log_dir)


def test_stamp_tables_carry_the_events_the_readers_take(run):
    tables = stamps.read_tables(run.log_dir)
    rows = [row for table in tables.values() for row in table]
    assert len(rows) == 6
    for row in rows:
        assert {"enqueue_filename", "runner0_start", "inference0_start",
                "inference0_finish", "runner1_start", "inference1_start",
                "inference1_finish"} <= set(row)
        assert stamps.finish_key(row) == "inference1_finish"


def test_the_log_check_passes(run):
    assert parse_utils.check_job(run.log_dir) == []


@pytest.mark.parametrize("key", REMOVED_ROOT_KEYS)
def test_removed_root_key_is_rejected_by_name(key):
    with pytest.raises(ConfigError, match=repr(key)):
        parse_config(dict(CONFIG, **{key: {"enabled": True}}))
