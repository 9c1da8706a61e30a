"""The size floor guards a cell's *definition* on the CPU. A
configuration's file records the projection its row cap was set by
(``size_record``: the largest bucket's stage program compiled for a
described v5e — nothing runs — as temporaries + arguments (weights and
one input batch) + the batches that may wait on the device, one per
ring slot); that record has to clear 4 GiB, a quarter of the chip.
Today's compile, which the configuration's family file makes
(``project_memory``), has to fit the chip and may not have grown by
more than 5% over the record. It may have shrunk: a program that has
come to need less memory is a saving, not a fault of the cell, so a
compile under the floor is a warning that names the remedy and no
failure (PR 25's change would have freed 3.7 GiB of lane padding and
failed the test this one replaces). On the chip r2p1d34-32f-yuv reads
6.54 GiB where this projects 6.24, nemotron3-nano-l14-ep2 10.60 where
this projects 10.48 (ledger, PR 30). One file, the topology inside a
fixture, as the on-chip-measurement guide requires."""

import os
import warnings

import pytest

from benchmarks import manifest as mm

MANIFEST = mm.load()
ONE_CHIP = sorted({w["config"] for w in MANIFEST["workloads"]
                   if w["chips"] == 1})
GIB = 2 ** 30
FLOOR = 4 * GIB


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", ONE_CHIP)
def test_size_record_clears_the_floor_and_the_compile_keeps_to_it(
        name, one_chip):
    config = mm.load_config_file(MANIFEST, name)
    record = config["size_record"]
    assert record["projected_gib"] * GIB >= FLOOR, record
    assert abs(sum(record["bytes"].values()) / GIB
               - record["projected_gib"]) < 0.01, record
    family = mm.load_family(config["family"])
    today = family.project_memory(config, one_chip)
    projected = sum(today[part] for part in record["bytes"])
    told = "%s: %.2f GiB at %d rows, %.2f on record (PR %d)" % (
        name, projected / GIB, today["rows"], record["projected_gib"],
        record["read_in_pr"])
    assert today["rows"] == record["rows"], told
    assert projected <= 14 * GIB, told  # it fits a 16 GB chip
    assert projected <= 1.05 * record["projected_gib"] * GIB, told
    if projected < FLOOR:
        warnings.warn(told + ": the program has come to need less than "
                      "the 4 GiB floor of a new cell; a benchmark PR "
                      "raises max_rows, the row buckets or the ring and "
                      "records the new projection")


def test_a_compile_under_the_floor_warns_and_does_not_fail(monkeypatch,
                                                          recwarn):
    """The rule above on numbers alone: a family whose program has
    shrunk to 2.8 GiB (ISSUE 31's projection of the padded clip gone)
    passes with a warning; one that has grown by 6% fails."""
    config = {"family": "shrunk", "size_record": {
        "rows": 48, "projected_gib": 6.24, "read_in_pr": 31,
        "bytes": {"temporaries": int(5.76 * GIB),
                  "arguments": int(0.27 * GIB),
                  "waiting": int(0.21 * GIB)}}}
    sizes = {"rows": 48, "temporaries": int(2.31 * GIB),
             "arguments": int(0.27 * GIB), "waiting": int(0.22 * GIB)}

    class Family:
        @staticmethod
        def project_memory(config, sharding):
            return sizes
    monkeypatch.setattr(mm, "load_config_file", lambda *a, **k: config)
    monkeypatch.setattr(mm, "load_family", lambda *a, **k: Family)
    check = test_size_record_clears_the_floor_and_the_compile_keeps_to_it
    check("shrunk", None)
    assert any("benchmark PR raises max_rows" in str(w.message)
               for w in recwarn.list)
    sizes["temporaries"] = int(6.15 * GIB)
    with pytest.raises(AssertionError):
        check("shrunk", None)
