"""The size floor guards itself on the CPU: the largest bucket of each
one-chip configuration, compiled for a described v5e (nothing runs),
projects at least 4 GiB of device memory: the program's temporaries and
arguments (weights and one input batch) plus the batches that may wait
on the device, one per ring slot. On the chip the yuv configuration
read 6.45 GiB where this projects 6.46, the dct one 4.49 where this
projects 4.2 (my chip runs, PR 23). One file, the topology inside a
fixture, as the on-chip-measurement guide requires."""

import os

import pytest

from benchmarks import manifest as mm

MANIFEST = mm.load()
ONE_CHIP = sorted({w["config"] for w in MANIFEST["workloads"]
                   if w["chips"] == 1})
FLOOR = 4 * 2 ** 30


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
def test_largest_bucket_clears_the_floor(name, one_chip):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rnb_tpu.models.r2p1d import model as stage
    from rnb_tpu.models.r2p1d.network import R2Plus1DClassifier
    config = mm.load_config_file(MANIFEST, name)
    step = config["pipeline_config"]["pipeline"][-1]
    sizes = tuple(step["layer_sizes"])
    rows, frames = max(step["row_buckets"]), step["consecutive_frames"]
    apply = stage._shared_apply(1, 5, 400, sizes,
                                pixel_path=step["pixel_path"])
    shapes = jax.eval_shape(
        lambda k: R2Plus1DClassifier(layer_sizes=sizes).init(
            k, np.zeros((1, 2, 14, 14, 3), np.float32), train=False),
        jax.random.key(0))
    variables = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), shapes)
    shape = stage.R2P1DRunner.input_shape_for(
        start_index=1, max_rows=rows, consecutive_frames=frames,
        pixel_path=step["pixel_path"])[0]
    dtype = getattr(jnp, stage.R2P1DRunner.input_dtype_for(
        start_index=1, pixel_path=step["pixel_path"]))
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    memory = apply.lower(variables, x).compile().memory_analysis()
    waiting = config["pipeline_config"]["pipeline"][0]["num_shared_tensors"] \
        * int(np.prod(shape)) * np.dtype(dtype).itemsize
    projected = memory.temp_size_in_bytes + memory.argument_size_in_bytes \
        + waiting
    assert projected >= FLOOR, "%s: %.2f GiB at %d rows" % (
        name, projected / 2 ** 30, rows)
    assert projected <= 14 * 2 ** 30  # and it fits a 16 GB chip
