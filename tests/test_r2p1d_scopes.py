"""The named scopes of the R(2+1)D programs and the table the final
stage writes of them (``rnb_tpu/hloscopes.py``): on a tiny network
compiled for the CPU, what a trace's reader will find on the chip.
"""

import contextlib
import json
import logging
import os

import numpy as np
import pytest

SIZES = (1, 1, 1, 1)
CLASSES = 8
FRAMES = 2
STAGES = ("stem", "stage2", "stage3", "stage4", "stage5")
SCOPES = ("ingest",) + STAGES + ("head",)


def scopes_in(op_name):
    return [part for part in op_name.split("/") if part in SCOPES]


_TABLES = {}


def table_of(start, end, pixel_path, rows):
    """The scope table of the serving applier's program for one layer
    range and row count, compiled here once a module."""
    import jax
    import jax.numpy as jnp

    from rnb_tpu import hloscopes
    from rnb_tpu.models.r2p1d import checkpoint as ckpt
    from rnb_tpu.models.r2p1d import model as stage
    key = (start, end, pixel_path, rows)
    if key not in _TABLES:
        apply = stage._shared_apply(start, end, CLASSES, SIZES,
                                    pixel_path=pixel_path)
        variables = ckpt.load_or_init(start, end, CLASSES, SIZES, None)
        shape = stage.R2P1DRunner.input_shape_for(
            start_index=start, max_rows=rows, consecutive_frames=FRAMES,
            pixel_path=pixel_path)[0]
        dtype = getattr(jnp, stage.R2P1DRunner.input_dtype_for(
            start_index=start, pixel_path=pixel_path))
        text = apply.lower(variables, jax.ShapeDtypeStruct(
            shape, dtype)).compile().as_text()
        _TABLES[key] = hloscopes.scopes_of_hlo(text)
    return _TABLES[key]


def convolutions(table):
    return {key: op_name for key, op_name in table.items()
            if op_name.endswith("conv_general_dilated")}


@pytest.mark.parametrize("rows", [2, 4])
def test_every_convolution_lies_under_exactly_one_stage(rows):
    convs = convolutions(table_of(1, 5, "yuv420", rows))
    # stem 2; stages 2-5 four a block, 3-5 a projection shortcut too
    assert len(convs) >= 2 + 4 * 4 + 3
    for key, op_name in convs.items():
        found = scopes_in(op_name)
        assert len(found) == 1 and found[0] in STAGES, (key, op_name)
    assert {scopes_in(op_name)[0] for op_name in convs.values()} \
        == set(STAGES)
    # the scope says which stage, where the module path says conv2 for
    # the second stage and for a block's second convolution alike
    assert all("/stage%d/conv%d/" % (n, n) in op_name
               for op_name in convs.values()
               for n in (2, 3, 4, 5) if "/stage%d/" % n in op_name)


@pytest.mark.parametrize("start,end,pixel_path,held", [
    (1, 5, "yuv420", SCOPES),
    (1, 5, "rgb", STAGES + ("head",)),
    (1, 2, "yuv420", ("ingest", "stem", "stage2")),
    (3, 5, "rgb", ("stage3", "stage4", "stage5", "head")),
    (2, 4, "rgb", ("stage2", "stage3", "stage4")),
])
def test_a_range_opens_the_scopes_of_what_it_holds(start, end, pixel_path,
                                                   held):
    """``ingest`` is what stands in front of layer 1 inside the stage's
    program (an rgb stage is handed normalised clips), ``head`` the pool
    and the linear layer of a range that reaches layer 5."""
    table = table_of(start, end, pixel_path, 2)
    found = {scope for op_name in table.values()
             for scope in scopes_in(op_name)[:1]}
    assert found == set(held)


def test_two_buckets_keys_do_not_collide():
    """An instruction's name recurs in each bucket's program; its result
    shape carries the rows, so the key is one bucket's. What the two
    tables share is shaped like a weight, and lies in the same scope or
    is a speck (a batch-norm scale of 64 channels)."""
    small, large = (table_of(1, 5, "yuv420", rows) for rows in (2, 4))
    shared = set(small) & set(large)
    for rows, table in ((2, small), (4, large)):
        with_rows = {key for key in table
                     if key.split(" ")[1].split("[")[1].startswith(
                         "%d," % rows)}
        assert set(convolutions(table)) <= with_rows
        assert not with_rows & shared
    for key in shared:
        dims = [int(d) for d in
                key.split(" ")[1].split("[")[1][:-1].split(",") if d]
        assert scopes_in(small[key])[:1] == scopes_in(large[key])[:1] \
            or int(np.prod(dims)) <= 64, key


def test_scopes_change_neither_logits_nor_parameter_paths(monkeypatch):
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict

    from rnb_tpu import hloscopes
    from rnb_tpu.models.r2p1d.network import R2Plus1DClassifier
    model = R2Plus1DClassifier(num_classes=CLASSES, layer_sizes=SIZES)
    x = jax.random.normal(jax.random.key(1), (2, FRAMES, 112, 112, 3),
                          jnp.bfloat16)

    def run():
        variables = model.init(jax.random.key(0), x, train=False)
        program = jax.jit(
            lambda v, x: model.apply(v, x, train=False)).lower(
            variables, x).compile()
        return (variables, np.asarray(program(variables, x)),
                hloscopes.scopes_of_hlo(program.as_text()))

    variables, logits, table = run()
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name in SCOPES else real(name))
    bare_variables, bare_logits, bare_table = run()
    assert any(scopes_in(op_name) for op_name in table.values())
    assert not any(scopes_in(op_name) for op_name in bare_table.values())
    assert np.array_equal(logits, bare_logits)
    paths = sorted(flatten_dict(variables))
    assert paths == sorted(flatten_dict(bare_variables))
    # the tree is the modules': no scope's name is a key of it
    assert not {part for path in paths for part in path} & set(SCOPES)
    assert ("params", "net", "conv2", "block0", "conv1", "spatial",
            "kernel") in paths
    for path, leaf in flatten_dict(variables).items():
        assert np.array_equal(leaf, flatten_dict(bare_variables)[path])


@pytest.fixture(scope="module")
def runner_and_compilations():
    """A final stage at two row buckets, and the backend compilations
    its construction logged by program name."""
    import jax

    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    seen = []

    class Keep(logging.Handler):
        def emit(self, record):
            seen.append(record.getMessage())

    handler = Keep(level=logging.DEBUG)
    loggers = [logging.getLogger(name) for name in (
        "jax._src.dispatch", "jax._src.interpreters.pxla",
        "jax._src.compiler")]
    for logger in loggers:
        logger.addHandler(handler)
    try:
        with jax.log_compiles(True):
            # a class count no other test compiles: nothing is found
            # in the appliers' cache
            stage = R2P1DRunner(
                jax.devices()[0], start_index=1, end_index=5,
                num_classes=CLASSES + 3, layer_sizes=SIZES, max_rows=4,
                row_buckets=[2, 4], consecutive_frames=FRAMES,
                num_warmups=1, pixel_path="yuv420")
    finally:
        for logger in loggers:
            logger.removeHandler(handler)
    return stage, [m for m in seen
                   if m.startswith("Finished XLA compilation of jit(apply)")]


def test_the_table_costs_no_second_compilation(runner_and_compilations):
    stage, compilations = runner_and_compilations
    assert len(stage._warmed_programs) == 2
    assert len(compilations) == 2, compilations


def test_the_final_stage_writes_its_table_whole(runner_and_compilations,
                                                tmp_path):
    from rnb_tpu import hloscopes
    stage, _ = runner_and_compilations
    table = stage.scope_table()
    assert {scope for op_name in table.values()
            for scope in scopes_in(op_name)[:1]} == set(SCOPES)
    # both buckets' programs are in it
    for rows in (2, 4):
        assert any(key.endswith(" f32[%d,%d]" % (rows, CLASSES + 3))
                   for key in table)
    stage.bind_log_dir(str(tmp_path))
    stage.finalize()
    stage.finalize()  # a replica writes the same table to the same name
    assert os.listdir(str(tmp_path)) == [hloscopes.TABLE_FILE]
    with open(os.path.join(str(tmp_path), hloscopes.TABLE_FILE)) as f:
        text = f.read()
    assert text == json.dumps(table) and json.loads(text) == table


@pytest.mark.parametrize("kwargs", [
    dict(end_index=4), dict(num_warmups=0)],
    ids=["a_range_short_of_the_head", "no_warm_up"])
def test_no_table_without_a_warmed_final_program(kwargs, tmp_path):
    """A stage that hands activations on is not the trace's last step,
    and a stage told not to warm up is not made to compile."""
    import jax

    from rnb_tpu.models.r2p1d.model import R2P1DRunner
    stage = R2P1DRunner(jax.devices()[0], **dict(dict(
        start_index=1, end_index=5, num_classes=CLASSES,
        layer_sizes=SIZES, max_rows=2, consecutive_frames=FRAMES,
        num_warmups=1, pixel_path="yuv420"), **kwargs))
    stage.bind_log_dir(str(tmp_path))
    stage.finalize()
    assert stage.scope_table() == {} and os.listdir(str(tmp_path)) == []


def test_write_table_leaves_nothing_behind_when_it_fails(tmp_path):
    from rnb_tpu import hloscopes
    with pytest.raises(TypeError):
        hloscopes.write_table(str(tmp_path), {"key": object()})
    assert os.listdir(str(tmp_path)) == []


def test_a_cached_executable_is_not_handed_to_a_program_of_other_scopes(
        tmp_path):
    """The persistent cache's key leaves metadata out unless told
    otherwise: a program would then read another's ``op_name``s out of
    its own executable. ``enable_compilation_cache`` tells it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from rnb_tpu import hloscopes
    from rnb_tpu.benchmark import enable_compilation_cache
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key")
    before = {name: getattr(jax.config, name) for name in names}

    def scopes_of_a_program_under(scope):
        def apply(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x).sum()
        text = jax.jit(apply).lower(jnp.ones((64, 64))).compile().as_text()
        return {part for op_name in hloscopes.scopes_of_hlo(text).values()
                for part in op_name.split("/")} & {"stage2", "stage3"}

    try:
        enable_compilation_cache()
        compilation_cache.reset_cache()
        # a described-v5e fixture of an earlier file on this worker may
        # have left the cache off (they turn it off and never on again)
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        assert scopes_of_a_program_under("stage2") == {"stage2"}
        assert os.listdir(str(tmp_path))  # it was cached
        assert scopes_of_a_program_under("stage3") == {"stage3"}
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
