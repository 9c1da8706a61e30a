"""Intra-stage tensor parallelism: shard one R(2+1)D stage over a ring.

PR 9's scale-out replicates whole stages, so a stage can never exceed
one device's HBM or FLOPs. This module is the other axis: partition
the stage's *channel* dimensions over a ``shard_degree``-sized mesh
axis via ``shard_map``, the Gemma-on-TPU serving protocol (PAPERS.md)
applied to the R(2+1)D backbone — shard the filter axes, keep ONE
executable, measure the collective tax honestly.

What is sharded (and why the result is bit-identical):

* every **temporal** conv kernel's output-channel axis and the
  classification head's column axis live SHARDED at rest — each mesh
  member holds ``1/degree`` of those bytes, which is where degree k
  buys its per-device HBM headroom — and are ring-all-gathered to
  full width right before their op (``nn.map_variables`` swaps the
  gathered kernel in). The op then runs at FULL width, so the
  activation path is op-for-op the unsharded program: a gather is
  pure data movement, and the gathered kernel is bitwise the
  unsharded one. This weight-gathered form is deliberate — slicing
  the *compute* per member (``features // k`` output channels each)
  is mathematically exact but NOT bitwise under XLA's bf16
  excess-precision fusion: changing the op graph changes which
  intermediate roundings are elided, a measured 1-ulp drift on the
  CPU twins. Only a structurally identical compute graph survives.
* the **spatial** convs, BatchNorms, shortcuts and pooling stay
  replicated: the factorization's ``mid`` widths (83/230/921...) are
  not divisible by 2/4. By the (2+1)D parameter-parity construction
  the temporal half carries ~half the stage's parameters, so degree
  k drops per-device *sharded* bytes by 1/k while the replicated
  half stays — the HBM sizing rule README "Intra-stage sharding"
  documents. Compute is NOT divided — sharding here is parameter
  residency (FSDP-style serving), and the planner's cost model says
  so (collective tax measured, compute invariant).

The kernel reassembly is
:func:`rnb_tpu.ops.handoff_dma.ring_all_gather_body` — n-1 one-step
ring hops riding the same scaffolding as the handoff's remote-DMA
``ring_shift``, pure data movement, so parity survives. A head stage
(``end == NUM_LAYERS``) computes full-width logits, keeps only its
own column block (a slice — pure movement), and leaves its logits
*channel-sharded* out of the forward jit; the one merge gather is a
SEPARATE jitted collective the stage times on the host
(``exec{i}.collective``) — the collective tax is a measured number in
the logs, never an assumption buried in a fused program.

Config surface: step key ``shard: {degree, axis, hbm_budget_mb}``
(rnb_tpu.config validates; ``_expand_shard`` moves the lane's device
list into ``shard_devices`` extras). ``hbm_budget_mb`` arms the
launch-time feasibility gate: a projected per-device footprint
(replicated params + sharded params / degree + the ragged pool) over
budget REJECTS the launch — the honest "this stage does not fit at
this degree" failure the headline shard config demonstrates at degree
1.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


def is_sharded_param(path: Sequence[str]) -> bool:
    """Is the variables-tree leaf at ``path`` (key names, root first)
    partitioned on its output-channel axis? Exactly the temporal conv
    kernels and the classification head — the axes
    network.SpatioTemporalConv/R2Plus1DClassifier declare as
    ``features // shards`` wide."""
    names = tuple(str(p) for p in path)
    if len(names) >= 2 and names[-2] == "temporal" \
            and names[-1] == "kernel":
        return True
    if len(names) >= 2 and names[-2] == "linear" \
            and names[-1] in ("kernel", "bias"):
        return True
    return False


def _tree_paths(tree, prefix=()):
    """[(path tuple, leaf)] over a nested dict tree (flax variables)."""
    out = []
    if isinstance(tree, dict):
        for key in sorted(tree):
            out.extend(_tree_paths(tree[key], prefix + (str(key),)))
    else:
        out.append((prefix, tree))
    return out


def shard_param_specs(variables, axis_name: str = "tp"):
    """A ``PartitionSpec`` tree matching ``variables``: sharded leaves
    (see :func:`is_sharded_param`) partition their LAST axis over
    ``axis_name``; everything else is replicated."""
    import jax
    from jax.sharding import PartitionSpec as P

    def spec_for(path, leaf):
        names = tuple(str(getattr(p, "key", p)) for p in path)
        if is_sharded_param(names):
            ndim = int(np.ndim(leaf))
            return P(*([None] * (ndim - 1) + [axis_name]))
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, variables)


def _leaf_nbytes(leaf) -> int:
    nbytes = getattr(leaf, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    # abstract leaves (jax.eval_shape's ShapeDtypeStruct) size from
    # shape x dtype — the projection never needs materialized weights
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        size = 1
        for extent in shape:
            size *= int(extent)
        return size * int(np.dtype(dtype).itemsize)
    return int(np.asarray(leaf).nbytes)


def split_param_bytes(variables) -> Tuple[int, int]:
    """(replicated_bytes, sharded_bytes) of one stage's variables —
    the two halves of the per-device HBM projection: replicated bytes
    land whole on every shard member, sharded bytes divide by the
    degree. Works on concrete arrays and on abstract
    ``jax.eval_shape`` trees alike, so feasibility is computable
    before any weight is materialized."""
    replicated = sharded = 0
    for path, leaf in _tree_paths(variables):
        nbytes = _leaf_nbytes(leaf)
        if is_sharded_param(path):
            sharded += nbytes
        else:
            replicated += nbytes
    return replicated, sharded


def projected_device_mb(replicated_bytes: int, sharded_bytes: int,
                        pool_bytes: int, degree: int) -> float:
    """Per-device HBM projection (MiB) at ``degree``: the feasibility
    number the launch gate and the planner both use — one formula, so
    they can never disagree."""
    degree = max(1, int(degree))
    return (float(replicated_bytes) + float(sharded_bytes) / degree
            + float(pool_bytes)) / (1 << 20)


def min_feasible_degree(replicated_bytes: int, sharded_bytes: int,
                        pool_bytes: int, budget_mb: float,
                        candidates: Sequence[int] = (1, 2, 4, 8)
                        ) -> Optional[int]:
    """The smallest candidate degree whose projection fits the budget,
    or None when even the largest candidate does not fit (the
    replicated half alone can exceed a small budget — sharding cannot
    save a stage whose *unshardable* bytes are too big)."""
    for degree in sorted(int(d) for d in candidates):
        if projected_device_mb(replicated_bytes, sharded_bytes,
                               pool_bytes, degree) <= float(budget_mb):
            return degree
    return None


def shardable_widths(start: int, end: int, num_classes: int) -> List[int]:
    """The declared output-channel widths sharding slices for a
    [start..end] stage — every temporal conv's feature count plus the
    head when the range ends the network. The shard degree must divide
    ALL of them (validated at construction and statically by rnb-lint
    RNB-G010)."""
    from rnb_tpu.models.r2p1d.network import LAYER_FEATURES, NUM_LAYERS
    widths: List[int] = []
    for layer in range(int(start), int(end) + 1):
        widths.append(64 if layer == 1 else LAYER_FEATURES[layer])
    if int(end) == NUM_LAYERS:
        widths.append(int(num_classes))
    return widths


def validate_degree(degree: int, start: int, end: int,
                    num_classes: int) -> None:
    """Raise ValueError unless ``degree`` divides every width
    :func:`shardable_widths` declares for the range."""
    degree = int(degree)
    if degree < 1:
        raise ValueError("shard degree must be >= 1, got %d" % degree)
    for width in shardable_widths(start, end, num_classes):
        if width % degree:
            raise ValueError(
                "shard degree %d does not divide the declared channel "
                "width %d of layers [%d..%d] (num_classes=%d)"
                % (degree, width, start, end, num_classes))


def build_shard_mesh(devices: Sequence, degree: int,
                     axis_name: str = "tp"):
    """One lane's shard sub-mesh: a single-axis ring of exactly
    ``degree`` resolved devices."""
    from rnb_tpu.parallel.mesh import build_mesh
    devices = list(devices)
    if len(devices) != int(degree):
        raise ValueError(
            "shard mesh wants exactly degree=%d devices, got %d"
            % (degree, len(devices)))
    return build_mesh(devices, axes={axis_name: int(degree)})


def shard_variables(variables, mesh, axis_name: str = "tp"):
    """Place a host variables tree onto the shard mesh: sharded leaves
    split their last axis over the ring, the rest replicate."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def place(path, leaf):
        names = tuple(str(getattr(p, "key", p)) for p in path)
        if is_sharded_param(names):
            spec = P(*([None] * (np.ndim(leaf) - 1) + [axis_name]))
        else:
            spec = P()
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, variables)


def make_sharded_apply(start: int, end: int, num_classes: int,
                       layer_sizes: tuple, mesh,
                       factored_shortcut: bool = False,
                       pixel_path: str = "rgb", ragged: bool = False,
                       axis_name: str = "tp"):
    """The sharded twin of model._shared_apply: ONE jitted
    ``shard_map`` body over the ring — the ingest (identical HLO to
    the unsharded applier's) on every member's replicated copy of the
    input, then the sharded network. The ingest sits INSIDE the body:
    the yuv420 one is plain jnp and fuses with the first convolution
    there as it does unsharded; the dct one holds a Pallas kernel
    where this compiles for a TPU, and the partitioner refuses a
    Mosaic kernel outside a shard_map ("cannot be automatically
    partitioned"). A head range returns
    logits still CHANNEL-SHARDED on the class axis (merge them with
    :func:`make_merge` — the host-timed collective); a mid-pipeline
    range's output is already full-width (the last temporal gather
    reassembled it) and comes back replicated."""
    import jax
    from jax.sharding import PartitionSpec as P
    from rnb_tpu.models.r2p1d.network import (NUM_LAYERS,
                                              R2Plus1DClassifier)

    degree = int(mesh.shape[axis_name])
    validate_degree(degree, start, end, num_classes)
    model = R2Plus1DClassifier(start=start, end=end,
                               num_classes=num_classes,
                               layer_sizes=tuple(layer_sizes),
                               factored_shortcut=bool(factored_shortcut),
                               shards=degree, shard_axis=axis_name)
    head = (int(end) == NUM_LAYERS)

    if pixel_path == "yuv420":
        from rnb_tpu.models.r2p1d.model import FRAME_HW
        if ragged:
            from rnb_tpu.ops.ragged import ragged_normalize_yuv420

            def ingest(x, rows_valid):
                return ragged_normalize_yuv420(x, rows_valid, FRAME_HW,
                                               FRAME_HW)
        else:
            from rnb_tpu.ops.yuv import normalize_yuv420

            def ingest(x, rows_valid):
                del rows_valid
                return normalize_yuv420(x, FRAME_HW, FRAME_HW)
    elif pixel_path == "dct":
        from rnb_tpu.models.r2p1d.model import FRAME_HW
        if ragged:
            from rnb_tpu.ops.dct import ragged_normalize_dct

            def ingest(x, rows_valid):
                return ragged_normalize_dct(x, rows_valid, FRAME_HW,
                                            FRAME_HW)
        else:
            from rnb_tpu.ops.dct import normalize_dct

            def ingest(x, rows_valid):
                del rows_valid
                return normalize_dct(x, FRAME_HW, FRAME_HW)
    else:
        def ingest(x, rows_valid):
            del rows_valid
            return x

    if ragged:
        def network(variables, x, rows_valid):
            with jax.named_scope("ingest"):
                x = ingest(x, rows_valid)
            return model.apply(variables, x, train=False)
    else:
        def network(variables, x):
            with jax.named_scope("ingest"):
                x = ingest(x, None)
            return model.apply(variables, x, train=False)

    def build(variables_specs):
        return jax.jit(jax.shard_map(
            network, mesh=mesh,
            in_specs=(variables_specs,) + (P(),) * (2 if ragged else 1),
            out_specs=(P(None, axis_name) if head else P()),
            check_vma=False))

    def applier_for(variables):
        return build(shard_param_specs(variables, axis_name))

    return applier_for


def make_merge(mesh, axis_name: str = "tp"):
    """The head stage's one merge collective: channel-sharded logits ->
    the full-width value, replicated, via the ring all-gather. Jitted
    separately from the forward ON PURPOSE: the stage host-times this
    call as ``exec{i}.collective``, so the collective tax is a span in
    the trace."""
    import jax
    from jax.sharding import PartitionSpec as P
    from rnb_tpu.ops.handoff_dma import ring_all_gather_body

    degree = int(mesh.shape[axis_name])
    fn = jax.shard_map(
        ring_all_gather_body(axis_name, degree, axis=-1),
        mesh=mesh, in_specs=P(None, axis_name), out_specs=P(),
        check_vma=False)
    return jax.jit(fn)
