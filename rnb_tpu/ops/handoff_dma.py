"""On-device resharding primitives for the inter-stage handoff.

The device-resident edge contract (:mod:`rnb_tpu.handoff`) re-homes a
committed producer array onto the consumer's device/sharding without
ever materializing host memory. This module owns the *how*:

* :func:`reshard` — the one entry the edge calls: ``jax.device_put``
  onto the target device or ``NamedSharding`` (ICI on real hardware,
  a buffer copy on the virtual-CPU mesh), with a remote-DMA fast path
  engaged when (a) the platform is a real TPU and (b) the move is a
  pure ring shift of a one-axis-sharded array across its mesh — the
  stage-boundary pattern of a stage-partitioned pipeline, where stage
  i's cores hand their shard to stage i+1's neighboring cores.
* :func:`ring_shift` — the underlying collective, in two bodies with
  one contract: a **Pallas** ``make_async_remote_copy`` kernel (each
  core DMAs its whole local shard straight into its neighbor's HBM —
  no gather, no host, no XLA collective scheduling) gated to real TPU
  hardware, and a ``shard_map`` + ``lax.ppermute`` **CPU-testable
  twin** that compiles on the 8-virtual-device harness so tier-1 can
  pin the semantics (``ring_shift(x, k)`` == ``jnp.roll`` by ``k``
  shards along the sharded axis) without touching a TPU.
* :func:`ring_shift_amount` — the pattern detector: given source and
  target shardings, the shift ``k`` that turns one placement into the
  other, or ``None`` when the move is not a ring shift (then
  ``device_put`` is the honest path).
* :func:`ring_all_gather` / :func:`ring_psum_scatter` — the intra-stage
  sharding collectives (rnb_tpu.parallel.shardplan): both are built on
  the SAME one-step ring movement as :func:`ring_shift` — n-1 neighbor
  hops, each hop the Pallas remote-DMA kernel on real TPU or the
  ``lax.ppermute`` twin everywhere else — composed with local
  slice/update (gather) or slice/add (reduce-scatter) arithmetic.
  The all-gather is pure data movement (chunk placement), so its
  result is BITWISE the concatenation of the shards — the property
  the sharded stage forward's logit bit-parity rests on. The
  reduce-scatter adds in ring order, which is a *different* float
  summation order than a tree psum; it is shipped for the TPU
  reduction path and pinned against a jnp reference on exactly
  representable values (tests/test_handoff.py), never used where
  bit-parity against an unsharded forward is claimed.

Kernel lineage: the right-permute example of the public Pallas guide
"Distributed Computing in Pallas for TPUs"
(docs.jax.dev/en/latest/pallas/tpu/distributed.html) — semaphore
pair in scratch, ``memory_space=pl.ANY`` refs, ``DeviceIdType.MESH``
neighbor addressing, and that guide's neighbor barrier ahead of the
first DMA.
"""

from __future__ import annotations

from typing import Optional, Tuple

from rnb_tpu.utils.lazy_jax import jax_numpy as _jax_numpy


def dma_available() -> bool:
    """Is the Pallas remote-DMA path usable? Real TPU backends only —
    interpret mode cannot emulate cross-device semaphores, and the
    CPU twin exists precisely so everything else stays testable. The
    ring collectives move mesh-sharded operands, and meshes are built
    from the default backend's devices, so the default backend is the
    platform they run on; a backend that fails to initialize raises
    here, it does not answer "use the twin"."""
    jax, _ = _jax_numpy()
    return jax.default_backend() == "tpu"


def _mesh_axis(mesh) -> Optional[str]:
    """The mesh's single axis name, or None for multi-axis meshes
    (the ring-shift pattern is defined over one ring)."""
    names = tuple(mesh.axis_names)
    return names[0] if len(names) == 1 else None


def ring_shift_amount(src_sharding, dst_sharding) -> Optional[int]:
    """The ring shift ``k`` (in device positions, 1 <= k < n) that
    maps the source placement onto the target placement, or None when
    the move is not a pure ring shift.

    Pattern: both are ``NamedSharding`` s with equal specs over
    single-axis meshes of the same size, and the target mesh's device
    ring is the source's rotated by ``k`` — then "reshard src→dst"
    moves every shard to the device ``k`` positions along the ring,
    which is exactly one neighbor-DMA per core.
    """
    import numpy as np
    for s in (src_sharding, dst_sharding):
        if s is None or not hasattr(s, "mesh") or not hasattr(s, "spec"):
            return None
    src_mesh, dst_mesh = src_sharding.mesh, dst_sharding.mesh
    axis = _mesh_axis(src_mesh)
    if axis is None or _mesh_axis(dst_mesh) != axis:
        return None
    if tuple(src_sharding.spec) != tuple(dst_sharding.spec):
        return None
    src_devs = list(np.ravel(src_mesh.devices))
    dst_devs = list(np.ravel(dst_mesh.devices))
    n = len(src_devs)
    if n < 2 or len(dst_devs) != n:
        return None
    for k in range(1, n):
        if dst_devs == src_devs[k:] + src_devs[:k]:
            return k
    return None


def _pallas_shift_body(axis_name: str, n: int, shift: int,
                       collective_id: int = 0):
    """The Pallas remote-copy body for one core: DMA the whole local
    shard into the neighbor ``shift`` positions along the ring. Gated
    to real TPU by the caller (``dma_available``).

    ``collective_id`` names the barrier semaphore the kernel's entry
    handshake uses. Back-to-back kernels of one multi-hop collective
    take different ids: with a shared semaphore, a core already in hop
    k+1 would signal a neighbor still waiting in hop k, which could
    then start writing into a third core that has not entered hop k.
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax import lax

    mesh_id = pltpu.DeviceIdType.MESH

    def kernel(input_ref, output_ref, send_sem, recv_sem):
        my_id = lax.axis_index(axis_name)
        receiver = lax.rem(my_id + shift, n)
        sender = lax.rem(my_id - shift + n, n)
        # Neither core may write into the other's output buffer before
        # that core has entered the kernel and owns it: signal the core
        # this one writes to and the core that writes here, then wait
        # for both of their signals.
        barrier = pltpu.get_barrier_semaphore()
        for peer in (sender, receiver):
            pltpu.semaphore_signal(barrier, inc=1, device_id=(peer,),
                                   device_id_type=mesh_id)
        pltpu.semaphore_wait(barrier, 2)
        copy = pltpu.make_async_remote_copy(
            src_ref=input_ref,
            dst_ref=output_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(receiver,),
            device_id_type=mesh_id,
        )
        copy.start()
        copy.wait()

    def body(x_shard):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
        )
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x_shard.shape, x_shard.dtype),
            grid_spec=grid_spec,
            compiler_params=pltpu.CompilerParams(
                collective_id=collective_id),
        )(x_shard)

    return body


def _ppermute_shift_body(axis_name: str, n: int, shift: int):
    """The CPU-testable twin: the identical shard movement spelled as
    a ``lax.ppermute`` collective, compiled by the stock CPU backend
    so tier-1 pins the contract the TPU kernel must honor."""
    from jax import lax

    perm = [(i, (i + shift) % n) for i in range(n)]

    def body(x_shard):
        return lax.ppermute(x_shard, axis_name, perm)

    return body


def _one_step_shift_body(axis_name: str, n: int, use_pallas: bool):
    """The shared ring primitive both collectives below ride:
    ``shift(buf, hop)`` moves every core's buffer to its +1 neighbor —
    the Pallas remote-DMA kernel on real TPU (hop ``k`` of a
    collective on barrier semaphore ``k``), the ppermute twin
    everywhere else."""
    if not use_pallas:
        twin = _ppermute_shift_body(axis_name, n, 1)
        return lambda buf, hop: twin(buf)
    return lambda buf, hop: _pallas_shift_body(
        axis_name, n, 1, collective_id=hop)(buf)


def ring_all_gather_body(axis_name: str, n: int, axis: int = -1,
                         use_pallas: bool = False):
    """Per-core body (usable inside an enclosing ``shard_map``): local
    shard -> the full concatenation along ``axis``, assembled by n-1
    one-step ring hops. Pure movement — each global chunk lands at
    ``chunk_index * chunk`` exactly once, so the result is bitwise the
    unsharded array on every core."""
    from jax import lax
    import jax.numpy as jnp

    shift = _one_step_shift_body(axis_name, n, use_pallas)

    def body(x_shard):
        if n == 1:
            return x_shard
        ax = axis % x_shard.ndim
        chunk = x_shard.shape[ax]
        idx = lax.axis_index(axis_name)
        full = list(x_shard.shape)
        full[ax] = chunk * n
        out = lax.dynamic_update_slice_in_dim(
            jnp.zeros(full, x_shard.dtype), x_shard, idx * chunk,
            axis=ax)
        buf = x_shard
        for s in range(1, n):
            buf = shift(buf, s - 1)
            # after s hops this core holds the shard that started on
            # core (idx - s) mod n — place it at that chunk's offset
            src = lax.rem(idx - s + n, n)
            out = lax.dynamic_update_slice_in_dim(out, buf, src * chunk,
                                                  axis=ax)
        return out

    return body


def ring_psum_scatter_body(axis_name: str, n: int, axis: int = -1,
                           use_pallas: bool = False):
    """Per-core body: full-width local operand -> this core's chunk of
    the cross-core elementwise sum (``lax.psum_scatter`` semantics),
    as n-1 one-step ring hops each followed by one local chunk add.
    Ring order sums left-to-right around the ring — a different float
    association than a tree reduction (see module docstring)."""
    from jax import lax

    shift = _one_step_shift_body(axis_name, n, use_pallas)

    def body(x_local):
        ax = axis % x_local.ndim
        width = x_local.shape[ax]
        if width % n:
            raise ValueError(
                "ring_psum_scatter: axis %d extent %d not divisible "
                "by %d ring members" % (ax, width, n))
        if n == 1:
            return x_local
        chunk = width // n
        idx = lax.axis_index(axis_name)

        def piece(m):
            return lax.dynamic_slice_in_dim(x_local, m * chunk, chunk,
                                            axis=ax)

        # the accumulator seeded on core j ends on core j+n-1 carrying
        # chunk (j-1) mod n the whole way: core j seeds chunk j-1, and
        # at hop s adds chunk (j-1-s) mod n to the partial it received
        acc = piece(lax.rem(idx - 1 + n, n))
        for s in range(1, n):
            acc = shift(acc, s - 1)
            acc = acc + piece(lax.rem(idx - 1 - s + 2 * n, n))
        return acc

    return body


def ring_all_gather(x, mesh, axis_name: Optional[str] = None,
                    axis: int = -1, use_pallas: Optional[bool] = None):
    """Standalone entry: ``x`` sharded along ``axis`` over the mesh
    ring -> the same *value* fully replicated on every core (bitwise
    the unsharded array). ``use_pallas`` defaults to
    :func:`dma_available`."""
    jax, _ = _jax_numpy()
    from jax.sharding import PartitionSpec

    if axis_name is None:
        axis_name = _mesh_axis(mesh)
        if axis_name is None:
            raise ValueError("ring_all_gather needs a single-axis mesh "
                             "or an explicit axis_name")
    n = int(mesh.shape[axis_name])
    ax = axis % x.ndim
    if x.shape[ax] % n:
        raise ValueError(
            "ring_all_gather: axis %d extent %d not divisible by %d "
            "ring members" % (ax, x.shape[ax], n))
    if use_pallas is None:
        use_pallas = dma_available()
    in_spec = [None] * x.ndim
    in_spec[ax] = axis_name
    fn = jax.shard_map(
        ring_all_gather_body(axis_name, n, axis=ax,
                             use_pallas=use_pallas),
        mesh=mesh, in_specs=PartitionSpec(*in_spec),
        out_specs=PartitionSpec(), check_vma=False)
    return jax.jit(fn)(x)


def ring_psum_scatter(x, mesh, axis_name: Optional[str] = None,
                      axis: int = -1,
                      use_pallas: Optional[bool] = None):
    """Standalone entry: ``x`` carries one full-width operand per core
    stacked on axis 0 (global shape ``(n, ...)``); returns the
    cross-core elementwise sum scattered along ``axis`` of the operand
    — core i holds chunk i, i.e. ``lax.psum_scatter`` over the ring.
    The returned global array is the concatenation of those chunks
    (== the full sum)."""
    jax, _ = _jax_numpy()
    from jax.sharding import PartitionSpec

    if axis_name is None:
        axis_name = _mesh_axis(mesh)
        if axis_name is None:
            raise ValueError("ring_psum_scatter needs a single-axis "
                             "mesh or an explicit axis_name")
    n = int(mesh.shape[axis_name])
    if x.shape[0] != n:
        raise ValueError(
            "ring_psum_scatter: leading axis %d must equal the %d ring "
            "members (one operand per core)" % (x.shape[0], n))
    op_axis = (axis % (x.ndim - 1)) + 1  # operand axis in the stacked x
    if x.shape[op_axis] % n:
        raise ValueError(
            "ring_psum_scatter: axis %d extent %d not divisible by %d "
            "ring members" % (op_axis - 1, x.shape[op_axis], n))
    if use_pallas is None:
        use_pallas = dma_available()
    inner = ring_psum_scatter_body(axis_name, n, axis=axis,
                                   use_pallas=use_pallas)

    def body(x_stack):  # local (1, ...) slab -> this core's sum chunk
        return inner(x_stack[0])

    out_spec = [None] * (x.ndim - 1)
    out_spec[op_axis - 1] = axis_name
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=PartitionSpec(axis_name),
                       out_specs=PartitionSpec(*out_spec),
                       check_vma=False)
    return jax.jit(fn)(x)


def ring_shift(x, mesh, axis_name: Optional[str] = None, shift: int = 1,
               use_pallas: Optional[bool] = None):
    """Move every device's shard of ``x`` to the device ``shift``
    positions along the mesh ring; value-wise this is ``jnp.roll`` by
    ``shift`` shards along the sharded axis. ``use_pallas`` defaults
    to :func:`dma_available` — the remote-DMA kernel on real TPU, the
    ppermute twin everywhere else."""
    jax, _ = _jax_numpy()
    from jax.sharding import PartitionSpec

    if axis_name is None:
        axis_name = _mesh_axis(mesh)
        if axis_name is None:
            raise ValueError("ring_shift needs a single-axis mesh or an "
                             "explicit axis_name")
    n = int(mesh.shape[axis_name])
    shift = int(shift) % n
    if shift == 0:
        return x
    if use_pallas is None:
        use_pallas = dma_available()
    body = (_pallas_shift_body(axis_name, n, shift) if use_pallas
            else _ppermute_shift_body(axis_name, n, shift))
    spec = PartitionSpec(axis_name)
    fn = jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
    return jax.jit(fn)(x)


def reshard(data, target):
    """Re-home ``data`` onto ``target`` (a device or a sharding)
    without host materialization. On real TPU, a move matching the
    ring-shift pattern routes through the remote-DMA kernel (one
    neighbor copy per core, overlappable with compute); everything
    else — including the whole virtual-CPU harness — is one
    ``jax.device_put``, which the runtime executes device-to-device
    for committed ``jax.Array`` inputs."""
    jax, _ = _jax_numpy()
    if hasattr(target, "device_set") and dma_available():
        shift = ring_shift_amount(getattr(data, "sharding", None),
                                  target)
        if shift is not None:
            src_mesh = data.sharding.mesh
            shifted = ring_shift(data, src_mesh, shift=shift,
                                 use_pallas=True)
            # every shard now sits on its target device (src device
            # i+k holds global shard i, which is exactly where the
            # rotated target mesh wants it); wrap the in-place buffers
            # under the target sharding — no further movement. NB the
            # shifted Array's *value* reads rotated under the source
            # sharding; under the target sharding the same buffers
            # spell the original value, which is what a reshard means.
            shards = [s.data for s in shifted.addressable_shards]
            return jax.make_array_from_single_device_arrays(
                data.shape, target, shards)
    return jax.device_put(data, target)
