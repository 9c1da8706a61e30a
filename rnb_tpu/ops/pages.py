"""Gather-from-pages: the device-side consumption seam of the pager.

The page allocator (rnb_tpu.pager) keeps cached rows resident in one
device slab — ``(num_pages * page_rows,) + row_shape`` — and a cache
hit is a list of page references, not bytes. This module provides the
two primitives that make those references usable without any host
memcpy:

* :func:`gather_rows` — overlay slab rows onto a row pool **on
  device**: ``out[i] = slab[src_rows[i]]`` where ``src_rows[i] >= 0``,
  ``out[i] = pool[i]`` otherwise. This runs once per emission, after
  the pool's transfer and before the normalize dispatch, so hit rows
  never exist as host bytes at all (a row memcpy in the blob arm, a
  dispatch in the paged arm). Following the house kernel
  pattern (rnb_tpu/ops/ragged.py):

  - **TPU**: a Pallas kernel over a ``PrefetchScalarGridSpec`` — the
    per-row source table is scalar-prefetched into SMEM, the slab
    BlockSpec's index_map picks each program's source page block from
    it (clamped for sentinel rows), and ``pl.when`` selects
    slab-vs-passthrough so sentinel programs never read the slab;
  - **any other platform**: a masked ``jnp`` formulation
    (:func:`gather_rows_reference`) with the identical contract;
  - **interpret mode**: the Pallas body runs on CPU via
    ``interpret=True`` and tests assert it matches the reference
    bit-for-bit.

* :func:`write_rows_page` — publish rows into the slab: one donated
  jit (``donate_argnums=0``) of gather + ``dynamic_update_slice``, so
  the slab updates in place (no copy of the resident pages) and keeps
  ONE jit signature per (slab, source-pool) shape pair — the source
  index vector is always ``page_rows`` long (clamp-padded), never a
  per-entry length, so the compilestats steady window sees no new
  signatures however entries are sized.

Numerics contract: gather output rows are the exact bytes of their
source (slab row or pool row) — the primitive moves bytes, it never
computes — which is what makes paged cache hits and feature-page hits
bit-identical to the uncached path by construction.
"""

from __future__ import annotations

import functools

from rnb_tpu.ops.ragged import LANES

#: sublane rows per grid step of the gather kernel (same budget rule
#: as ragged.BLOCK_SUBLANES: far under VMEM, low grid overhead)
BLOCK_SUBLANES = 512


# -- reference (masked jnp) -------------------------------------------
#
# jax imports stay inside the functions: rnb-lint and config parsing
# import pager/ops modules without touching a backend.

def gather_rows_reference(pool, slab, src_rows):
    """Masked-jnp twin of the Pallas gather: bit-identical contract.

    ``src_rows`` is int32 ``(pool_rows,)``; entry ``i >= 0`` selects
    slab row ``i``'s replacement, ``-1`` keeps ``pool[i]``. Sentinel
    entries are clamped before the take so no out-of-bounds row is
    ever addressed (its value is discarded by the mask).
    """
    import jax.numpy as jnp
    src = jnp.asarray(src_rows, jnp.int32)
    mask = (src >= 0).reshape((pool.shape[0],) + (1,) * (pool.ndim - 1))
    safe = jnp.clip(src, 0, slab.shape[0] - 1)
    return jnp.where(mask, jnp.take(slab, safe, axis=0,
                                    mode="clip").astype(pool.dtype),
                     pool)


@functools.lru_cache(maxsize=None)
def _gather_jit():
    """The one jitted gather: the Pallas kernel where it is compiled
    for a TPU and the rows are lane-divisible, the masked-jnp
    reference elsewhere — decided at lowering time
    (``lax.platform_dependent``) by the platform of the device the
    pool lives on, so a kernel Mosaic refuses raises instead of
    giving way to the twin."""
    import jax

    def gather(pool, slab, src_rows):
        if _lane_divisible(pool):
            return jax.lax.platform_dependent(
                pool, slab, src_rows,
                tpu=functools.partial(_gather_rows_pallas,
                                      interpret=False),
                default=gather_rows_reference)
        return gather_rows_reference(pool, slab, src_rows)

    return jax.jit(gather)


# -- Pallas kernel -----------------------------------------------------

def _lane_divisible(pool) -> bool:
    """Can the kernel tile this pool's rows to (sublanes, LANES)?"""
    import numpy as np
    per_row = int(np.prod(pool.shape[1:])) if pool.ndim > 1 else 0
    return per_row > 0 and per_row % LANES == 0


def _gather_rows_kernel(src_ref, pool_ref, slab_ref, o_ref):
    """One (pool-row, sublane-chunk) program: copy the prefetched
    source slab block when the row has one, pass the pool block
    through otherwise — sentinel programs execute a single store."""
    from jax.experimental import pallas as pl

    row = pl.program_id(0)

    @pl.when(src_ref[row] >= 0)
    def _hit():
        o_ref[:] = slab_ref[:]

    @pl.when(src_ref[row] < 0)
    def _miss():
        o_ref[:] = pool_ref[:]


def _gather_rows_pallas(pool, slab, src_rows, interpret: bool):
    """Pallas gather over ``(rows, per_row)`` lanes: grid = (pool
    rows, sublane chunks); the source table is scalar-prefetched so
    the slab BlockSpec's index_map resolves each program's source page
    block before its body runs (clamped to block 0 for sentinels — the
    fetched block is discarded by the ``pl.when`` predicate)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = pool.shape[0]
    slab_rows = slab.shape[0]
    per_row = int(np.prod(pool.shape[1:]))
    sublanes = per_row // LANES
    flat_pool = pool.reshape(rows, sublanes, LANES)
    flat_slab = slab.reshape(slab_rows, sublanes, LANES)
    block = min(BLOCK_SUBLANES, sublanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, pl.cdiv(sublanes, block)),
        in_specs=[
            pl.BlockSpec((1, block, LANES),
                         lambda i, j, src: (i, j, 0)),
            pl.BlockSpec((1, block, LANES),
                         lambda i, j, src: (jnp.maximum(src[i], 0),
                                            j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, LANES),
                               lambda i, j, src: (i, j, 0)),
    )
    out = pl.pallas_call(
        _gather_rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, sublanes, LANES),
                                       pool.dtype),
        interpret=interpret,
    )(jnp.asarray(src_rows, jnp.int32), flat_pool, flat_slab)
    return out.reshape(pool.shape)


def gather_rows(pool, slab, src_rows, interpret: bool = False):
    """Row pool with slab rows overlaid: ``out[i] = slab[src_rows[i]]``
    where ``src_rows[i] >= 0``, else ``pool[i]`` — on device, zero
    host bytes moved.

    ``pool`` is ``(pool_rows,) + row_shape``, ``slab`` is
    ``(slab_rows,) + row_shape`` (same trailing shape and dtype),
    ``src_rows`` int32 ``(pool_rows,)`` with ``-1`` sentinels. The
    fixed-length source table is the signature discipline: every
    gather of a given (pool, slab) pair dispatches through one
    compiled executable regardless of how many rows hit
    (:func:`_gather_jit`; ``interpret=True`` runs the kernel body on
    any platform, for tests).
    """
    import numpy as np

    src_rows = np.asarray(src_rows, np.int32)
    if interpret and _lane_divisible(pool):
        return _gather_rows_pallas(pool, slab, src_rows, True)
    return _gather_jit()(pool, slab, src_rows)


# -- page writes -------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _page_writer_jit():
    """The one donated slab writer: gather ``page_rows`` source rows
    (clamp-padded indices, so the index vector length never varies)
    and splice them at the destination row. ``donate_argnums=0``
    updates the slab buffer in place on backends that honor donation
    (verified on the CPU backend: the buffer pointer is stable across
    writes), so publishing a page never copies the resident slab."""
    import jax

    def _write(slab, src_pool, src_idx, dst_row):
        import jax.numpy as jnp
        from jax import lax
        rows = jnp.take(src_pool, src_idx, axis=0,
                        mode="clip").astype(slab.dtype)
        start = (dst_row,) + (0,) * (slab.ndim - 1)
        return lax.dynamic_update_slice(slab, rows, start)

    return jax.jit(_write, donate_argnums=(0,))


def write_rows_page(slab, src_pool, src_idx, dst_row):
    """-> new slab value with ``src_pool[src_idx]`` written at rows
    ``[dst_row, dst_row + len(src_idx))``. ``src_idx`` must always be
    ``page_rows`` long (pad by repeating a valid index — the padded
    rows land in the page's dead tail, which no gather ever
    references); ``dst_row`` is a page-aligned row offset."""
    import numpy as np
    return _page_writer_jit()(slab, src_pool,
                              np.asarray(src_idx, np.int32),
                              np.int32(dst_row))
