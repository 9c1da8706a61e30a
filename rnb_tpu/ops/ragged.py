"""Ragged row-pool dispatch: one compiled shape, zero padding FLOPs.

Row bucketing (PR 4/5 era) made batch shapes *bounded* — every
emission pads up to the next warmed bucket — but each bucket is still
one XLA executable (a warmup matrix of one compile per (bucket,
dtype)), every pad row still burns FLOPs in the consuming stage, and
the autotune controller is quantized to the pre-warmed set. Following
Ragged Paged Attention (PAPERS.md), this module provides the ragged
alternative: stages dispatch a **flat row pool of fixed capacity**
``(pool_rows, ...)`` — ONE compiled shape for the stage's whole life —
plus a scalar ``rows_valid`` and a per-request ``segment_offsets``
table carried on :class:`rnb_tpu.stage.RaggedBatch`. The forward
primitive masks/skips rows past ``rows_valid``:

* **TPU**: a Pallas kernel over a ``PrefetchScalarGridSpec`` —
  ``rows_valid`` is scalar-prefetched into SMEM and the grid's row
  programs use ``pl.when(row < rows_valid)`` so pad-row blocks execute
  a zero-store only, no arithmetic — zero padding FLOPs;
* **any other platform**: a masked ``jnp`` formulation with the identical
  contract (valid rows bit-identical to the bucketed path's
  ``normalize_u8``; pad rows exactly zero), so the tier-1 harness
  exercises the same semantics the TPU kernel compiles;
* **interpret mode**: the Pallas kernel body itself runs on CPU via
  ``interpret=True`` (tests assert it matches the jnp fallback
  bit-for-bit).

The scalar is *traced*, never static: any ``rows_valid`` in
``[0, pool_rows]`` dispatches through the same executable, which is
what deletes the warmup matrix and frees the autotune controller from
the warmed-bucket restriction (decisions become continuous).

Numerics contract: rows ``< rows_valid`` are bit-identical to the
bucketed path applied to the same rows; rows ``>= rows_valid`` are
exactly zero out of the masking primitives (the network consumes the
pool at its one shape and per-row outputs are independent of other
rows, so valid-row logits stay bit-identical to the bucketed path's —
asserted in tests/test_ragged.py on both pixel paths).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

@dataclasses.dataclass(frozen=True)
class RaggedSettings:
    """Validated, defaulted view of the ``ragged`` root config key.

    ``pool_rows`` is the one dispatch shape's row capacity; ``None``
    defers to each participating stage's declared max rows (the
    common case — the pool IS the stage's max shape, so ring sizing
    and declared wire shapes are unchanged).
    """

    pool_rows: Optional[int] = None

    @staticmethod
    def from_config(raw: Optional[dict]) -> Optional["RaggedSettings"]:
        """Settings from the (schema-validated) config dict, or None
        when ragged is absent or ``enabled`` is false."""
        if not raw or not raw.get("enabled", True):
            return None
        pool_rows = raw.get("pool_rows")
        return RaggedSettings(
            pool_rows=int(pool_rows) if pool_rows is not None else None)


def resolve_pool_rows(pool_rows: Optional[int], declared_max: int,
                      what: str) -> int:
    """The one pool-capacity rule every ragged stage shares: an
    explicit ``ragged.pool_rows`` must EQUAL the stage's declared max
    row axis — the pool is the stage's one compiled shape, so a
    different capacity would silently change every declared wire
    shape, ring size and warmup signature (rnb-lint RNB-G009 rejects
    the mismatch statically; this is the runtime backstop)."""
    declared_max = int(declared_max)
    if pool_rows is None:
        return declared_max
    pool_rows = int(pool_rows)
    if pool_rows != declared_max:
        raise ValueError(
            "ragged.pool_rows=%d does not match %s=%d — the pool is "
            "the stage's one compiled shape, so its capacity must "
            "equal the declared max row axis" % (pool_rows, what,
                                                 declared_max))
    return pool_rows


def segment_offsets_of(counts: Sequence[int]) -> Tuple[int, ...]:
    """The cumulative segment table for per-request row ``counts``:
    ``(0, counts[0], counts[0]+counts[1], ...)`` — request i owns rows
    ``[offsets[i], offsets[i+1])``."""
    offsets = [0]
    for n in counts:
        offsets.append(offsets[-1] + int(n))
    return tuple(offsets)


def check_segment_offsets(offsets: Sequence[int], valid: int) -> None:
    """Assert a segment table partitions ``[0, valid)``: offsets are
    nondecreasing, start at 0 and end exactly at ``valid`` — request i
    owns rows ``[offsets[i], offsets[i+1])``. The executor applies
    this to every RaggedBatch it publishes (rnb_tpu.runner
    validate_payload), so a broken fill can never silently ship."""
    offsets = tuple(int(o) for o in offsets)
    if len(offsets) < 2:
        raise ValueError("segment_offsets needs >= 2 entries "
                         "(got %r)" % (offsets,))
    if offsets[0] != 0:
        raise ValueError("segment_offsets must start at 0, got %r"
                         % (offsets,))
    if any(b < a for a, b in zip(offsets, offsets[1:])):
        raise ValueError("segment_offsets must be nondecreasing, "
                         "got %r" % (offsets,))
    if offsets[-1] != int(valid):
        raise ValueError(
            "segment_offsets %r end at %d but rows_valid=%d — the "
            "segment table must partition the valid rows"
            % (offsets, offsets[-1], int(valid)))


# -- the masking/forward primitives -----------------------------------
#
# jax imports stay inside the functions: rnb-lint and config parsing
# import this module for RaggedSettings without touching a backend.

def _row_mask(pool, rows_valid):
    """Boolean (R, 1, 1, ...) row mask broadcastable over the pool."""
    import jax.numpy as jnp
    rows = pool.shape[0]
    idx = jnp.arange(rows).reshape((rows,) + (1,) * (pool.ndim - 1))
    return idx < rows_valid


def ragged_mask_rows(pool, rows_valid):
    """Zero every row ``>= rows_valid`` of ``pool`` (same dtype/shape).

    The minimal ragged primitive: turns a pool whose pad tail may hold
    garbage (a staging slot mid-recycle, an un-zeroed fill) into the
    exact bytes the bucketed path would have shipped for its pad rows
    (zeros) — inside the consuming jit, at the one compiled shape.
    """
    import jax.numpy as jnp
    return jnp.where(_row_mask(pool, rows_valid), pool,
                     jnp.zeros((), pool.dtype))


#: lane width of the TPU VPU — the Pallas kernel tiles each pool row
#: to (sublanes, LANES); rows whose byte count is not lane-divisible
#: fall back to the masked jnp formulation
LANES = 128
#: sublane rows per grid step (uint8 min tile is 32; a healthy
#: multiple keeps grid overhead low while staying far under VMEM)
BLOCK_SUBLANES = 512


def _ragged_normalize_kernel(rows_valid_ref, x_ref, o_ref):
    """One (pool-row, sublane-chunk) program: normalize when the row
    is valid, store zeros otherwise — pad programs execute no
    arithmetic (the ``pl.when`` predicate skips the whole body)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    row = pl.program_id(0)

    @pl.when(row < rows_valid_ref[0])
    def _valid():
        # Mosaic has no direct uint8->bf16 cast; widen via int32/f32.
        # Same FMA-proof formulation as ops.preprocess.normalize_u8.
        x = x_ref[:].astype(jnp.int32).astype(jnp.float32)
        o_ref[:] = ((x * 2.0 - 255.0)
                    * jnp.float32(1.0 / 255.0)).astype(o_ref.dtype)

    @pl.when(row >= rows_valid_ref[0])
    def _pad():
        o_ref[:] = jnp.zeros_like(o_ref)


def _ragged_normalize_pallas(pool, rows_valid, dtype, interpret: bool):
    """Pallas ragged normalize over ``(R, per_row)`` lanes: grid =
    (pool rows, sublane chunks); ``rows_valid`` is scalar-prefetched
    so every program's predicate is resolved before its body runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = pool.shape[0]
    per_row = int(np.prod(pool.shape[1:]))
    sublanes = per_row // LANES
    flat = pool.reshape(rows, sublanes, LANES)
    block = min(BLOCK_SUBLANES, sublanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, pl.cdiv(sublanes, block)),
        in_specs=[pl.BlockSpec((1, block, LANES),
                               lambda i, j, rv: (i, j, 0))],
        out_specs=pl.BlockSpec((1, block, LANES),
                               lambda i, j, rv: (i, j, 0)),
    )
    out = pl.pallas_call(
        _ragged_normalize_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, sublanes, LANES), dtype),
        interpret=interpret,
    )(jnp.asarray(rows_valid, jnp.int32).reshape(1), flat)
    return out.reshape(pool.shape)


def ragged_normalize_u8(pool, rows_valid, dtype=None,
                        interpret: bool = False):
    """uint8 row pool -> normalized ``dtype`` pool; pad rows zeroed.

    The ragged twin of ``ops.preprocess.normalize_u8``: valid rows are
    bit-identical to the bucketed preprocess applied to the same rows
    (same FMA-proof formulation); rows ``>= rows_valid`` come out
    exactly zero without being read by any arithmetic. The Pallas
    grid-skip kernel where the computation is compiled for a TPU (or
    under ``interpret=True`` anywhere, for tests), the masked jnp
    formulation elsewhere — chosen at lowering time by
    ``lax.platform_dependent``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from rnb_tpu.ops.preprocess import normalize_u8

    if dtype is None:
        dtype = jnp.bfloat16

    def masked(pool, rows_valid):
        return jnp.where(_row_mask(pool, rows_valid),
                         normalize_u8(pool, dtype=dtype),
                         jnp.zeros((), dtype))

    per_row = int(np.prod(pool.shape[1:])) if pool.ndim > 1 else 0
    if not (pool.dtype == jnp.uint8 and per_row > 0
            and per_row % LANES == 0):
        return masked(pool, rows_valid)
    if interpret:
        return _ragged_normalize_pallas(pool, rows_valid, dtype, True)
    return jax.lax.platform_dependent(
        pool, jnp.asarray(rows_valid, jnp.int32),
        tpu=lambda p, rv: _ragged_normalize_pallas(p, rv, dtype, False),
        default=masked)


def ragged_normalize_yuv420(pool, rows_valid, height: int, width: int,
                            dtype=None):
    """Packed 4:2:0 u8 row pool -> normalized NDHWC frames; rows past
    ``rows_valid`` enter the converter as zero bytes — exactly the
    bytes the bucketed path ships for its pad rows — so valid-row
    outputs are bit-identical to the bucketed fused ingest and pad
    rows are deterministic regardless of what the pool tail held.
    The mask runs at the u8 level (1.5 bytes/pixel), before the
    converter widens to f32."""
    import jax.numpy as jnp

    from rnb_tpu.ops.yuv import normalize_yuv420

    if dtype is None:
        dtype = jnp.bfloat16
    return normalize_yuv420(ragged_mask_rows(pool, rows_valid),
                            height, width, dtype=dtype)
