"""DCT-domain ingest: packed dequantized coefficients -> normalized
bfloat16 frames, fused on-device.

The ``dct`` pixel path moves the LAST per-pixel host stage of the MJPEG
pipeline onto the accelerator. The host decoder stops at
entropy-decoded, **dequantized** 8x8 DCT coefficients (the exact cut
point before ``Idct8x8`` in native/decode.cpp) and ships them in a
sparse packed row format; the consuming network stage runs

    IDCT  ->  2x nearest chroma upsample  ->  BT.601 YUV->RGB
          ->  u8 quantize  ->  normalize to [-1, 1]

as ONE fused step ahead of conv1 — a Pallas kernel on TPU (grid-skip
over ``rows_valid`` exactly like rnb_tpu/ops/ragged.py), a bit-identical
masked-jnp twin on CPU, and the kernel body itself under
``interpret=True`` in tests. This both *deletes* host IDCT work (the
dominant per-pixel term of MJPEG decode) and cuts wire bytes again on
top of YUV 4:2:0's 2x: quantized-then-dequantized coefficients are
sparse, so the packed format ships ~half the bytes of the packed-plane
yuv420 path at the default budget.

Wire row format (``dct_frame_elems`` int16 elements per frame; one clip
row is ``(consecutive_frames, elems)``), for even H, W with
``H % 16 == W % 16 == 0`` (one MCU = 16x16 luma under 4:2:0):

    [0 : NB)            per-block nonzero coefficient counts
    [NB : NB+C)         dequantized coefficient values (int16),
                        concatenated per block in block order,
                        ascending zigzag order within a block
    [NB+C : NB+2C)      the zigzag index (0..63) of each value

where ``NB = num_dct_blocks(H, W)`` (Y blocks in raster order, then U,
then V) and ``C = coeffs`` is the per-frame coefficient budget
(``default_dct_coeffs`` picks the largest C that keeps the frame at
half the packed-yuv420 byte count). Unused value/position slots are
zero. A frame whose nonzero count exceeds ``C`` cannot ship losslessly
and the decoder raises a *classified permanent* error instead of
silently truncating spectrum (see README "DCT-domain ingest" for when
yuv420 stays preferable).

The device unpack (counts -> per-entry block ids via searchsorted ->
one static-shape scatter) is plain jnp inside the same jit and is
garbage-tolerant: out-of-range counts/positions are clamped/dropped so
an uninitialized ragged pool tail can never corrupt valid rows or trap.

Numerics contract: the host AAN IDCT (native/decode.cpp) and this
on-device direct-basis IDCT are both float32 implementations of the
same transform, so reconstructed u8 planes agree within +-1 LSB at
round boundaries (tests bound this against the yuv420 pixel path); the
Pallas kernel and the jnp twin share one frame-conversion function and
are asserted BIT-identical. Pad rows (``>= rows_valid``) come out
exactly zero from both.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

#: zigzag scan: position k in the scan -> natural (row-major u*8+v)
#: coefficient index. Identical to kZigzag in native/decode.cpp.
ZIGZAG_NATURAL = np.array([
    0,  1,  8,  16, 9,  2,  3,  10, 17, 24, 32, 25, 18, 11, 4,  5,
    12, 19, 26, 33, 40, 48, 41, 34, 27, 20, 13, 6,  7,  14, 21, 28,
    35, 42, 49, 56, 57, 50, 43, 36, 29, 22, 15, 23, 30, 37, 44, 51,
    58, 59, 52, 45, 38, 31, 39, 46, 53, 60, 61, 54, 47, 55, 62, 63],
    dtype=np.int32)


def _check_geometry(height: int, width: int) -> None:
    if height % 16 or width % 16:
        raise ValueError(
            "the dct pixel path needs H and W divisible by 16 (one "
            "4:2:0 MCU is 16x16 luma), got %dx%d" % (height, width))


def num_dct_blocks(height: int, width: int) -> int:
    """8x8 blocks per frame at 4:2:0: Y (H/8 * W/8) + U + V (quarter
    resolution each)."""
    _check_geometry(height, width)
    return (height // 8) * (width // 8) + 2 * (height // 16) * (width // 16)


def default_dct_coeffs(height: int, width: int) -> int:
    """Default per-frame coefficient budget: the largest C for which
    the packed frame (int16) costs no more than HALF the packed
    yuv420 frame — the wire-byte headline this path ships by default
    (raise ``dct_coeffs_per_frame`` for high-entropy content at the
    cost of some of the reduction)."""
    _check_geometry(height, width)
    packed_yuv = height * width * 3 // 2      # bytes, u8 planes
    max_elems = (packed_yuv // 2) // 2        # int16 elems in half that
    coeffs = (max_elems - num_dct_blocks(height, width)) // 2
    if coeffs < 1:
        raise ValueError("geometry %dx%d too small for the dct wire "
                         "format" % (height, width))
    return coeffs


def dct_frame_elems(height: int, width: int,
                    coeffs: Optional[int] = None) -> int:
    """int16 elements of one packed coefficient frame."""
    nb = num_dct_blocks(height, width)
    if coeffs is None:
        coeffs = default_dct_coeffs(height, width)
    coeffs = int(coeffs)
    if coeffs < 1:
        raise ValueError("dct coefficient budget must be >= 1, got %r"
                         % (coeffs,))
    return nb + 2 * coeffs


def coeffs_from_elems(height: int, width: int, elems: int) -> int:
    """Recover the coefficient budget C from a wire row's trailing
    axis (the inverse of :func:`dct_frame_elems`)."""
    nb = num_dct_blocks(height, width)
    coeffs, rem = divmod(int(elems) - nb, 2)
    if rem or coeffs < 1:
        raise ValueError(
            "%d is not a valid dct frame length for %dx%d (expected "
            "num_blocks=%d + 2*C)" % (elems, height, width, nb))
    return coeffs


def pack_frame_dct(zz: np.ndarray, height: int, width: int,
                   coeffs: Optional[int] = None) -> np.ndarray:
    """Pack one frame's dense zigzag-order coefficients into the wire
    format.

    ``zz`` is ``(num_blocks, 64)`` int16 — dequantized coefficients in
    zigzag scan order per block, blocks in Y-raster/U-raster/V-raster
    order. Raises ValueError when the nonzero count exceeds the
    budget (callers classify it permanent: re-decoding cannot shrink
    the spectrum).
    """
    nb = num_dct_blocks(height, width)
    if coeffs is None:
        coeffs = default_dct_coeffs(height, width)
    coeffs = int(coeffs)
    zz = np.asarray(zz, dtype=np.int16)
    if zz.shape != (nb, 64):
        raise ValueError("expected (%d, 64) zigzag coefficients for "
                         "%dx%d, got %r" % (nb, height, width, zz.shape))
    block_idx, pos_idx = np.nonzero(zz)   # row-major: block-then-zigzag
    total = block_idx.size
    if total > coeffs:
        raise ValueError(
            "frame has %d nonzero DCT coefficients but the wire "
            "budget is %d — raise dct_coeffs_per_frame (or use "
            "pixel_path yuv420 for this content)" % (total, coeffs))
    out = np.zeros(nb + 2 * coeffs, dtype=np.int16)
    counts = np.bincount(block_idx, minlength=nb)
    out[:nb] = counts.astype(np.int16)
    out[nb:nb + total] = zz[block_idx, pos_idx]
    out[nb + coeffs:nb + coeffs + total] = pos_idx.astype(np.int16)
    return out


def unpack_frame_dct_numpy(wire: np.ndarray, height: int,
                           width: int) -> np.ndarray:
    """Wire frame -> dense ``(num_blocks, 64)`` zigzag coefficients
    (numpy; the host-side inverse of :func:`pack_frame_dct`, for
    tests and oracles)."""
    nb = num_dct_blocks(height, width)
    coeffs = coeffs_from_elems(height, width, wire.shape[-1])
    wire = np.asarray(wire, dtype=np.int64)
    counts = np.clip(wire[:nb], 0, 64)
    total = min(int(counts.sum()), coeffs)
    block = np.repeat(np.arange(nb), counts)[:total]
    vals = wire[nb:nb + total]
    poss = np.clip(wire[nb + coeffs:nb + coeffs + total], 0, 63)
    zz = np.zeros((nb, 64), dtype=np.int16)
    zz[block, poss] = vals[: block.size].astype(np.int16)
    return zz


# -- IDCT bases (host-built constants) --------------------------------

def _idct_basis8() -> np.ndarray:
    """M[y, u] = c(u)/2 * cos((2y+1) u pi / 16) — one 1-D 8-point
    inverse DCT pass; the 2-D block IDCT is M @ C @ M^T."""
    y, u = np.meshgrid(np.arange(8), np.arange(8), indexing="ij")
    m = 0.5 * np.cos((2 * y + 1) * u * np.pi / 16.0)
    m[:, 0] *= 1.0 / np.sqrt(2.0)
    return m.astype(np.float32)


def _plane_bases(height: int, width: int):
    """The four constant matrices of the fused frame conversion:

    * ``ly (H, H)`` / ``lyt (W, W)``: block-diagonal ``I ⊗ M8`` so the
      WHOLE luma plane's IDCT is two dense matmuls over the block-tiled
      coefficient matrix — MXU-shaped work instead of 8x8 batches;
    * ``lcr (H, H/2)`` / ``lcct (W/2, W)``: the same for chroma with
      the 2x nearest upsample folded in (rows duplicated — replication
      commutes with the later rounding, so this is exactly the
      "round the half-res plane, then repeat" host semantics).
    """
    m = _idct_basis8()
    ly = np.kron(np.eye(height // 8, dtype=np.float32), m)
    lyt = np.kron(np.eye(width // 8, dtype=np.float32), m).T
    cb_r = np.kron(np.eye(height // 16, dtype=np.float32), m)
    cb_c = np.kron(np.eye(width // 16, dtype=np.float32), m)
    lcr = np.repeat(cb_r, 2, axis=0)
    lcct = np.repeat(cb_c, 2, axis=0).T
    return (np.ascontiguousarray(ly), np.ascontiguousarray(lyt),
            np.ascontiguousarray(lcr), np.ascontiguousarray(lcct))


# -- device unpack (jnp, inside the consuming jit) --------------------

def unpack_dct_rows(x, height: int, width: int):
    """Packed wire rows ``(..., F, elems)`` int16 -> block-tiled dense
    coefficient planes ``(ycoef (..., F, H, W), ucoef/vcoef (..., F,
    H/2, W/2))`` as int32.

    Block-tiled layout: the 8x8 tile of ``ycoef`` at block (i, j)
    holds that block's natural-order coefficients, so the plane IDCT
    is ``ly @ ycoef @ lyt``. Garbage-tolerant by construction (clamped
    counts/positions, out-of-range entries dropped into a dump slot):
    an uninitialized pool tail decodes to SOMETHING deterministic and
    is then masked by the caller, never trapping.
    """
    import jax
    import jax.numpy as jnp

    nb = num_dct_blocks(height, width)
    coeffs = coeffs_from_elems(height, width, x.shape[-1])
    lead = x.shape[:-1]
    flat = x.reshape((-1, x.shape[-1]))
    counts = jnp.clip(flat[:, :nb].astype(jnp.int32), 0, 64)
    cum = jnp.cumsum(counts, axis=-1)                    # inclusive
    total = jnp.minimum(cum[:, -1], coeffs)
    vals = flat[:, nb:nb + coeffs].astype(jnp.int32)
    poss = jnp.clip(flat[:, nb + coeffs:nb + 2 * coeffs]
                    .astype(jnp.int32), 0, 63)
    entry = jnp.arange(coeffs, dtype=jnp.int32)
    block = jax.vmap(
        lambda c: jnp.searchsorted(c, entry, side="right"))(cum)
    natural = jnp.asarray(ZIGZAG_NATURAL)[poss]
    ok = (entry[None, :] < total[:, None]) & (block < nb)
    # one extra dump slot swallows every invalid entry
    target = jnp.where(ok, block * 64 + natural, nb * 64)
    dense = jax.vmap(
        lambda t, v: jnp.zeros(nb * 64 + 1, jnp.int32).at[t].set(v)
    )(target, jnp.where(ok, vals, 0))[:, : nb * 64]

    ny = (height // 8) * (width // 8)
    nc = (height // 16) * (width // 16)

    def tiled(blocks, bh, bw):
        # (B, bh*bw, 8, 8) -> block-tiled (B, bh*8, bw*8)
        t = blocks.reshape((-1, bh, bw, 8, 8))
        return t.transpose((0, 1, 3, 2, 4)).reshape(
            (-1, bh * 8, bw * 8))

    ycoef = tiled(dense[:, : ny * 64], height // 8, width // 8)
    ucoef = tiled(dense[:, ny * 64:(ny + nc) * 64],
                  height // 16, width // 16)
    vcoef = tiled(dense[:, (ny + nc) * 64:], height // 16, width // 16)
    return (ycoef.reshape(lead + ycoef.shape[1:]),
            ucoef.reshape(lead + ucoef.shape[1:]),
            vcoef.reshape(lead + vcoef.shape[1:]))


# -- the fused frame conversion (shared by kernel, twin, interpret) ---

def _frame_rgb_planes(cy, cu, cv, ly, lyt, lcr, lcct, dtype):
    """Block-tiled coefficient planes ``(..., H, W)`` -> the three
    normalized colour planes ``(r, g, b)``, each ``(..., H, W)``. The
    SINGLE function both the Pallas kernel body (one 2-D frame per
    grid program) and the jnp twin (all frames batched over the
    leading dims — ``jnp.matmul`` broadcasts) call, so the two are
    structurally identical op for op; the bit-parity contract tier-1
    asserts batched-vs-per-frame matmul rounding agreement on this
    backend.

    Stages mirror the host pixel pipeline exactly: IDCT (+128 level
    shift), per-plane round-half-up u8 quantize (native Idct8x8's
    ``ClipByte(px + 0.5)``), BT.601 in the same op order as
    rnb_tpu/ops/yuv.py, clip, truncate to u8, then the FMA-proof
    normalize formulation of ops/preprocess.normalize_u8_reference.

    The IDCT matmuls ask for ``Precision.HIGHEST``: coefficients reach
    +-2^11, and a TPU's default single-pass bf16 product would move
    reconstructed pixels by whole levels, outside the +-1 LSB the
    module promises against the host decoder. The CPU backend
    multiplies f32 in full either way, so the pinned numerics there
    are unchanged.
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    highest = jax.lax.Precision.HIGHEST

    def plane(coef, left, right):
        c = coef.astype(jnp.int32).astype(f32)
        p = jnp.matmul(left, jnp.matmul(c, right, precision=highest,
                                        preferred_element_type=f32),
                       precision=highest, preferred_element_type=f32)
        # level shift + the host decoder's round-half-up u8 quantize
        return jnp.clip(jnp.floor(p + (128.0 + 0.5)), 0.0, 255.0)

    y = plane(cy, ly, lyt)
    uf = plane(cu, lcr, lcct) - 128.0
    vf = plane(cv, lcr, lcct) - 128.0

    def normalized(channel):
        # the yuv420 path's u8 quantization step (clip + truncate),
        # kept in f32, then the single-rounding normalize
        q = jnp.floor(jnp.clip(channel, 0.0, 255.0))
        return ((q * 2.0 - 255.0) * f32(1.0 / 255.0)).astype(dtype)

    return (normalized(y + 1.402 * vf),
            normalized(y - 0.344136 * uf - 0.714136 * vf),
            normalized(y + 1.772 * uf))


def _dct_kernel(rows_valid_ref, cy_ref, cu_ref, cv_ref, ly_ref,
                lyt_ref, lcr_ref, lcct_ref, o_ref):
    """One (pool-row, frame) program: full fused conversion when the
    row is valid, a zero store otherwise — pad programs run no
    IDCT/convert arithmetic (the ``pl.when`` predicate skips the whole
    body, rnb_tpu/ops/ragged.py discipline). The output block is
    channel-planar ``(1, 1, 3, H, W)``: W sits on the lanes, where a
    channel-minor ``(H, W, 3)`` block would pad its 3-wide minor
    dimension to a full 128-lane tile."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    row = pl.program_id(0)

    @pl.when(row < rows_valid_ref[0])
    def _valid():
        planes = _frame_rgb_planes(
            cy_ref[0, 0], cu_ref[0, 0], cv_ref[0, 0], ly_ref[:],
            lyt_ref[:], lcr_ref[:], lcct_ref[:], o_ref.dtype)
        for channel, value in enumerate(planes):
            o_ref[0, 0, channel] = value

    @pl.when(row >= rows_valid_ref[0])
    def _pad():
        o_ref[:] = jnp.zeros_like(o_ref)


def _dct_convert_pallas(ycoef, ucoef, vcoef, rows_valid, height: int,
                        width: int, dtype, interpret: bool):
    """Pallas dispatch over (pool rows, frames): ``rows_valid`` is
    scalar-prefetched so every program's predicate resolves before its
    body; the IDCT bases ride as whole-array inputs every program
    reads. Per program the VMEM blocks are one int32 luma plane, two
    quarter planes, the four f32 bases and the planar output — about
    0.4 MB at 112x112 with lanes padded to 128, double-buffered far
    under the 16 MiB default scoped limit, so no other limit is
    stated. (The channel-minor output this replaced needed 16.3 MB and
    was refused by the compiler on a v5e.) The kernel's planar result is moved to
    the network's NDHWC layout by XLA outside it (a pure transpose:
    the values are untouched)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, frames = ycoef.shape[0], ycoef.shape[1]
    h2, w2 = height // 2, width // 2
    ly, lyt, lcr, lcct = _plane_bases(height, width)
    const = lambda shape: pl.BlockSpec(  # noqa: E731 — local spec rule
        shape, lambda i, j, rv: tuple(0 for _ in shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, frames),
        in_specs=[
            pl.BlockSpec((1, 1, height, width),
                         lambda i, j, rv: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, h2, w2), lambda i, j, rv: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, h2, w2), lambda i, j, rv: (i, j, 0, 0)),
            const(ly.shape), const(lyt.shape), const(lcr.shape),
            const(lcct.shape),
        ],
        out_specs=pl.BlockSpec((1, 1, 3, height, width),
                               lambda i, j, rv: (i, j, 0, 0, 0)),
    )
    out = pl.pallas_call(
        _dct_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (rows, frames, 3, height, width), dtype),
        interpret=interpret,
    )(jnp.asarray(rows_valid, jnp.int32).reshape(1), ycoef, ucoef,
      vcoef, jnp.asarray(ly), jnp.asarray(lyt), jnp.asarray(lcr),
      jnp.asarray(lcct))
    return jnp.moveaxis(out, 2, -1)


def _dct_convert_jnp(ycoef, ucoef, vcoef, height: int, width: int,
                     dtype):
    """The jnp twin's conversion over ``(rows, frames)`` planes: ONE
    call of the SAME function the kernel body runs, with the plane
    matmuls batched over the leading dims (XLA CPU's batched GEMM
    runs the identical per-frame contraction — bit-equality with the
    interpret-mode kernel is asserted in tests/test_dct.py)."""
    import jax.numpy as jnp

    ly, lyt, lcr, lcct = _plane_bases(height, width)
    return jnp.stack(_frame_rgb_planes(
        ycoef, ucoef, vcoef, jnp.asarray(ly), jnp.asarray(lyt),
        jnp.asarray(lcr), jnp.asarray(lcct), dtype), axis=-1)


def _dct_convert(ycoef, ucoef, vcoef, rows_valid, height: int,
                 width: int, dtype, interpret: bool):
    """Coefficient planes -> normalized NDHWC frames with rows
    ``>= rows_valid`` exactly zero: the Pallas kernel where the
    computation is compiled for a TPU (or under ``interpret``
    anywhere), the masked jnp twin elsewhere — chosen at lowering time
    by ``lax.platform_dependent``, i.e. by the device the operands live
    on. A kernel Mosaic refuses raises; it never gives way to the
    twin."""
    import jax
    import jax.numpy as jnp

    if interpret:
        return _dct_convert_pallas(ycoef, ucoef, vcoef, rows_valid,
                                   height, width, dtype, True)

    def kernel(ycoef, ucoef, vcoef, rows_valid):
        return _dct_convert_pallas(ycoef, ucoef, vcoef, rows_valid,
                                   height, width, dtype, False)

    def twin(ycoef, ucoef, vcoef, rows_valid):
        out = _dct_convert_jnp(ycoef, ucoef, vcoef, height, width, dtype)
        rows = out.shape[0]
        mask = jnp.arange(rows).reshape((rows, 1, 1, 1, 1)) < rows_valid
        return jnp.where(mask, out, jnp.zeros((), out.dtype))

    return jax.lax.platform_dependent(
        ycoef, ucoef, vcoef, jnp.asarray(rows_valid, jnp.int32),
        tpu=kernel, default=twin)


def normalize_dct(pool, height: int, width: int, dtype=None,
                  interpret: bool = False):
    """Packed coefficient rows ``(N, F, elems)`` int16 -> normalized
    ``dtype`` NDHWC frames — the bucketed-path ingest (every row
    converted; pad rows are zero wire bytes, which decode to a
    deterministic flat mid-gray frame — zero coefficients -> all
    planes 128. Deterministic-pad is the shared contract with the
    yuv420 path; the pad frame VALUE differs per pixel path, and
    per-row network outputs never depend on pad rows either way)."""
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.bfloat16
    return _dct_convert(*unpack_dct_rows(pool, height, width),
                        pool.shape[0], height, width, dtype, interpret)


def ragged_normalize_dct(pool, rows_valid, height: int, width: int,
                         dtype=None, interpret: bool = False):
    """The ragged seam replacing ``ragged_normalize_yuv420`` on the
    dct pixel path: packed coefficient row pool + traced ``rows_valid``
    -> normalized NDHWC pool whose rows ``>= rows_valid`` are exactly
    zero. On TPU (or under ``interpret=True``) the Pallas grid skips
    pad (row, frame) programs outright — no IDCT, no conversion
    arithmetic on rows nobody reads; the jnp twin masks the converted
    output with the identical result. The unpack stays garbage-
    tolerant, so an uninitialized pool tail is safe on both paths."""
    import jax.numpy as jnp

    if dtype is None:
        dtype = jnp.bfloat16
    return _dct_convert(*unpack_dct_rows(pool, height, width),
                        rows_valid, height, width, dtype, interpret)


# -- numpy oracle (tests only) ----------------------------------------

def dct_rows_to_rgb_numpy(wire: np.ndarray, height: int,
                          width: int) -> np.ndarray:
    """Packed wire rows ``(..., elems)`` -> u8 RGB ``(..., H, W, 3)``:
    the pure-numpy mirror of the fused conversion minus the final
    normalize, for comparing against the pixel decode backends."""
    ly, lyt, lcr, lcct = _plane_bases(height, width)
    nb = num_dct_blocks(height, width)
    lead = wire.shape[:-1]
    flat = wire.reshape((-1, wire.shape[-1]))
    out = np.empty((flat.shape[0], height, width, 3), np.uint8)
    ny = (height // 8) * (width // 8)
    nc = (height // 16) * (width // 16)
    nat = np.zeros(64, dtype=np.int64)
    nat[:] = ZIGZAG_NATURAL

    def tiled(blocks, bh, bw):
        return blocks.reshape(bh, bw, 8, 8).transpose(0, 2, 1, 3) \
            .reshape(bh * 8, bw * 8)

    for i in range(flat.shape[0]):
        zz = unpack_frame_dct_numpy(flat[i], height, width)
        dense = np.zeros((nb, 64), np.float32)
        dense[np.arange(nb)[:, None], nat[None, :]] = zz
        cy = tiled(dense[:ny], height // 8, width // 8)
        cu = tiled(dense[ny:ny + nc], height // 16, width // 16)
        cv = tiled(dense[ny + nc:], height // 16, width // 16)

        def plane(c, left, right):
            p = left.astype(np.float64) @ c.astype(np.float64) \
                @ right.astype(np.float64)
            return np.clip(np.floor(p + 128.5), 0, 255)

        y = plane(cy, ly, lyt)
        u = plane(cu, lcr, lcct)
        v = plane(cv, lcr, lcct)
        rgb = np.stack([
            y + 1.402 * (v - 128.0),
            y - 0.344136 * (u - 128.0) - 0.714136 * (v - 128.0),
            y + 1.772 * (u - 128.0),
        ], axis=-1)
        out[i] = np.floor(np.clip(rgb, 0, 255)).astype(np.uint8)
    return out.reshape(lead + (height, width, 3))
