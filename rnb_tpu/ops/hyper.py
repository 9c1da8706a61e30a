"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): a residual stream ``n`` wide,
mixed into and out of every sublayer by mappings made from the token's
own stream.

With ``X`` a token's ``n`` streams of ``C`` channels, a sublayer ``F``
and one set of weights a sublayer (``phi`` (n C, 2n + n^2), ``alpha``
(3,), ``bias`` (2n + n^2,), the columns in the order pre | post | res,
``res`` row-major)::

    x^     = vec(X) / sqrt(mean(vec(X)^2) + eps)
    logits = alpha . (x^ phi) + bias
    h_pre  = sigmoid(pre)          h_post = 2 sigmoid(post)
    H_res  = SK(exp(clip(res)))    SK: ``iters`` x (columns over their
                                   sums + hc_eps, then rows)
    u      = h_pre X               the sublayer's input
    X'     = H_res X + h_post^T F(u)

``H_res[i, j]`` is stream j's weight in new stream i.

*Layout.* The pool's stream is ``(tokens, n C)``: a token's ``n``
streams side by side along lanes, ``vec(X)`` as it lies. (An ``n``
between the tokens and the channels would be the second-minor axis, and
4 bfloat16 rows pad to a tile of 16.) The Sinkhorn steps want the other
way round, **tokens along lanes**: ``(2n + n^2, tokens)`` float32 rows,
so that a step is adds and multiplies between whole rows (a ``(tokens,
n, n)`` array would pad every 4 x 4 to an (8, 128) tile). The mixings
want a token's coefficients beside its channels again, a column each:
``(tokens, 128)``, :data:`LANES` wide with the 2n + n^2 in front
(``*_tm``: token-major).

*The plain statement*: :func:`logits_of`, :func:`coefficients`,
:func:`mix_in` and :func:`mix_out`, in ``jax.numpy``. Nothing in the
program calls them but :func:`leave_lines`; the tests hold what the
stack calls to them, and ``scripts/hyper_sweep.py`` times them composed
beside it.

*What the stack calls*, three steps a sublayer:

- :func:`enter` — the way in of the stack's first sublayer: the
  statistic, the projection, ``h_pre`` and ``u``;
- :func:`coefficients_from` — ``h_post`` and ``H_res`` from the logits,
  which nothing needs before the sublayer's way out: ``jax.numpy``,
  tokens along lanes, every Sinkhorn step in one fusion;
- :func:`leave_enter` — a sublayer's way out *and the next one's way
  in*: ``X'`` is rounded and written once, and while a tile of it is in
  VMEM its statistic, its 2n + n^2 projections (the matrix unit, on the
  rounded values the next sublayer would read back) and the next ``u``
  are made from it; :func:`leave_lines` is the last sublayer's way out,
  on the lines the head reads.

The first and the third are one Pallas kernel (:data:`KERNEL_NAME`), a
step a tile of :data:`_TOKENS` tokens, with the way out as an option.
On the v5e at 8,192 tokens of 4 x 3,584 (my chip runs, PR 62,
``scripts/hyper_sweep.py``; the floor of a sublayer — the stream read
once and written once with ``u`` and ``y`` — is 0.72 ms): XLA's passes
of the plain statement 2.55-2.64 ms a sublayer (the mappings 0.64, the
way in 0.41, the way out 1.73 alone), which is why it is not what the
stack calls; the kernel 1.01 alone and 0.89 in the stack, at 128 and at
256 tokens a step alike (its traffic is the floor's and ``y`` in
float32 as its product wrote it: 0.80 ms at the HBM's rate), the first
way in 0.43; :func:`coefficients_from` 0.26 in the stack. PERF.md
section 6 has the account.

*Precision.* The stream is the activations' dtype; the statistic, the
projection's accumulation, the sigmoids, ``exp``, every Sinkhorn step
and both sums are float32 (:data:`COMPUTE`), ``u`` and ``X'`` rounded
once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the mappings' arithmetic, as the configuration states it. Lowered
#: from outside it has to come out not correct: ``tests/test_xing4.py``
#: does that under the interpreter, ``scripts/prefill_control.py``'s
#: ``mappings_bfloat16`` to what Mosaic compiles for the chip
COMPUTE = jnp.float32
KERNEL_NAME = "hyper_mix"
#: a token's coefficients and logits, token-major: whole lanes
LANES = 128
#: tokens a step, and the channels of a stream mixed at a time
_TOKENS = 128
_CHANNELS = 512
_VMEM_LIMIT = 64 * 2 ** 20


def rows_of(n: int) -> int:
    """The coefficient rows a sublayer's mappings have: pre, post, res."""
    return 2 * n + n * n


def sinkhorn_step(m, hc_eps: float):
    """``m[i][j]``: arrays of one shape, positive -> every column over
    its sum + ``hc_eps``, then every row. Nothing but adds, multiplies
    and one reciprocal a sum."""
    n = len(m)
    m = [list(row) for row in m]
    for j in range(n):
        r = 1.0 / (sum(m[i][j] for i in range(n)) + hc_eps)
        for i in range(n):
            m[i][j] = m[i][j] * r
    for i in range(n):
        r = 1.0 / (sum(m[i]) + hc_eps)
        m[i] = [entry * r for entry in m[i]]
    return m


def sinkhorn(m, iters: int, hc_eps: float):
    """``iters`` x :func:`sinkhorn_step`, every one written out in the
    program, so that XLA makes one fusion of them all."""
    return lax.fori_loop(0, iters, lambda _, m: sinkhorn_step(m, hc_eps),
                         m, unroll=True)


def defect(m):
    """The largest distance of a row or column sum of ``m[i][j]`` from
    1, elementwise."""
    n = len(m)
    sums = [sum(m[i]) for i in range(n)] \
        + [sum(m[i][j] for i in range(n)) for j in range(n)]
    worst = abs(sums[0] - 1.0)
    for s in sums[1:]:
        worst = jnp.maximum(worst, abs(s - 1.0))
    return worst


def coefficients_of(logits, n: int, iters: int, hc_eps: float, clamp):
    """``logits`` (2n + n^2, ...) -> the coefficients of the same shape
    (``h_pre``, ``h_post``, ``H_res`` row-major) and the defect of
    ``H_res`` (...,), in the logits' dtype."""
    pre = jax.nn.sigmoid(logits[:n])
    post = 2.0 * jax.nn.sigmoid(logits[n:2 * n])
    grown = jnp.exp(jnp.clip(logits[2 * n:], clamp[0], clamp[1]))
    m = sinkhorn([[grown[i * n + j] for j in range(n)] for i in range(n)],
                 iters, hc_eps)
    res = jnp.stack([m[i][j] for i in range(n) for j in range(n)])
    return jnp.concatenate([pre, post, res]), defect(m)


def _scale(alpha, n: int):
    """``alpha`` (3,) -> a factor a logit (2n + n^2,)."""
    return jnp.repeat(alpha.astype(COMPUTE), jnp.array([n, n, n * n]),
                      total_repeat_length=rows_of(n))


def logits_of(x, phi, alpha, bias, n: int, eps: float):
    """``x`` (T, n C) the stream; ``phi`` (n C, 2n + n^2); ``alpha``
    (3,), ``bias`` (2n + n^2,) float32 -> (2n + n^2, T): ``alpha (x^
    phi) + bias``, tokens along lanes."""
    f = COMPUTE
    xf = x.astype(f)
    inv = lax.rsqrt(jnp.mean(xf * xf, -1) + eps)
    # x^ phi = (x phi) / rms: the stream enters the product as it lies
    proj = lax.dot_general(phi.astype(x.dtype), x,
                           (((0,), (1,)), ((), ())),
                           preferred_element_type=f)
    return proj * inv[None, :] * _scale(alpha, n)[:, None] \
        + bias.astype(f)[:, None]


def coefficients(x, phi, alpha, bias, *, n: int, iters: int, eps: float,
                 hc_eps: float, clamp):
    """-> (float32 (2n + n^2, T): rows ``h_pre`` | ``h_post`` | ``H_res``
    row-major, tokens along lanes; (T,) the defect of each token's
    ``H_res``) of the stream ``x`` (T, n C)."""
    coef, worst = coefficients_of(logits_of(x, phi, alpha, bias, n, eps),
                                  n, iters, hc_eps, clamp)
    return coef.astype(jnp.float32), worst.astype(jnp.float32)


def mix_in(x, coef, n: int, out_dtype):
    """``u = h_pre X``: ``x`` (T, n C), ``coef``'s first n rows ->
    (T, C)."""
    c = x.shape[-1] // n
    f = COMPUTE
    u = sum(coef[j].astype(f)[:, None] * x[:, j * c:(j + 1) * c].astype(f)
            for j in range(n))
    return u.astype(out_dtype)


def mix_out(x, y, coef, n: int):
    """``X' = H_res X + h_post^T y``: ``x`` (T, n C), ``y`` (T, C) the
    sublayer's output -> (T, n C) in the stream's dtype, rounded once."""
    c = x.shape[-1] // n
    f = COMPUTE
    coef = coef.astype(f)
    streams = [x[:, j * c:(j + 1) * c].astype(f) for j in range(n)]
    y = y.astype(f)
    out = [sum(coef[2 * n + i * n + j][:, None] * streams[j]
               for j in range(n)) + coef[n + i][:, None] * y
           for i in range(n)]
    return jnp.concatenate(out, -1).astype(x.dtype)


# -- token-major: what the stack calls ------------------------------------


def token_major(rows):
    """``rows`` (k, T) -> float32 (T, :data:`LANES`), the k in front."""
    return jnp.pad(rows.astype(jnp.float32).T,
                   ((0, 0), (0, LANES - rows.shape[0])))


def coefficients_from(logits_tm, *, n: int, iters: int, hc_eps: float,
                      clamp):
    """``logits_tm`` (T, LANES) as :func:`enter` wrote them, T whole
    rows of :data:`LANES` -> (float32 (T, LANES): a token's ``h_pre`` |
    ``h_post`` | ``H_res`` row-major in front; (T,) the defect of each
    token's ``H_res``). The Sinkhorn steps run tokens along lanes
    between two small transposes."""
    k, tokens = rows_of(n), logits_tm.shape[0]
    if tokens % LANES:
        raise ValueError("%d tokens: not whole rows of %d lanes"
                         % (tokens, LANES))
    # a coefficient's tokens over sublanes *and* lanes: as (T,) rows each
    # of the 2n + n^2 lies in one sublane of eight, and XLA spent 0.28 ms
    # a sublayer on them, 0.11 of it gathering the rows back into one
    # array (my chip run, PR 62)
    logits = logits_tm[:, :k].T.astype(COMPUTE).reshape(
        k, tokens // LANES, LANES)
    coef, worst = coefficients_of(logits, n, iters, hc_eps, clamp)
    return token_major(coef.reshape(k, tokens)), \
        worst.reshape(tokens).astype(jnp.float32)


def leave_lines(x, y, coef_tm, n: int):
    """The way out on a few lines: ``x`` (lines, n C), ``y`` (lines, C),
    ``coef_tm`` (lines, LANES) -> ``X'`` (lines, n C)."""
    return mix_out(x, y, coef_tm[:, :rows_of(n)].T, n)


def _mix_kernel(*refs, n: int, eps: float, leave: bool, chunk: int):
    """One tile of tokens: the way out (``leave``), then the statistic,
    the projection and ``u`` of what it wrote (of ``x`` without it)."""
    f = COMPUTE
    if leave:
        x_ref, y_ref, coef_ref, phi_ref, scale_ref, bias_ref, \
            new_ref, u_ref, logits_ref = refs
        coef = coef_ref[...].astype(f)
    else:
        x_ref, phi_ref, scale_ref, bias_ref, u_ref, logits_ref = refs
        new_ref = x_ref
    tokens, wide = x_ref.shape
    c = wide // n
    squares = jnp.zeros((tokens, chunk), f)
    proj = jnp.zeros((tokens, LANES), jnp.float32)
    for c0 in range(0, c, chunk):
        def at(j, c0=c0):
            return slice(j * c + c0, j * c + c0 + chunk)
        if leave:
            streams = [x_ref[:, at(j)].astype(f) for j in range(n)]
            y = y_ref[:, c0:c0 + chunk].astype(f)
        for i in range(n):
            if leave:
                def col(k):
                    return coef[:, k:k + 1]
                new = (sum(col(2 * n + i * n + j) * streams[j]
                           for j in range(n))
                       + col(n + i) * y).astype(new_ref.dtype)
                new_ref[:, at(i)] = new
            else:
                new = x_ref[:, at(i)]
            rounded = new.astype(f)
            squares = squares + rounded * rounded
            proj = proj + jnp.dot(new, phi_ref[at(i), :],
                                  preferred_element_type=jnp.float32)
    inv = lax.rsqrt(jnp.sum(squares, -1, keepdims=True).astype(f) / wide
                    + eps)
    logits = proj.astype(f) * inv * scale_ref[...].astype(f) \
        + bias_ref[...].astype(f)
    logits_ref[...] = logits.astype(jnp.float32)
    pre = jax.nn.sigmoid(logits[:, :n])
    for c0 in range(0, c, chunk):
        u = sum(pre[:, j:j + 1]
                * new_ref[:, j * c + c0:j * c + c0 + chunk].astype(f)
                for j in range(n))
        u_ref[:, c0:c0 + chunk] = u.astype(u_ref.dtype)


def _mix_call(x, left, phi, alpha, bias, *, n: int, eps: float,
              interpret: bool):
    """The kernel over the pool. ``left``: None, or (``y``,
    ``coef_tm``) of the sublayer being left."""
    tokens, wide = x.shape
    c = wide // n
    k = rows_of(n)
    step, chunk = _TOKENS, min(_CHANNELS, c)
    if tokens % step or c % chunk or k > LANES:
        raise ValueError("%d tokens in steps of %d, %d channels in "
                         "chunks of %d, %d coefficients"
                         % (tokens, step, c, chunk, k))
    phi = jnp.pad(phi.astype(x.dtype), ((0, 0), (0, LANES - k)))
    scale = jnp.pad(_scale(alpha, n), (0, LANES - k))[None].astype(
        jnp.float32)
    bias = jnp.pad(bias.astype(jnp.float32), (0, LANES - k))[None]

    def tile(width):
        return pl.BlockSpec((step, width), lambda i: (i, 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))
    weights = [whole((wide, LANES)), whole((1, LANES)), whole((1, LANES))]
    made = [jax.ShapeDtypeStruct((tokens, c), x.dtype),
            jax.ShapeDtypeStruct((tokens, LANES), jnp.float32)]
    if left is None:
        operands, in_specs = (x,), [tile(wide)]
        out_specs = [tile(c), tile(LANES)]
    else:
        operands = (x,) + tuple(left)
        in_specs = [tile(wide), tile(c), tile(LANES)]
        made = [jax.ShapeDtypeStruct(x.shape, x.dtype)] + made
        out_specs = [tile(wide), tile(c), tile(LANES)]
    operands += (phi, scale, bias)
    return pl.pallas_call(
        functools.partial(_mix_kernel, n=n, eps=eps,
                          leave=left is not None, chunk=chunk),
        grid=(tokens // step,), in_specs=in_specs + weights,
        out_specs=out_specs, out_shape=made,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        # the projection, the statistic, ``u`` and, leaving, ``X'``
        cost_estimate=pl.CostEstimate(
            flops=tokens * wide * (2 * LANES + 4
                                   + (2 * n + 2 if left else 0)),
            transcendentals=tokens * (n + 1),
            bytes_accessed=sum(v.size * v.dtype.itemsize
                               for v in operands + tuple(made))),
        interpret=interpret, name=KERNEL_NAME,
    )(*operands)


def enter(x, phi, alpha, bias, *, n: int, eps: float,
          interpret: bool = False):
    """The way in of the stream ``x`` (T, n C) as it stands -> (``u``
    (T, C) in the stream's dtype, the sublayer's logits float32 (T,
    LANES) token-major)."""
    return tuple(_mix_call(x, None, phi, alpha, bias, n=n, eps=eps,
                           interpret=interpret))


def leave_enter(x, y, coef_tm, phi, alpha, bias, *, n: int, eps: float,
                interpret: bool = False):
    """A sublayer's way out and the next one's way in: ``x`` (T, n C),
    ``y`` (T, C) the sublayer's output, ``coef_tm`` (T, LANES) its
    coefficients (:func:`coefficients_from`), and the *next* sublayer's
    ``phi``, ``alpha``, ``bias`` -> (``X'`` (T, n C), the next ``u``
    (T, C), the next logits (T, LANES))."""
    return tuple(_mix_call(x, (y, coef_tm), phi, alpha, bias, n=n, eps=eps,
                           interpret=interpret))
