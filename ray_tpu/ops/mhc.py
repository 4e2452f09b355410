"""A residual path of several streams: manifold-constrained
hyper-connections (mHC, arXiv 2512.24880, after hyper-connections, arXiv
2409.19606).

Where every other model carries one residual stream `x` and a sublayer
leaves `x + f(norm(x))`, this one carries n copies, X `[B, T, n, d]`, from
the embedding to the final norm, and each sublayer, with its own `phi`
`[n*d, n*n + 2n]`, `b` `[n*n + 2n]` and `alpha` `[3]`:

    u      = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)   one statistic a
                                                        token over all n*d
    m      = u phi                                      [n*n + 2n]
    H_pre  = sigmoid(alpha_0 m[:n] + b[:n])             [n]
    H_post = 2 sigmoid(alpha_1 m[n:2n] + b[n:2n])       [n]
    A      = clamp(alpha_2 mat(m[2n:]) + b[2n:])        [n, n], row-major
    H_res  = sinkhorn(exp(A))                           doubly stochastic
    h      = sum_i H_pre[i] X[i]                        `read`
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y       `write`, y = f(norm(h))

`stream_maps` is everything down to H_res, `read` and `write` the two
mixes: the plain float32-summing forms, which the tests hold `enter` and
`leave` to and nothing else calls since PR 67. `expand` is the entry (the
embedding repeated into every stream) and `collapse` the exit (the streams
summed).

`enter` and `leave` are what the model runs (`models/transformer.py`'s
`entering` and `residual`, the one rule of how a residual is formed; no
sublayer knows of the streams): a sublayer's mixing as two differentiable
operations with backwards of their own (`jax.custom_vjp`), on the stream
FLAT, `[B, T, n*d]`, stream i the columns `i*d:(i+1)*d` (how the model
carries it; a `[B, T, n, d]` stream is reshaped on the way in and out).
`enter` makes the statistic, the product with phi, the three maps and
`h`, and hands the stream back as an output, so that the cotangent
`leave` gives the stream arrives in `enter`'s backward, which writes the
ONE total gradient of the stream, in the stream's dtype: `dX_from_leave +
H_pre dh + (dm r) phi^T + c X` (c X the statistic's term), with `dphi =
X^T (dm r)` beside it. `leave`'s backward makes its share of the stream's
gradient (`H_res^T dX'`, in the stream's dtype), `dy` and the per-token
dot products that are H_res's and H_post's gradients in one pass over
`dX'`, `X` and `y`. Every sum is float32 and is rounded once; no float32
array of the stream's shape is written. What `enter`'s forward hands its
backward is `m` `[n*n + 2n, B, T]` and `r` `[B, T]`, float32, under the
`checkpoint_name` `MAPS_RESIDUALS`: a layer under `Transformer._remat`
keeps them and makes neither the statistic nor the product again, only
`h` and the 20 rounds on the kept `m`. H_post and H_res are made of m
OUTSIDE `enter`'s `custom_vjp`, in plain `jax.numpy` that autodiff takes
backward (a `jax.vjp` inside a backward rule names its equations by no
scope); H_pre's sigmoid is inside it, its derivative by hand, because its
cotangent is made in the pass that uses it.

A bfloat16 stream has no second and third bfloat16 piece, so its float32
product with phi needs phi's three pieces only: `q = X [phi_hi | phi_mid |
phi_lo]`, ONE pass of the MXU over 3 (n*n + 2n) of its 128 columns, the
three groups of columns then added in float32: the products the
`Precision.HIGHEST` einsum of `stream_maps` forms, in another order of
addition. `dphi` is the same with `dm r` in pieces. `(dm r) phi^T` has two
float32 operands and keeps HIGHEST's six products of pieces, stacked along
the contraction (6 x 24 = 144 rows, two tiles of the MXU).

Each pass over the stream is one of two implementations chosen at trace
time (`stream_mix_impl`, as `ops/kda.kda_delta_impl`): four pallas kernels
(`mhc_enter_fwd`: the product, the statistic, m; `mhc_leave_fwd`;
`mhc_leave_bwd`; `mhc_enter_bwd`) on one TPU device where the stream is
bfloat16, d whole lane tiles and the tokens whole blocks, the same pass in
`jax.numpy` anywhere else (the CPU, a float32 stream, a mesh above one
device: GSPMD can partition those). `h` is `jax.numpy` on both: a kernel
that wrote it would have to run again under remat to give it back, the
product with it, and XLA fuses the read into the norm behind it. The
sigmoids, the clamp, the rounds and their derivatives are `jax.numpy` on
`[n*n + 2n, B, T]` on both.

The maps, the Sinkhorn rounds and both mixes' sums are float32; the stream
is kept in the compute dtype. Three choices of form, all for the TPU's
tiling (PERF.md section 6, PRs 66 and 67): the maps, and every record of
one value a token, live with the tokens as their LAST axis (`[n, B, T]`,
`[n, n, B, T]`; a `[B, T, 4, 4]` array would pad its two minor axes to a
whole tile, and a record handed to a kernel by token made XLA lay the
rounds out rows-minor: the kernels turn a block's record themselves); a
mix is a sum of n scaled slices of the stream, not an einsum over `[n, n]`
(XLA lowers that one to a convolution of 4 x 4 products over the widest
tensor of the step); and the stream is flat, because XLA lays `[B, T, 4,
3584]` out tokens-minor or in `(4, 128)` tiles and a kernel's whole token
rows then cost a relayout at its door. The per-token scale of the
statistic is applied after the product with `phi`, `(x phi) r` for `(x r)
phi`: the normed stream `[B, T, n*d]` f32 is never written.

Scopes (metadata only; PERF.md section 3): `mhc/maps` (all of `enter`
but `h`, forward and backward: the statistic, the product, the maps, the
stream's total gradient), `mhc/pre` (`h`, inside `mhc/maps`: the readers
book an op by the last of these names in its path), `mhc/post` (`leave`,
forward and backward), `mhc/expand`, `mhc/collapse`.
"""

from __future__ import annotations

import functools

from ray_tpu.ops.kda import _dot
from ray_tpu.ops.ssm import _one_tpu_device

# The `checkpoint_name` of what `enter`'s forward hands its backward (m and
# r; module docstring), for a `jax.checkpoint` policy to save, as
# `ops.kda.DELTA_RESIDUALS` is.
MAPS_RESIDUALS = "mhc_maps_residuals"
MIX_LANES = 128
MIX_TOKENS = 128         # tokens a grid step takes (PERF.md section 6, PR 67)


def sinkhorn(m, rounds: int, eps: float):
    """m `[n, n, ...]` positive, rows on the first axis -> `rounds` times
    every row divided by its sum + eps, then every column by its sum +
    eps: doubly stochastic in the limit (the columns sum to 1 within eps
    after every round, the rows within what the last column step moved)."""
    for _ in range(rounds):
        m = m / (m.sum(axis=1, keepdims=True) + eps)
        m = m / (m.sum(axis=0, keepdims=True) + eps)
    return m


def stream_maps(x, phi, b, alpha, *, rounds: int, norm_eps: float,
                hc_eps: float, clamp: float):
    """The stream x `[B, T, n, d]` and a sublayer's phi `[n*d, n*n + 2n]`,
    b `[n*n + 2n]`, alpha `[3]` -> (H_pre `[n, B, T]`, H_post `[n, B, T]`,
    H_res `[n, n, B, T]`), float32 (module docstring)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n, d = x.shape[-2:]
    with jax.named_scope("mhc/maps"):
        x32 = x.astype(f32)
        r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=(-2, -1)) + norm_eps)
        # float32 at the router's precision: a rounded map moves every
        # column of the stream
        m = jnp.einsum("btnd,ndm->mbt", x32,
                       phi.astype(f32).reshape(n, d, -1),
                       precision=jax.lax.Precision.HIGHEST) * r
        alpha, b = alpha.astype(f32), b.astype(f32)[:, None, None]
        pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
        a = jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:], -clamp, clamp)
        res = sinkhorn(jnp.exp(a).reshape((n, n) + a.shape[1:]), rounds,
                       hc_eps)
    return pre, post, res


def read(x, pre):
    """What the sublayer reads: h `[B, T, d]` = sum_i H_pre[i] X[i], summed
    in float32, in the stream's dtype."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc/pre"):
        h = sum(pre[i][..., None] * x[:, :, i].astype(jnp.float32)
                for i in range(x.shape[2]))
        return h.astype(x.dtype)


def write(x, y, post, res):
    """The stream after the sublayer, y `[B, T, d]` what it made:
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y, summed in float32, in
    the stream's dtype."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc/post"):
        n = x.shape[2]
        y32 = y.astype(jnp.float32)
        streams = [x[:, :, j].astype(jnp.float32) for j in range(n)]
        out = [sum(res[i, j][..., None] * streams[j] for j in range(n))
               + post[i][..., None] * y32 for i in range(n)]
        return jnp.stack(out, axis=2).astype(x.dtype)


def expand(x, n: int, flat: bool = False):
    """The embedding x `[B, T, d]` repeated into n streams, `[B, T, n, d]`
    or `flat` `[B, T, n*d]`."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc/expand"):
        if flat:
            return jnp.concatenate([x] * n, axis=-1)
        return jnp.broadcast_to(x[:, :, None, :],
                                x.shape[:2] + (n, x.shape[-1]))


def collapse(x, n=None):
    """The streams `[B, T, n, d]`, or flat `[B, T, n*d]` with n given,
    summed (in float32) -> `[B, T, d]`."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc/collapse"):
        if n:
            return sum(_streams(x, n)).astype(x.dtype)
        return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


def _maps_rows(pre, post, res):
    """A sublayer's three maps as the rows `[n*n + 2n, B, T]` of one
    record: H_pre, H_post, then H_res row by row, the order of `m`."""
    import jax.numpy as jnp

    return jnp.concatenate([pre, post, res.reshape((-1,) + res.shape[2:])])


def maps_by_token(pre, post, res):
    """`_maps_rows` by token, `[B, T, n*n + 2n]`."""
    import jax.numpy as jnp

    return jnp.moveaxis(_maps_rows(pre, post, res), 0, -1)


def marginal_error(maps, n: int):
    """The largest |rowsum - 1| and |colsum - 1| of H_res over a stack of
    `maps_by_token` records `[..., n*n + 2n]`."""
    import jax.numpy as jnp

    res = maps[..., 2 * n:].reshape(maps.shape[:-1] + (n, n))
    return jnp.maximum(jnp.max(jnp.abs(res.sum(-1) - 1.0)),
                       jnp.max(jnp.abs(res.sum(-2) - 1.0)))


# ---- the mixing the model runs: `enter` and `leave` ----------------------


def mix_shape_ok(tokens: int, n: int, d: int, dtype) -> bool:
    """Whether the kernels tile a stream: bfloat16 (one piece in a float32
    product), each stream whole lane tiles, the tokens whole blocks, and
    five groups of n*n + 2n columns in one lane tile (n up to 4)."""
    import jax.numpy as jnp

    return (jnp.dtype(dtype) == jnp.bfloat16 and d % MIX_LANES == 0
            and tokens % MIX_TOKENS == 0
            and 5 * (n * n + 2 * n) <= MIX_LANES)


def stream_mix_impl(mesh, tokens: int, n: int, d: int, dtype) -> str:
    """`"pallas"` where the program runs on one TPU device and the kernels
    tile the stream (`mix_shape_ok`; `tokens` is B*T), else `"xla"` (the
    same passes in `jax.numpy`: any platform, any shape, a float32 stream,
    and GSPMD can partition them). Decided at trace time, as
    `ops/kda.kda_delta_impl` decides for the delta rule."""
    return "pallas" if _one_tpu_device(mesh) and mix_shape_ok(
        tokens, n, d, dtype) else "xla"


def _pieces(a):
    """A float32 array as three bfloat16 pieces `[3, ...]` (float32 holds
    them), hi + mid + lo = a to float32's last bit: what
    `Precision.HIGHEST` multiplies."""
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    a = a.astype(f32)
    hi = a.astype(bf16).astype(f32)
    mid = (a - hi).astype(bf16).astype(f32)
    lo = (a - hi - mid).astype(bf16).astype(f32)
    return jnp.stack([hi, mid, lo])


def _streams(x, n: int):
    """The n streams of a flat stream `[..., n*d]`, float32."""
    import jax.numpy as jnp

    d = x.shape[-1] // n
    return [x[..., i * d:(i + 1) * d].astype(jnp.float32) for i in range(n)]


# -- the passes over the stream in `jax.numpy` (the "xla" implementation);
# the kernels below are the same passes. x, dx `[B, T, n*d]`, y, dh `[B, T,
# d]`; what there is one of a token lives tokens LAST, `[rows, B, T]`
# float32, as the maps do: a record's row k is `record[k]`.


def _stats_xla(x, phi, norm_eps: float, n: int):
    """x and phi `[n*d, M]` -> (m `[M, B, T]`: the product (module
    docstring: phi's three pieces where x is bfloat16, the HIGHEST product
    of two float32 operands else) under the statistic; the statistic r
    `[B, T]`), float32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if x.dtype == jnp.bfloat16:
        q = sum(_dot(x, piece.astype(jnp.bfloat16), ((2,), (0,)))
                for piece in _pieces(phi))
    else:
        q = _dot(x.astype(f32), phi.astype(f32), ((2,), (0,)), exact=True)
    ssq = sum(jnp.sum(s * s, axis=-1) for s in _streams(x, n))
    r = jax.lax.rsqrt(ssq / x.shape[-1] + norm_eps)
    return jnp.moveaxis(q, -1, 0) * r, r


def _leave_fwd_xla(x, y, maps, n: int):
    """`write`'s X' on a flat stream: maps `[M, B, T]` the sublayer's
    record (`_maps_rows`)."""
    import jax.numpy as jnp

    xs, y32 = _streams(x, n), y.astype(jnp.float32)
    out = [sum(maps[2 * n + i * n + j][..., None] * xs[j] for j in range(n))
           + maps[n + i][..., None] * y32 for i in range(n)]
    return jnp.concatenate(out, -1).astype(x.dtype)


def _leave_bwd_xla(dxp, x, y, maps, n: int):
    """`leave`'s backward: dxp `[B, T, n*d]` the cotangent of X' -> (the
    stream's share `H_res^T dX'` in the stream's dtype, dy, the record's
    cotangent `[M, B, T]`: 0 for H_pre, `<dX'[i], y>` for H_post,
    `<dX'[i], X[j]>` for H_res)."""
    import jax.numpy as jnp

    dxs, xs, y32 = _streams(dxp, n), _streams(x, n), y.astype(jnp.float32)
    dy = sum(maps[n + i][..., None] * dxs[i] for i in range(n))
    dxa = [sum(maps[2 * n + i * n + j][..., None] * dxs[i] for i in range(n))
           for j in range(n)]
    dots = [jnp.zeros_like(maps[0])] * n \
        + [jnp.sum(dxs[i] * y32, -1) for i in range(n)] \
        + [jnp.sum(dxs[i] * xs[j], -1) for i in range(n) for j in range(n)]
    return (jnp.concatenate(dxa, -1).astype(x.dtype), dy.astype(y.dtype),
            jnp.stack(dots))


def _enter_record(d_m, m, pre, slope, r, n: int):
    """What `enter`'s backward reads of a token, `[M + 3n + 2, B, T]`
    float32: the cotangent of m with H_pre's own cotangent in its first n
    rows (the pass adds the read's to it and takes both through the
    sigmoid), H_pre, the sigmoid's slope times alpha_0, m's first n rows,
    r, and `<dm, m>` over the other rows."""
    import jax.numpy as jnp

    rest = jnp.sum(d_m[n:] * m[n:], axis=0)
    return jnp.concatenate([d_m, pre, slope, m[:n], r[None], rest[None]])


def _enter_bwd_xla(x, dxin, dh, record, phi, n: int):
    """`enter`'s backward over the stream: record `_enter_record`'s ->
    (the stream's total gradient in its dtype, the read's cotangent of
    H_pre `[n, B, T]`, dphi `[n*d, M]` float32)."""
    import jax.numpy as jnp

    f32 = jnp.float32
    maps = phi.shape[1]
    xs, dxs, dh32 = _streams(x, n), _streams(dxin, n), dh.astype(f32)
    d = dh.shape[-1]
    r, dot_m = record[maps + 3 * n], record[maps + 3 * n + 1]
    read = [jnp.sum(dh32 * xs[i], -1) for i in range(n)]
    head = [(record[i] + read[i]) * record[maps + n + i] for i in range(n)]
    dot_m = dot_m + sum(head[i] * record[maps + 2 * n + i] for i in range(n))
    c = (-dot_m * r * r / (n * d))[..., None]
    g = jnp.moveaxis(jnp.concatenate(
        [jnp.stack(head), record[n:maps]]) * r, 0, -1)
    through_phi = _dot(g, phi.astype(f32), ((2,), (1,)), exact=True)
    dx = [dxs[i] + record[maps + i][..., None] * dh32
          + through_phi[..., i * d:(i + 1) * d] + c * xs[i]
          for i in range(n)]
    batch = tuple(range(x.ndim - 1))
    if x.dtype == jnp.bfloat16:
        dphi = sum(_dot(x, piece.astype(jnp.bfloat16), (batch, batch))
                   for piece in _pieces(g))
    else:
        dphi = _dot(x.astype(f32), g, (batch, batch), exact=True)
    return (jnp.concatenate(dx, -1).astype(x.dtype), jnp.stack(read), dphi)


# -- the same passes as pallas TPU kernels over blocks of MIX_TOKENS whole
# token rows. A record comes and goes tokens last, as XLA keeps it (rows
# on the sublanes, a block's tokens on the lanes), and is turned in the
# kernel: by token, `[tokens, 128]`, row k is lane k, and a token's value
# scales that token's row of the stream as a `[tokens, 1]` column. (A
# record handed over by token, `[B*T, rows]`, made XLA lay the maps' own
# arithmetic out rows-minor, 16 of 128 lanes: PERF.md section 6, PR 67.)


def _rows8(k: int) -> int:
    return -(-k // 8) * 8


def _by_token(rows):
    """`[rows, tokens]` float32 (whole sublane tiles of rows, one lane
    tile of tokens) -> `[tokens, 128]`, 0 in the lanes past the rows."""
    import jax.numpy as jnp

    k, tokens = rows.shape
    return jnp.concatenate(
        [rows, jnp.zeros((MIX_LANES - k, tokens), rows.dtype)]).T


def _at_lane(lane, k: int, column):
    """`column` `[tokens, 1]` at lane k of a `[tokens, 128]` record, 0
    elsewhere."""
    import jax.numpy as jnp

    return jnp.where(lane == k, column, 0.0)


def _enter_fwd_kernel(x_ref, phi_ref, out_ref, *, n: int, maps: int,
                      norm_eps: float):
    """out `[rows, tokens]`: m's rows, then r."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    q = jnp.dot(x_ref[...], phi_ref[...], preferred_element_type=f32)
    # the three pieces' products lie side by side: add them lane by lane
    q = q + pltpu.roll(q, MIX_LANES - maps, 1) \
        + pltpu.roll(q, MIX_LANES - 2 * maps, 1)
    d = x_ref.shape[1] // n
    ssq = 0.0
    for i in range(n):
        s = x_ref[:, i * d:(i + 1) * d].astype(f32)
        ssq = ssq + jnp.sum(s * s, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(ssq / (n * d) + norm_eps)
    lane = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
    out = jnp.where(lane < maps, q * r, _at_lane(lane, maps, r))
    out_ref[...] = out.T[:out_ref.shape[0]]


def _leave_fwd_kernel(x_ref, y_ref, maps_ref, out_ref, *, n: int):
    import jax.numpy as jnp

    f32 = jnp.float32
    d = y_ref.shape[1]
    maps = _by_token(maps_ref[...])
    y = y_ref[...].astype(f32)
    xs = [x_ref[:, j * d:(j + 1) * d].astype(f32) for j in range(n)]
    for i in range(n):
        out = maps[:, n + i:n + i + 1] * y
        for j in range(n):
            k = 2 * n + i * n + j
            out = out + maps[:, k:k + 1] * xs[j]
        out_ref[:, i * d:(i + 1) * d] = out.astype(out_ref.dtype)


def _leave_bwd_kernel(dxp_ref, x_ref, y_ref, maps_ref, dxa_ref, dy_ref,
                      dots_ref, *, n: int):
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    d = y_ref.shape[1]
    maps = _by_token(maps_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, maps.shape, 1)
    y = y_ref[...].astype(f32)
    dxs = [dxp_ref[:, i * d:(i + 1) * d].astype(f32) for i in range(n)]
    dots, dy = jnp.zeros(maps.shape, f32), 0.0
    for i in range(n):
        dy = dy + maps[:, n + i:n + i + 1] * dxs[i]
        dots = dots + _at_lane(lane, n + i,
                               jnp.sum(dxs[i] * y, -1, keepdims=True))
    dy_ref[...] = dy.astype(dy_ref.dtype)
    for j in range(n):
        xj = x_ref[:, j * d:(j + 1) * d].astype(f32)
        share = 0.0
        for i in range(n):
            k = 2 * n + i * n + j
            share = share + maps[:, k:k + 1] * dxs[i]
            dots = dots + _at_lane(lane, k,
                                   jnp.sum(dxs[i] * xj, -1, keepdims=True))
        dxa_ref[:, j * d:(j + 1) * d] = share.astype(dxa_ref.dtype)
    dots_ref[...] = dots.T[:dots_ref.shape[0]]


def _enter_bwd_kernel(x_ref, dxin_ref, dh_ref, record_ref, phit_ref, dx_ref,
                      read_ref, dphi_ref, *, n: int, maps: int):
    """`_enter_bwd_xla` on a block of tokens. phit `[256, n*d]` bfloat16,
    `_phi_rows`'; dphi `[128, n*d]` float32 is summed over the
    grid's steps, its rows phi's columns against g's three pieces."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, bf16 = jnp.float32, jnp.bfloat16
    d = dh_ref.shape[1]
    record = _by_token(record_ref[...])
    lane = jax.lax.broadcasted_iota(jnp.int32, record.shape, 1)

    def col(k):
        return record[:, k:k + 1]

    r, dot_m = col(maps + 3 * n), col(maps + 3 * n + 1)
    dh = dh_ref[...].astype(f32)
    xs = [x_ref[:, i * d:(i + 1) * d].astype(f32) for i in range(n)]
    dm = jnp.where((lane >= n) & (lane < maps), record, 0.0)
    read = jnp.zeros(record.shape, f32)
    for i in range(n):
        read_i = jnp.sum(dh * xs[i], -1, keepdims=True)
        read = read + _at_lane(lane, i, read_i)
        head = (col(i) + read_i) * col(maps + n + i)
        dm = dm + _at_lane(lane, i, head)
        dot_m = dot_m + head * col(maps + 2 * n + i)
    read_ref[...] = read.T[:read_ref.shape[0]]
    c = -dot_m * r * r / (n * d)
    g = dm * r                              # [tokens, 128], 0 past `maps`
    hi = g.astype(bf16).astype(f32)
    mid = (g - hi).astype(bf16).astype(f32)
    lo = (g - hi - mid).astype(bf16).astype(f32)

    def at(a, group):   # a's first lanes moved to the group'th `maps` lanes
        return pltpu.roll(a, group * maps, 1) if group else a

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, f32)

    by_piece = (hi + at(mid, 1) + at(lo, 2)).astype(bf16)
    dphi_ref[...] += _dot(by_piece, x_ref[...], ((0,), (0,)))
    # HIGHEST's six products of pieces, stacked along the contraction
    # against `_phi_rows`': hi hi, hi mid, mid hi, mid mid, hi lo |
    # lo hi
    stacked = jnp.concatenate(
        [hi + at(hi, 1) + at(mid, 2) + at(mid, 3) + at(hi, 4), lo],
        axis=1).astype(bf16)
    for i in range(n):
        through_phi = jnp.dot(stacked, phit_ref[:, i * d:(i + 1) * d],
                              preferred_element_type=f32)
        dx_ref[:, i * d:(i + 1) * d] = (
            dxin_ref[:, i * d:(i + 1) * d].astype(f32) + col(maps + i) * dh
            + through_phi + c * xs[i]).astype(dx_ref.dtype)


def _padded(blocks, axis: int):
    """`blocks` side by side along `axis`, 0 up to a lane tile, bfloat16."""
    import jax.numpy as jnp

    a = jnp.concatenate(blocks, axis)
    pad = [(0, 0), (0, 0)]
    pad[axis] = (0, MIX_LANES - a.shape[axis])
    return jnp.pad(a, pad).astype(jnp.bfloat16)


def _phi_columns(phi):
    """phi `[n*d, M]` -> `[n*d, 128]` bfloat16: its three pieces side by
    side, for the product with the stream."""
    return _padded(list(_pieces(phi)), 1)


def _phi_rows(phi):
    """phi `[n*d, M]` -> `[256, n*d]` bfloat16: its pieces transposed, in
    the order `_enter_bwd_kernel` stacks g's against."""
    import jax.numpy as jnp

    hi, mid, lo = (piece.T for piece in _pieces(phi))
    return jnp.concatenate([_padded([hi, mid, hi, mid, lo], 0),
                            _padded([hi], 0)])


@functools.lru_cache(maxsize=None)
def _passes(impl: str, n: int, interpret: bool):
    """(`_stats_xla`, `_leave_fwd_xla`, `_leave_bwd_xla`, `_enter_bwd_xla`)
    for n streams, or under "pallas" the four kernels behind the same
    signatures, each `pallas_call` behind a `jax.jit` of its own for the
    reason of `ops/ssm._scan_calls`: a pallas kernel's body is traced anew
    by every call, in every program of a job."""
    if impl != "pallas":
        return (functools.partial(_stats_xla, n=n),
                functools.partial(_leave_fwd_xla, n=n),
                functools.partial(_leave_bwd_xla, n=n),
                functools.partial(_enter_bwd_xla, n=n))
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32, bt, w = jnp.float32, MIX_TOKENS, MIX_LANES
    params = pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=100 * 1024 * 1024)

    def by_token(width):     # a block of whole token rows of the stream
        return pl.BlockSpec((bt, width), lambda t: (t, 0))

    def by_row(rows):        # the same tokens of a record, tokens last
        return pl.BlockSpec((rows, bt), lambda t: (0, t))

    def whole(shape):        # an operand every step reads, or sums into
        return pl.BlockSpec(shape, lambda t: (0, 0))

    def flat(a):
        return a.reshape(-1, a.shape[-1])

    def rows(record):        # `[k, B, T]` -> `[k up to a sublane tile, B*T]`
        record = record.reshape(record.shape[0], -1)
        return jnp.pad(record, ((0, _rows8(len(record)) - len(record)),
                                (0, 0)))

    @functools.partial(jax.jit, inline=True, static_argnums=2)
    def stats(x, phi, norm_eps):
        tokens, nd = flat(x).shape
        maps = phi.shape[1]
        out = pl.pallas_call(
            functools.partial(_enter_fwd_kernel, n=n, maps=maps,
                              norm_eps=norm_eps),
            grid=(tokens // bt,),
            in_specs=[by_token(nd), whole((nd, w))],
            out_specs=by_row(_rows8(maps + 1)),
            out_shape=jax.ShapeDtypeStruct((_rows8(maps + 1), tokens), f32),
            compiler_params=params, interpret=interpret,
            name="mhc_enter_fwd")(flat(x), _phi_columns(phi))
        lead = x.shape[:-1]
        return out[:maps].reshape((maps,) + lead), out[maps].reshape(lead)

    @functools.partial(jax.jit, inline=True)
    def leave_fwd(x, y, maps):
        tokens, nd = flat(x).shape
        return pl.pallas_call(
            functools.partial(_leave_fwd_kernel, n=n),
            grid=(tokens // bt,),
            in_specs=[by_token(nd), by_token(nd // n),
                      by_row(_rows8(len(maps)))],
            out_specs=by_token(nd),
            out_shape=jax.ShapeDtypeStruct((tokens, nd), x.dtype),
            compiler_params=params, interpret=interpret,
            name="mhc_leave_fwd")(flat(x), flat(y), rows(maps)).reshape(
                x.shape)

    @functools.partial(jax.jit, inline=True)
    def leave_bwd(dxp, x, y, maps):
        tokens, nd = flat(x).shape
        d, k = nd // n, _rows8(len(maps))
        dxa, dy, dots = pl.pallas_call(
            functools.partial(_leave_bwd_kernel, n=n),
            grid=(tokens // bt,),
            in_specs=[by_token(nd), by_token(nd), by_token(d), by_row(k)],
            out_specs=[by_token(nd), by_token(d), by_row(k)],
            out_shape=[jax.ShapeDtypeStruct((tokens, nd), x.dtype),
                       jax.ShapeDtypeStruct((tokens, d), y.dtype),
                       jax.ShapeDtypeStruct((k, tokens), f32)],
            # the share of the stream's gradient where dX' was: read and
            # written block by block, no second buffer of the stream's size
            input_output_aliases={0: 0},
            compiler_params=params, interpret=interpret,
            name="mhc_leave_bwd")(flat(dxp), flat(x), flat(y), rows(maps))
        return (dxa.reshape(x.shape), dy.reshape(y.shape),
                dots[:len(maps)].reshape(maps.shape))

    @functools.partial(jax.jit, inline=True)
    def enter_bwd(x, dxin, dh, record, phi):
        tokens, nd = flat(x).shape
        d, maps = nd // n, phi.shape[1]
        dx, read, dphi = pl.pallas_call(
            functools.partial(_enter_bwd_kernel, n=n, maps=maps),
            grid=(tokens // bt,),
            in_specs=[by_token(nd), by_token(nd), by_token(d),
                      by_row(_rows8(len(record))), whole((2 * w, nd))],
            out_specs=[by_token(nd), by_row(_rows8(n)), whole((w, nd))],
            out_shape=[jax.ShapeDtypeStruct((tokens, nd), x.dtype),
                       jax.ShapeDtypeStruct((_rows8(n), tokens), f32),
                       jax.ShapeDtypeStruct((w, nd), f32)],
            input_output_aliases={1: 0},     # as in `leave_bwd`
            compiler_params=params, interpret=interpret,
            name="mhc_enter_bwd")(flat(x), flat(dxin), flat(dh),
                                  rows(record), _phi_rows(phi))
        dphi = dphi[:maps] + dphi[maps:2 * maps] + dphi[2 * maps:3 * maps]
        return (dx.reshape(x.shape),
                read[:n].reshape((n,) + x.shape[:-1]), dphi.T)

    return stats, leave_fwd, leave_bwd, enter_bwd


@functools.lru_cache(maxsize=None)
def _enter_op(impl: str, n: int, norm_eps: float, interpret: bool):
    """(x, phi, b's first n, alpha_0) -> (h, H_pre, the rest of m `[M - n,
    B, T]`, x) on a flat stream x `[B, T, n*d]` under `jax.custom_vjp`
    (module docstring), its passes over the stream `impl`'s. H_post and
    H_res are made of m's rest outside it, by `enter`, and autodiff hands
    their cotangent of m back in. Built once a process and
    configuration."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    f32 = jnp.float32
    stats, _, _, enter_bwd = _passes(impl, n, interpret)

    # `enter` calls this under `mhc/maps`: the scope of a `custom_vjp`'s
    # call is its backward rule's too
    def enter_fwd(x, phi, b, alpha):
        m, r = checkpoint_name(stats(x, phi, norm_eps), MAPS_RESIDUALS)
        pre = jax.nn.sigmoid(alpha.astype(f32) * m[:n]
                             + b.astype(f32)[:, None, None])
        with jax.named_scope("mhc/pre"):
            h = sum(pre[i][..., None] * s
                    for i, s in enumerate(_streams(x, n))).astype(x.dtype)
        return (h, pre, m[n:], x), (x, phi, b, alpha, m, r, pre)

    def enter_bwd_rule(kept, cotangents):
        x, phi, b, alpha, m, r, pre = kept
        dh, d_pre, d_rest, dxin = cotangents
        slope = pre * (1.0 - pre)
        record = _enter_record(jnp.concatenate([d_pre, d_rest]), m, pre,
                               alpha.astype(f32) * slope, r, n)
        dx, read_pre, d_phi = enter_bwd(x, dxin, dh, record, phi)
        # through the sigmoid: H_pre's own cotangent and the read's
        through = (d_pre + read_pre) * slope
        d_b = jnp.sum(through, axis=tuple(range(1, through.ndim)))
        d_alpha = jnp.sum(through * m[:n])
        return (dx, d_phi.astype(phi.dtype), d_b.astype(b.dtype),
                d_alpha.astype(alpha.dtype))

    @jax.custom_vjp
    def op(x, phi, b, alpha):
        return enter_fwd(x, phi, b, alpha)[0]

    op.defvjp(enter_fwd, enter_bwd_rule)
    return op


@functools.lru_cache(maxsize=None)
def _leave_op(impl: str, n: int, interpret: bool):
    """(x, y, H_post, H_res) -> X' on a flat stream under
    `jax.custom_vjp`, its passes `impl`'s."""
    import jax
    import jax.numpy as jnp

    _, forward, backward, _ = _passes(impl, n, interpret)

    def record(post, res):
        return _maps_rows(jnp.zeros_like(post), post, res)

    # called under `mhc/post`, as `_enter_op`'s is under `mhc/maps`
    def leave_fwd(x, y, post, res):
        return forward(x, y, record(post, res)), (x, y, post, res)

    def leave_bwd_rule(kept, dxp):
        x, y, post, res = kept
        dxa, dy, dots = backward(dxp, x, y, record(post, res))
        return dxa, dy, dots[n:2 * n], dots[2 * n:].reshape(res.shape)

    @jax.custom_vjp
    def op(x, y, post, res):
        return leave_fwd(x, y, post, res)[0]

    op.defvjp(leave_fwd, leave_bwd_rule)
    return op


def _flat(x, n: int, d: int, mesh, interpret: bool):
    """(the stream x, `[B, T, n, d]` or flat, as `[B, T, n*d]`; the
    implementation of its passes)."""
    import math

    tokens = math.prod(x.shape[:2])
    impl = "pallas" if interpret and mix_shape_ok(tokens, n, d, x.dtype) \
        else stream_mix_impl(mesh, tokens, n, d, x.dtype)
    return x.reshape(x.shape[:2] + (n * d,)), impl


def enter(x, phi, b, alpha, *, rounds: int, norm_eps: float, hc_eps: float,
          clamp: float, mesh=None, interpret: bool = False):
    """The stream x (`[B, T, n, d]` or flat) and a sublayer's phi, b, alpha
    -> (h `[B, T, d]` what the sublayer reads, (H_pre, H_post, H_res) as
    `stream_maps` gives them, and the stream itself, which `leave` is to
    be handed: module docstring). `mesh` is what the program runs on, for
    `stream_mix_impl`'s choice; `interpret` is the tests' (the kernels, on
    the CPU)."""
    import math

    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n = math.isqrt(phi.shape[1] + 1) - 1
    flat, impl = _flat(x, n, phi.shape[0] // n, mesh, interpret)
    with jax.named_scope("mhc/maps"):
        h, pre, rest, through = _enter_op(impl, n, norm_eps, interpret)(
            flat, phi, b[:n], alpha[0])
        alpha, b = alpha.astype(f32), b.astype(f32)[:, None, None]
        post = 2.0 * jax.nn.sigmoid(alpha[1] * rest[:n] + b[n:2 * n])
        a = jnp.clip(alpha[2] * rest[n:] + b[2 * n:], -clamp, clamp)
        res = sinkhorn(jnp.exp(a).reshape((n, n) + a.shape[1:]), rounds,
                       hc_eps)
    return h, (pre, post, res), through.reshape(x.shape)


def leave(x, y, post, res, *, mesh=None, interpret: bool = False):
    """The stream after the sublayer, `write`'s X' in x's shape: x the
    stream as `enter` handed it back, y `[B, T, d]` what the sublayer
    made."""
    import jax

    n = post.shape[0]
    flat, impl = _flat(x, n, y.shape[-1], mesh, interpret)
    with jax.named_scope("mhc/post"):
        return _leave_op(impl, n, interpret)(flat, y, post, res).reshape(
            x.shape)
