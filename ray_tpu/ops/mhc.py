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
mixes, `expand` the entry (the embedding repeated into every stream) and
`collapse` the exit (the streams summed). `models/transformer.py` calls
them from `entering` and `residual`, the one rule of how a residual is
formed; no sublayer knows of the streams.

Plain `jax.numpy` that XLA fuses, no kernel. The maps, the Sinkhorn rounds
and both mixes' sums are float32; the stream is kept in the compute dtype.
Two choices of form, both for the TPU's tiling (PERF.md section 6, PR 66):
the maps live with the tokens as their LAST axis (`[n, B, T]`,
`[n, n, B, T]`; a `[B, T, 4, 4]` array would pad its two minor axes to a
whole tile), and a mix is a sum of n scaled slices of the stream, not an
einsum over `[n, n]` (XLA lowers that one to a convolution of 4 x 4
products over the widest tensor of the step). The per-token scale of the
statistic is applied after the product with `phi`, `(x phi) r` for
`(x r) phi`: the normed stream `[B, T, n*d]` f32 is never written.

Scopes (metadata only; PERF.md section 3): `mhc/maps`, `mhc/pre`,
`mhc/post`, `mhc/expand`, `mhc/collapse`.
"""

from __future__ import annotations


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


def expand(x, n: int):
    """The embedding x `[B, T, d]` repeated into n streams."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc/expand"):
        return jnp.broadcast_to(x[:, :, None, :],
                                x.shape[:2] + (n, x.shape[-1]))


def collapse(x):
    """The streams `[B, T, n, d]` summed (in float32) -> `[B, T, d]`."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("mhc/collapse"):
        return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


def maps_by_token(pre, post, res):
    """A sublayer's three maps as one record `[B, T, n*n + 2n]`: H_pre,
    H_post, then H_res row by row, the order of `m`."""
    import jax.numpy as jnp

    flat = jnp.concatenate(
        [pre, post, res.reshape((-1,) + res.shape[2:])])
    return jnp.moveaxis(flat, 0, -1)


def marginal_error(maps, n: int):
    """The largest |rowsum - 1| and |colsum - 1| of H_res over a stack of
    `maps_by_token` records `[..., n*n + 2n]`."""
    import jax.numpy as jnp

    res = maps[..., 2 * n:].reshape(maps.shape[:-1] + (n, n))
    return jnp.maximum(jnp.max(jnp.abs(res.sum(-1) - 1.0)),
                       jnp.max(jnp.abs(res.sum(-2) - 1.0)))
