"""Mamba-2 mixer: a selective state-space layer in its chunked (SSD) form;
at the end of the module the Mamba-1 mixer (`selective_scan`,
`selective_scan_pallas`, `mamba1_mixer`), whose decay differs for every
channel and state index: its scan too is two implementations of one
algorithm, chosen at trace time by `selective_scan_impl`.

The reference has no state-space layer of any kind; this fills that row
beside `ops/attention.py` and `ops/moe.py`. One mixer, H heads of width P
over G groups of state N (Dao & Gu, "Transformers are SSMs", 2024, as
`nemotron_h` and `mamba2` publish it):

    [z | x | B | C | dt] = h W_in          widths H·P, H·P, G·N, G·N, H
    [x | B | C] = silu(conv1d([x | B | C]))  depthwise, causal, with bias
    dt = softplus(dt + dt_bias),  A = -exp(A_log)            per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T               S is P x N
    y_t = S_t C_t + D x_t            head h reads its group's B and C
    y = grouped_rmsnorm(y * silu(z)) * gain   groups of H·P / G channels
    out = y W_out

**The scan in chunks**: within a chunk of Q steps the recurrence unrolls
into the quadratic form `y_i = sum_{j<=i} L_ij (C_i.B_j) dt_j x_j` with
`L_ij = exp(sum_{j<m<=i} dt_m A)`; what a chunk adds to the state is one
more product, and the T/Q chunk states are chained. The decays, their
running sums `cum` and the state are float32; the products take their
operands in the compute dtype and accumulate in float32; `y` leaves in
float32. Nothing is `[T, T]`. T must be a multiple of the chunk: any
other T is refused, not padded. Two implementations of the one
algorithm, chosen at trace time by `ssd_scan_impl` (no option, in the
manner of `ops/moe.grouped_matmul_impl`):

- `"xla"` (`ssd_scan`): three einsums over `[T/Q, H, Q, Q]` blocks, the
  chunk states, a `lax.scan` over them, autodiff's backward. The blocks
  and the `[T/Q, H, P, N]` states go through HBM. It runs on the CPU, on
  a mesh above one device (GSPMD cannot partition a pallas call) and for
  every shape the kernel does not tile, and it is the oracle of the
  kernel's tests.
- `"pallas"` (`ssd_scan_pallas`): on one TPU device where the shapes tile
  (`scan_shape_ok`). Grid `(batch, head blocks, chunks)`, the chunk axis
  sequential; one grid step takes one chunk and the heads of one block
  (`scan_head_block`: at most `SCAN_WIDTH` lanes of `x`, inside one B/C
  group). It reads `x [Q, heads·P]`, `B [Q, N]`, `C [Q, N]` as lane-dense
  blocks of the `[B, T, H·P]` and `[B, T, G·N]` layouts the convolution
  leaves (no transpose or reshape of `x` or `y` around the call), `dt`
  with the steps on the lanes `[heads, Q]` (1 MB, turned by XLA) and `a`.
  In VMEM and never in HBM: `cum` (a float32 product of `dt a` with a
  0/1 matrix at the highest precision: the running sum and both of its
  layouts in one), `C Bᵀ` once a group, per head the masked decay
  `exp(cum_i - cum_j)` (masked before the exponential) and the weights
  `[Q, Q]`, and the carried state `[N, heads·P]` float32 in a scratch
  that persists over the chunk axis. Two heads of 64 share a lane tile:
  each head's weights multiply the tile's 128 lanes (the MXU is that
  wide anyway) and a lane mask keeps its half. The forward
  (`ssd_scan_fwd`) also writes the state entering each chunk,
  `[T/Q, N, H·P]` float32: its one residual, alive between remat's
  forward and the backward of the same layer. The backward
  (`ssd_scan_bwd`, under `jax.custom_vjp`) walks the chunks in reverse
  with the state's cotangent carried in VMEM, makes a chunk's `cum`,
  decays and weights again, reads `x`, `B`, `C`, `dt`, `a`, the entering
  states and `dy`, and returns `dx`, `dB`, `dC`, `ddt` and the cotangent
  of `dt a`, from which `da` is one sum. Roundings as above; `x *
  to_end` is formed in float32 before its one rounding where `ssd_scan`
  multiplies two rounded factors, and `cum`'s sums are added in the
  MXU's order, not `cumsum`'s.

**Packed documents.** `segment_ids` `[B, T]` int32 (one id a position,
each document one run of equal ids; None: one document a sequence, and
nothing below is traced) reach `mamba2_mixer`, `causal_conv` and both
scans, and a document's outputs and gradients are those of the document
run alone, exactly: no large negative decay, a masked term is 0.

- `causal_conv`: a tap that lies in another document reads zero, as a
  tap before t = 0 does.
- the scans: `S_t` starts from zero at a document's first step.
  Boundaries fall anywhere, so inside a chunk the pairs (i, j) of two
  documents are masked with the pairs above the diagonal, before the
  exponential; the state that enters a chunk reaches the steps of the
  document the chunk before ended in and no others (`carry`); of a
  chunk's steps those of its last document alone add to the state that
  leaves it, and the entering state passes through only where the whole
  chunk is that one document (`keep`, and `carry` of the last step:
  `chunk_marks`). The backward masks the same three terms, so the state's
  cotangent stops where the state did. `ssd_scan` multiplies by the
  marks; the kernels take one operand more, `[B, 8, T]` float32 rows
  (ids, `carry`, `keep`) in blocks of `[8, Q]`, which ride the float32
  turn that lays `dt` down the sublanes (`_decays`), so a chunk costs no
  product more. The marks and masks outside the kernels are built under
  the scope `segments` (`ssm/conv/segments`, `ssm/scan/segments`).
- Mamba-1 (`selective_scan`, its kernels, `mamba1_mixer`) takes no
  `segment_ids`: `models/transformer.py` refuses them for its kinds.

**A share of the heads.** The mixer is told its heads and groups by the
weights it is given (`A_log` has H entries, the convolution H·P + 2·G·N
channels): given the columns of `W_in`, the channels of the convolution
and the norm, and the rows of `W_out` that belong to some of the groups
with their heads, it computes that share's part of the output
projection's sum. The gated norm's groups are the B/C groups, so it stays
local to a share. No code stands in for absent heads.

Scopes (PERF.md section 3): `ssm/in_proj`, `ssm/conv`, `ssm/scan`,
`ssm/gate_norm`, `ssm/out_proj`; a Mamba-1 mixer `ssm/in_proj`,
`ssm/conv`, `ssm/x_proj`, `ssm/scan`, `ssm/gate`, `ssm/out_proj`.
"""

from __future__ import annotations

import functools
from typing import Any, Dict


def causal_conv(x, w, b, segment_ids=None):
    """Depthwise causal convolution over time: x `[B, T, C]`, w `[C, K]`,
    b `[C]` -> `y_t = b + sum_j w[:, j] x_{t-K+1+j}` (zeros before t = 0).
    K shifted multiply-adds: K is 4. With `segment_ids` `[B, T]` (packed
    documents, module docstring) a tap that would read another document
    reads zero, as it does before t = 0."""
    import jax
    import jax.numpy as jnp

    k, t = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    if segment_ids is not None:
        with jax.named_scope("segments"):
            ids = jnp.pad(segment_ids, ((0, 0), (k - 1, 0)),
                          constant_values=-1)
            same = [(ids[:, j:j + t] == segment_ids)[..., None]
                    for j in range(k - 1)]
    y = b.astype(x.dtype)
    for j in range(k):
        tap = padded[:, j:j + t]
        if segment_ids is not None and j < k - 1:
            tap = jnp.where(same[j], tap, 0)
        y = y + tap * w[:, j].astype(x.dtype)
    return y


def chunk_marks(segment_ids, chunk: int):
    """What a chunked scan needs of packed documents (module docstring),
    from segment_ids `[B, T]`: `carry` and `keep`, bool `[B, T]`. `carry`:
    the step lies in the document of the last step of the chunk before
    (the state that enters the chunk reaches it; False all through the
    first chunk, which nothing enters). `keep`: it lies in the document of
    its own chunk's last step (what it adds to the state leaves the
    chunk)."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("segments"):
        bsz, t = segment_ids.shape
        by_chunk = segment_ids.reshape(bsz, t // chunk, chunk)
        last = by_chunk[:, :, -1:]
        before = jnp.concatenate(
            [jnp.full((bsz, 1, 1), -1, segment_ids.dtype), last[:, :-1]],
            axis=1)
        return ((by_chunk == before).reshape(bsz, t),
                (by_chunk == last).reshape(bsz, t))


def _whole_chunks(t: int, chunk: int) -> int:
    """T / chunk; a T that is no whole chunks is refused, not padded."""
    if t % chunk:
        raise ValueError(f"the scan takes whole chunks: {t} steps are no "
                         f"multiple of {chunk}")
    return t // chunk


def ssd_scan(x, dt, a, b, c, chunk: int, segment_ids=None):
    """The selective scan `S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T`,
    `y_t = S_t c_t` in chunks: x `[B, T, H, P]`, dt `[B, T, H]` (after
    softplus, float32), a `[H]` (negative, float32), b and c
    `[B, T, G, N]` with head h in group h // (H / G) -> y `[B, T, H, P]`
    float32. T % chunk != 0 is refused. With `segment_ids` `[B, T]` the
    state starts from zero at every document's first step (module
    docstring)."""
    import jax
    import jax.numpy as jnp

    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    nc, q, r = _whole_chunks(t, chunk), chunk, h // g
    f32 = jnp.float32
    xc = x.reshape(bsz, nc, q, g, r, p)
    bc = b.reshape(bsz, nc, q, g, n)
    cc = c.reshape(bsz, nc, q, g, n)
    dtc = dt.astype(f32).reshape(bsz, nc, q, g, r)
    # log-decay up to and including each step of its chunk
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(g, r), axis=2)
    # ---- within a chunk: the quadratic form ------------------------
    lower = jnp.tril(jnp.ones((q, q), bool))
    if segment_ids is not None:
        carried, kept = (m.reshape(bsz, nc, q, 1, 1).astype(f32)
                         for m in chunk_marks(segment_ids, q))
        with jax.named_scope("segments"):
            ids = segment_ids.reshape(bsz, nc, q)
            lower = lower & (ids[:, :, :, None] == ids[:, :, None, :])
    diff = cum[:, :, :, None] - cum[:, :, None, :]        # [.., i, j, g, r]
    # masked before the exponential: above the diagonal the sum is
    # positive and would overflow
    decay = jnp.exp(jnp.where(lower[..., None, None], diff, -jnp.inf))
    scores = jnp.einsum("zcign,zcjgn->zcijg", cc, bc,
                        preferred_element_type=f32)
    weights = (scores[..., None] * decay
               * dtc[:, :, None, :]).astype(x.dtype)      # [z, c, i, j, g, r]
    y = jnp.einsum("zcijgr,zcjgrp->zcigrp", weights, xc,
                   preferred_element_type=f32)
    # ---- each chunk's own contribution to the state at its end ------
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc           # [z, c, j, g, r]
    if segment_ids is not None:
        to_end = to_end * kept
    states = jnp.einsum("zcjgn,zcjgrp->zcgrpn", bc,
                        xc * to_end[..., None].astype(x.dtype),
                        preferred_element_type=f32)
    # ---- the chunk states chained: T/Q steps of a scan --------------
    chunk_decay = jnp.exp(cum[:, :, -1])                   # [z, c, g, r]
    entered = jnp.exp(cum)         # the entering state's decay to step i
    if segment_ids is not None:
        entered = entered * carried
        chunk_decay = entered[:, :, -1]

    def step(carry, inp):
        state, decay_c = inp
        return carry * decay_c[..., None, None] + state, carry

    _, entering = jax.lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                # [z, c, g, r, p, n]
    # ---- what the state entering a chunk adds to its steps ----------
    y = y + jnp.einsum("zcign,zcgrpn->zcigrp", cc,
                       entering.astype(x.dtype),
                       preferred_element_type=f32) * entered[..., None]
    return y.reshape(bsz, t, h, p)


# ---- the scan as a pallas TPU kernel ------------------------------------
# One grid step takes at most SCAN_WIDTH lanes of x: 16 heads of 64, the
# whole of a Nemotron group, 0.5 MB of carried state in VMEM (the fastest
# of 16, 8, 4 and 2 heads a step on the v5e: PERF.md section 6, PR 42).
SCAN_LANES = 128
SCAN_WIDTH = 1024
SCAN_CHUNKS = (128, 256)
# ... and at most SCAN_HEAD_STEPS heads x steps: a head's `[Q, Q]` decays
# and weights live on the kernel's stack once a head of the block, and at
# chunk 256 sixteen heads' overrun the v5e's 16 MiB of scoped VMEM in the
# backward kernel (by 16 KiB; PERF.md section 6, PR 68), eight fit
SCAN_HEAD_STEPS = 2048
_SCAN_MARKS = 8     # a sublane tile of rows: ids, carry, keep, five unused


def scan_head_block(heads_per_group: int, head_dim: int, chunk: int = 128):
    """Heads one grid step of the kernel takes: the most that divide a
    B/C group, are whole sublane tiles of 8 (dt reaches the kernel with
    the heads on the sublanes), stay inside `SCAN_WIDTH` lanes of x and,
    times the chunk's steps, inside `SCAN_HEAD_STEPS`; None where no
    count does."""
    return next((hb for hb in range(heads_per_group, 0, -1)
                 if heads_per_group % hb == 0 and hb % 8 == 0
                 and hb * head_dim <= SCAN_WIDTH
                 and hb * chunk <= SCAN_HEAD_STEPS), None)


def scan_shape_ok(seq_len: int, heads: int, head_dim: int, groups: int,
                  state: int, chunk: int) -> bool:
    """Whether the kernel tiles the scan: whole chunks of 128 or 256
    steps, a head width that divides a lane tile, a state of whole lane
    tiles, a head block (`scan_head_block`)."""
    return (chunk in SCAN_CHUNKS and seq_len % chunk == 0
            and head_dim in (64, 128) and state % SCAN_LANES == 0
            and heads % groups == 0
            and scan_head_block(heads // groups, head_dim, chunk)
            is not None)


def _one_tpu_device(mesh) -> bool:
    """Whether the program runs on one TPU device: the mesh's, or the
    default device where there is no mesh. Where a pallas call can run:
    GSPMD cannot partition one."""
    import jax

    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    return device.platform == "tpu" and (mesh is None or mesh.size == 1)


def ssd_scan_impl(mesh, seq_len: int, heads: int, head_dim: int,
                  groups: int, state: int, chunk: int) -> str:
    """`"pallas"` (`ssd_scan_pallas`) where the program runs on one TPU
    device and the kernel tiles the shapes (`scan_shape_ok`), else
    `"xla"` (`ssd_scan`: any platform, any whole number of chunks, and
    GSPMD can partition it, which it cannot a pallas call). Decided at
    trace time, like `ops/moe.grouped_matmul_impl`; a job may print it to
    say what a step compiled with."""
    return "pallas" if _one_tpu_device(mesh) and scan_shape_ok(
        seq_len, heads, head_dim, groups, state, chunk) else "xla"


def _head_lanes(hb: int, p: int):
    """The kernel's walk over a block's heads: per lane tile of x, the
    heads that lie in it as (index in the block, lane mask or None where
    a head is the whole tile)."""
    import jax
    import jax.numpy as jnp

    per_tile = SCAN_LANES // p
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, SCAN_LANES), 1)
    tiles = []
    for t in range(hb * p // SCAN_LANES):
        tiles.append((slice(t * SCAN_LANES, (t + 1) * SCAN_LANES), [
            (t * per_tile + k,
             None if per_tile == 1 else
             (lane >= k * p) & (lane < (k + 1) * p))
            for k in range(per_tile)]))
    return tiles


def _last_row(v):
    """`v[-1:]` of a `[Q, lanes]` value as a masked sum: Mosaic folds a
    slice of a lane broadcast into a broadcast both ways, which it does
    not lower."""
    import jax
    import jax.numpy as jnp

    last = jax.lax.broadcasted_iota(
        jnp.int32, (v.shape[0], 1), 0) == v.shape[0] - 1
    return jnp.sum(jnp.where(last, v, 0.0), axis=0, keepdims=True)


def _by_head(heads, column):
    """`[rows, 1]` columns of some heads laid over a tile's lanes:
    `column(h)` on the lanes of head h."""
    import jax.numpy as jnp

    out = None
    for h, mask in heads:
        out = column(h) if out is None or mask is None else jnp.where(
            mask, column(h), out)
    return out


def _decays(dt_ref, a_ref, marks_ref=None):
    """dt `[hb, Q]` (the steps on the lanes) and a `[hb, 1]` of a block's
    heads -> dt and `cum`, the log-decay up to and including each step of
    the chunk, both ways round: `[Q, 128]` with head h in lane h, and
    `[hb, Q]`; and the `[Q, Q]` 0/1 matrix of the steps j <= i. The
    running sum and the turn are float32 products with a 0/1 matrix at
    the highest precision: the MXU adds what a `cumsum` adds, and turns
    exactly. With `marks_ref` (`_SCAN_MARKS` rows of `[Q]`: the steps'
    document ids, `carry` and `keep` of `chunk_marks`, as float32) the
    marks ride the turn in the lanes behind the heads', and a sixth
    value comes back: `same [Q, Q]` (steps i and j of one document) with
    `carry` and `keep` as `[Q, 1]` columns, all bool."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    hb, q = dt_ref.shape[2:]
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=f32)
    nt = (((1,), (1,)), ((), ()))
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    upto = (row >= col).astype(f32)                  # [i, j]: j <= i
    dt_r = dt_ref[0, 0]
    dta_r = dt_r * a_ref[0]
    pad = jnp.zeros((SCAN_LANES - hb, q), f32)
    turned = [dt_r, pad] if marks_ref is None else [
        dt_r, marks_ref[0], pad[_SCAN_MARKS:]]
    dt_c = jax.lax.dot_general(
        (row == col).astype(f32), jnp.concatenate(turned), nt, **exact)
    cum_c = jax.lax.dot_general(upto, jnp.concatenate([dta_r, pad]), nt,
                                **exact)
    cum_r = jax.lax.dot_general(dta_r, upto, nt, **exact)
    if marks_ref is None:
        return dt_c, cum_c, dt_r, cum_r, upto
    # whole numbers and 0/1 through an exact turn: compared with room
    same = jnp.abs(dt_c[:, hb:hb + 1] - marks_ref[0, 0:1, :]) < 0.5
    return dt_c, cum_c, dt_r, cum_r, upto, (
        same, dt_c[:, hb + 1:hb + 2] > 0.5, dt_c[:, hb + 2:hb + 3] > 0.5)


def _scan_fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, *rest, hb: int,
                     p: int):
    """One chunk of one head block: x `[Q, hb·P]`, B and C `[Q, N]`, dt
    `[hb, Q]`, a `[hb, 1]` -> y `[Q, hb·P]` float32 and the state that
    entered the chunk, `[N, hb·P]`; `state` carries it over the chunks.
    Packed documents bring one more operand behind `a`, the chunk's marks
    (`_decays`): a pair of steps of two documents has no weight, the
    entering state reaches the steps of the document it belongs to, and
    the steps of the chunk's last document alone reach the leaving
    state."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    *marks, y_ref, st_ref, state = rest
    f32 = jnp.float32
    cdt = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    bmat, cmat = b_ref[0], c_ref[0]
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)     # [i, j]
    b_t = bmat.T                                                 # [N, Q]
    dt_c, cum_c, dt_r, cum_r, upto, *docs = _decays(dt_ref, a_ref, *marks)
    lower = upto > 0
    entered = jnp.exp(cum_c)              # the entering state's decay to i
    to_end = jnp.exp(_last_row(cum_c) - cum_c) * dt_c
    if docs:
        same, carry, keep = docs[0]
        lower = lower & same
        entered = jnp.where(carry, entered, 0.0)
        to_end = jnp.where(keep, to_end, 0.0)
    for lanes, heads in _head_lanes(hb, p):
        xt = x_ref[0, :, lanes]
        s_in = state[:, lanes]
        st_ref[0, 0, :, lanes] = s_in
        entered_t = _by_head(heads, lambda h: entered[:, h:h + 1])
        y = jnp.dot(cmat, s_in.astype(cdt),
                    preferred_element_type=f32) * entered_t
        intra = None
        for h, mask in heads:
            # masked before the exponential: above the diagonal the sum
            # is positive and would overflow
            decay = jnp.exp(jnp.where(
                lower, cum_c[:, h:h + 1] - cum_r[h:h + 1, :], -jnp.inf))
            weights = (scores * decay * dt_r[h:h + 1, :]).astype(cdt)
            part = jnp.dot(weights, xt, preferred_element_type=f32)
            intra = part if intra is None else jnp.where(mask, part, intra)
        y_ref[0, :, lanes] = y + intra
        xs = (xt.astype(f32)
              * _by_head(heads, lambda h: to_end[:, h:h + 1])).astype(cdt)
        # the chunk's whole decay is the entering state's to the last step
        state[:, lanes] = s_in * _last_row(entered_t) + jnp.dot(
            b_t, xs, preferred_element_type=f32)


def _scan_bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, st_ref, g_ref,
                     *rest, hb: int, p: int):
    """The same chunk going backward: besides the forward's operands the
    state that entered `[N, hb·P]` and dy `[Q, hb·P]` float32 -> dx, this
    head block's part of dB and dC `[Q, N]`, and `[hb, Q]` each the
    cotangent of dt where it stands alone and that of `dt a`, the summand
    of `cum`. `dstate` carries the cotangent of the state a chunk hands
    on; `later` gathers what reaches `cum_i` as the later step of a pair.
    Everything `[Q, Q]` is held transposed (`[j, i]`), so that no product
    takes a transposed operand but dC's. Packed documents bring the
    chunk's marks behind dy, and mask what the forward masks."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    *marks, dx_ref, db_ref, dc_ref, ddt_ref, ddta_ref, dstate, later = rest
    f32 = jnp.float32
    q = x_ref.shape[1]
    cdt = x_ref.dtype
    nt = (((1,), (1,)), ((), ()))

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    bmat, cmat = b_ref[0], c_ref[0]
    scores_t = jax.lax.dot_general(bmat, cmat, nt,
                                   preferred_element_type=f32)   # [j, i]
    c_t = cmat.T                                                 # [N, Q]
    dt_c, cum_c, _, cum_r, upto, *docs = _decays(dt_ref, a_ref, *marks)
    upper = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)            # [j, i]
    head_lane = jax.lax.broadcasted_iota(jnp.int32, (1, SCAN_LANES), 1)
    entered = jnp.exp(cum_c)
    decay_to_end = jnp.exp(_last_row(cum_c) - cum_c)
    if docs:
        same, carry, keep = docs[0]     # `same` is its own transpose
        upper = upper & same
        entered = jnp.where(carry, entered, 0.0)
        decay_to_end = jnp.where(keep, decay_to_end, 0.0)
    to_end = decay_to_end * dt_c
    dscores_t = jnp.zeros((q, q), f32)
    db = jnp.zeros(db_ref.shape[1:], f32)
    dc = jnp.zeros(dc_ref.shape[1:], f32)
    # per head, head h in lane h: [Q, 128] and [1, 128]
    d_intra = jnp.zeros((q, SCAN_LANES), f32)     # sum_i dW_ij s_ij L_ij
    d_to_end = jnp.zeros((q, SCAN_LANES), f32)    # sum_p x_jp (B dS)_jp
    d_entered = jnp.zeros((q, SCAN_LANES), f32)   # sum_p dy_ip (C S)_ip
    d_chunk_decay = jnp.zeros((1, SCAN_LANES), f32)
    for lanes, heads in _head_lanes(hb, p):
        xt = x_ref[0, :, lanes]
        x32 = xt.astype(f32)
        gt = g_ref[0, :, lanes]
        s_in = st_ref[0, 0, :, lanes]
        ds_out = dstate[:, lanes]
        s_in_c, ds_out_c, gt_c = (s_in.astype(cdt), ds_out.astype(cdt),
                                  gt.astype(cdt))
        from_state = jnp.dot(cmat, s_in_c, preferred_element_type=f32)
        dxs = jnp.dot(bmat, ds_out_c, preferred_element_type=f32)
        g_state = gt * from_state
        x_dxs = x32 * dxs
        ds_s = jnp.sum(ds_out * s_in, axis=0, keepdims=True)     # [1, 128]
        dx = None
        for h, mask in heads:
            here = head_lane == h

            def only(v):
                return v if mask is None else jnp.where(mask, v, 0.0)

            dw_t = jax.lax.dot_general(
                only(x32).astype(cdt), gt_c, nt,
                preferred_element_type=f32)                      # [j, i]
            decay_t = jnp.exp(jnp.where(
                upper, cum_r[h:h + 1, :] - cum_c[:, h:h + 1], -jnp.inf))
            sl_t = scores_t * decay_t
            w_t = sl_t * dt_c[:, h:h + 1]
            a_t = dw_t * sl_t
            d_intra = jnp.where(
                here, jnp.sum(a_t, axis=1, keepdims=True), d_intra)
            later[h:h + 1, :] = jnp.sum(
                a_t * dt_c[:, h:h + 1], axis=0, keepdims=True)
            dscores_t = dscores_t + dw_t * (decay_t * dt_c[:, h:h + 1])
            part = jnp.dot(w_t.astype(cdt), gt_c,
                           preferred_element_type=f32)
            dx = part if dx is None else jnp.where(mask, part, dx)
            d_to_end = jnp.where(
                here, jnp.sum(only(x_dxs), axis=1, keepdims=True), d_to_end)
            d_entered = jnp.where(
                here, jnp.sum(only(g_state), axis=1, keepdims=True),
                d_entered)
            d_chunk_decay = jnp.where(
                here, jnp.sum(only(ds_s), axis=1, keepdims=True),
                d_chunk_decay)
        to_end_t = _by_head(heads, lambda h: to_end[:, h:h + 1])
        dx_ref[0, :, lanes] = (dx + dxs * to_end_t).astype(dx_ref.dtype)
        xs = (x32 * to_end_t).astype(cdt)
        entered_t = _by_head(heads, lambda h: entered[:, h:h + 1])
        ge = (gt * entered_t).astype(cdt)
        db = db + jax.lax.dot_general(xs, ds_out_c, nt,
                                      preferred_element_type=f32)
        dc = dc + jax.lax.dot_general(ge, s_in_c, nt,
                                      preferred_element_type=f32)
        dstate[:, lanes] = ds_out * _last_row(entered_t) + jnp.dot(
            c_t, ge, preferred_element_type=f32)
    db = db + jnp.dot(dscores_t.astype(cdt), cmat,
                      preferred_element_type=f32)
    dc = dc + jnp.dot(dscores_t.T.astype(cdt), bmat,
                      preferred_element_type=f32)
    db_ref[0] = db.astype(db_ref.dtype)
    dc_ref[0] = dc.astype(dc_ref.dtype)
    # cum_j reaches y through the weights of column j (-), through to_end
    # (-), as cum_i of the entering state's term (+) and of the weights
    # of row i (+, `later`); cum of the chunk's last step through to_end
    # of every step and through the chunk's decay
    through_end = d_to_end * to_end
    last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    dcum_c = d_entered * entered - d_intra * dt_c - through_end + jnp.where(
        last, jnp.sum(through_end, axis=0, keepdims=True)
        + d_chunk_decay * _last_row(entered), 0.0)
    dcum_r = dcum_c.T[:hb] + later[:hb]                          # [hb, i]
    # the transpose of the running sum: dt_j a reaches every cum_i, i >= j
    ddta = jnp.dot(dcum_r, upto,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=f32)
    ddta_ref[0, 0] = ddta
    ddt_ref[0, 0] = (d_intra + d_to_end * decay_to_end).T[:hb] \
        + ddta * a_ref[0]


@functools.lru_cache(maxsize=None)
def _scan_calls(bsz: int, t: int, h: int, p: int, groups: int, n: int,
                chunk: int, hb: int, interpret: bool, segmented: bool):
    """The scan of one set of shapes under `jax.custom_vjp`, built once a
    process (`functools.lru_cache`) with each `pallas_call` behind a
    `jax.jit` of its own: a pallas kernel's body is traced anew by every
    call, in every program of a job (two scans of layers, the forward,
    remat's forward and the backward of each), and jit's cache hands the
    first trace to all of them. `segmented`: the scan takes one operand
    more, the marks of packed documents `[B, _SCAN_MARKS, T]` float32
    (data: no cotangent), and every kernel call is handed them."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nc, q, blocks, width = t // chunk, chunk, h // hb, hb * p
    per_group = blocks // groups
    f32 = jnp.float32
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    def specs(reverse):
        """The block specs of a walk over the chunks, first to last or
        last to first: x-wide, a group's B or C, a head block's part of
        dB or dC, the entering states, dt-like rows, a."""
        def at(ci):
            return nc - 1 - ci if reverse else ci
        return (
            pl.BlockSpec((1, q, width), lambda bi, hi, ci: (bi, at(ci), hi)),
            pl.BlockSpec((1, q, n),
                         lambda bi, hi, ci: (bi, at(ci), hi // per_group)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, at(ci), hi)),
            pl.BlockSpec((1, 1, n, width),
                         lambda bi, hi, ci: (bi, at(ci), 0, hi)),
            pl.BlockSpec((1, 1, hb, q),
                         lambda bi, hi, ci: (bi, hi, 0, at(ci))),
            pl.BlockSpec((1, hb, 1), lambda bi, hi, ci: (hi, 0, 0)),
            [pl.BlockSpec((1, _SCAN_MARKS, q),
                          lambda bi, hi, ci: (bi, 0, at(ci)))] * segmented)

    @functools.partial(jax.jit, inline=True)
    def forward(x, dt_rows, a_col, b, c, *marks):
        wide, grouped, _, states, rows, heads, marked = specs(False)
        return pl.pallas_call(
            functools.partial(_scan_fwd_kernel, hb=hb, p=p),
            grid=(bsz, blocks, nc),
            in_specs=[wide, grouped, grouped, rows, heads] + marked,
            out_specs=[wide, states],
            out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), f32),
                       jax.ShapeDtypeStruct((bsz, nc, n, h * p), f32)],
            scratch_shapes=[pltpu.VMEM((n, width), f32)],
            compiler_params=params, interpret=interpret,
            name="ssd_scan_fwd")(x, b, c, dt_rows, a_col, *marks)

    @functools.partial(jax.jit, inline=True)
    def backward(x, dt_rows, a_col, b, c, marks, entering, dy):
        wide, grouped, per_block, states, rows, heads, marked = specs(True)
        # a group's head blocks each see its B and C: their parts of dB
        # and dC are summed in float32
        part = b.dtype if per_group == 1 else f32
        return pl.pallas_call(
            functools.partial(_scan_bwd_kernel, hb=hb, p=p),
            grid=(bsz, blocks, nc),
            in_specs=[wide, grouped, grouped, rows, heads, states, wide]
            + marked,
            out_specs=[wide, per_block, per_block, rows, rows],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((bsz, t, blocks * n), part),
                       jax.ShapeDtypeStruct((bsz, t, blocks * n), part),
                       jax.ShapeDtypeStruct(dt_rows.shape, f32),
                       jax.ShapeDtypeStruct(dt_rows.shape, f32)],
            scratch_shapes=[pltpu.VMEM((n, width), f32),
                            pltpu.VMEM((SCAN_LANES, q), f32)],
            compiler_params=params, interpret=interpret,
            name="ssd_scan_bwd")(x, b, c, dt_rows, a_col, entering, dy,
                                 *marks)

    @jax.custom_vjp
    def scan(x, dt_rows, a_col, b, c, *marks):
        return forward(x, dt_rows, a_col, b, c, *marks)[0]

    def scan_fwd(x, dt_rows, a_col, b, c, *marks):
        y, entering = forward(x, dt_rows, a_col, b, c, *marks)
        return y, (x, dt_rows, a_col, b, c, marks, entering)

    def scan_bwd(res, dy):
        _, dt_rows, a_col, b, _, marks, _ = res
        dx, db, dc, ddt, ddta = backward(*res, dy)

        def over_blocks(d):
            return d if per_group == 1 else d.reshape(
                bsz, t, groups, per_group, n).sum(axis=3).reshape(
                    bsz, t, groups * n).astype(b.dtype)
        da = jnp.sum(ddta * dt_rows, axis=(0, 3)).reshape(a_col.shape)
        return (dx, ddt, da, over_blocks(db), over_blocks(dc)) \
            + (None,) * len(marks)

    scan.defvjp(scan_fwd, scan_bwd)
    return scan


def ssd_scan_pallas(x, dt, a, b, c, chunk: int, groups: int, *,
                    segment_ids=None, head_block=None,
                    interpret: bool = False):
    """`ssd_scan` as a pallas TPU kernel with a backward kernel of its
    own (module docstring), on the layouts the convolution leaves: x
    `[B, T, H·P]`, dt `[B, T, H]` (after softplus, float32), a `[H]`, b
    and c `[B, T, G·N]` -> y `[B, T, H·P]` float32; `segment_ids`
    `[B, T]` as `ssd_scan` takes them, None: no operand for them. The
    shapes are `scan_shape_ok`'s to vouch for; `head_block` and
    `interpret` are the tests' (a block smaller than `scan_head_block`'s,
    the kernel on the CPU)."""
    import jax
    import jax.numpy as jnp

    bsz, t, h = dt.shape
    p, n = x.shape[2] // h, b.shape[2] // groups
    _whole_chunks(t, chunk)
    hb = head_block or scan_head_block(h // groups, p, chunk)
    marks = ()
    if segment_ids is not None:
        carry_keep = chunk_marks(segment_ids, chunk)
        with jax.named_scope("segments"):
            # the steps on the lanes, a sublane tile of rows: [B, 8, T]
            rows = jnp.stack((segment_ids,) + carry_keep,
                             axis=1).astype(jnp.float32)
            marks = (jnp.pad(rows,
                             ((0, 0), (0, _SCAN_MARKS - 3), (0, 0))),)
    scan = _scan_calls(bsz, t, h, p, groups, n, chunk, hb, interpret,
                       segment_ids is not None)
    # the steps on the lanes: [B, blocks, hb, T]
    dt_rows = jnp.swapaxes(dt.astype(jnp.float32), 1, 2).reshape(
        bsz, h // hb, hb, t)
    return scan(x, dt_rows, a.astype(jnp.float32).reshape(h // hb, hb, 1),
                b, c, *marks)


def gated_norm(y, z, gain, groups: int, eps: float):
    """`grouped_rmsnorm(y * silu(z)) * gain`: y, z `[..., C]`, the RMS
    over each of `groups` runs of C / groups channels, in float32. A
    group is a slice of the last axis, not a `[..., groups, C / groups]`
    reshape: on the TPU that shape has another tiling, and the compiler
    copied every `[B, T, C]` operand of the norm and of its backward into
    it (PERF.md section 6, PR 42)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    v = y.astype(f32) * jax.nn.silu(z.astype(f32))
    width = v.shape[-1] // groups
    runs = [v[..., g * width:(g + 1) * width] for g in range(groups)]
    normed = [run * jax.lax.rsqrt(
        jnp.mean(run * run, axis=-1, keepdims=True) + eps) for run in runs]
    return jnp.concatenate(normed, axis=-1) * gain.astype(f32)


def mamba2_mixer(h, lp: Dict[str, Any], *, head_dim: int, state: int,
                 chunk: int, eps: float, mesh=None, segment_ids=None):
    """h `[B, T, d]` (normed, compute dtype) -> the mixer's output before
    the residual, `[B, T, d]`. lp: `w_in [d, 2·H·P + 2·G·N + H]` and
    `w_out [H·P, d]` in the compute dtype; `conv_w [H·P + 2·G·N, K]`,
    `conv_b`, `dt_bias [H]`, `A_log [H]`, `D [H]`, `gate_norm [H·P]`.
    Heads and groups are read off the leaves (module docstring); `mesh`
    is what the program runs on, for `ssd_scan_impl`'s choice;
    `segment_ids` `[B, T]` int32 or None: packed documents (module
    docstring), handed to the convolution and the scan."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    heads = lp["A_log"].shape[0]
    inner = heads * head_dim
    conv_dim = lp["conv_w"].shape[0]
    groups = (conv_dim - inner) // (2 * state)
    bsz, t, _ = h.shape
    with jax.named_scope("ssm/in_proj"):
        zxbcdt = jnp.einsum("btd,de->bte", h, lp["w_in"])
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + conv_dim]
        dt = zxbcdt[..., inner + conv_dim:]
    with jax.named_scope("ssm/conv"):
        xbc = jax.nn.silu(causal_conv(xbc, lp["conv_w"], lp["conv_b"],
                                      segment_ids))
        x, b, c = (xbc[..., :inner], xbc[..., inner:inner + groups * state],
                   xbc[..., inner + groups * state:])
    with jax.named_scope("ssm/scan"):
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
        a, skip = -jnp.exp(lp["A_log"].astype(f32)), lp["D"].astype(f32)
        if ssd_scan_impl(mesh, t, heads, head_dim, groups, state,
                         chunk) == "pallas":
            # the kernel reads the convolution's own layouts
            y = ssd_scan_pallas(x, dt, a, b, c, chunk, groups,
                                segment_ids=segment_ids)
        else:
            y = ssd_scan(x.reshape(bsz, t, heads, head_dim), dt, a,
                         b.reshape(bsz, t, groups, state),
                         c.reshape(bsz, t, groups, state),
                         chunk, segment_ids).reshape(bsz, t, inner)
        y = y + x.astype(f32) * jnp.repeat(skip, head_dim)
    with jax.named_scope("ssm/gate_norm"):
        y = gated_norm(y, z, lp["gate_norm"], groups, eps).astype(h.dtype)
    with jax.named_scope("ssm/out_proj"):
        return jnp.einsum("bte,ed->btd", y, lp["w_out"])


# ---- Mamba-1 ---------------------------------------------------------------
# The selective scan of Gu & Dao, "Mamba" (2023), as `phi4flash` publishes
# it: C channels, each with its OWN state of N numbers and its own decay
#
#     s_t = exp(dt_t (x) A) . s_{t-1} + (dt_t . x_t) (x) B_t     s is C x N
#     y_t = s_t C_t
#
# `exp(dt_{t,c} A_{c,n})` differs for every channel c and state index n,
# where Mamba-2's decay is one scalar a head: there is no `[Q, Q]` block
# product to unroll a chunk into, and the states of all steps `[T, C, N]`
# in float32 are 5.4 GB at 16,384 tokens and 5,120 channels. Two
# implementations, chosen at trace time by `selective_scan_impl` (no
# option, as `ssd_scan_impl` chooses for Mamba-2):
#
# - `"xla"` (`selective_scan`): chunks with the state carried between
#   them, each a checkpoint; a chunk's `[chunk, N, C]` float32 states go
#   through HBM in every pass. It runs on the CPU, on a mesh above one
#   device and for every shape the kernels do not tile, and it is the
#   oracle of the kernels' tests.
# - `"pallas"` (`selective_scan_pallas`): on one TPU device where the
#   shapes tile (`scan1_shape_ok`). Grid `(batch, channel blocks, time
#   blocks)`, the time axis sequential. x `[Q, Cb]` and dt `[Q, Cb]` are
#   read as lane-dense blocks of the `[B, T, C]` layout they arrive in and
#   `y + D x` is written the same way, in the compute dtype; b and c
#   arrive with (step, n) on the lanes (1 MB each, turned by XLA) and are
#   laid down the sublanes once a block (`_columns`). The state `[N,
#   lanes]` float32 (n on the sublanes, the channels on the lanes) is in
#   registers over a block's steps and in a VMEM scratch between blocks:
#   a step is `s = exp(dt_t a) s + b_t (dt_t x_t)`, `y_t = sum_n c_t s`,
#   in float32, every decay the exponential of a non-positive number. The
#   forward (`selective_scan_fwd`) also writes the state entering each
#   time block, `[T/Q, N, C]` float32: its one residual besides its
#   inputs. The backward (`selective_scan_bwd`, under `jax.custom_vjp`)
#   walks the time blocks in reverse with the state's cotangent carried in
#   VMEM, makes a block's states again from its entering state into VMEM,
#   and returns dx, ddt, this channel block's part of db and dc (their
#   sums over the lanes turned back by one transpose a block), and da and
#   dD summed over time in their output blocks.


def _scan_steps(chunk: int) -> int:
    """Steps a sub-chunk takes one after another: the chunk's other
    factor, its sub-chunks, run side by side. 64 where it divides."""
    return next(q for q in (64, 32, 16, 8, 4, 2, 1) if chunk % q == 0)


def selective_scan(x, dt, a, b, c, chunk: int):
    """Mamba-1's recurrence (above): x `[B, T, C]`, dt `[B, T, C]` (after
    softplus, float32), a `[C, N]` (negative, float32), b and c
    `[B, T, N]` -> y `[B, T, C]` float32. T % chunk != 0 is refused.

    T/chunk chunks run one after another (`lax.scan`, the state `[B, N,
    C]` float32 its carry: channels on the lanes) and each is a
    `jax.checkpoint`: what the backward pass keeps is a chunk's inputs and
    entering state, and one chunk's states at a time are alive. Inside a
    chunk, its chunk/Q sub-chunks of Q steps run side by side from a zero
    state, Q steps of one `[B, chunk/Q, N, C]` update each (few, wide
    steps where the plain recurrence is T narrow ones); the sub-chunks'
    entering states are then chained (chunk/Q steps) and what each
    entering state adds to its steps' outputs, `sum_n C_t[n]
    exp(cum_t a)[c, n] s_in[c, n]` with `cum` the running sum of dt
    inside the sub-chunk, is one elementwise pass with no recurrence.
    Every decay is the exponential of a non-positive number: nothing
    overflows, whatever dt."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    bsz, t, ch = x.shape
    n = b.shape[-1]
    groups, q = _whole_chunks(t, chunk), _scan_steps(chunk)
    sub = chunk // q
    f32 = jnp.float32
    a_t = a.astype(f32).T                                   # [N, C]

    @jax.checkpoint
    def one_chunk(s0, inp):
        xg, dtg, bg, cg = inp          # [B, sub, Q, C] x2, [B, sub, Q, N] x2
        dtx = dtg * xg.astype(f32)

        def step(s, at):
            dt_q, dtx_q, b_q, c_q = at          # [B, sub, C] x2, [B, sub, N] x2
            s = jnp.exp(dt_q[:, :, None] * a_t) * s \
                + b_q[..., None] * dtx_q[:, :, None]
            return s, jnp.sum(s * c_q[..., None], axis=2)

        by_step = [jnp.moveaxis(v, 2, 0) for v in (
            dtg, dtx, bg.astype(f32), cg.astype(f32))]
        local, y = lax.scan(step, jnp.zeros((bsz, sub, n, ch), f32),
                            by_step)
        # the sub-chunks' entering states, chained
        cum = jnp.cumsum(dtg, axis=2)                       # [B, sub, Q, C]
        through = jnp.exp(cum[:, :, -1, None] * a_t)        # [B, sub, N, C]

        def chain(s, at):
            decay, added = at
            return decay * s + added, s

        s_end, entering = lax.scan(
            chain, s0, (jnp.moveaxis(through, 1, 0),
                        jnp.moveaxis(local, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)             # [B, sub, N, C]
        carried = jnp.sum(
            cg.astype(f32)[..., None] * jnp.exp(cum[:, :, :, None] * a_t)
            * entering[:, :, None], axis=3)                 # [B, sub, Q, C]
        return s_end, jnp.moveaxis(y, 0, 2) + carried

    def chunks(v):   # [B, T, W] -> [groups, B, sub, Q, W]
        return jnp.moveaxis(
            v.reshape(bsz, groups, sub, q, v.shape[-1]), 1, 0)

    _, y = lax.scan(one_chunk, jnp.zeros((bsz, n, ch), f32),
                    (chunks(x), chunks(dt.astype(f32)), chunks(b),
                     chunks(c)))
    return jnp.moveaxis(y, 0, 1).reshape(bsz, t, ch)


# ---- the Mamba-1 scan as a pallas TPU kernel --------------------------------
# One grid step takes SCAN1_STEPS steps of at most SCAN1_CHANNELS channels
# and walks them `SCAN1_GROUP` lanes at a time, the steps one after
# another with the group's state `[N, lanes]` float32 in registers (found
# on the v5e: PERF.md section 6, PR 45). The tiling is the kernel's own:
# `chunk` is only what T must divide by.
SCAN1_STEPS = 128
SCAN1_CHANNELS = 2560
SCAN1_GROUP = 640


def scan1_channel_block(channels: int):
    """Channels one grid step of the Mamba-1 kernels takes: the most
    whole lane tiles that divide the channels and stay inside
    `SCAN1_CHANNELS`; None where the channels are no whole lane tiles."""
    return next((cb for cb in range(SCAN1_CHANNELS, 0, -SCAN_LANES)
                 if channels % cb == 0), None)


def _scan1_group(block: int) -> int:
    """Lanes of a channel block whose state is carried in registers at
    once: the most whole lane tiles that divide it inside `SCAN1_GROUP`."""
    return next(g for g in range(min(block, SCAN1_GROUP), 0, -SCAN_LANES)
                if block % g == 0)


def scan1_shape_ok(seq_len: int, channels: int, state: int,
                   chunk: int) -> bool:
    """Whether the Mamba-1 kernels tile the scan: T whole chunks and whole
    time blocks, channels a whole number of channel blocks, a state of
    whole sublane tiles."""
    return (seq_len % chunk == 0 and seq_len % SCAN1_STEPS == 0
            and state % 8 == 0 and scan1_channel_block(channels) is not None)


def selective_scan_impl(mesh, seq_len: int, channels: int, state: int,
                        chunk: int) -> str:
    """`"pallas"` (`selective_scan_pallas`) where the program runs on one
    TPU device and the kernels tile the shapes (`scan1_shape_ok`), else
    `"xla"` (`selective_scan`: any platform, any whole number of chunks,
    and GSPMD can partition it). Decided at trace time, as
    `ssd_scan_impl` decides for Mamba-2."""
    return "pallas" if _one_tpu_device(mesh) and scan1_shape_ok(
        seq_len, channels, state, chunk) else "xla"


def _columns(row):
    """A block's b or c as it arrives, `[1, Q·N]` with (step, n) on the
    lanes -> `[Q·N, 128]`: rows t·N .. t·N + N are step t's N numbers down
    the sublanes, each across all lanes (the state has n on the sublanes
    and the channels on the lanes). A sublane broadcast and one
    transpose."""
    import jax.numpy as jnp

    return jnp.broadcast_to(row, (SCAN_LANES, row.shape[1])).T


def _lane_sums(acc):
    """`[Q·N, 128]` -> `[1, Q·N]`, each row's sum over the lanes: the
    turn of `_columns` back, for db and dc."""
    import jax.numpy as jnp

    return jnp.sum(acc.T, axis=0, keepdims=True)


def _steps(q: int, body, init):
    """`body(i, j, carry)` over the steps t = 8 i + j, 0 .. q - 1, a
    sublane tile of steps to a loop body: Mosaic loads a row of a tile at
    a dynamic tile index i and a static row j, not at a dynamic row, and
    what a step does beside the chain of its state overlaps its
    neighbours'."""
    import jax

    def tile(i, carry):
        for j in range(8):
            carry = body(i, j, carry)
        return carry

    return jax.lax.fori_loop(0, q // 8, tile, init)


def _each_group(groups: int, body):
    """`body(g)` for the lane groups 0 .. groups - 1 as one loop, not
    `groups` copies of its steps: a kernel's body is traced and lowered
    in every program that holds a call (warm `setup_s`: PERF.md section
    6, PR 45). The bodies write to refs, the kernel's memory."""
    import jax

    jax.lax.fori_loop(0, groups, lambda g, carry: (body(g), carry)[1], 0)


def _to_groups(scr, v):
    """`[rows, Cb]` laid into a scratch `[G, rows/8, 8, W]`: lane group g
    apart (the kernels walk the groups in a loop, and a loop's index can
    choose a leading axis, not a lane), a step's row at `[g, t // 8,
    t % 8]`. Rows of a's kind, `[N, Cb]`, go to `[G, N/8, 8, W]` alike."""
    width = scr.shape[-1]
    for g in range(scr.shape[0]):
        scr[g] = v[:, g * width:(g + 1) * width].reshape(scr.shape[1:])


def _from_groups(scr):
    """`_to_groups` back: `[G, rows/8, 8, W]` -> `[rows, Cb]`."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [scr[g].reshape(-1, scr.shape[-1]) for g in range(scr.shape[0])],
        axis=1)


def _lay_down(x_ref, dt_ref, b_ref, c_ref, a_ref, dt_scr, u_scr, a_scr,
              bcol, ccol):
    """What both kernels read a step at a time, laid down once a block:
    dt, `dt x` and a by lane groups, b and c down the sublanes."""
    import jax.numpy as jnp

    _to_groups(dt_scr, dt_ref[0])
    _to_groups(u_scr, dt_ref[0] * x_ref[0].astype(jnp.float32))
    _to_groups(a_scr, a_ref[...])
    bcol[...] = _columns(b_ref[0])
    ccol[...] = _columns(c_ref[0])


def _scan1_fwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref,
                      st_ref, state, dt_scr, u_scr, y_scr, a_scr, bcol,
                      ccol, *, n: int):
    """One time block of one channel block: x `[Q, Cb]`, dt `[Q, Cb]`
    float32, b and c `[1, Q·N]`, a `[N, Cb]`, D `[1, Cb]` -> `y + D x`
    `[Q, Cb]` in x's dtype and the state that entered the block,
    `[N, Cb]`; `state` carries it over the time blocks, by lane groups."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    q = x_ref.shape[1]
    groups, tiles = state.shape[0], state.shape[-1] // SCAN_LANES

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    st_ref[0, 0] = _from_groups(state)
    _lay_down(x_ref, dt_ref, b_ref, c_ref, a_ref, dt_scr, u_scr, a_scr,
              bcol, ccol)

    def group(g):
        a_g = a_scr[g].reshape(n, -1)

        def step(i, j, s):
            rows = pl.ds(pl.multiple_of((i * 8 + j) * n, n), n)
            # a tile's columns over the group's tiles: the same registers
            b_t = pltpu.repeat(bcol[rows, :], tiles, axis=1)
            c_t = pltpu.repeat(ccol[rows, :], tiles, axis=1)
            # the exponential of a non-positive number
            s = jnp.exp(dt_scr[g, i, j:j + 1, :] * a_g) * s \
                + b_t * u_scr[g, i, j:j + 1, :]
            y_scr[g, i, j:j + 1, :] = jnp.sum(c_t * s, axis=0, keepdims=True)
            return s

        state[g] = _steps(q, step, state[g].reshape(n, -1)).reshape(
            state.shape[1:])

    _each_group(groups, group)
    # f32, rounded once
    y_ref[0] = (_from_groups(y_scr)
                + x_ref[0].astype(f32) * d_ref[...]).astype(y_ref.dtype)


def _scan1_bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, st_ref,
                      g_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                      dd_ref, dstate, da_acc, dt_scr, u_scr, g_scr, du_scr,
                      ddta_scr, a_scr, st_scr, bcol, ccol, dbacc, dcacc,
                      states, *, n: int):
    """The same block going backward (the time blocks last to first):
    besides the forward's operands the state that entered `[N, Cb]` and
    dy `[Q, Cb]` -> dx, ddt `[Q, Cb]`, this channel block's part of db
    and dc `[1, Q·N]`, and, summed over the time blocks (`da_acc`, the
    output block itself), da `[N, Cb]` and dD `[1, Cb]`. A lane group's
    states are made again from the entering state into `states` (row
    block t + 1 is s_t, row block 0 the entering state) and never leave
    VMEM; `dstate` carries `g_{t+1} * dL/ds_{t+1}`, the cotangent a block
    hands the one before it."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    q = x_ref.shape[1]
    groups, width = dstate.shape[0], dstate.shape[-1]
    tiles = width // SCAN_LANES

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        da_acc[...] = jnp.zeros_like(da_acc)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    _lay_down(x_ref, dt_ref, b_ref, c_ref, a_ref, dt_scr, u_scr, a_scr,
              bcol, ccol)
    _to_groups(g_scr, g_ref[0].astype(f32))
    _to_groups(st_scr, st_ref[0, 0])
    dbacc[...] = jnp.zeros_like(dbacc)
    dcacc[...] = jnp.zeros_like(dcacc)

    def block(t):
        return pl.ds(pl.multiple_of(t * n, n), n)

    def over_tiles(v):
        """`[N, W]` -> `[N, 128]`: the sum of the group's lane tiles, what
        is left of a sum over the channels before `_lane_sums`."""
        return functools.reduce(lambda u, k: u + v[
            :, k * SCAN_LANES:(k + 1) * SCAN_LANES], range(1, tiles),
            v[:, :SCAN_LANES])

    def group(g):
        a_g = a_scr[g].reshape(n, width)
        states[0:n, :] = st_scr[g].reshape(n, width)

        def forward(i, j, s):
            t = i * 8 + j
            s = jnp.exp(dt_scr[g, i, j:j + 1, :] * a_g) * s \
                + pltpu.repeat(bcol[block(t), :], tiles, axis=1) \
                * u_scr[g, i, j:j + 1, :]
            states[block(t + 1), :] = s
            return s

        _steps(q, forward, states[0:n, :])

        def backward(i, j, carry):
            i, j = q // 8 - 1 - i, 7 - j
            t = i * 8 + j
            lam, da = carry
            b_t = pltpu.repeat(bcol[block(t), :], tiles, axis=1)
            c_t = pltpu.repeat(ccol[block(t), :], tiles, axis=1)
            dt_t = dt_scr[g, i, j:j + 1, :]
            dy_t = g_scr[g, i, j:j + 1, :]
            lam = lam + c_t * dy_t                            # dL/ds_t
            dcacc[block(t), :] += over_tiles(dy_t * states[block(t + 1), :])
            dbacc[block(t), :] += over_tiles(lam * u_scr[g, i, j:j + 1, :])
            du_scr[g, i, j:j + 1, :] = jnp.sum(b_t * lam, axis=0,
                                               keepdims=True)
            lam = jnp.exp(dt_t * a_g) * lam       # what s_{t-1} receives
            w = lam * states[block(t), :]                 # dL/d(dt_t a)
            ddta_scr[g, i, j:j + 1, :] = jnp.sum(w * a_g, axis=0,
                                                 keepdims=True)
            return lam, da + w * dt_t

        lam, da = _steps(q, backward, (dstate[g].reshape(n, width),
                                       da_acc[g].reshape(n, width)))
        dstate[g] = lam.reshape(dstate.shape[1:])
        da_acc[g] = da.reshape(da_acc.shape[1:])

    _each_group(groups, group)
    x32, dy = x_ref[0].astype(f32), g_ref[0].astype(f32)
    du = _from_groups(du_scr)
    dx_ref[0] = (du * dt_ref[0] + dy * d_ref[...]).astype(dx_ref.dtype)
    ddt_ref[0] = _from_groups(ddta_scr) + du * x32
    da_ref[0] = _from_groups(da_acc)
    dd_ref[0] += jnp.sum(dy * x32, axis=0, keepdims=True)
    db_ref[0, 0] = _lane_sums(dbacc[...])
    dc_ref[0, 0] = _lane_sums(dcacc[...])


@functools.lru_cache(maxsize=None)
def _scan1_calls(bsz: int, t: int, ch: int, n: int, q: int, cb: int,
                 group: int, interpret: bool):
    """The Mamba-1 scan of one set of shapes under `jax.custom_vjp`, built
    once a process with each `pallas_call` behind a `jax.jit` of its own,
    for `_scan_calls`' reason."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nt, blocks = t // q, ch // cb
    f32 = jnp.float32
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 1024 * 1024)

    def specs(reverse):
        """The block specs of a walk over the time blocks, first to last
        or last to first: x-wide, b-like rows, a, D, the entering states,
        a channel block's part of db or dc, da's and dD's sums."""
        def at(ti):
            return nt - 1 - ti if reverse else ti
        return (
            pl.BlockSpec((1, q, cb), lambda bi, ci, ti: (bi, at(ti), ci)),
            pl.BlockSpec((1, 1, q * n), lambda bi, ci, ti: (bi, 0, at(ti))),
            pl.BlockSpec((n, cb), lambda bi, ci, ti: (0, ci)),
            pl.BlockSpec((1, cb), lambda bi, ci, ti: (0, ci)),
            pl.BlockSpec((1, 1, n, cb),
                         lambda bi, ci, ti: (bi, at(ti), 0, ci)),
            pl.BlockSpec((1, 1, 1, q * n),
                         lambda bi, ci, ti: (bi, ci, 0, at(ti))),
            pl.BlockSpec((1, n, cb), lambda bi, ci, ti: (bi, 0, ci)),
            pl.BlockSpec((1, 1, cb), lambda bi, ci, ti: (bi, 0, ci)))

    def columns():
        return pltpu.VMEM((q * n, SCAN_LANES), f32)

    def by_groups(rows):
        return pltpu.VMEM((cb // group, rows // 8, 8, group), f32)

    @functools.partial(jax.jit, inline=True)
    def forward(x, dt, a_t, b_row, c_row, d_row):
        wide, rows, a_spec, d_spec, states, _, _, _ = specs(False)
        return pl.pallas_call(
            functools.partial(_scan1_fwd_kernel, n=n),
            grid=(bsz, blocks, nt),
            in_specs=[wide, wide, rows, rows, a_spec, d_spec],
            out_specs=[wide, states],
            out_shape=[jax.ShapeDtypeStruct((bsz, t, ch), x.dtype),
                       jax.ShapeDtypeStruct((bsz, nt, n, ch), f32)],
            scratch_shapes=[by_groups(n)] + [by_groups(q)] * 3
            + [by_groups(n)] + [columns()] * 2,
            compiler_params=params, interpret=interpret,
            name="selective_scan_fwd")(x, dt, b_row, c_row, a_t, d_row)

    @functools.partial(jax.jit, inline=True)
    def backward(x, dt, a_t, b_row, c_row, d_row, entering, dy):
        wide, rows, a_spec, d_spec, states, parts, da_spec, dd_spec = \
            specs(True)
        part = jax.ShapeDtypeStruct((bsz, blocks, 1, t * n), f32)
        return pl.pallas_call(
            functools.partial(_scan1_bwd_kernel, n=n),
            grid=(bsz, blocks, nt),
            in_specs=[wide, wide, rows, rows, a_spec, d_spec, states, wide],
            out_specs=[wide, wide, parts, parts, da_spec, dd_spec],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(x.shape, f32), part, part,
                       jax.ShapeDtypeStruct((bsz, n, ch), f32),
                       jax.ShapeDtypeStruct((bsz, 1, ch), f32)],
            scratch_shapes=[by_groups(n)] * 2 + [by_groups(q)] * 5
            + [by_groups(n)] * 2 + [columns()] * 4
            + [pltpu.VMEM(((q + 1) * n, group), f32)],
            compiler_params=params, interpret=interpret,
            name="selective_scan_bwd")(x, dt, b_row, c_row, a_t, d_row,
                                       entering, dy)

    @jax.custom_vjp
    def scan(x, dt, a_t, b_row, c_row, d_row):
        return forward(x, dt, a_t, b_row, c_row, d_row)[0]

    def scan_fwd(*operands):
        y, entering = forward(*operands)
        return y, (operands, entering)

    def scan_bwd(res, dy):
        operands, entering = res
        dx, ddt, db, dc, da, dd = backward(*operands, entering, dy)
        # the channel blocks' parts of db and dc, the rows' of da and dD
        return (dx, ddt, da.sum(axis=0), db.sum(axis=1), dc.sum(axis=1),
                dd.sum(axis=0))

    scan.defvjp(scan_fwd, scan_bwd)
    return scan


def selective_scan_pallas(x, dt, a, b, c, d, chunk: int, *,
                          interpret: bool = False):
    """`selective_scan` with the `D x` skip as a pallas TPU kernel and a
    backward kernel of its own: x `[B, T, C]`, dt `[B, T, C]` (after
    softplus, float32), a `[C, N]` (negative), b and c `[B, T, N]`, d
    `[C]` -> `y + d x` `[B, T, C]` in x's dtype (computed in float32,
    rounded once). `chunk` is what T must divide by, as it must in
    `selective_scan`; the kernels tile time by `SCAN1_STEPS`. The shapes
    are `scan1_shape_ok`'s to vouch for; `interpret` is the tests' (the
    kernel on the CPU)."""
    import jax.numpy as jnp

    bsz, t, ch = x.shape
    n = b.shape[-1]
    _whole_chunks(t, chunk)
    _whole_chunks(t, SCAN1_STEPS)
    cb = scan1_channel_block(ch)
    scan = _scan1_calls(bsz, t, ch, n, SCAN1_STEPS, cb, _scan1_group(cb),
                        interpret)
    f32 = jnp.float32
    # a with the channels on the lanes, b and c with (step, n) on the
    # lanes: 1 MB each, turned by XLA
    return scan(x, dt.astype(f32), a.astype(f32).T,
                b.astype(f32).reshape(bsz, 1, t * n),
                c.astype(f32).reshape(bsz, 1, t * n),
                d.astype(f32).reshape(1, ch))


def mamba1_mixer(h, lp: Dict[str, Any], *, chunk: int, mesh=None):
    """h `[B, T, d]` (normed, compute dtype) -> (the mixer's output before
    the residual `[B, T, d]`, the scan's output y `[B, T, C]` in the
    compute dtype: with the `D x` skip, before the gate). lp: `w_in
    [d, 2·C]` ([x | z]), `w_x [C, R + 2·N]` ([delta | B | C]), `w_dt
    [R, C]` and `w_out [C, d]` in the compute dtype; `conv_w [C, K]`,
    `conv_b`, `dt_bias [C]`, `A_log [C, N]`, `D [C]`. Channels, state and
    the step's rank are read off the leaves. B, C and delta come off the
    convolved x, not off the stream; dt, the decays and the state are
    float32. `mesh` is what the program runs on, for
    `selective_scan_impl`'s choice."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    inner, n = lp["A_log"].shape
    rank = lp["w_dt"].shape[0]
    with jax.named_scope("ssm/in_proj"):
        xz = jnp.einsum("btd,de->bte", h, lp["w_in"])
        x, z = xz[..., :inner], xz[..., inner:]
    with jax.named_scope("ssm/conv"):
        x = jax.nn.silu(causal_conv(x, lp["conv_w"], lp["conv_b"]))
    with jax.named_scope("ssm/x_proj"):
        dbc = jnp.einsum("btc,ce->bte", x, lp["w_x"],
                         preferred_element_type=f32)
        b, c = dbc[..., rank:rank + n], dbc[..., rank + n:]
        dt = jnp.einsum("btr,rc->btc", dbc[..., :rank].astype(h.dtype),
                        lp["w_dt"], preferred_element_type=f32)
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(f32))
    with jax.named_scope("ssm/scan"):
        a = -jnp.exp(lp["A_log"].astype(f32))
        if selective_scan_impl(mesh, x.shape[1], inner, n, chunk) == "pallas":
            # the kernel reads the convolution's own layout and adds D x
            y = selective_scan_pallas(x, dt, a, b, c, lp["D"], chunk)
        else:
            y = selective_scan(x, dt, a, b, c, chunk)
            y = (y + x.astype(f32) * lp["D"].astype(f32)).astype(h.dtype)
    with jax.named_scope("ssm/gate"):
        gated = y * jax.nn.silu(z)
    with jax.named_scope("ssm/out_proj"):
        return jnp.einsum("bte,ed->btd", gated, lp["w_out"]), y
