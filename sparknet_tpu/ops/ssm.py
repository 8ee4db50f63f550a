"""In-graph selective state-space layer (Mamba) — the recurrent layer type.

Mamba (Gu & Dao 2023, arXiv:2312.00752, section 3; layer semantics as in
the published ``mamba_ssm`` ``Mamba`` module and the SambaY decoder's
Mamba layers, arXiv:2507.06607).  Prototxt surface::

    layer {
      name: "mamba0" type: "Mamba" bottom: "x" top: "y" [top: "memory"]
      mamba_param { d_state: 16 d_conv: 4 expand: 2 dt_rank: 160 }
    }

[B, S, E] -> [B, S, E]; blobs, every matrix ``[out, in]``:

  W_in (2·d_inner, E)          [x~ ; z], no bias
  conv_w (d_inner, d_conv), conv_b (d_inner)     depthwise, causal
  W_x (dt_rank + 2N, d_inner)  [delta ; B ; C], no bias
  W_dt (d_inner, dt_rank), b_dt (d_inner)
  A_log (d_inner, N), D (d_inner)
  W_out (E, d_inner)           no bias

With v the input: [x~, z] = W_in v; c = silu(conv(x~) + conv_b), tap k of
``d_conv`` on x~[t - (d_conv - 1) + k]; [delta, B_t, C_t] = W_x c;
Δ = softplus(W_dt delta + b_dt); A = -exp(A_log);
h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t ⊙ c_t) B_tᵀ, h_0 = 0, h ∈ R^{d_inner×N};
y_t = h_t C_t + D ⊙ c_t; out = W_out (y ⊙ silu(z)).  A second top, where
the prototxt names one, is y itself, before the gate: the MEMORY a
``GatedMemoryUnit`` far down the net reads (SambaY).

The scan (:func:`selective_scan`, device scope ``R.scan``) has two
paths behind one ``custom_vjp`` surface and one oracle
(:func:`selective_scan_steps`); which one runs is read off the backend
and the widths (:func:`takes_kernel`), as ``ops/moe.py grouped_matmul``
does, and no flag, variable or ``Config`` field says otherwise.  h, Δ, the
exponential, the softplus and every sum are f32 in both, and neither
ever holds the [S, d_inner, N] states of a whole sequence: the forward
keeps the state every ``TIME_BLOCK`` / ``CHUNK`` steps
([B, S / 64, N, d_inner] f32, 10.5 MB a layer at 2,048 tokens of
5120 x 16) and the backward recomputes one block's states from its start.

* **On a TPU, where ``d_inner`` fills whole lane groups (% 128) and
  ``d_state`` whole f32 sublane groups (% 8): two Pallas kernels.**  Grid
  (batch, time blocks of ``TIME_BLOCK`` steps, d-blocks of 512 lanes),
  sequential; the running state [N, d-block] lives in VMEM scratch from
  one time block to the next and in vector registers from one step to
  the next, N down the sublanes, so HBM sees ``c``, ``dt_pre`` and y once
  a pass, as the layer makes them ([B, S, d], S on the sublanes), and the
  kept states.  B_t and C_t are handed over in f32 with a lane axis of
  their own ([B, S, N, 128], 16.8 MB each, which XLA keeps in VMEM), so
  that a step loads them down the sublanes.  The forward does softplus,
  Δ·c, exp(Δ·A), the recurrence, the C read-out (a sum over sublanes)
  and the D skip.  The backward walks the time blocks from the last:
  per block it recomputes the states into VMEM (2.1 MB), then runs
  g_t = C_t dy_tᵀ + exp(Δ_{t+1} A) ⊙ g_{t+1} and forms d(c), d(dt_pre)
  (softplus' derivative inside) and the sums for d(A_log) per step from
  values that never leave VMEM; d(B_t) and d(C_t) leave it summed over
  d_inner's lane groups but not over the lanes ([B, S, N, 128]: XLA adds
  128 numbers).  Mosaic loads one row only from a sublane it knows, so
  the step loops run over groups of 8 steps, unrolled.  What is left is
  the vector unit's work, not a loop's latency: 13 multiplies and adds a
  step and lane group forward, 37 backward (Mosaic's LLO), at 1.4 to 1.8
  a cycle of 1.5 GHz.
* **Elsewhere (the CPU, odd widths, the tier-1 tests' tiny nets): the
  loop form**, ``lax.scan`` over chunks of ``CHUNK`` steps around a
  ``lax.scan`` over the steps of a chunk; its backward walks the chunks
  from the last, recomputes one chunk's states, runs the adjoint
  recurrence over the same steps and takes every gradient of the chunk
  from those two [CHUNK, B, N, d_inner] arrays in whole-array
  operations.  The state is laid out [B, N, d_inner].  On the chip it is
  2,048 dependent ``while`` iterations a pass, each through HBM.

Timed on the v5e (TPU v5 lite) at 1 x 2,048 x 5120, state 16, alone
(``tools/scan_kernel.py``; PERF.md section 6, PR 33): the kernels 0.51 ms
forward and 1.65 ms forward + backward, the loop form 1.86 and 6.72 (time
blocks of 128 steps 0.5 % faster, d-blocks of 256 lanes 6 % slower, of
1,024 refused: VMEM).  From the time-step oracle the kernels' forward and
six gradients lie 0 to 2e-6 (relative), the loop form's 7e-5 to 2.6e-3.
In the hybrid cell's step (``phi4flash-solo-s2048``, traced):
the scan 1.66 ms a layer where the loop form took 6.0 (``ssm.scan_roofline``
3.86 -> 13.96 %), the step 106.36 -> 95.35 ms, 10.386-10.439 against
9.327-9.360 sequences/s.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparknet_tpu.common import get_config
from sparknet_tpu.ops import fillers
from sparknet_tpu.ops.base import Layer, LayerOutput
from sparknet_tpu.ops.registry import register
from sparknet_tpu.proto.text_format import Message

# device scope of the scan (discretisation, recurrence, C read-out, D
# skip; forward and backward) inside the layer's ``L.<name>`` scope; in
# common.CACHE_SCOPES
SCAN_SCOPE = "R.scan"
# the loop form's steps between two kept states.  Timed once on the v5e
# at 1 x 2048 x 5120, state 16, forward + backward (PERF.md section 6,
# PR 32), when it was the chip's path too: 6.8 ms at 64 (7.2 at 32, 8.5
# at 128, 11.3 at 256: the backward's whole-array work on a chunk's
# states grows with it); unrolling the inner loop 8 or 16 times gave 7.1
# and 7.8 ms, so it is not unrolled.  Since PR 33 the chip runs the
# kernels at these widths and the constant serves the CPU and odd widths.
CHUNK = 64
# the kernels: lanes and f32 / bf16 sublanes of a vector register; steps of
# a time block (= between two kept states); widths of a d-block, the
# first that divides d_inner
LANES, SUBLANES, PACKED = 128, 8, 16
TIME_BLOCK = 64
D_BLOCKS = (512, 256, 128)
# softplus(b_dt) at initialisation (mamba_ssm's dt_min, dt_max)
DT_MIN, DT_MAX = 0.001, 0.1


def chunking(seq_len: int, chunk: int | None = None) -> tuple[int, int]:
    """(steps a chunk, chunks) the scan cuts ``seq_len`` steps into; the
    last chunk is padded with steps that leave the state as it is."""
    length = min(chunk or CHUNK, seq_len)
    return length, -(-seq_len // length)


def saved_state_bytes(batch: int, seq_len: int, d_inner: int, d_state: int,
                      chunk: int | None = None) -> int:
    """f32 bytes of the chunk-start states one layer's forward keeps for
    its backward (``chunk``: the steps between two of them)."""
    return chunking(seq_len, chunk)[1] * batch * d_state * d_inner * 4


def _time_major(x, length: int, chunks: int):
    """[B, S, F] -> [chunks, length, B, F] f32, zero steps appended."""
    x = jnp.swapaxes(x.astype(jnp.float32), 0, 1)
    pad = chunks * length - x.shape[0]
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
    return x.reshape((chunks, length) + x.shape[1:])


def _batch_major(x, seq_len: int):
    """[chunks, length, B, F] -> [B, S, F]."""
    x = x.reshape((-1,) + x.shape[2:])[:seq_len]
    return jnp.swapaxes(x, 0, 1)


def _inputs(c, dt_pre, b_mat, c_mat, a_log, chunk):
    length, chunks = chunking(c.shape[1], chunk)
    cut = lambda x: _time_major(x, length, chunks)
    # a padded step has Δ = 0: exp(0 A) = 1 and Δ c = 0 leave h alone
    delta = cut(jax.nn.softplus(dt_pre.astype(jnp.float32)))
    c_t = cut(c)
    a_t = -jnp.exp(a_log.astype(jnp.float32)).T  # [N, d]
    return delta, c_t, delta * c_t, cut(b_mat), cut(c_mat), a_t


def _advance(h, delta, u, b_vec, a_t):
    """One step: h [B, N, d] -> exp(Δ_t A) ⊙ h + (Δ_t ⊙ c_t) B_tᵀ, with
    ``delta``, ``u`` = Δ_t ⊙ c_t [B, d] and ``b_vec`` [B, N]."""
    return (jnp.exp(delta[:, None, :] * a_t) * h
            + u[:, None, :] * b_vec[:, :, None])


def _states(h, delta, u, b_mat, a_t):
    """One chunk's recurrence from state ``h`` [B, N, d]: -> (last state,
    the state after each step [L, B, N, d])."""
    def step(h, x):
        h = _advance(h, *x, a_t)
        return h, h

    return lax.scan(step, h, (delta, u, b_mat))


def _scan_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, chunk):
    delta, c_t, u, b_t, cm_t, a_t = _inputs(c, dt_pre, b_mat, c_mat, a_log,
                                            chunk)

    def one_chunk(h, xs):
        def step(h, x):
            d1, u1, b1, c1 = x
            h = _advance(h, d1, u1, b1, a_t)
            return h, jnp.sum(h * c1[:, :, None], axis=1)

        last, y = lax.scan(step, h, xs)
        return last, (h, y)

    h0 = jnp.zeros((c.shape[0], a_t.shape[0], a_t.shape[1]), jnp.float32)
    _, (starts, y) = lax.scan(one_chunk, h0, (delta, u, b_t, cm_t))
    y = y + d_skip.astype(jnp.float32) * c_t
    return _batch_major(y, c.shape[1]).astype(c.dtype), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _selective_scan(c, dt_pre, b_mat, c_mat, a_log, d_skip, chunk):
    return _scan_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, chunk)[0]


def _vjp_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, chunk):
    y, starts = _scan_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, chunk)
    return y, (c, dt_pre, b_mat, c_mat, a_log, d_skip, starts)


def _vjp_bwd(chunk, res, dy):
    c, dt_pre, b_mat, c_mat, a_log, d_skip, starts = res
    with jax.named_scope(SCAN_SCOPE):
        delta, c_t, u, b_t, cm_t, a_t = _inputs(c, dt_pre, b_mat, c_mat,
                                                a_log, chunk)
        chunks, length = delta.shape[:2]
        dy_t = _time_major(dy, length, chunks)
        d_f32 = d_skip.astype(jnp.float32)

        def one_chunk(carry, xs):
            g_next, d_a = carry  # exp(Δ_{t+1} A) ⊙ g_{t+1} of the chunk after
            h0, dl, cl, ul, bl, cml, dyl = xs
            _, hs = _states(h0, dl, ul, bl, a_t)

            def back(g_in, x):
                d1, c1, dy1 = x
                g = g_in + c1[:, :, None] * dy1[:, None, :]
                return jnp.exp(d1[:, None, :] * a_t) * g, g

            g_out, gs = lax.scan(back, g_next, (dl, cml, dyl), reverse=True)
            h_prev = jnp.concatenate([h0[None], hs[:-1]], axis=0)
            # d(loss) / d(Δ_t A): exp'(x) = exp(x)
            d_log = gs * h_prev * jnp.exp(dl[:, :, None, :] * a_t)
            d_u = jnp.sum(gs * bl[..., None], axis=2)  # [L, B, d]
            d_delta = jnp.sum(d_log * a_t, axis=2) + d_u * cl
            d_c = d_u * dl + d_f32 * dyl
            d_b = jnp.sum(gs * ul[:, :, None, :], axis=3)
            d_cm = jnp.sum(hs * dyl[:, :, None, :], axis=3)
            d_a = d_a + jnp.sum(d_log * dl[:, :, None, :], axis=(0, 1))
            return (g_out, d_a), (d_delta, d_c, d_b, d_cm)

        zero = jnp.zeros(starts.shape[1:], jnp.float32)
        (_, d_a), (d_delta, d_c, d_b, d_cm) = lax.scan(
            one_chunk, (zero, jnp.zeros_like(a_t)),
            (starts, delta, c_t, u, b_t, cm_t, dy_t), reverse=True)
        seq = c.shape[1]
        d_pre = _batch_major(d_delta, seq) * jax.nn.sigmoid(
            dt_pre.astype(jnp.float32))
        d_skip_g = jnp.sum(dy.astype(jnp.float32) * c.astype(jnp.float32),
                           axis=(0, 1))
        # A = -exp(A_log): dA / dA_log = A
        d_alog = d_a.T * -jnp.exp(a_log.astype(jnp.float32))
        return (_batch_major(d_c, seq).astype(c.dtype),
                d_pre.astype(dt_pre.dtype),
                _batch_major(d_b, seq).astype(b_mat.dtype),
                _batch_major(d_cm, seq).astype(c_mat.dtype),
                d_alog.astype(a_log.dtype), d_skip_g.astype(d_skip.dtype))


_selective_scan.defvjp(_vjp_fwd, _vjp_bwd)


# ------------------------------------------------- the kernels (a TPU)
def scan_tiles(d_inner: int, d_state: int) -> bool:
    """Whether the kernels' blocks tile the state: ``d_inner`` in whole
    lane groups, ``d_state`` in whole f32 sublane groups."""
    return d_inner % LANES == 0 and d_state % SUBLANES == 0


def takes_kernel(d_inner: int, d_state: int) -> bool:
    """Whether :func:`selective_scan` runs the kernels on these widths:
    on a TPU where they tile, read off the backend and the shapes."""
    return jax.default_backend() == "tpu" and scan_tiles(d_inner, d_state)


def time_block(seq_len: int, chunk: int | None = None) -> int:
    """Steps of one kernel time block = steps between two kept states: a
    multiple of the bf16 sublane count, no longer than the sequence
    rounded up to one."""
    up = lambda x: -(-x // PACKED) * PACKED
    return min(up(chunk or TIME_BLOCK), up(seq_len))


def _d_block(d_inner: int) -> int:
    return next(b for b in D_BLOCKS if d_inner % b == 0)


def _row(ref, g, s):
    """Step ``s`` of the ``g``-th group of SUBLANES steps of a
    [steps / SUBLANES, SUBLANES, d-block] scratch: [1, d-block], which
    broadcasts over the state's sublanes.  (Mosaic loads one row only
    from a sublane it knows when it compiles: ``g`` is a loop's counter,
    ``s`` is unrolled.)"""
    return ref[g, pl.ds(s, 1), :]


def _put(ref, x):
    """A [steps, d-block] value into a scratch :func:`_row` reads."""
    ref[...] = x.reshape(ref.shape)


def _wide(x, width):
    """B_t or C_t [N, LANES] across a d-block: the same registers again."""
    return jnp.concatenate([x] * (width // LANES), axis=1)


def _next_state(h, a, dl_scr, u_scr, bb_ref, g, s):
    """h_t from h_{t-1} at step t = ``g`` * SUBLANES + ``s`` of the block:
    exp(Δ_t A) ⊙ h + (Δ_t ⊙ c_t) B_tᵀ, all [N, d-block]."""
    return (jnp.exp(_row(dl_scr, g, s) * a) * h + _row(u_scr, g, s)
            * _wide(bb_ref[g * SUBLANES + s], a.shape[1]))


def _fold(x):
    """[N, d-block] -> [N, LANES]: the sum over its lane groups."""
    return sum(x[:, m * LANES:(m + 1) * LANES]
               for m in range(x.shape[-1] // LANES))


def _fwd_kernel(c_ref, dt_ref, bb_ref, cb_ref, a_ref, d_ref, y_ref, start_ref,
                h_scr, dl_scr, u_scr, y_scr):
    """One (batch, time block, d-block) of the forward.  ``h_scr`` holds
    every d-block's running state from one time block to the next."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _():
        h_scr[j] = jnp.zeros(h_scr.shape[1:], jnp.float32)

    start_ref[...] = h_scr[j]
    c_f = c_ref[...].astype(jnp.float32)
    delta = jax.nn.softplus(dt_ref[...].astype(jnp.float32))
    _put(dl_scr, delta)
    _put(u_scr, delta * c_f)
    a = a_ref[...]

    def steps(g, h):
        for s in range(SUBLANES):
            h = _next_state(h, a, dl_scr, u_scr, bb_ref, g, s)
            c_t = _wide(cb_ref[g * SUBLANES + s], a.shape[1])
            y_scr[g, pl.ds(s, 1), :] = jnp.sum(h * c_t, axis=0,
                                               keepdims=True)
        return h

    h_scr[j] = lax.fori_loop(0, dl_scr.shape[0], steps, h_scr[j])
    y_ref[...] = (y_scr[...].reshape(c_f.shape)
                  + d_ref[...] * c_f).astype(y_ref.dtype)


def _bwd_kernel(c_ref, dt_ref, bb_ref, cb_ref, a_ref, d_ref, dy_ref,
                start_ref, dc_ref, ddt_ref, pb_ref, pc_ref, da_ref,
                g_scr, da_scr, hs_scr, dl_scr, u_scr, dy_scr, du_scr, dd_scr):
    """One (batch, time block from the last, d-block) of the backward:
    the block's states again from its kept start into ``hs_scr`` (slot
    t + 1 is the state after step t), then the adjoint recurrence from
    the block's last step with g = exp(Δ_{t+1} A) ⊙ g_{t+1} and the sum
    for d(A) carried in ``g_scr`` / ``da_scr`` across time blocks.
    ``pb_ref`` / ``pc_ref`` take d(B_t), d(C_t) summed over the d-blocks
    but not yet over the lanes."""
    i, j = pl.program_id(1), pl.program_id(2)
    groups, width = dl_scr.shape[0], a_ref.shape[1]

    @pl.when(i == 0)
    def _():
        g_scr[j] = jnp.zeros(g_scr.shape[1:], jnp.float32)
        da_scr[j] = jnp.zeros(da_scr.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _():
        pb_ref[...] = jnp.zeros(pb_ref.shape, jnp.float32)
        pc_ref[...] = jnp.zeros(pc_ref.shape, jnp.float32)

    dt = dt_ref[...].astype(jnp.float32)
    c_f = c_ref[...].astype(jnp.float32)
    dy_f = dy_ref[...].astype(jnp.float32)
    delta = jax.nn.softplus(dt)
    _put(dl_scr, delta)
    _put(u_scr, delta * c_f)
    _put(dy_scr, dy_f)
    a = a_ref[...]
    hs_scr[0] = start_ref[...]

    def again(g, h):
        for s in range(SUBLANES):
            h = _next_state(h, a, dl_scr, u_scr, bb_ref, g, s)
            hs_scr[g * SUBLANES + s + 1] = h
        return h

    lax.fori_loop(0, groups, again, start_ref[...])

    def back(r, carry):
        g, (g_t, da) = groups - 1 - r, carry
        for s in reversed(range(SUBLANES)):
            t = g * SUBLANES + s
            dl, dy_t = _row(dl_scr, g, s), _row(dy_scr, g, s)
            b_t = _wide(bb_ref[t], width)
            g_t = g_t + _wide(cb_ref[t], width) * dy_t
            pc_ref[t] += _fold(hs_scr[t + 1] * dy_t)
            pb_ref[t] += _fold(g_t * _row(u_scr, g, s))
            du_scr[g, pl.ds(s, 1), :] = jnp.sum(g_t * b_t, axis=0,
                                                keepdims=True)
            g_t = jnp.exp(dl * a) * g_t  # what step t - 1 takes over
            # d(loss) / d(Δ_t A) = g_t ⊙ h_{t-1} ⊙ exp(Δ_t A): exp' = exp
            d_log = g_t * hs_scr[t]
            dd_scr[g, pl.ds(s, 1), :] = jnp.sum(d_log * a, axis=0,
                                                keepdims=True)
            da = da + d_log * dl
        return g_t, da

    g_scr[j], da_scr[j] = lax.fori_loop(0, groups, back,
                                        (g_scr[j], da_scr[j]))
    da_ref[...] = da_scr[j]
    d_u = du_scr[...].reshape(c_f.shape)
    # softplus' = sigmoid(x) = exp(x - softplus(x)): Mosaic's logistic
    # divides by an approximate reciprocal (3e-5 on the v5e)
    ddt_ref[...] = ((dd_scr[...].reshape(c_f.shape) + d_u * c_f)
                    * jnp.exp(dt - delta)).astype(ddt_ref.dtype)
    dc_ref[...] = (d_u * delta + d_ref[...] * dy_f).astype(dc_ref.dtype)


def _kernel_operands(c, dt_pre, b_mat, c_mat, a_log, d_skip, block, dy=None):
    """What both kernels read: the sequences as the layer made them,
    padded to whole time blocks with steps that leave the state alone
    (Δ = softplus(-1e4) = 0); B_t and C_t in f32 along a lane axis of
    their own, so that a step loads them down the sublanes; A = -exp(A_log)
    as [N, d]; D as a row."""
    pad = -c.shape[1] % block
    steps = lambda x, fill=0: jnp.pad(
        x, ((0, 0), (0, pad), (0, 0)), constant_values=fill) if pad else x
    lanes = lambda x: jnp.broadcast_to(
        steps(x).astype(jnp.float32)[..., None], x.shape[:1]
        + (x.shape[1] + pad, x.shape[2], LANES))
    seqs = [steps(c), steps(dt_pre, -1e4), lanes(b_mat), lanes(c_mat),
            -jnp.exp(a_log.astype(jnp.float32)).T,
            d_skip.astype(jnp.float32)[None]]
    return seqs if dy is None else seqs + [steps(dy)]


def _specs(batch, seq, d_inner, n, block, rev):
    """(grid, d-block width, the BlockSpecs by kind).  ``rev``: the time
    blocks from the last to the first."""
    d_blk = _d_block(d_inner)
    blocks = seq // block
    at = (lambda i: blocks - 1 - i) if rev else (lambda i: i)
    return (batch, blocks, d_inner // d_blk), d_blk, dict(
        seq=pl.BlockSpec((None, block, d_blk),
                         lambda b, i, j: (b, at(i), j)),
        vec=pl.BlockSpec((None, block, n, LANES),
                         lambda b, i, j: (b, at(i), 0, 0)),
        a=pl.BlockSpec((n, d_blk), lambda b, i, j: (0, j)),
        row=pl.BlockSpec((1, d_blk), lambda b, i, j: (0, j)),
        state=pl.BlockSpec((None, None, n, d_blk),
                           lambda b, i, j: (b, at(i), 0, j)),
        da=pl.BlockSpec((None, n, d_blk), lambda b, i, j: (b, 0, j)))


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


# jitted: a process traces each kernel's body once for its shapes, not once
# a layer in every program that holds the net (the hybrid cell's set-up
# traced them 18 times, ~4 s of a 41 s set-up; PERF.md section 6, PR 33)
@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _kernel_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, block, interpret):
    """-> (y [B, S, d] in ``c``'s dtype, the state at each time block's
    start [B, S / block, N, d] f32)."""
    ops = _kernel_operands(c, dt_pre, b_mat, c_mat, a_log, d_skip, block)
    (batch, seq, d_inner), n = ops[0].shape, a_log.shape[1]
    grid, d_blk, spec = _specs(batch, seq, d_inner, n, block, rev=False)
    f32 = jnp.float32
    y, starts = pl.pallas_call(
        _fwd_kernel,
        grid=grid,
        in_specs=[spec["seq"], spec["seq"], spec["vec"], spec["vec"],
                  spec["a"], spec["row"]],
        out_specs=(spec["seq"], spec["state"]),
        out_shape=(jax.ShapeDtypeStruct((batch, seq, d_inner), c.dtype),
                   jax.ShapeDtypeStruct((batch, grid[1], n, d_inner), f32)),
        scratch_shapes=[pltpu.VMEM((grid[2], n, d_blk), f32)]
        + [pltpu.VMEM((block // SUBLANES, SUBLANES, d_blk), f32)] * 3,
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="ssm_scan_fwd")(*ops)
    return y[:, :c.shape[1]], starts


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _kernel_bwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, starts, dy, block,
                interpret):
    """The six cotangents, in the primals' dtypes."""
    ops = _kernel_operands(c, dt_pre, b_mat, c_mat, a_log, d_skip, block, dy)
    (batch, seq, d_inner), n = ops[0].shape, a_log.shape[1]
    grid, d_blk, spec = _specs(batch, seq, d_inner, n, block, rev=True)
    f32 = jnp.float32
    shape = jax.ShapeDtypeStruct
    d_c, d_pre, p_b, p_c, d_a = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[spec["seq"], spec["seq"], spec["vec"], spec["vec"],
                  spec["a"], spec["row"], spec["seq"], spec["state"]],
        out_specs=(spec["seq"], spec["seq"], spec["vec"], spec["vec"],
                   spec["da"]),
        out_shape=(shape((batch, seq, d_inner), c.dtype),
                   shape((batch, seq, d_inner), dt_pre.dtype),
                   shape((batch, seq, n, LANES), f32),
                   shape((batch, seq, n, LANES), f32),
                   shape((batch, n, d_inner), f32)),
        scratch_shapes=[pltpu.VMEM((grid[2], n, d_blk), f32)] * 2
        + [pltpu.VMEM((block + 1, n, d_blk), f32)]
        + [pltpu.VMEM((block // SUBLANES, SUBLANES, d_blk), f32)] * 5,
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="ssm_scan_bwd")(*ops, starts)
    true = c.shape[1]
    d_skip_g = jnp.sum(dy.astype(f32) * c.astype(f32), axis=(0, 1))
    # A = -exp(A_log): dA / dA_log = A, which ops[4] holds as [N, d]
    d_alog = (jnp.sum(d_a, axis=0) * ops[4]).T
    return (d_c[:, :true], d_pre[:, :true],
            jnp.sum(p_b[:, :true], axis=-1).astype(b_mat.dtype),
            jnp.sum(p_c[:, :true], axis=-1).astype(c_mat.dtype),
            d_alog.astype(a_log.dtype), d_skip_g.astype(d_skip.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_scan(c, dt_pre, b_mat, c_mat, a_log, d_skip, block, interpret):
    return _kernel_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, block,
                       interpret)[0]


def _kernel_vjp_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, block, interpret):
    y, starts = _kernel_fwd(c, dt_pre, b_mat, c_mat, a_log, d_skip, block,
                            interpret)
    return y, (c, dt_pre, b_mat, c_mat, a_log, d_skip, starts)


def _kernel_vjp_bwd(block, interpret, res, dy):
    with jax.named_scope(SCAN_SCOPE):
        return _kernel_bwd(*res, dy, block, interpret)


_kernel_scan.defvjp(_kernel_vjp_fwd, _kernel_vjp_bwd)


def selective_scan_kernel(c, dt_pre, b_mat, c_mat, a_log, d_skip,
                          block: int | None = None, interpret: bool = False):
    """:func:`selective_scan` by the kernels, whatever the backend:
    ``interpret`` is for the tests, which have no TPU."""
    if not scan_tiles(*a_log.shape):
        raise ValueError(f"the scan kernels do not tile d_inner x d_state = "
                         f"{a_log.shape[0]} x {a_log.shape[1]}")
    with jax.named_scope(SCAN_SCOPE):
        return _kernel_scan(c, dt_pre, b_mat, c_mat, a_log, d_skip,
                            time_block(c.shape[1], block), interpret)


def selective_scan(c, dt_pre, b_mat, c_mat, a_log, d_skip,
                   chunk: int | None = None):
    """y_t = h_t C_t + D ⊙ c_t over h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t ⊙ c_t)
    B_tᵀ, Δ = softplus(``dt_pre``), A = -exp(``a_log``), h_0 = 0.

    ``c``, ``dt_pre`` [B, S, d]; ``b_mat``, ``c_mat`` [B, S, N]; ``a_log``
    [d, N]; ``d_skip`` [d] -> [B, S, d] in ``c``'s dtype.  ``chunk``: steps
    between kept states (``TIME_BLOCK`` for the kernels, ``CHUNK`` for the
    loop form; tests pass others).  Which of the two runs is read off the
    backend and the widths (:func:`takes_kernel`)."""
    if takes_kernel(*a_log.shape):
        return selective_scan_kernel(c, dt_pre, b_mat, c_mat, a_log, d_skip,
                                     chunk)
    with jax.named_scope(SCAN_SCOPE):
        return _selective_scan(c, dt_pre, b_mat, c_mat, a_log, d_skip, chunk)


def selective_scan_steps(c, dt_pre, b_mat, c_mat, a_log, d_skip):
    """:func:`selective_scan` as one ``lax.scan`` over time under plain
    autodiff: the oracle the chunked scan is tested against."""
    f32 = lambda x: jnp.swapaxes(x.astype(jnp.float32), 0, 1)
    a = -jnp.exp(a_log.astype(jnp.float32))  # [d, N]

    def step(h, x):
        c1, d1, b1, cm1 = x
        h = (jnp.exp(d1[..., None] * a) * h
             + (d1 * c1)[..., None] * b1[:, None, :])
        return h, jnp.sum(h * cm1[:, None, :], axis=-1) + d_skip * c1

    h0 = jnp.zeros((c.shape[0],) + a.shape, jnp.float32)
    _, y = lax.scan(step, h0, (f32(c), jax.nn.softplus(f32(dt_pre)),
                               f32(b_mat), f32(c_mat)))
    return jnp.swapaxes(y, 0, 1).astype(c.dtype)


def causal_conv(x, weight, bias):
    """Depthwise causal convolution over time: x [B, S, d], weight
    [d, K], bias [d]; tap k multiplies x[t - (K - 1) + k]."""
    taps = weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    seq = x.shape[1]
    return bias + sum(padded[:, k:k + seq] * weight[:, k]
                      for k in range(taps))


@register
class MambaLayer(Layer):
    """The selective state-space layer (module docstring).
    ``mamba_param { d_state d_conv expand dt_rank weight_filler }``;
    ``dt_rank`` defaults to ceil(E / 16).  b_dt starts where softplus(b_dt)
    is log-uniform in [``DT_MIN``, ``DT_MAX``] = [0.001, 0.1],
    A_log at log(1 .. N) in every row, D at 1, conv_w uniform in
    ±d_conv^-0.5 and conv_b at 0, W_dt uniform in ±dt_rank^-0.5 (the
    published ``mamba_ssm`` initialisation)."""

    TYPE = "Mamba"
    # b_dt, A_log and D stay in the parameter dtype under a narrower
    # compute dtype: the scan's own arithmetic is f32
    F32_BLOBS = (5, 6, 7)

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("mamba_param")
        self.d_state = p.get_int("d_state", 16)
        self.d_conv = p.get_int("d_conv", 4)
        self.expand = p.get_int("expand", 2)
        self.dt_rank = p.get_int("dt_rank", 0)
        self.weight_filler = (
            p.get_msg("weight_filler") if p.has("weight_filler")
            else Message().set("type", "xavier"))
        # what Solver._fence_stats reports; known once shapes are (init)
        self.chunk = self.saved_bytes = 0
        self.kernel = False

    def init(self, key, in_shapes):
        B, S, E = in_shapes[0]
        d, n, taps = self.expand * E, self.d_state, self.d_conv
        rank = self.dt_rank or math.ceil(E / 16)
        # the path selective_scan takes at these widths, and what it keeps
        self.kernel = takes_kernel(d, n)
        self.chunk = time_block(S) if self.kernel else chunking(S)[0]
        self.saved_bytes = saved_state_bytes(B, S, d, n, self.chunk)
        k_in, k_conv, k_x, k_dt, k_b, k_out = jax.random.split(key, 6)
        dtype = get_config().param_dtype
        uniform = lambda k, shape, lim: jax.random.uniform(
            k, shape, dtype, -lim, lim)
        dt = jnp.exp(jax.random.uniform(k_b, (d,), jnp.float32)
                     * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        return [
            fillers.fill(self.weight_filler, k_in, (2 * d, E), dtype),
            uniform(k_conv, (d, taps), taps ** -0.5),
            jnp.zeros((d,), dtype),
            fillers.fill(self.weight_filler, k_x, (rank + 2 * n, d), dtype),
            uniform(k_dt, (d, rank), rank ** -0.5),
            # softplus^-1(dt)
            (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                             (d, n)).astype(dtype),
            jnp.ones((d,), dtype),
            fillers.fill(self.weight_filler, k_out, (E, d), dtype),
        ], {}

    def apply(self, params, state, inputs, *, train, rng=None) -> LayerOutput:
        w_in, conv_w, conv_b, w_x, w_dt, b_dt, a_log, d_skip, w_out = params
        x = inputs[0]  # [B, S, E]
        d, n = a_log.shape
        rank = w_dt.shape[1]
        xz = x @ w_in.T
        c = jax.nn.silu(causal_conv(xz[..., :d], conv_w, conv_b))
        dbc = c @ w_x.T
        dt_pre = (dbc[..., :rank] @ w_dt.T).astype(jnp.float32) + b_dt
        y = selective_scan(c, dt_pre, dbc[..., rank:rank + n],
                           dbc[..., rank + n:], a_log, d_skip)
        out = (y * jax.nn.silu(xz[..., d:])) @ w_out.T
        return LayerOutput(outputs=[out, y][:max(len(self.tops), 1)])
