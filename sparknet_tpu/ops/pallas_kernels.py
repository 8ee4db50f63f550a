"""Hand-written pallas TPU kernels for ops XLA lowers poorly.

The reference hand-writes CUDA for every layer (ref:
caffe/src/caffe/layers/*.cu, ~3,500 LoC); on TPU, XLA:TPU covers nearly
all of it — pallas is reserved for the few ops whose natural lowering
fights the tiler.  Cross-channel LRN is the canonical case (ref:
caffe/src/caffe/layers/lrn_layer.cu): a size-5 sliding window over the
channel axis of NCHW lowers to a reduce_window whose window sits on a
non-minor axis; the kernel below instead reshapes to put space on the
128-lane minor axis, keeps the whole channel fiber resident in VMEM, and
computes the window sum as ``size`` static shifted adds on the VPU with
the x^2 buffer computed once.

``lrn_across_channels`` defaults to the XLA formulation everywhere; the
pallas kernel is opt-in via ``SPARKNET_LRN_IMPL=pallas`` (or
``force='pallas'``).  Every kernel here compiles under Mosaic on the
installed toolchain and matches its XLA twin on a v5e at its caller's
full-width shapes (``chip_smoke.py`` re-proves it on every run);
interpret mode is used by tests to pin equivalence chip-free.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# spatial tile on the minor (lane) axis; multiple of 128
_TILE = 512


def _lrn_kernel(size: int, alpha: float, beta: float, k: float, x_ref, o_ref):
    """One (batch, spatial-tile) block: refs are [1, C, T]."""
    x = x_ref[0]
    sq = x * x
    C = x.shape[0]
    pad = (size - 1) // 2
    acc = sq
    # static shifted adds over the channel axis (size is tiny: 3/5);
    # shifts past the channel count have zero window overlap — skip them
    # (same clamp as _windowed_channel_sum)
    for off in range(1, min(pad, C - 1) + 1):
        zeros = jnp.zeros((off, x.shape[1]), x.dtype)
        acc = acc + jnp.concatenate([sq[off:], zeros], axis=0)  # c+off
        acc = acc + jnp.concatenate([zeros, sq[: C - off]], axis=0)  # c-off
    scale = k + (alpha / size) * acc
    o_ref[0] = x * jnp.power(scale, -beta)


def _lrn_pallas(x: jax.Array, size: int, alpha: float, beta: float, k: float,
                interpret: bool = False) -> jax.Array:
    """x: NCHW float32/bf16.  Grid over (batch, spatial tiles); each block
    holds the full channel fiber so the window never crosses blocks."""
    B, C, H, W = x.shape
    S = H * W
    pad_s = (-S) % _TILE
    xr = x.reshape(B, C, S)
    if pad_s:
        xr = jnp.pad(xr, ((0, 0), (0, 0), (0, pad_s)))
    Sp = S + pad_s
    kernel = functools.partial(_lrn_kernel, size, alpha, beta, k)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, C, Sp), x.dtype),
        grid=(B, Sp // _TILE),
        in_specs=[
            pl.BlockSpec((1, C, _TILE), lambda b, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((1, C, _TILE), lambda b, s: (b, 0, s)),
        interpret=interpret,
    )(xr)
    return out[:, :, :S].reshape(B, C, H, W)


def lrn_across_channels_xla(x, size, alpha, beta, k, channel_axis=1):
    """reduce_window fallback (identical math, ref: lrn_layer.cpp).
    ``channel_axis``: 1 for NCHW blobs (default), 3 for NHWC — where the
    sliding window sits on the MINOR axis, the orientation the tiler
    likes natively."""
    sq = x * x
    pad = (size - 1) // 2
    dims = [1] * x.ndim
    dims[channel_axis] = size
    padding = [(0, 0)] * x.ndim
    padding[channel_axis] = (pad, size - 1 - pad)
    summed = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add,
        window_dimensions=tuple(dims),
        window_strides=(1,) * x.ndim,
        padding=tuple(padding),
    )
    return x * jnp.power(k + (alpha / size) * summed, -beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def _lrn_diff(x, size, alpha, beta, k, interpret):
    """Differentiable wrapper: pallas forward, XLA-derived backward (the
    backward recomputes through the reduce_window formulation — same math,
    and the VJP stays out of the hand-written kernel)."""
    return _lrn_pallas(x, size, alpha, beta, k, interpret=interpret)


def _lrn_diff_fwd(x, size, alpha, beta, k, interpret):
    return _lrn_pallas(x, size, alpha, beta, k, interpret=interpret), x


def _lrn_diff_bwd(size, alpha, beta, k, interpret, x, g):
    _, vjp = jax.vjp(lambda t: lrn_across_channels_xla(t, size, alpha, beta, k), x)
    return vjp(g)


_lrn_diff.defvjp(_lrn_diff_fwd, _lrn_diff_bwd)


def _windowed_channel_sum(sq, size, axis=1):
    """Sum over a symmetric ``size`` window on ``axis`` as static shifted
    adds (size-1 adds of sliced views) — the formulation the pallas
    kernel uses, expressed in HLO so XLA can fuse it with neighbors.
    reduce_window puts the window on a non-minor axis of NCHW, which the
    TPU tiler handles an order of magnitude below the bandwidth bound at
    AlexNet's norm1 shape (measured: docs/pallas_shootout_r3.json).
    ``axis=3`` is the NHWC orientation (window already minor)."""
    pad = (size - 1) // 2
    C = sq.shape[axis]
    acc = sq
    if axis == 1:
        for off in range(1, min(pad, C - 1) + 1):
            zeros = jnp.zeros_like(sq[:, :off])
            acc = acc + jnp.concatenate([sq[:, off:], zeros], axis=1)
            acc = acc + jnp.concatenate([zeros, sq[:, : C - off]], axis=1)
        return acc
    assert axis == sq.ndim - 1, "channel window must sit on axis 1 or last"
    for off in range(1, min(pad, C - 1) + 1):
        zeros = jnp.zeros_like(sq[..., :off])
        acc = acc + jnp.concatenate([sq[..., off:], zeros], axis=axis)
        acc = acc + jnp.concatenate([zeros, sq[..., : C - off]], axis=axis)
    return acc


def _pow_neg(u, beta):
    """u ** -beta without the exp/ln chain for the betas the zoo uses
    (0.75 everywhere: AlexNet/CaffeNet/GoogLeNet LRN layers).  rsqrt and
    sqrt are single fast VPU ops; jnp.power lowers to exp(-beta*log(u))."""
    if beta == 0.75:
        return jax.lax.rsqrt(u) * jax.lax.rsqrt(jnp.sqrt(u))
    if beta == 0.5:
        return jax.lax.rsqrt(u)
    if beta == 1.0:
        return 1.0 / u
    return jnp.power(u, -beta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lrn_across_channels_fused(x, size, alpha, beta, k, channel_axis=1):
    """LRN with shifted-add window sums, rsqrt-formulated power, and a
    hand-derived VJP (ref: caffe/src/caffe/layers/lrn_layer.cpp:108
    CrossChannelForward_cpu, :180 CrossChannelBackward_cpu — same math,
    reformulated for the VPU instead of the per-pixel CUDA loops).

    forward:  scale = k + alpha/size * wsum(x^2);  y = x * scale^-beta
    backward: dx = g*scale^-beta - (2*alpha*beta/size) * x * wsum(g*y/scale)
    (the window is symmetric, so the adjoint of wsum is wsum itself).
    The VJP recomputes scale from the saved x instead of storing it: the
    step is HBM-bound, so size-1 adds + a rsqrt chain are cheaper than a
    297 MB residual round-trip at AlexNet's norm1 shape.
    ``channel_axis``: 1 (NCHW, default) or last (NHWC)."""
    scale = k + (alpha / size) * _windowed_channel_sum(x * x, size,
                                                       channel_axis)
    return x * _pow_neg(scale, beta)


def _lrn_fused_fwd(x, size, alpha, beta, k, channel_axis):
    return lrn_across_channels_fused(x, size, alpha, beta, k,
                                     channel_axis), x


def _lrn_fused_bwd(size, alpha, beta, k, channel_axis, x, g):
    scale = k + (alpha / size) * _windowed_channel_sum(x * x, size,
                                                       channel_axis)
    p = _pow_neg(scale, beta)  # scale^-beta
    # y/scale = x * scale^(-beta-1); windowed sum is its own adjoint
    w = _windowed_channel_sum(g * x * p / scale, size, channel_axis)
    return (g * p - (2.0 * alpha * beta / size) * x * w,)


lrn_across_channels_fused.defvjp(_lrn_fused_fwd, _lrn_fused_bwd)


def lrn_across_channels(x, size, alpha, beta, k, force: str | None = None,
                        channel_axis: int = 1):
    """Cross-channel LRN; ``force`` = 'fused' | 'pallas' | 'interpret' |
    'xla' | None.

    None consults ``SPARKNET_LRN_IMPL`` (fused|pallas|xla); the default
    is the XLA formulation.  Differentiable on every path.

    ``channel_axis``: 1 for NCHW blobs (default), 3 for NHWC
    (``Config.layout = "nhwc"``).  The hand-written pallas kernel is
    NCHW-tuned (it exists to move the window onto the minor axis, which
    NHWC already has): a pallas/interpret request it cannot honour — a
    channels-last or non-rank-4 input — raises, as does an unknown
    ``force``; no request is quietly answered by another formulation."""
    import os

    if size % 2 == 0:
        raise ValueError(f"LRN local_size must be odd, got {size}")
    if force is None:
        force = os.environ.get("SPARKNET_LRN_IMPL", "xla")
    if force == "fused":
        return lrn_across_channels_fused(x, size, alpha, beta, k,
                                         channel_axis)
    if force == "xla":
        return lrn_across_channels_xla(x, size, alpha, beta, k,
                                       channel_axis)
    if force in ("pallas", "interpret"):
        if x.ndim != 4 or channel_axis != 1:
            raise ValueError(
                f"LRN impl {force!r} takes a rank-4 NCHW input "
                f"(channel_axis=1); got rank {x.ndim}, channel_axis="
                f"{channel_axis} — use 'xla' or 'fused' there")
        return _lrn_diff(x, size, alpha, beta, k, force == "interpret")
    raise ValueError(f"unknown LRN impl {force!r} "
                     "(fused|pallas|interpret|xla)")


# ---------------------------------------------------------------------------
# Flash attention (blocked online-softmax), the long-context MXU kernel.
# ---------------------------------------------------------------------------

_BQ = 128  # query rows per block (sublane-friendly)
_BK = 128  # key rows per inner step


def _flash_kernel(causal: bool, sm_scale: float, num_kb: int, s_real: int,
                  q_ref, k_ref, v_ref, o_ref):
    """One (batch*head, q-block) cell: q_ref [1, BQ, D]; k/v refs hold the
    full [1, S, D] fiber in VMEM; the [BQ, S] score matrix is never
    materialized — K is walked in BK-wide steps with a running max and
    denominator (the flash-attention recurrence)."""
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * sm_scale  # [BQ, D]
    D = q.shape[-1]

    def step(j, carry):
        o_acc, m, l = carry
        k = k_ref[0, pl.dslice(j * _BK, _BK), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * _BK, _BK), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [BQ, BK]
        cols = j * _BK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # padded key columns (beyond the true sequence) never participate
        s = jnp.where(cols < s_real, s, -1e30)
        if causal:
            rows = qi * _BQ + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        o_new = o_acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((q.shape[0], D), jnp.float32)
    m0 = jnp.full((q.shape[0],), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((q.shape[0],), jnp.float32)
    if causal:
        # blocks strictly above the diagonal contribute nothing; stop after
        # the q block's own diagonal block
        upper = jnp.minimum((qi + 1) * _BQ + _BK - 1, num_kb * _BK) // _BK
    else:
        upper = num_kb
    o_acc, m, l = jax.lax.fori_loop(0, upper, step, (o0, m0, l0))
    o_ref[0] = (o_acc / l[:, None]).astype(o_ref.dtype)


def _flash_pallas(q, k, v, causal: bool, interpret: bool = False):
    B, H, S, D = q.shape
    pad_q = (-S) % _BQ
    pad_k = (-S) % _BK
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # zero-pad K/V; the kernel masks padded columns by index
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    Sq, Sk = S + pad_q, S + pad_k
    kernel = functools.partial(
        _flash_kernel, causal, 1.0 / float(D) ** 0.5, Sk // _BK, S
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        grid=(B * H, Sq // _BQ),
        in_specs=[
            pl.BlockSpec((1, _BQ, D), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda bh, i: (bh, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda bh, i: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _BQ, D), lambda bh, i: (bh, i, 0)),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :S].reshape(B, H, S, D)


def attention_xla(q, k, v, causal: bool = False):
    """Unblocked stable-softmax attention (the oracle + backward path)."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[2]
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
        v.astype(jnp.float32),
    ).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_diff(q, k, v, causal, interpret):
    return _flash_pallas(q, k, v, causal, interpret=interpret)


def _flash_diff_fwd(q, k, v, causal, interpret):
    return _flash_pallas(q, k, v, causal, interpret=interpret), (q, k, v)


def _flash_diff_bwd(causal, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda a, b, c: attention_xla(a, b, c, causal), q, k, v)
    return vjp(g)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def lrn_vmem_bytes(channels: int, itemsize: int = 4) -> int:
    """Static VMEM bound for one ``_lrn_pallas`` grid cell at a given
    channel-fiber depth.  Reads the kernel's actual tile constant so a
    retuned ``_TILE`` moves the bound (and trips the banked memory
    manifest) automatically.  Terms: the [1, C, _TILE] input and output
    blocks, double-buffered by the pallas pipeline (x2 each), plus the
    kernel's three fiber-sized temporaries (``sq``, the shifted-add
    ``acc``, ``scale``)."""
    fiber = channels * _TILE * itemsize
    return (2 + 2 + 3) * fiber


def flash_vmem_bytes(seq_len: int, head_dim: int, itemsize: int = 4) -> int:
    """Static VMEM bound for one ``_flash_pallas`` grid cell.  The K/V
    BlockSpecs keep the FULL [1, S, D] fiber resident (the kernel's
    design: K is walked in ``_BK`` steps but never re-fetched), so the
    bound is linear in sequence length — this formula is where the
    kernel's long-context ceiling becomes arithmetic.  Terms: K+V full
    fibers and Q+O ``_BQ`` blocks (each double-buffered, x2), plus the
    f32 compute temporaries (q/o_acc [BQ, D], s/p [BQ, BK], the per-step
    K/V f32 casts [BK, D], and the m/l running stats)."""
    sk = seq_len + (-seq_len) % _BK
    blocks = 2 * (2 * sk * head_dim) + 2 * (2 * _BQ * head_dim)
    temps = 4 * (2 * _BQ * head_dim + 2 * _BQ * _BK
                 + 2 * _BK * head_dim + 4 * _BQ)
    return blocks * itemsize + temps


def vmem_audit_points() -> list:
    """The shapes the static VMEM audit (``analysis/memcheck.py``)
    prices against the v5e budget: every pallas kernel at the largest
    fiber any zoo family feeds it, plus a long-context planning point
    for the flash kernel's full-fiber K/V residency.  Pure arithmetic —
    importable and evaluable with zero chip time."""
    return [
        {"kernel": "lrn", "note": "alexnet/caffenet norm2 fiber (C=256, "
                                  "f32, worst zoo LRN depth)",
         "bytes": lrn_vmem_bytes(256)},
        {"kernel": "lrn", "note": "googlenet conv2/norm2 fiber (C=192, "
                                  "f32)",
         "bytes": lrn_vmem_bytes(192)},
        {"kernel": "flash", "note": "charlm default (S=128, D=16 per "
                                    "head, f32)",
         "bytes": flash_vmem_bytes(128, 16)},
        {"kernel": "flash", "note": "long-context planning point "
                                    "(S=8192, D=64, f32): the full-"
                                    "fiber K/V BlockSpec's ceiling",
         "bytes": flash_vmem_bytes(8192, 64)},
        {"kernel": "paged", "note": "charlm decode block (T=16, H=4, "
                                    "D=16 per head, f32 pools)",
         "bytes": paged_vmem_bytes(16, 4, 16)},
        {"kernel": "paged", "note": "long-context planning point "
                                    "(T=64, H=8, D=64, f32): per-cell "
                                    "VMEM is one block, NOT one fiber "
                                    "— seq_len-independent by design",
         "bytes": paged_vmem_bytes(64, 8, 64)},
    ]


def flash_attention(q, k, v, causal: bool = False, force: str | None = None):
    """Blocked attention for [B, H, S, D]; ``force`` = 'pallas' |
    'interpret' | 'xla' | None (None consults ``SPARKNET_ATTN_IMPL``,
    default xla).  Differentiable on every path; the pallas forward pairs
    with an XLA-derived backward like the LRN kernel."""
    import os

    if force is None:
        force = os.environ.get("SPARKNET_ATTN_IMPL", "xla")
    if force == "xla":
        return attention_xla(q, k, v, causal)
    if force in ("pallas", "interpret"):
        return _flash_diff(q, k, v, causal, force == "interpret")
    raise ValueError(f"unknown attention impl {force!r} "
                     "(pallas|interpret|xla)")


# ---------------------------------------------------------------------------
# Paged decode attention: one query token against a block-paged KV cache.
# ---------------------------------------------------------------------------
#
# The serving decode path (serve/paged.py, ISSUE 19) stores K/V in
# fixed-size blocks inside a shared [num_blocks, block_tokens, H, D]
# pool; each slot owns a small int32 block TABLE instead of a contiguous
# [seq_len] rectangle.  Attention then needs a block-GATHER: row b reads
# the T-token blocks its table names, in table order, and runs the same
# online-softmax recurrence the flash kernel uses — columns beyond the
# row's current position are masked to -1e30 BEFORE the softmax, so
# garbage in unwritten cache lines (the null block, a freed block's
# stale contents, a neighbour slot's tokens) contributes exactly 0.0 and
# every row's output is a pure function of its own (q, table, position).
# That independence is the paged exactness gate: interleaved decode is
# bitwise equal to decoding alone under the SAME compiled program.
#
# The pallas path grids over (row, table entry) and lets the pipeline
# fetch each table-named block: PrefetchScalarGridSpec scalar-prefetches
# the tables, so the K/V BlockSpec index_map reads the block id before
# the body runs — the kernel never materializes the [B, MB*T, H, D]
# gather the XLA twin pays for.
# Forward-only by design (decode is inference; no vjp), so unlike the
# flash kernel there is no custom_vjp pairing.


def paged_attention_xla(q, k_pool, v_pool, tables, positions):
    """Gather-then-attend oracle for the paged decode step.

    ``q`` [B, H, D] (one query token per slot), ``k_pool``/``v_pool``
    [num_blocks, block_tokens, H, D], ``tables`` [B, MB] int32 pool
    block ids in sequence order, ``positions`` [B] int32 absolute
    position of each row's query token (row b attends to logical
    columns 0..positions[b] inclusive).  Same stable-softmax f32 core
    as :func:`attention_xla`."""
    B, H, D = q.shape
    T = k_pool.shape[1]
    MB = tables.shape[1]
    k = k_pool[tables].reshape(B, MB * T, H, D)
    v = v_pool[tables].reshape(B, MB * T, H, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    cols = jnp.arange(MB * T, dtype=jnp.int32)
    s = jnp.where(cols[None, None, :] <= positions[:, None, None],
                  s, -1e30)
    return jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32)).astype(q.dtype)


def _paged_kernel(block_tokens: int, scale: float,
                  tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref):
    """One grid cell = (slot row b, table entry m): the K/V refs hold
    the [1, T, H, D] pool block the row's table names at m (gathered by
    the BlockSpec index_map off the scalar-prefetched table), folded
    into the flash-style online-softmax carry kept in VMEM scratch
    across the row's m cells."""
    b, m = pl.program_id(0), pl.program_id(1)

    @pl.when(m == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    # One query token per row leaves the MXU nothing to do, and Mosaic
    # takes no dot whose batch axis (H) sits in the middle of [T, H, D]:
    # scores and the weighted sum are VPU multiplies + reductions, every
    # carry kept rank-3 ([., H, .], heads on sublanes) so no step
    # changes layout.
    q = q_ref[...].astype(jnp.float32) * scale  # [1, H, D]
    k = k_ref[0].astype(jnp.float32)  # [T, H, D]
    v = v_ref[0].astype(jnp.float32)
    s = jnp.sum(k * q, axis=2, keepdims=True)  # [T, H, 1]
    cols = m * block_tokens + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    s = jnp.where(cols <= pos_ref[b], s, -1e30)
    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=0, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)  # [1, H, 1]
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=0, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.sum(p * v, axis=0,
                                                  keepdims=True)
    m_ref[...] = m_new

    @pl.when(m == pl.num_programs(1) - 1)
    def _():
        # positions are clamped >= 0, so column 0 is always live and
        # l > 0 for every row (idle slots included)
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_pallas(q, k_pool, v_pool, tables, positions,
                  interpret: bool = False):
    B, H, D = q.shape
    T = k_pool.shape[1]
    MB = tables.shape[1]
    kernel = functools.partial(_paged_kernel, T, 1.0 / float(D) ** 0.5)
    row = lambda b, m, tbl, pos: (b, 0, 0)  # noqa: E731
    block = lambda b, m, tbl, pos: (tbl[b, m], 0, 0, 0)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, MB),
            in_specs=[
                pl.BlockSpec((1, H, D), row),
                pl.BlockSpec((1, T, H, D), block),
                pl.BlockSpec((1, T, H, D), block),
            ],
            out_specs=pl.BlockSpec((1, H, D), row),
            scratch_shapes=[
                pltpu.VMEM((1, H, D), jnp.float32),
                pltpu.VMEM((1, H, 1), jnp.float32),
                pltpu.VMEM((1, H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, positions, q, k_pool, v_pool)


def paged_attention(q, k_pool, v_pool, tables, positions,
                    force: str | None = None):
    """Paged decode attention dispatcher; ``force`` = 'pallas' |
    'interpret' | 'xla' | None (None consults ``SPARKNET_PAGED_IMPL``,
    default xla — the virtual CPU mesh twin and the exactness-gate
    path).  Forward-only: the decode step never differentiates."""
    import os

    if force is None:
        force = os.environ.get("SPARKNET_PAGED_IMPL", "xla")
    if force == "xla":
        return paged_attention_xla(q, k_pool, v_pool, tables, positions)
    if force in ("pallas", "interpret"):
        return _paged_pallas(q, k_pool, v_pool, tables, positions,
                             interpret=force == "interpret")
    raise ValueError(f"unknown paged attention impl {force!r} "
                     "(pallas|interpret|xla)")


def paged_vmem_bytes(block_tokens: int, heads: int, head_dim: int,
                     itemsize: int = 4) -> int:
    """Static VMEM bound for one ``_paged_kernel`` grid cell.  Unlike
    the flash kernel's full-fiber K/V residency, the paged kernel keeps
    ONE [T, H, D] block of K and V in flight (the pipeline's two
    buffers each), so the bound is linear in block_tokens and
    INDEPENDENT of sequence length — the arithmetic form of "per token
    decode work stops paying O(seq_len)".  Terms: q + o [1, H, D] and
    K + V [1, T, H, D] blocks (double-buffered by the pipeline, x2
    each) at pool itemsize, the f32 carry scratch (acc, m, l), and the
    f32 compute temporaries (k/v casts, the s/p [T, H] score tiles,
    corr and m_new)."""
    hd = heads * head_dim
    blocks = 2 * (2 * hd + 2 * block_tokens * hd) * itemsize
    scratch = (hd + 2 * heads) * 4              # acc, m, l carry
    temps = (2 * block_tokens * hd              # k/v f32 casts
             + 2 * heads * block_tokens         # s, p score tiles
             + 2 * heads) * 4                   # m_new, corr
    return blocks + scratch + temps
