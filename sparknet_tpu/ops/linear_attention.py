"""In-graph gated-DeltaNet linear attention — the matrix-state layer type.

Gated Delta Networks (Yang, Kautz & Hatamizadeh 2024, arXiv:2412.06464),
on the delta rule of Yang et al. 2024 (arXiv:2406.06484); layer semantics
as the Qwen3-Next family's ``config.json`` sizes them (``model_type:
qwen3_next``, ``linear_*`` keys).  Prototxt surface::

    layer {
      name: "gdn0" type: "GatedDeltaNet" bottom: "x" top: "y"
      delta_param { num_k_heads: 16 num_v_heads: 32 head_k_dim: 128
                    head_v_dim: 128 conv_kernel: 4 norm_eps: 1e-6 }
    }

[B, S, E] -> [B, S, E]; with H_k key heads of d_k and H_v value heads of
d_v (H_k divides H_v; key head h // (H_v / H_k) serves value head h),
K = H_k d_k and V = H_v d_v; blobs, every matrix ``[out, in]``, no biases:

  W_qkvz (2K + 2V, E)     rows [q ; k ; v ; z]
  W_ba (2 H_v, E)         rows [b ; a]
  conv_w (2K + V, taps)   depthwise, causal, over [q ; k ; v], no bias
  dt_bias (H_v), A_log (H_v)
  norm (d_v)              the gated RMSNorm's one weight, shared by heads
  W_out (E, V)

With x the input: [q, k, v, z] = W_qkvz x, [b, a] = W_ba x;
[q, k, v] <- silu(conv([q, k, v])); per value head beta_t = sigmoid(b_t),
g_t = -exp(A_log) softplus(a_t + dt_bias), alpha_t = exp(g_t); q and k
L2-normalised over d_k per head, q scaled by d_k^-1/2; the gated delta
rule on a state S in R^{d_k x d_v} a value head, from zero:

    S <- alpha_t S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
    o_t = S^T q_t

and y = W_out (RMSNorm_{d_v}(o) * silu(z)).

The core (:func:`gated_delta_rule`, device scope ``D.delta``: the gates,
the normalisation of q and k, the rule; forward and backward) is CHUNKED,
and :func:`gated_delta_rule_steps` (a ``lax.scan`` over time, one token a
step, exactly the four assignments above, under plain autodiff) is its
definition and what the tests hold it to.  The chunked rule has two
paths behind ``custom_vjp`` surfaces; which one runs is read off the
backend and the shapes (:func:`takes_kernel`), as ``ops/ssm.py`` does,
and no flag, variable or ``Config`` field says otherwise.  The state, the
gates, the decay, A, its inverse and every sum are f32 in both; the
matmul operands W, U_0, the decayed q and k, the masked Q K^T and U are
in q's dtype in both.

Within a chunk of C = ``CHUNK`` tokens that starts from the state S_0,
with gamma_i = sum_{j <= i} g_j (the log-decay from the chunk's start):

    A_ij = beta_i exp(gamma_i - gamma_j) (k_i . k_j), j < i   (strictly lower)
    T = (I + A)^-1
    W = T (beta e^gamma . K),  U_0 = T (beta . V):   U = U_0 - W S_0
    O = (e^gamma . Q) S_0 + (M . Q K^T) U,   M_ij = exp(gamma_i - gamma_j), j <= i
    S_C = e^{gamma_C} S_0 + (e^{gamma_C - gamma} . K)^T U

(the WY form of arXiv:2406.06484 section 3 with arXiv:2412.06464's
decay).  Both paths keep, besides the layer's inputs, the state at every
chunk's start (f32, 134 MB a layer at 4,096 tokens of 32 heads of
128 x 128), never a state a token (8.6 GB), and their backward forms a
chunk's quantities again from its kept start.

* **On a TPU, with d_k and d_v in whole lane groups (% 128) and chunks of
  64: one Pallas kernel a pass** (``_delta_fwd_kernel``,
  ``_delta_bwd_kernel``; the comment above them has the layout).  A grid
  step holds a chunk of a block of key heads with all their value heads:
  it reads q, k, v once, as the convolution wrote them (the layer hands
  over its one [B, S, 2K + V] array; the index maps cut a head block out
  of it), normalises q and k, cumulates the gates, and forms A, T, W,
  U_0, the decayed q and k and the masked Q K^T in VMEM, batched over
  the block's value heads; the [d_k, d_v] states live in VMEM scratch
  across the sequential chunk axis and leave it only as the kept
  chunk-start states.  No [C, C] array reaches HBM in either pass.  T is
  taken by joins alone (2 x 2 diagonal blocks, then five doublings), its
  ten products in three bf16 passes each.  The backward is written by
  hand: d S is carried in VMEM from the last chunk to the first, the
  inverse has its own rule (d A = -T^T d T T^T), d gamma is the row sums
  less the column sums of the masked products and d g its reverse
  cumulation, the normalisation's rule closes the kernel; the gates'
  derivative stays a ``jax.vjp`` in XLA (512 KB arrays).
* **Elsewhere (the CPU, the tier-1 tests' 8 x 16 heads, shorter chunks):
  the XLA chunked form.**  Everything that does not read S_0 (T, W, U_0,
  the masked Q K^T, the decayed q and k) is formed for ALL chunks at once
  in batched matmuls; what is sequential is a ``lax.scan`` over the S / C
  chunks whose body is the last three lines: four matmuls a chunk on a
  [d_k, d_v] state a head.  T is taken in ten whole [C, C] ``HIGHEST``
  matmuls a chunk (:func:`_unit_lower_inverse`: 16-row diagonal blocks by
  their finite Neumann product, then joined pairwise).  The backward
  walks the chunks from the last with the ``vjp`` of the scan's body at
  the kept start state and pulls the cotangents of the chunk-parallel
  quantities back through the ``vjp`` of their own function: no
  derivative of this path is written by hand.  On the chip it was the
  only path until PR 48: some thirty [2048 chunk-heads, 64, 64] f32
  arrays a pass through HBM, each lane-padded to 128.

Timed on the v5e (TPU v5 lite) at 1 x 4,096 tokens, 16 / 32 heads of 128,
bf16, alone (``tools/delta_kernel.py``; PERF.md section 6, PR 48): the
kernels 1.43 ms forward and 3.43 ms forward + backward, the XLA form
3.95 and 10.36; both 4.83e-3 from the definition's forward (the operands'
bf16).  A first version that walked a block's value heads one after the
other took 3.3 and 7.4 ms, 2.2 of the forward's in the inverse: each of
its ten products waited for the one before, ~140 cycles a product, and
Mosaic did not interleave the heads; batched over the heads the same
products overlap.  In the cell's step (``qwen3next-solo-s4096``, traced):
``D.delta`` 1.18 + 1.79 ms a layer where the XLA form took 3.8 + 5.1-5.4
(``gdn.core_roofline`` 4.08 -> 12.53 %), the step 151.69 -> 129.64 ms,
7.665-7.679 against 6.546-6.555 sequences/s.
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
from sparknet_tpu.ops.blocks import rms_norm
from sparknet_tpu.ops.registry import register
from sparknet_tpu.ops.ssm import DT_MAX, DT_MIN, causal_conv
from sparknet_tpu.proto.text_format import Message

# device scope of the core (gates, q/k normalisation, the chunked rule;
# forward and backward) inside the layer's ``L.<name>`` scope; in
# common.CACHE_SCOPES
DELTA_SCOPE = "D.delta"
# tokens of a chunk (= between two kept states), on both paths.  Timed on
# the v5e at 1 x 4,096 tokens, 16 / 32 heads of 128, forward + backward,
# alone, when the XLA form was the chip's path too (PERF.md section 6,
# PR 47: 10.44 ms at 64, 16.63 at 32, 11.73 at 128); the kernels' blocks
# are built on it (two f32 [64, 64] tiles fill a vector register row).
CHUNK = 64
# rows of a diagonal block the inverse takes by its Neumann product
INVERSE_BASE = 16
# A at initialisation is uniform in (0, A_MAX): the published code's
A_MAX = 16.0


def chunking(seq_len: int, chunk: int | None = None) -> tuple[int, int]:
    """(tokens a chunk, chunks) the core cuts ``seq_len`` tokens into: a
    chunk is a power of two no longer than ``chunk``; the last chunk is
    padded with tokens that leave the state as it is."""
    length = min(chunk or CHUNK, 1 << max(seq_len - 1, 0).bit_length())
    if length & (length - 1):
        raise ValueError(f"a chunk of {length} tokens is no power of two "
                         "(the inverse joins its blocks pairwise)")
    return length, -(-seq_len // length)


def saved_state_bytes(batch: int, seq_len: int, v_heads: int, d_k: int,
                      d_v: int, chunk: int | None = None) -> int:
    """f32 bytes of the chunk-start states one layer's forward keeps for
    its backward."""
    return chunking(seq_len, chunk)[1] * batch * v_heads * d_k * d_v * 4


def gates(a, b, a_log, dt_bias):
    """(g, beta) [B, S, H_v] f32: the log-decay g = -exp(A_log) softplus(a +
    dt_bias) and the writing strength beta = sigmoid(b)."""
    f32 = jnp.float32
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))
    return g, jax.nn.sigmoid(b.astype(f32))


def _unit(x, scale: float = 1.0):
    """``x`` L2-normalised over its last axis (f32 statistics), times
    ``scale``, in ``x``'s dtype."""
    return _unit_parts(x, scale)[0]


def _unit_parts(x, scale):
    """(:func:`_unit`, ``x`` in f32, 1 / its norm [..., 1])."""
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + 1e-6)
    return (xf * (inv * scale)).astype(x.dtype), xf, inv


def _unit_pull(d_y, xf, inv, scale):
    """:func:`_unit`'s cotangent from ``d_y`` f32, by hand for the kernels:
    y = x n with n = scale / |x|, so d x = n d y - x (d y . y) / |x|^2."""
    of_norm = jnp.sum(d_y * (xf * (inv * scale)), axis=-1, keepdims=True)
    return (inv * scale) * d_y - xf * (inv * inv * of_norm)


def gated_delta_rule_steps(q, k, v, a, b, a_log, dt_bias):
    """The definition: one ``lax.scan`` over time, one token a step, under
    plain autodiff.  ``q``, ``k`` [B, S, H_k, d_k]; ``v`` [B, S, H_v, d_v];
    ``a``, ``b`` [B, S, H_v]; ``a_log``, ``dt_bias`` [H_v] -> o
    [B, S, H_v, d_v] in ``v``'s dtype."""
    rep = v.shape[2] // q.shape[2]
    g, beta = gates(a, b, a_log, dt_bias)
    f32 = lambda x: jnp.swapaxes(x.astype(jnp.float32), 0, 1)
    heads = lambda x: jnp.repeat(f32(x), rep, axis=2)  # key head h // rep
    qs = heads(_unit(q, q.shape[-1] ** -0.5))
    ks = heads(_unit(k))

    def step(s, x):
        q1, k1, v1, g1, b1 = x
        s = jnp.exp(g1)[..., None, None] * s
        u = b1[..., None] * (v1 - jnp.einsum("bhkv,bhk->bhv", s, k1))
        s = s + k1[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q1)

    s0 = jnp.zeros((q.shape[0], v.shape[2], q.shape[3], v.shape[3]),
                   jnp.float32)
    _, o = lax.scan(step, s0, (qs, ks, f32(v), f32(g), f32(beta)))
    return jnp.swapaxes(o, 0, 1).astype(v.dtype)


def _inverse(a):
    n = a.shape[-1]
    idx = jnp.arange(n)
    same = lambda b: (idx[:, None] // b) == (idx[None, :] // b)
    dot = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    base = min(INVERSE_BASE, n)
    eye = jnp.eye(n, dtype=a.dtype)
    # the diagonal blocks of ``base`` rows, each nilpotent of that order:
    # (I + d)^-1 = (I - d)(I + d^2)(I + d^4) .. (I + d^(base/2))
    power = jnp.where(same(base), a, 0.0)
    x = eye - power
    for _ in range(1, (base - 1).bit_length()):
        power = dot(power, power)
        x = dot(x, eye + power)
    # two inverted diagonal blocks and what lies between them
    while base < n:
        between = jnp.where(same(2 * base) & ~same(base), a, 0.0)
        x = x - dot(dot(x, between), x)
        base *= 2
    return x


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + ``a``)^-1 for strictly lower-triangular ``a`` [..., n, n], n a
    power of two, in whole [n, n] matmuls (nothing smaller: a TPU pads a
    [2, 2] tile to [8, 128]): the diagonal blocks of ``INVERSE_BASE`` rows
    by their finite Neumann product, then pairs of inverted blocks joined,
    X <- X - X A_between X (which is [[X_11, 0], [-X_22 A_21 X_11, X_22]]),
    until one block is left.  The Neumann product over all n rows would
    cancel catastrophically where keys repeat (its terms grow like
    C(n, n/2)); over 16 rows they stay under 6,435 and the joins are as
    stable as forward substitution.  The backward is the inverse's own
    rule, d a = -X^T d X X^T, not the ten matmuls differentiated."""
    return _inverse(a)


def _inverse_fwd(a):
    x = _inverse(a)
    return x, x


def _inverse_bwd(x, d_x):
    dot = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    x_t = jnp.swapaxes(x, -1, -2)
    return (-dot(dot(x_t, d_x), x_t),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _chunks(x, length: int, chunks: int):
    """[B, S, ...] -> [B, chunks, length, ...], zero tokens appended."""
    pad = chunks * length - x.shape[1]
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    return x.reshape((x.shape[0], chunks, length) + x.shape[2:])


def _chunk_parallel(q, k, v, a, b, a_log, dt_bias, length: int):
    """Everything of the chunked rule that reads no state, for all chunks
    at once -> (W, U_0, e^gamma . Q, the masked Q K^T, e^{gamma_C - gamma}
    . K, e^{gamma_C}), chunk-major for the scan: [N, B, H_k, R, C, .] with
    R = H_v / H_k value heads a key head.  The gates, gamma, every
    exponential, A and T are f32; the six results are matmul operands of
    the scan and are handed over in ``q``'s dtype (the MXU rounds an f32
    operand to bf16 anyway; e^{gamma_C}, which scales the f32 state,
    stays f32).  A padded token is all zeros (g = 0, beta = 0, k = 0): it
    leaves the state as it is."""
    seq, hk = q.shape[1], q.shape[2]
    chunks = -(-seq // length)
    rep = v.shape[2] // hk
    cdt, f32 = q.dtype, jnp.float32
    g, beta = gates(a, b, a_log, dt_bias)
    cut = lambda x: _chunks(x, length, chunks)
    # the gates head-major, [B, N, H_k, R, C]: a trailing [H_k, R] = [16, 2]
    # would be padded to a TPU tile 64 times its size
    heads = lambda x: jnp.moveaxis(cut(x), 2, -1).reshape(
        x.shape[0], chunks, hk, rep, length)
    qc, kc = cut(_unit(q, q.shape[-1] ** -0.5)), cut(_unit(k))
    vc = cut(v).reshape((v.shape[0], chunks, length, hk, rep, v.shape[3]))
    gam, bet = jnp.cumsum(heads(g), axis=-1), heads(beta)
    kk = jnp.einsum("bnihd,bnjhd->bnhij", kc, kc, preferred_element_type=f32)
    qk = jnp.einsum("bnihd,bnjhd->bnhij", qc, kc, preferred_element_type=f32)
    # gamma_i - gamma_j, [B, N, H_k, R, C, C]: <= 0 wherever it is read
    diff = gam[..., :, None] - gam[..., None, :]
    rows, cols = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    decay = lambda seen: jnp.exp(jnp.where(seen, diff, -jnp.inf))
    t = _unit_lower_inverse(
        bet[..., :, None] * decay(rows > cols) * kk[:, :, :, None])
    e_gam = jnp.exp(gam)
    out = "nbhric"  # chunk-major, then [B, H_k, R, C, .]
    mm = functools.partial(jnp.einsum, preferred_element_type=f32)
    # T's columns scaled, then one matmul with the keys / the values
    w = mm(f"bnhrij,bnjhc->{out}",
           (t * (bet * e_gam)[..., None, :]).astype(cdt), kc).astype(cdt)
    u0 = mm(f"bnhrij,bnjhrc->{out}",
            (t * bet[..., None, :]).astype(cdt), vc).astype(cdt)
    scaled = lambda e, x: jnp.einsum(  # e [B, N, H_k, R, C], x [B, N, C, H_k, d]
        f"bnhri,bnihc->{out}", e, x.astype(f32)).astype(cdt)
    p = jnp.moveaxis(decay(rows >= cols) * qk[:, :, :, None], 1, 0)
    last = gam[..., -1:]  # gamma_C
    return (w, u0, scaled(e_gam, qc), p.astype(cdt),
            scaled(jnp.exp(last - gam), kc),
            jnp.moveaxis(jnp.exp(last[..., 0]), 1, 0))


def _chunk_step(s, xs):
    """One chunk from its start state ``s`` [B, H_k, R, d_k, d_v] f32 ->
    (the next chunk's start state, the chunk's outputs [B, H_k, R, C, d_v]
    f32)."""
    w, u0, qd, p, kd, last = xs
    mm = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    u = u0 - mm(w, s)
    o = mm(qd, s) + mm(p, u.astype(p.dtype))
    return (last[..., None, None] * s
            + mm(jnp.swapaxes(kd, -1, -2), u.astype(kd.dtype))), o


def _outputs(o, like, seq: int):
    """[N, B, H_k, R, C, d_v] -> [B, S, H_v, d_v] in ``like``'s dtype."""
    n, bsz, hk, rep, length, dv = o.shape
    o = jnp.transpose(o, (1, 0, 4, 2, 3, 5)).reshape(
        bsz, n * length, hk * rep, dv)
    return o[:, :seq].astype(like.dtype)


def _rule_fwd(q, k, v, a, b, a_log, dt_bias, length):
    pre = _chunk_parallel(q, k, v, a, b, a_log, dt_bias, length)
    s0 = jnp.zeros(pre[0].shape[1:4] + (q.shape[-1], v.shape[-1]),
                   jnp.float32)

    def step(s, xs):
        nxt, o = _chunk_step(s, xs)
        return nxt, (s, o)

    _, (starts, o) = lax.scan(step, s0, pre)
    return _outputs(o, v, q.shape[1]), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _rule(q, k, v, a, b, a_log, dt_bias, length):
    return _rule_fwd(q, k, v, a, b, a_log, dt_bias, length)[0]


def _rule_vjp_fwd(q, k, v, a, b, a_log, dt_bias, length):
    o, starts = _rule_fwd(q, k, v, a, b, a_log, dt_bias, length)
    return o, (q, k, v, a, b, a_log, dt_bias, starts)


def _rule_vjp_bwd(length, res, d_o):
    *inputs, starts = res
    with jax.named_scope(DELTA_SCOPE):
        pre, pull = jax.vjp(
            lambda *xs: _chunk_parallel(*xs, length), *inputs)
        n, bsz, hk, rep = pre[0].shape[:4]
        # d_o [B, S, H_v, d_v] -> chunk-major [N, B, H_k, R, C, d_v] f32
        d_o = _chunks(d_o.astype(jnp.float32), length, n).reshape(
            bsz, n, length, hk, rep, -1)
        d_o = jnp.transpose(d_o, (1, 0, 3, 4, 2, 5))

        def back(d_s, xs):
            s, d_o1, *pre1 = xs
            _, pull1 = jax.vjp(_chunk_step, s, tuple(pre1))
            d_s, d_pre1 = pull1((d_s, d_o1))
            return d_s, d_pre1

        _, d_pre = lax.scan(back, jnp.zeros_like(starts[0]),
                            (starts, d_o, *pre), reverse=True)
        return pull(d_pre)


_rule.defvjp(_rule_vjp_fwd, _rule_vjp_bwd)


# ------------------------------------------------- the kernels (a TPU)
# One Pallas kernel a pass.  Grid (batch, blocks of HEAD_BLOCK key heads,
# blocks of CHUNK_BLOCK chunks), the chunk axis sequential; the state of
# every value head of the block, [d_k, d_v] f32, lives in VMEM scratch
# from one grid step to the next.  A grid step reads its chunks' q, k, v
# (and d o) once, as the layer holds them ([B, S, H d]: a head is a block
# of 128 lanes, cut by the index map), and forms everything else of the
# module docstring's chunk in VMEM: k k^T and q k^T once a key head, then
# for ALL value heads of the block at once (one batched matmul a line:
# the heads' dependent chains of small matmuls overlap in the MXUs, where
# one head at a time waited 140 cycles a product on its own result) A, T,
# W, U_0, the decayed q and k, the masked Q K^T and the three lines that
# read the state.  HBM sees q, k, v, o, the gates and the kept chunk-start
# states, and nothing [C, C].
#
# g and beta come in twice, each way 512 KB at the cell's size: down the
# sublanes ([B, groups, S, heads], a column a head: what scales rows) and
# along the lanes ([B, groups, N, heads, C], a row a head: what scales
# columns), so that no vector is transposed in the kernel; their
# cotangents leave it the same two ways and XLA adds them.  gamma is g
# cumulated by a product with the lower-triangular ones (f32 g in three
# bf16 pieces: the products are exact), d g the same with its transpose.
#
# T = (I + A)^-1 by joins alone: the 2 x 2 diagonal blocks of I + A invert
# to I - A exactly, and five joins X <- X - X A_between X double the
# block (what ``_unit_lower_inverse`` does from 16 rows on: as stable as
# forward substitution, no Neumann growth at all).  The ten [C, C]
# products take f32 operands as bf16 hi + lo pairs, three MXU passes a
# product (the lo x lo term, 2^-16 of the product, is dropped), summed in
# f32: Mosaic knows one-pass and six-pass products only.  Under f32
# inputs every operand is f32 and the products are ``HIGHEST``.
# key heads a grid step (with all their value heads), and chunks a grid
# step.  From ``tools/delta_kernel.py``'s table (PERF.md section 6, PR 48):
# forward + backward 3.43 ms at (4, 1), 3.38 at (4, 2), 3.45 / 3.43 at
# (8, 1) / (8, 2), 3.44 / 3.75 at (16, 1) / (16, 2): level, so the smallest
# body, which Mosaic compiles in 6.5 s where (16, 2) takes 48
HEAD_BLOCK = 4
CHUNK_BLOCK = 1
LANES = 128

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))
# batched over the leading axis (the heads of a grid step)
_BNN = (((2,), (1,)), ((0,), (0,)))
_BNT = (((2,), (2,)), ((0,), (0,)))
_BTN = (((1,), (1,)), ((0,), (0,)))


def _mm(x, y, dims=_BNN):
    return lax.dot_general(x, y, dims, preferred_element_type=jnp.float32)


def _pieces(x, count):
    """f32 ``x`` as ``count`` bf16 pieces, largest first: 8 bits each."""
    out = []
    for _ in range(count):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(jnp.float32)
    return out


def _mm32(x, y, dims=_BNN):
    """An f32 x f32 product kept f32, in three bf16 passes."""
    (xh, xl), (yh, yl) = _pieces(x, 2), _pieces(y, 2)
    return _mm(xh, yh, dims) + (_mm(xl, yh, dims) + _mm(xh, yl, dims))


def _mm_exact(x, y, dims=_BNN):
    return lax.dot_general(x, y, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _mm_ones(x, ones, dims, f32_ops, ones_first=False):
    """f32 ``x`` times a matrix of zeros and ones (that matrix times ``x``
    where ``ones_first``), exactly: ``x`` in three bf16 pieces."""
    pair = (lambda a: (ones, a)) if ones_first else (lambda a: (a, ones))
    if f32_ops:
        return _mm_exact(*pair(x), dims)
    ones = ones.astype(jnp.bfloat16)
    return sum(_mm(*pair(piece), dims) for piece in _pieces(x, 3))


def _iota2(n):
    return (lax.broadcasted_iota(jnp.int32, (n, n), 0),
            lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _join_inverse(a, mm):
    """(I + ``a``)^-1, ``a`` [H, C, C] f32 strictly lower, by joins from
    2 x 2 blocks up; ``mm`` is the f32 product."""
    n = a.shape[-1]
    rows, cols = _iota2(n)
    same = lambda b: (rows // b) == (cols // b)
    x = jnp.where(rows == cols, 1.0, 0.0) - jnp.where(same(2), a, 0.0)
    base = 2
    while base < n:
        between = jnp.where(same(2 * base) & ~same(base), a, 0.0)
        x = x - mm(mm(x, between), x)
        base *= 2
    return x


def _heads(ref, tok, width, count):
    """[count, C, width]: the heads of a [C, count * width] block."""
    return jnp.stack([ref[tok, h * width:(h + 1) * width]
                      for h in range(count)])


def _repeat(x, rep):
    """[heads, ...] -> [heads * rep, ...]: a key head's array once for
    each of its value heads."""
    return jnp.stack([x[h] for h in range(x.shape[0]) for _ in range(rep)])


def _gates_of(g_col, b_col, g_row, b_row, f32_ops):
    """(gamma's columns [H, C, 1], its rows [H, 1, C], beta's columns,
    its rows) of a chunk's H value heads, from g and beta [C, H] and
    [H, C]."""
    n = g_col.shape[0]
    rows, cols = _iota2(n)
    lower = jnp.where(rows >= cols, 1.0, 0.0)  # gamma = lower @ g
    gam_col = _mm_ones(g_col, lower, _NN, f32_ops, ones_first=True)
    gam_row = _mm_ones(g_row, lower, _NT, f32_ops)
    heads = range(g_row.shape[0])
    columns = lambda x: jnp.stack([x[:, j:j + 1] for j in heads])
    rows_of = lambda x: jnp.stack([x[j:j + 1, :] for j in heads])
    return (columns(gam_col), rows_of(gam_row), columns(b_col),
            rows_of(b_row))


def _chunk(q, k, v, kk, qk, gc, gr, bc, br, f32_ops):
    """One chunk of H value heads, everything that reads no state, from
    values in VMEM: ``q``, ``k`` [H, C, d_k], ``v`` [H, C, d_v] (the
    operands' dtype), ``kk`` = k k^T and ``qk`` = q k^T [H, C, C] f32,
    gamma and beta as columns ``gc``, ``bc`` [H, C, 1] and as rows ``gr``,
    ``br`` [H, 1, C]."""
    cdt, f32 = q.dtype, jnp.float32
    n = kk.shape[-1]
    rows, cols = _iota2(n)
    e = jnp.exp(jnp.minimum(gc - gr, 0.0))  # gamma_i - gamma_j where read
    below = jnp.where(rows > cols, e, 0.0)
    within = jnp.where(rows >= cols, e, 0.0)
    a = bc * below * kk
    t = _join_inverse(a, _mm_exact if f32_ops else _mm32)
    tw = (t * (br * jnp.exp(gr))).astype(cdt)
    tu = (t * br).astype(cdt)
    qd32 = jnp.exp(gc) * q.astype(f32)
    kd32 = jnp.exp(gc[:, n - 1:n] - gc) * k.astype(f32)
    p32 = within * qk
    # e^gamma_C as rows [H, 1, d_v]: Mosaic broadcasts along one axis at a
    # time, and folds two broadcasts into one
    is_last = lax.broadcasted_iota(jnp.int32, (n, 1), 0) == n - 1
    last = jnp.exp(jnp.sum(jnp.where(
        is_last, jnp.broadcast_to(gc, v.shape), 0.0), axis=1, keepdims=True))
    return dict(a=a, t=t, tw=tw, tu=tu, below=below, within=within,
                w=_mm(tw, k).astype(cdt), u0=_mm(tu, v).astype(cdt),
                qd32=qd32, kd32=kd32, p32=p32, qd=qd32.astype(cdt),
                kd=kd32.astype(cdt), p=p32.astype(cdt), last=last)


def _delta_fwd_kernel(q_ref, k_ref, v_ref, gc_ref, bc_ref, gr_ref, br_ref,
                      o_ref, start_ref, s_scr, *, heads, rep, chunk, blocks):
    """One (batch, head block, chunk block) of the forward."""
    dk, dv = s_scr.shape[1:]
    cdt = q_ref.dtype
    f32_ops = cdt == jnp.float32
    repeat = functools.partial(_repeat, rep=rep)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros(s_scr.shape, jnp.float32)

    for m in range(blocks):
        tok = slice(m * chunk, (m + 1) * chunk)
        q = _unit(_heads(q_ref, tok, dk, heads), dk ** -0.5)
        k = _unit(_heads(k_ref, tok, dk, heads))
        kk, qk = repeat(_mm(k, k, _BNT)), repeat(_mm(q, k, _BNT))
        c = _chunk(repeat(q), repeat(k), _heads(v_ref, tok, dv, heads * rep),
                   kk, qk, *_gates_of(gc_ref[tok, :], bc_ref[tok, :],
                                      gr_ref[m], br_ref[m], f32_ops), f32_ops)
        s = s_scr[...]
        start_ref[m] = s
        sb = s.astype(cdt)
        u = (c["u0"].astype(jnp.float32) - _mm(c["w"], sb)).astype(cdt)
        o = _mm(c["qd"], sb) + _mm(c["p"], u)
        for j in range(heads * rep):
            o_ref[tok, j * dv:(j + 1) * dv] = o[j].astype(o_ref.dtype)
        s_scr[...] = c["last"] * s + _mm(c["kd"], u, _BTN)


def _delta_bwd_kernel(q_ref, k_ref, v_ref, gc_ref, bc_ref, gr_ref, br_ref,
                      do_ref, start_ref, dq_ref, dk_ref, dv_ref, dgc_ref,
                      dbc_ref, dgr_ref, dbr_ref, ds_scr, *, heads, rep, chunk,
                      blocks):
    """One (batch, head block, chunk block from the last) of the backward:
    the chunk's quantities again from q, k, v, the gates and the kept
    start state; the sequential lines' cotangents with d S carried in
    ``ds_scr``; then the chunk-parallel part's, by hand (module docstring).
    d g and d beta leave as columns and as rows, to be added."""
    dk, dv = ds_scr.shape[1:]
    cdt, f32 = q_ref.dtype, jnp.float32
    f32_ops = cdt == jnp.float32
    mm32 = _mm_exact if f32_ops else _mm32
    rows, cols = _iota2(chunk)
    lanes = lambda x: jnp.sum(x, axis=-1, keepdims=True)     # -> [H, C, 1]
    sublanes = lambda x: jnp.sum(x, axis=-2, keepdims=True)  # -> [H, 1, .]
    is_last = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    lower = jnp.where(rows >= cols, 1.0, 0.0)
    repeat = functools.partial(_repeat, rep=rep)
    # over the value heads of a key head: [H, ...] -> [heads, ...]
    shared = lambda x: jnp.stack([
        sum(x[h * rep + r] for r in range(rep)) for h in range(heads)])

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros(ds_scr.shape, f32)

    for m in reversed(range(blocks)):
        tok = slice(m * chunk, (m + 1) * chunk)
        q1, *q_parts = _unit_parts(_heads(q_ref, tok, dk, heads), dk ** -0.5)
        k1, *k_parts = _unit_parts(_heads(k_ref, tok, dk, heads), 1.0)
        kk, qk = repeat(_mm(k1, k1, _BNT)), repeat(_mm(q1, k1, _BNT))
        q, k = repeat(q1), repeat(k1)
        v = _heads(v_ref, tok, dv, heads * rep)
        gc, gr, bc, br = _gates_of(gc_ref[tok, :], bc_ref[tok, :],
                                   gr_ref[m], br_ref[m], f32_ops)
        c = _chunk(q, k, v, kk, qk, gc, gr, bc, br, f32_ops)
        s, d_s = start_ref[m], ds_scr[...]
        sb, d_sb = s.astype(cdt), d_s.astype(cdt)
        u = (c["u0"].astype(f32) - _mm(c["w"], sb)).astype(cdt)
        d_o = _heads(do_ref, tok, dv, heads * rep).astype(cdt)
        # O = Qd S + P U;  S' = last S + Kd^T U;  U = U_0 - W S
        d_qd = _mm(d_o, sb, _BNT)
        d_p = _mm(d_o, u, _BNT)
        d_u = (_mm(c["p"], d_o, _BTN) + _mm(c["kd"], d_sb)).astype(cdt)
        d_kd = _mm(u, d_sb, _BNT)
        d_last = lanes(sublanes(s * d_s) * c["last"])  # . e^gamma_C
        ds_scr[...] = (_mm(c["qd"], d_o, _BTN) + c["last"] * d_s
                       - _mm(c["w"], d_u, _BTN))
        d_w = (-_mm(d_u, sb, _BNT)).astype(cdt)
        # W = Tw k, U_0 = Tu v: Tw, Tu are T with its columns scaled
        d_tw, d_tu = _mm(d_w, k, _BNT), _mm(d_u, v, _BNT)
        d_v = _mm(c["tu"], d_u, _BTN)
        for j in range(heads * rep):
            dv_ref[tok, j * dv:(j + 1) * dv] = d_v[j].astype(dv_ref.dtype)
        decay = jnp.exp(gr)
        scale = br * decay  # beta_j e^gamma_j, rows
        d_scale = sublanes(d_tw * c["t"])
        d_br = sublanes(d_tu * c["t"]) + d_scale * decay
        # T = (I + A)^-1: d A = -T^T d T T^T, below the diagonal
        d_t = d_tw * scale + d_tu * br
        d_a = jnp.where(rows > cols, -mm32(
            mm32(c["t"], d_t, _BTN), c["t"], _BNT), 0.0)
        # A = beta_i e^(gamma_i - gamma_j) kk;  P = M . qk
        d_kk = shared(d_a * bc * c["below"]).astype(cdt)
        d_qk = shared(d_p * c["within"]).astype(cdt)
        d_bc = lanes(d_a * c["below"] * kk)
        both = d_a * c["a"] + d_p * c["p32"]
        # Qd = e^gamma . q;  Kd = e^(gamma_C - gamma) . k;  last
        of_kd = lanes(d_kd * c["kd32"])
        d_gc = (lanes(both) + lanes(d_qd * c["qd32"]) - of_kd
                + jnp.where(is_last, sublanes(of_kd) + d_last, 0.0))
        d_gr = d_scale * scale - sublanes(both)
        for j in range(heads * rep):
            dgc_ref[tok, j:j + 1] = d_gc[j]
            dbc_ref[tok, j:j + 1] = d_bc[j]
            dgr_ref[m, j:j + 1, :] = d_gr[j]
            dbr_ref[m, j:j + 1, :] = d_br[j]
        # gamma = lower @ g: d g = lower^T @ d gamma, either way
        dgc_ref[tok, :] = _mm_ones(dgc_ref[tok, :], lower, _TN, f32_ops,
                                   ones_first=True)
        dgr_ref[m] = _mm_ones(dgr_ref[m], lower, _NN, f32_ops)
        # kk = k k^T, qk = q k^T, once for the key head's value heads
        d_q = shared(jnp.exp(gc) * d_qd) + _mm(d_qk, k1)
        d_k = (shared(_mm(c["tw"], d_w, _BTN)
                      + jnp.exp(gc[:, chunk - 1:chunk] - gc) * d_kd)
               + _mm(d_kk, k1) + _mm(d_kk, k1, _BTN) + _mm(d_qk, q1, _BTN))
        d_q = _unit_pull(d_q, *q_parts, dk ** -0.5)
        d_k = _unit_pull(d_k, *k_parts, 1.0)
        for h in range(heads):
            dq_ref[tok, h * dk:(h + 1) * dk] = d_q[h].astype(dq_ref.dtype)
            dk_ref[tok, h * dk:(h + 1) * dk] = d_k[h].astype(dk_ref.dtype)


def kernel_tiles(d_k: int, d_v: int, chunk: int) -> bool:
    """Whether the kernels' blocks tile these heads: d_k and d_v in whole
    lane groups, chunks of ``CHUNK`` tokens."""
    return d_k % LANES == 0 and d_v % LANES == 0 and chunk == CHUNK


def takes_kernel(d_k: int, d_v: int, chunk: int) -> bool:
    """Whether :func:`gated_delta_rule` runs the kernels at these sizes:
    on a TPU where they tile, read off the backend and the shapes."""
    return jax.default_backend() == "tpu" and kernel_tiles(d_k, d_v, chunk)


def _head_block(k_heads: int) -> int:
    return next(b for b in range(min(HEAD_BLOCK, k_heads), 0, -1)
                if k_heads % b == 0)


def _whole_blocks(x):
    """[B, S, ...] -> [B, S', ...], S' whole chunk blocks: a padded token
    is all zeros and leaves the state as it is."""
    pad = -x.shape[1] % (CHUNK * CHUNK_BLOCK)
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) \
        if pad else x


def _kernel_gates(a, b, a_log, dt_bias, heads):
    """g and beta, f32, as the kernels read them: down the sublanes
    [B, groups, S', heads] and along the lanes [B, groups, N, heads, C],
    ``heads`` the value heads of a head block -> (g's columns, beta's,
    g's rows, beta's)."""
    g, beta = (_whole_blocks(x) for x in gates(a, b, a_log, dt_bias))
    bsz, seq = g.shape[:2]
    by_group = lambda x: x.reshape(bsz, seq // CHUNK, CHUNK, -1, heads)
    cols = lambda x: jnp.transpose(by_group(x), (0, 3, 1, 2, 4)).reshape(
        bsz, -1, seq, heads)
    rows = lambda x: jnp.transpose(by_group(x), (0, 3, 1, 4, 2))
    return cols(g), cols(beta), rows(g), rows(beta)


def _kernel_specs(bsz, n, dims, offsets, rev):
    """(grid, the BlockSpecs by kind, the keyword sizes of a kernel body).
    ``offsets``: the lane at which q, k and v start in the arrays they
    come in (the layer hands all three one array, the convolution's
    output); ``rev``: the chunk blocks from the last to the first."""
    hk, hv, dk, dv = dims
    heads, rep = _head_block(hk), hv // hk
    steps = n // CHUNK_BLOCK
    at = (lambda i: steps - 1 - i) if rev else (lambda i: i)
    tok = CHUNK * CHUNK_BLOCK
    wide = lambda width, off=0: pl.BlockSpec(
        (None, tok, width), lambda b, j, i: (b, at(i), off // width + j))
    kw, vw = heads * dk, heads * rep * dv
    return (bsz, hk // heads, steps), dict(
        q=wide(kw, offsets[0]), k=wide(kw, offsets[1]),
        v=wide(vw, offsets[2]), key=wide(kw), value=wide(vw),
        col=pl.BlockSpec((None, None, tok, heads * rep),
                         lambda b, j, i: (b, j, at(i), 0)),
        row=pl.BlockSpec((None, None, CHUNK_BLOCK, heads * rep, CHUNK),
                         lambda b, j, i: (b, j, at(i), 0, 0)),
        state=pl.BlockSpec((None, CHUNK_BLOCK, heads * rep, dk, dv),
                           lambda b, j, i: (b, at(i), j, 0, 0))), dict(
        heads=heads, rep=rep, chunk=CHUNK, blocks=CHUNK_BLOCK)


_SEQUENTIAL = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


# jitted, as ops/ssm.py's: a process traces each body once for its shapes,
# not once a layer in every program that holds the net
@functools.partial(jax.jit, static_argnames=("dims", "offsets", "interpret"))
def _kernel_fwd(q, k, v, a, b, a_log, dt_bias, dims, offsets, interpret):
    """``q``, ``k``, ``v`` [B, S, .]: arrays in which the heads of q, k and v
    lie side by side from lane ``offsets`` on, before the normalisation
    (the same array three times where the layer calls); ``dims`` = (H_k,
    H_v, d_k, d_v) -> (o [B, S, H_v d_v] in ``v``'s dtype, the state at
    every chunk's start [B, N, H_v, d_k, d_v] f32)."""
    hk, hv, dk, dv = dims
    bsz, seq = q.shape[:2]
    gate_ops = _kernel_gates(a, b, a_log, dt_bias, _head_block(hk) * hv // hk)
    padded = gate_ops[0].shape[2]
    n = padded // CHUNK
    grid, spec, sizes = _kernel_specs(bsz, n, dims, offsets, rev=False)
    o, starts = pl.pallas_call(
        functools.partial(_delta_fwd_kernel, **sizes),
        grid=grid,
        in_specs=[spec["q"], spec["k"], spec["v"], spec["col"], spec["col"],
                  spec["row"], spec["row"]],
        out_specs=(spec["value"], spec["state"]),
        out_shape=(jax.ShapeDtypeStruct((bsz, padded, hv * dv), v.dtype),
                   jax.ShapeDtypeStruct((bsz, n, hv, dk, dv), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((sizes["heads"] * sizes["rep"], dk, dv),
                                   jnp.float32)],
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="delta_rule_fwd")(*(_whole_blocks(x) for x in (q, k, v)),
                               *gate_ops)
    return o[:, :seq], starts


@functools.partial(jax.jit, static_argnames=("dims", "offsets", "interpret"))
def _kernel_bwd(q, k, v, a, b, a_log, dt_bias, starts, d_o, dims, offsets,
                interpret):
    """-> (d q [B, S, H_k d_k], d k, d v [B, S, H_v d_v], d a, d b,
    d A_log, d dt_bias), in the primals' dtypes."""
    hk, hv, dk, dv = dims
    bsz, seq = q.shape[:2]
    gate_ops, pull = jax.vjp(
        lambda *xs: _kernel_gates(*xs, _head_block(hk) * hv // hk),
        a, b, a_log, dt_bias)
    padded = gate_ops[0].shape[2]
    n = padded // CHUNK
    grid, spec, sizes = _kernel_specs(bsz, n, dims, offsets, rev=True)
    shape = jax.ShapeDtypeStruct
    f32 = jnp.float32
    d_q, d_k, d_v, *d_gates = pl.pallas_call(
        functools.partial(_delta_bwd_kernel, **sizes),
        grid=grid,
        in_specs=[spec["q"], spec["k"], spec["v"], spec["col"], spec["col"],
                  spec["row"], spec["row"], spec["value"], spec["state"]],
        out_specs=(spec["key"], spec["key"], spec["value"], spec["col"],
                   spec["col"], spec["row"], spec["row"]),
        out_shape=(shape((bsz, padded, hk * dk), q.dtype),
                   shape((bsz, padded, hk * dk), k.dtype),
                   shape((bsz, padded, hv * dv), v.dtype),
                   *(shape(x.shape, f32) for x in gate_ops)),
        scratch_shapes=[pltpu.VMEM((sizes["heads"] * sizes["rep"], dk, dv),
                                   f32)],
        compiler_params=_SEQUENTIAL, interpret=interpret,
        name="delta_rule_bwd")(*(_whole_blocks(x) for x in (q, k, v)),
                               *gate_ops, _whole_blocks(d_o), starts)
    return (d_q[:, :seq], d_k[:, :seq], d_v[:, :seq]) + pull(tuple(d_gates))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _kernel_rule(q, k, v, a, b, a_log, dt_bias, interpret):
    return _kernel_vjp_fwd(q, k, v, a, b, a_log, dt_bias, interpret)[0]


def _by_lanes(q, k, v):
    """The heads side by side, [B, S, H d], and (H_k, H_v, d_k, d_v)."""
    flat = lambda x: x.reshape(x.shape[:2] + (-1,))
    return (flat(q), flat(k), flat(v)), q.shape[2:3] + v.shape[2:3] + (
        q.shape[3], v.shape[3])


def _kernel_vjp_fwd(q, k, v, a, b, a_log, dt_bias, interpret):
    flat, dims = _by_lanes(q, k, v)
    o, starts = _kernel_fwd(*flat, a, b, a_log, dt_bias, dims, (0, 0, 0),
                            interpret)
    return o.reshape(v.shape), (q, k, v, a, b, a_log, dt_bias, starts)


def _kernel_vjp_bwd(interpret, res, d_o):
    q, k, v = res[:3]
    flat, dims = _by_lanes(q, k, v)
    with jax.named_scope(DELTA_SCOPE):
        d_q, d_k, d_v, *d_gates = _kernel_bwd(
            *flat, *res[3:], d_o.reshape(flat[2].shape), dims, (0, 0, 0),
            interpret)
    return (d_q.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            *d_gates)


_kernel_rule.defvjp(_kernel_vjp_fwd, _kernel_vjp_bwd)


def _check_heads(q, k, v):
    if v.shape[2] % q.shape[2] or k.shape != q.shape:
        raise ValueError(
            f"{q.shape[2]} key heads must divide {v.shape[2]} value heads, "
            "and q and k be alike")


def gated_delta_rule_kernel(q, k, v, a, b, a_log, dt_bias,
                            interpret: bool = False):
    """:func:`gated_delta_rule` by the kernels, whatever the backend:
    ``interpret`` is for the tests, which have no TPU."""
    _check_heads(q, k, v)
    if not kernel_tiles(q.shape[3], v.shape[3], CHUNK):
        raise ValueError(f"the delta-rule kernels do not tile heads of "
                         f"{q.shape[3]} x {v.shape[3]}")
    with jax.named_scope(DELTA_SCOPE):
        return _kernel_rule(q, k, v, a, b, a_log, dt_bias, interpret)


def gated_delta_rule(q, k, v, a, b, a_log, dt_bias, chunk: int | None = None):
    """:func:`gated_delta_rule_steps` in its chunked form (module
    docstring), under the device scope ``D.delta``.  ``chunk``: tokens
    between two kept states (``CHUNK``; tests pass others).  Which of the
    two paths runs is read off the backend and the shapes
    (:func:`takes_kernel`)."""
    _check_heads(q, k, v)
    length = chunking(q.shape[1], chunk)[0]
    if takes_kernel(q.shape[3], v.shape[3], length):
        return gated_delta_rule_kernel(q, k, v, a, b, a_log, dt_bias)
    with jax.named_scope(DELTA_SCOPE):
        return _rule(q, k, v, a, b, a_log, dt_bias, length)


# ---------------------------------------------------------------------
# the layer between its projections: what it keeps for the backward
# ---------------------------------------------------------------------
# Between W_qkvz / W_ba and W_out the layer is the convolution, the SiLU,
# the rule and the gated RMSNorm.  Under plain autodiff their residuals
# were 616 MB a layer at 4,096 tokens (the padded convolution input, its
# output before and after the SiLU, q, k and v again as the rule's own
# residuals, the normed o, silu(z), the gated product): with the chunk
# states 1.85 GB over three layers, and the cell's step did not fit the
# chip (PERF.md section 6, PR 47).  ``_mixer`` is one ``custom_vjp`` that
# keeps qkvz (once), b and a, o and the chunk-start states (283 MB), and
# whose backward forms the cheap elementwise parts again (the convolution
# with its SiLU and the gated norm each under its own ``vjp``) around the
# rule's own backward, on the path :func:`takes_kernel` names: the kernels
# read q, k and v out of the convolution's output where it lies and hand
# their cotangents back side by side; the XLA form takes them by heads.
# dims = (H_k, H_v, d_k, d_v, tokens a chunk, norm eps).


def _conv(dims, qkvz, conv_w):
    """[q ; k ; v] <- silu(conv(.)) of qkvz's first 2K + V features, the
    heads side by side: [B, S, 2K + V]."""
    hk, hv, dk, dv = dims[:4]
    return jax.nn.silu(causal_conv(
        qkvz[..., :2 * hk * dk + hv * dv], conv_w, 0.0))


def _split(dims, qkvz, conv_w):
    """:func:`_conv` by heads: q, k [B, S, H_k, d_k], v [B, S, H_v, d_v]."""
    hk, hv, dk, dv = dims[:4]
    kw = hk * dk
    B, S, _ = qkvz.shape
    qkv = _conv(dims, qkvz, conv_w)
    return (qkv[..., :kw].reshape(B, S, hk, dk),
            qkv[..., kw:2 * kw].reshape(B, S, hk, dk),
            qkv[..., 2 * kw:].reshape(B, S, hv, dv))


def _in_place(dims, qkv):
    """(q, k, v, the lanes they start at) for the kernels: the
    convolution's output three times, each head block cut out of it by
    the index map, where v's blocks start on one; else its slices."""
    hk, hv, dk, dv = dims[:4]
    kw = hk * dk
    if (2 * kw) % (_head_block(hk) * (hv // hk) * dv) == 0:
        return (qkv, qkv, qkv), (0, kw, 2 * kw)
    return (qkv, qkv, qkv[..., 2 * kw:]), (0, kw, 0)


def _gated_norm(dims, o, qkvz, norm_w):
    """RMSNorm_{d_v}(o) * silu(z), z the last V features of qkvz ->
    [B, S, V]."""
    hk, hv, dk, dv = dims[:4]
    z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(o.shape)
    y = rms_norm(o, norm_w, dims[5]) * jax.nn.silu(z)
    return y.reshape(o.shape[:2] + (hv * dv,))


def _mixer_fwd(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w):
    _, hv, dk, dv, length = dims[:5]
    gate_in = (ba[..., hv:], ba[..., :hv], a_log, dt_bias)
    if takes_kernel(dk, dv, length):
        ops, offsets = _in_place(dims, _conv(dims, qkvz, conv_w))
        with jax.named_scope(DELTA_SCOPE):
            o, starts = _kernel_fwd(*ops, *gate_in, dims[:4], offsets, False)
        o = o.reshape(o.shape[:2] + (hv, dv))
    else:
        q, k, v = _split(dims, qkvz, conv_w)
        with jax.named_scope(DELTA_SCOPE):
            o, starts = _rule_fwd(q, k, v, *gate_in, length)
    return (_gated_norm(dims, o, qkvz, norm_w),
            (qkvz, ba, conv_w, dt_bias, a_log, norm_w, o, starts))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mixer(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w):
    return _mixer_fwd(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w)[0]


def _mixer_bwd(dims, res, d_y):
    qkvz, ba, conv_w, dt_bias, a_log, norm_w, o, starts = res
    _, hv, dk, dv, length = dims[:5]
    gate_in = (ba[..., hv:], ba[..., :hv], a_log, dt_bias)
    _, pull_norm = jax.vjp(
        functools.partial(_gated_norm, dims), o, qkvz, norm_w)
    d_o, d_z, d_norm = pull_norm(d_y)
    if takes_kernel(dk, dv, length):
        qkv, pull_split = jax.vjp(
            functools.partial(_conv, dims), qkvz, conv_w)
        ops, offsets = _in_place(dims, qkv)
        with jax.named_scope(DELTA_SCOPE):
            d_q, d_k, d_v, *d_gates = _kernel_bwd(
                *ops, *gate_in, starts, d_o.reshape(d_o.shape[:2] + (-1,)),
                dims[:4], offsets, False)
        d_qkv, d_conv = pull_split(jnp.concatenate([d_q, d_k, d_v], axis=-1))
    else:
        (q, k, v), pull_split = jax.vjp(
            functools.partial(_split, dims), qkvz, conv_w)
        d_q, d_k, d_v, *d_gates = _rule_vjp_bwd(
            length, (q, k, v, *gate_in, starts), d_o)
        d_qkv, d_conv = pull_split((d_q, d_k, d_v))
    d_a, d_b, d_alog, d_dt = d_gates
    return (d_qkv + d_z, jnp.concatenate([d_b, d_a], axis=-1), d_conv, d_dt,
            d_alog, d_norm)


_mixer.defvjp(_mixer_fwd, _mixer_bwd)


@register
class GatedDeltaNetLayer(Layer):
    """The gated-DeltaNet token mixer (module docstring).
    ``delta_param { num_k_heads num_v_heads head_k_dim head_v_dim
    conv_kernel norm_eps weight_filler }``.  A starts uniform in (0, 16)
    (A_log its logarithm), dt_bias where softplus(dt_bias) is log-uniform
    in [0.001, 0.1] (the paper's published code, as Mamba's), conv_w
    uniform in +-conv_kernel^-0.5 (PyTorch's Conv1d), the norm at 1."""

    TYPE = "GatedDeltaNet"
    # dt_bias and A_log stay in the parameter dtype under a narrower
    # compute dtype: the gates' own arithmetic is f32
    F32_BLOBS = (3, 4)

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("delta_param")
        self.k_heads = p.get_int("num_k_heads")
        self.v_heads = p.get_int("num_v_heads", self.k_heads)
        self.d_k = p.get_int("head_k_dim")
        self.d_v = p.get_int("head_v_dim", self.d_k)
        self.taps = p.get_int("conv_kernel", 4)
        self.norm_eps = p.get_float("norm_eps", 1e-6)
        if self.v_heads % self.k_heads:
            raise ValueError(
                f"{self.name}: {self.k_heads} key heads must divide "
                f"{self.v_heads} value heads")
        self.weight_filler = (
            p.get_msg("weight_filler") if p.has("weight_filler")
            else Message().set("type", "xavier"))
        # what Solver._fence_stats reports; known once shapes are (init)
        self.chunk = self.saved_bytes = 0
        self.kernel = False  # the last trace took the Pallas kernels

    def init(self, key, in_shapes):
        B, S, E = in_shapes[0]
        kw, vw = self.k_heads * self.d_k, self.v_heads * self.d_v
        self.chunk = chunking(S)[0]
        self.kernel = takes_kernel(self.d_k, self.d_v, self.chunk)
        self.saved_bytes = saved_state_bytes(B, S, self.v_heads, self.d_k,
                                             self.d_v)
        k_in, k_ba, k_conv, k_dt, k_a, k_out = jax.random.split(key, 6)
        dtype = get_config().param_dtype
        lim = self.taps ** -0.5
        dt = jnp.exp(jax.random.uniform(k_dt, (self.v_heads,), jnp.float32)
                     * (math.log(DT_MAX) - math.log(DT_MIN))
                     + math.log(DT_MIN))
        a0 = jax.random.uniform(k_a, (self.v_heads,), jnp.float32,
                                1e-3, A_MAX)
        return [
            fillers.fill(self.weight_filler, k_in, (2 * kw + 2 * vw, E), dtype),
            fillers.fill(self.weight_filler, k_ba, (2 * self.v_heads, E),
                         dtype),
            jax.random.uniform(k_conv, (2 * kw + vw, self.taps), dtype,
                               -lim, lim),
            (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),  # softplus^-1(dt)
            jnp.log(a0).astype(dtype),
            jnp.ones((self.d_v,), dtype),
            fillers.fill(self.weight_filler, k_out, (E, vw), dtype),
        ], {}

    def apply(self, params, state, inputs, *, train, rng=None) -> LayerOutput:
        w_qkvz, w_ba, conv_w, dt_bias, a_log, norm_w, w_out = params
        x = inputs[0]  # [B, S, E]
        dims = (self.k_heads, self.v_heads, self.d_k, self.d_v,
                chunking(x.shape[1])[0], self.norm_eps)
        qkvz = x @ w_qkvz.T
        ba = jnp.dot(x, w_ba.T, preferred_element_type=jnp.float32)
        self.kernel = takes_kernel(self.d_k, self.d_v, dims[4])
        y = _mixer(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w)
        return LayerOutput(outputs=[y @ w_out.T])
