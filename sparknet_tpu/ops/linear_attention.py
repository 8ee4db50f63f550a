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
behind one ``custom_vjp`` surface, and :func:`gated_delta_rule_steps` (a
``lax.scan`` over time, one token a step, exactly the four assignments
above, under plain autodiff) is its definition and what the tests hold it
to.  One path on every backend; no flag, variable or ``Config`` field.
The state, the gates, the decay and every sum are f32.

Within a chunk of C = ``CHUNK`` tokens that starts from the state S_0,
with gamma_i = sum_{j <= i} g_j (the log-decay from the chunk's start):

    A_ij = beta_i exp(gamma_i - gamma_j) (k_i . k_j), j < i   (strictly lower)
    T = (I + A)^-1
    W = T (beta e^gamma . K),  U_0 = T (beta . V):   U = U_0 - W S_0
    O = (e^gamma . Q) S_0 + (M . Q K^T) U,   M_ij = exp(gamma_i - gamma_j), j <= i
    S_C = e^{gamma_C} S_0 + (e^{gamma_C - gamma} . K)^T U

(the WY form of arXiv:2406.06484 section 3 with arXiv:2412.06464's
decay).  Everything that does not read S_0 (T, W, U_0, the masked Q K^T,
the decayed q and k) is formed for ALL chunks at once in batched matmuls;
what is sequential is a ``lax.scan`` over the S / C chunks whose body is
the last three lines: four matmuls a chunk on a [d_k, d_v] state a head
(forming O afterwards for all chunks at once, with two matmuls left in
the scan, timed 10 % SLOWER on the v5e: the scan is not what costs;
PERF.md section 6, PR 47).
T is the inverse of a unit lower-triangular matrix, taken in ten whole
[C, C] matmuls a chunk (:func:`_unit_lower_inverse`: 16-row diagonal
blocks by their finite Neumann product, then joined pairwise) instead of
C dependent rows of forward substitution.

The forward keeps, besides the layer's inputs, the state at every chunk's
start ([S / C, B, H_v, d_k, d_v] f32: 134 MB a layer at 4,096 tokens of
32 heads of 128 x 128), never a state a token (8.6 GB).  The backward
forms the chunk-parallel quantities again, walks the chunks from the
last with the ``vjp`` of the scan's body at the kept start state, and
pulls the cotangents of the chunk-parallel quantities back through the
``vjp`` of their own function: no derivative is written by hand.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

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
# tokens of a chunk (= between two kept states).  Timed on the v5e at
# 1 x 4,096 tokens, 16 / 32 heads of 128, forward + backward, alone
# (PERF.md section 6, PR 47).
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
    xf = x.astype(jnp.float32)
    inv = lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + 1e-6)
    return (xf * (inv * scale)).astype(x.dtype)


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


def gated_delta_rule(q, k, v, a, b, a_log, dt_bias, chunk: int | None = None):
    """:func:`gated_delta_rule_steps` in its chunked form (module
    docstring), under the device scope ``D.delta``.  ``chunk``: tokens
    between two kept states (``CHUNK``; tests pass others)."""
    if v.shape[2] % q.shape[2] or k.shape != q.shape:
        raise ValueError(
            f"{q.shape[2]} key heads must divide {v.shape[2]} value heads, "
            "and q and k be alike")
    with jax.named_scope(DELTA_SCOPE):
        return _rule(q, k, v, a, b, a_log, dt_bias,
                     chunking(q.shape[1], chunk)[0])


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
# whose backward forms the cheap elementwise parts again (each under its
# own ``vjp``: no derivative is written by hand) around the rule's own
# backward.  dims = (H_k, H_v, d_k, d_v, tokens a chunk, norm eps).


def _split(dims, qkvz, conv_w):
    """[q, k, v] <- silu(conv(.)) of qkvz's first 2K + V features, by
    heads: q, k [B, S, H_k, d_k], v [B, S, H_v, d_v]."""
    hk, hv, dk, dv = dims[:4]
    kw, vw = hk * dk, hv * dv
    B, S, _ = qkvz.shape
    qkv = jax.nn.silu(causal_conv(qkvz[..., :2 * kw + vw], conv_w, 0.0))
    return (qkv[..., :kw].reshape(B, S, hk, dk),
            qkv[..., kw:2 * kw].reshape(B, S, hk, dk),
            qkv[..., 2 * kw:].reshape(B, S, hv, dv))


def _gated_norm(dims, o, qkvz, norm_w):
    """RMSNorm_{d_v}(o) * silu(z), z the last V features of qkvz ->
    [B, S, V]."""
    hk, hv, dk, dv = dims[:4]
    z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(o.shape)
    y = rms_norm(o, norm_w, dims[5]) * jax.nn.silu(z)
    return y.reshape(o.shape[:2] + (hv * dv,))


def _mixer_fwd(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w):
    hv = dims[1]
    q, k, v = _split(dims, qkvz, conv_w)
    with jax.named_scope(DELTA_SCOPE):
        o, starts = _rule_fwd(q, k, v, ba[..., hv:], ba[..., :hv], a_log,
                              dt_bias, dims[4])
    return (_gated_norm(dims, o, qkvz, norm_w),
            (qkvz, ba, conv_w, dt_bias, a_log, norm_w, o, starts))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mixer(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w):
    return _mixer_fwd(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w)[0]


def _mixer_bwd(dims, res, d_y):
    qkvz, ba, conv_w, dt_bias, a_log, norm_w, o, starts = res
    hv = dims[1]
    (q, k, v), pull_split = jax.vjp(
        functools.partial(_split, dims), qkvz, conv_w)
    _, pull_norm = jax.vjp(
        functools.partial(_gated_norm, dims), o, qkvz, norm_w)
    d_o, d_z, d_norm = pull_norm(d_y)
    d_q, d_k, d_v, d_a, d_b, d_alog, d_dt = _rule_vjp_bwd(
        dims[4], (q, k, v, ba[..., hv:], ba[..., :hv], a_log, dt_bias,
                  starts), d_o)
    d_qkv, d_conv = pull_split((d_q, d_k, d_v))
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
        self.kernel = False  # the last trace took the chunked path

    def init(self, key, in_shapes):
        B, S, E = in_shapes[0]
        kw, vw = self.k_heads * self.d_k, self.v_heads * self.d_v
        self.chunk = chunking(S)[0]
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
        self.kernel = True
        y = _mixer(dims, qkvz, ba, conv_w, dt_bias, a_log, norm_w)
        return LayerOutput(outputs=[y @ w_out.T])
