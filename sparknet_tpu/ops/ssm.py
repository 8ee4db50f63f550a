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

The scan (:func:`selective_scan`, device scope ``R.scan``) is ONE code
path on every backend: ``lax.scan`` over chunks of ``CHUNK`` steps around
a ``lax.scan`` over the steps of a chunk, h, Δ, the exponential and the
softplus in f32.  It never holds the [S, d_inner, N] states of a whole
sequence: the forward keeps the state at each chunk's start
([S / CHUNK, B, N, d_inner] f32, 10.5 MB a layer at 2,048 tokens of
5120 x 16), and the backward (a ``custom_vjp``) walks the chunks from the
last to the first, recomputes one chunk's states from its start, runs
the adjoint recurrence g_t = C_t dy_tᵀ + exp(Δ_{t+1} A) ⊙ g_{t+1} over the
same steps, and takes every gradient of the chunk from those two
[CHUNK, B, N, d_inner] arrays in whole-array operations.  The state is
laid out [B, N, d_inner]: d_inner on the lanes.
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
from sparknet_tpu.ops.registry import register
from sparknet_tpu.proto.text_format import Message

# device scope of the scan (discretisation, recurrence, C read-out, D
# skip; forward and backward) inside the layer's ``L.<name>`` scope; in
# common.CACHE_SCOPES
SCAN_SCOPE = "R.scan"
# steps between two kept states.  Timed once on the v5e at 1 x 2048 x
# 5120, state 16, forward + backward (PERF.md section 6, PR 32): 6.8 ms at
# 64 (7.2 at 32, 8.5 at 128, 11.3 at 256: the backward's whole-array
# work on a chunk's states grows with it); unrolling the inner loop 8 or
# 16 times gave 7.1 and 7.8 ms, so it is not unrolled.
CHUNK = 64
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
    its backward."""
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


def selective_scan(c, dt_pre, b_mat, c_mat, a_log, d_skip,
                   chunk: int | None = None):
    """y_t = h_t C_t + D ⊙ c_t over h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t ⊙ c_t)
    B_tᵀ, Δ = softplus(``dt_pre``), A = -exp(``a_log``), h_0 = 0.

    ``c``, ``dt_pre`` [B, S, d]; ``b_mat``, ``c_mat`` [B, S, N]; ``a_log``
    [d, N]; ``d_skip`` [d] -> [B, S, d] in ``c``'s dtype.  ``chunk``: steps
    between kept states (``CHUNK``; tests pass others)."""
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

    def init(self, key, in_shapes):
        B, S, E = in_shapes[0]
        d, n, taps = self.expand * E, self.d_state, self.d_conv
        rank = self.dt_rank or math.ceil(E / 16)
        self.chunk = chunking(S)[0]
        self.saved_bytes = saved_state_bytes(B, S, d, n)
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
