"""Pallas LRN kernel: interpret-mode equivalence with the XLA formulation
(value and gradient), mirroring the reference's per-layer gradient-check
discipline (ref: caffe/src/caffe/test/test_lrn_layer.cpp)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.ops.pallas_kernels import (
    lrn_across_channels,
    lrn_across_channels_xla,
)

CASES = [
    # (shape, size, alpha, beta, k)
    ((2, 5, 4, 4), 5, 1e-4, 0.75, 1.0),     # AlexNet params, tiny shape
    ((1, 96, 6, 6), 5, 1e-4, 0.75, 1.0),    # AlexNet conv1 channel count
    ((2, 8, 3, 7), 3, 5e-5, 0.75, 2.0),     # odd spatial, k != 1
]


@pytest.mark.parametrize("shape,size,alpha,beta,k", CASES)
def test_pallas_lrn_matches_xla(shape, size, alpha, beta, k):
    x = jnp.asarray(np.random.RandomState(0).randn(*shape) * 10, jnp.float32)
    ref = lrn_across_channels_xla(x, size, alpha, beta, k)
    out = lrn_across_channels(x, size, alpha, beta, k, force="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_pallas_lrn_gradient_matches_xla():
    x = jnp.asarray(np.random.RandomState(1).randn(1, 6, 4, 4) * 5, jnp.float32)

    g_pallas = jax.grad(
        lambda t: jnp.sum(lrn_across_channels(t, 5, 1e-4, 0.75, 1.0,
                                              force="interpret") ** 2))(x)
    g_xla = jax.grad(
        lambda t: jnp.sum(lrn_across_channels_xla(t, 5, 1e-4, 0.75, 1.0) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g_pallas), np.asarray(g_xla), atol=1e-4)


def test_pallas_lrn_nonaligned_spatial_padding():
    """Spatial size not a multiple of the tile exercises the pad/crop path."""
    x = jnp.asarray(np.random.RandomState(2).randn(1, 4, 13, 11), jnp.float32)
    ref = lrn_across_channels_xla(x, 3, 1e-4, 0.75, 1.0)
    out = lrn_across_channels(x, 3, 1e-4, 0.75, 1.0, force="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_lrn_layer_uses_selector_and_stays_correct():
    """The LRN layer's output is unchanged after the pallas wiring (CPU
    backend routes to XLA)."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.ops.registry import create_layer
    from sparknet_tpu.proto.text_format import Message

    lp = Message().set("name", "n").set("type", "LRN")
    lp.add("bottom", "x"); lp.add("top", "n")
    lp.set("lrn_param", Message().set("local_size", 5).set("alpha", 1e-4).set("beta", 0.75))
    layer = create_layer(lp, Phase.TRAIN)
    x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 5, 5), jnp.float32)
    out = layer.apply([], {}, [x], train=True).outputs[0]
    ref = lrn_across_channels_xla(x, 5, 1e-4, 0.75, 1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_lrn_even_size_rejected():
    x = jnp.zeros((1, 4, 2, 2), jnp.float32)
    with pytest.raises(ValueError, match="odd"):
        lrn_across_channels(x, 4, 1e-4, 0.75, 1.0)


# ------------------------------------------------------------ flash attention
class TestFlashAttention:
    """Blocked online-softmax kernel vs the unblocked oracle (interpret
    mode pins the pallas lowering on CPU; the TPU path shares the code)."""

    def _qkv(self, rng, B=2, H=3, S=256, D=64):
        mk = lambda: jnp.asarray(rng.randn(B, H, S, D) * 0.5, jnp.float32)
        return mk(), mk(), mk()

    @pytest.mark.parametrize("S", [128, 256, 200])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle(self, rng, S, causal):
        from sparknet_tpu.ops.pallas_kernels import attention_xla, flash_attention

        q, k, v = self._qkv(rng, S=S)
        ref = attention_xla(q, k, v, causal)
        out = flash_attention(q, k, v, causal, force="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_grad_matches_oracle(self, rng):
        from sparknet_tpu.ops.pallas_kernels import attention_xla, flash_attention

        q, k, v = self._qkv(rng, B=1, H=2, S=128, D=32)
        f = lambda a: jnp.sum(flash_attention(a, k, v, True, force="interpret") ** 2)
        g = lambda a: jnp.sum(attention_xla(a, k, v, True) ** 2)
        np.testing.assert_allclose(
            np.asarray(jax.grad(f)(q)), np.asarray(jax.grad(g)(q)), atol=5e-5
        )

    def test_env_dispatch_and_xla_default(self, rng, monkeypatch):
        from sparknet_tpu.ops.pallas_kernels import attention_xla, flash_attention

        q, k, v = self._qkv(rng, S=128)
        monkeypatch.delenv("SPARKNET_ATTN_IMPL", raising=False)
        default = flash_attention(q, k, v)  # default = xla formulation
        np.testing.assert_allclose(
            np.asarray(default), np.asarray(attention_xla(q, k, v)), atol=1e-6
        )
        monkeypatch.setenv("SPARKNET_ATTN_IMPL", "interpret")
        env = flash_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(env), np.asarray(attention_xla(q, k, v)), atol=2e-5
        )

    def test_bf16_inputs(self, rng):
        from sparknet_tpu.ops.pallas_kernels import attention_xla, flash_attention

        q, k, v = (x.astype(jnp.bfloat16) for x in self._qkv(rng, S=128))
        out = flash_attention(q, k, v, force="interpret")
        assert out.dtype == jnp.bfloat16
        ref = attention_xla(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2
        )

    def test_ulysses_with_interpret_kernel(self, rng, monkeypatch):
        """The sharded path composes with the kernel: ulysses local attention
        through the interpret-mode flash kernel still matches the oracle."""
        from jax.sharding import Mesh

        from sparknet_tpu.parallel.ring_attention import reference_attention
        from sparknet_tpu.parallel.ulysses import ulysses_self_attention

        monkeypatch.setenv("SPARKNET_ATTN_IMPL", "interpret")
        mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))
        q, k, v = self._qkv(rng, B=1, H=8, S=256, D=16)
        out = ulysses_self_attention(mesh, q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)

class TestFusedLrn:
    """The shifted-add + rsqrt + hand-VJP formulation must be numerically
    interchangeable with the reduce_window/power one (value AND gradient —
    the VJP is hand-derived, so the gradient check is the load-bearing
    pin; ref discipline: caffe/src/caffe/test/test_lrn_layer.cpp)."""

    @pytest.mark.parametrize("shape,size,alpha,beta,k", CASES)
    def test_value_matches_xla(self, shape, size, alpha, beta, k):
        from sparknet_tpu.ops.pallas_kernels import lrn_across_channels_fused

        x = jnp.asarray(np.random.RandomState(7).randn(*shape) * 10, jnp.float32)
        ref = lrn_across_channels_xla(x, size, alpha, beta, k)
        out = lrn_across_channels_fused(x, size, alpha, beta, k)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("beta", [0.75, 0.5, 1.0, 0.6])
    def test_grad_matches_autodiff_of_xla(self, beta):
        from sparknet_tpu.ops.pallas_kernels import lrn_across_channels_fused

        x = jnp.asarray(np.random.RandomState(8).randn(2, 7, 4, 5) * 5,
                        jnp.float32)
        # non-uniform cotangent so the windowed-sum adjoint is actually
        # exercised (sum() would feed g=1 everywhere)
        g_fused = jax.grad(lambda t: jnp.sum(
            lrn_across_channels_fused(t, 5, 1e-4, beta, 2.0) ** 2))(x)
        g_ref = jax.grad(lambda t: jnp.sum(
            lrn_across_channels_xla(t, 5, 1e-4, beta, 2.0) ** 2))(x)
        np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_selector_routes_fused(self, monkeypatch):
        monkeypatch.setenv("SPARKNET_LRN_IMPL", "fused")
        x = jnp.asarray(np.random.RandomState(9).randn(1, 6, 3, 3) * 4,
                        jnp.float32)
        out = lrn_across_channels(x, 5, 1e-4, 0.75, 1.0)
        ref = lrn_across_channels_xla(x, 5, 1e-4, 0.75, 1.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_numeric_gradient(self):
        """Central-difference check of the hand VJP itself, independent of
        the XLA formulation (both could share a bug through _pow_neg)."""
        from sparknet_tpu.ops.pallas_kernels import lrn_across_channels_fused

        rs = np.random.RandomState(10)
        x = rs.randn(1, 5, 2, 3).astype(np.float32) * 3
        co = rs.randn(1, 5, 2, 3).astype(np.float32)

        def f(t):
            return float(jnp.vdot(
                lrn_across_channels_fused(jnp.asarray(t), 5, 1e-2, 0.75, 1.0),
                jnp.asarray(co)))

        g = jax.grad(lambda t: jnp.vdot(
            lrn_across_channels_fused(t, 5, 1e-2, 0.75, 1.0),
            jnp.asarray(co)))(jnp.asarray(x))
        eps = 1e-2
        for idx in [(0, 0, 0, 0), (0, 2, 1, 1), (0, 4, 0, 2)]:
            xp = x.copy(); xp[idx] += eps
            xm = x.copy(); xm[idx] -= eps
            num = (f(xp) - f(xm)) / (2 * eps)
            assert abs(num - float(g[idx])) < 5e-3 * max(1.0, abs(num)), (
                idx, num, float(g[idx]))

    def test_window_wider_than_channels(self):
        """size=7 window on a 2-channel blob: shifts past the channel
        count contribute nothing; must match the reduce_window path
        instead of crashing (review finding, round 4)."""
        from sparknet_tpu.ops.pallas_kernels import lrn_across_channels_fused

        x = jnp.asarray(np.random.RandomState(11).randn(1, 2, 3, 3) * 5,
                        jnp.float32)
        ref = lrn_across_channels_xla(x, 7, 1e-2, 0.75, 1.0)
        out = lrn_across_channels_fused(x, 7, 1e-2, 0.75, 1.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        g_f = jax.grad(lambda t: jnp.sum(
            lrn_across_channels_fused(t, 7, 1e-2, 0.75, 1.0) ** 2))(x)
        g_r = jax.grad(lambda t: jnp.sum(
            lrn_across_channels_xla(t, 7, 1e-2, 0.75, 1.0) ** 2))(x)
        np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_r),
                                   rtol=1e-4, atol=1e-4)


def test_pallas_interpret_window_wider_than_channels():
    """The pallas kernel shares the shift clamp: size=7 on 2 channels in
    interpret mode must match reduce_window, not crash."""
    x = jnp.asarray(np.random.RandomState(12).randn(1, 2, 3, 3) * 5,
                    jnp.float32)
    ref = lrn_across_channels_xla(x, 7, 1e-2, 0.75, 1.0)
    out = lrn_across_channels(x, 7, 1e-2, 0.75, 1.0, force="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# -- dispatchers answer the request or raise: never a quiet XLA answer ------


def test_paged_kernel_matches_its_xla_twin_in_interpret_mode():
    from sparknet_tpu.ops.pallas_kernels import paged_attention

    rs = np.random.RandomState(0)
    B, T, H, D, NB, MB = 5, 8, 4, 16, 32, 4
    q = jnp.asarray(rs.randn(B, H, D), jnp.float32)
    kp = jnp.asarray(rs.randn(NB, T, H, D), jnp.float32)
    vp = jnp.asarray(rs.randn(NB, T, H, D), jnp.float32)
    tables = jnp.asarray(rs.randint(0, NB, (B, MB)), jnp.int32)
    pos = jnp.asarray(rs.randint(0, MB * T, (B,)), jnp.int32)
    ref = paged_attention(q, kp, vp, tables, pos, force="xla")
    out = paged_attention(q, kp, vp, tables, pos, force="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_lrn_dispatcher_raises_on_unknown_or_unsatisfiable_force():
    x = jnp.ones((2, 4, 3, 3), jnp.float32)
    with pytest.raises(ValueError, match="unknown LRN impl"):
        lrn_across_channels(x, 5, 1e-4, 0.75, 1.0, force="palas")
    # the kernel is NCHW-only: a channels-last or non-rank-4 request for
    # it must not come back from the XLA formulation
    for force in ("pallas", "interpret"):
        with pytest.raises(ValueError, match="rank-4 NCHW"):
            lrn_across_channels(x, 5, 1e-4, 0.75, 1.0, force=force,
                                channel_axis=3)
        with pytest.raises(ValueError, match="rank-4 NCHW"):
            lrn_across_channels(x[0], 5, 1e-4, 0.75, 1.0, force=force)


def test_lrn_dispatcher_reads_the_env_default(monkeypatch):
    x = jnp.ones((1, 4, 2, 2), jnp.float32)
    monkeypatch.setenv("SPARKNET_LRN_IMPL", "no-such-impl")
    with pytest.raises(ValueError, match="unknown LRN impl"):
        lrn_across_channels(x, 5, 1e-4, 0.75, 1.0)


def test_attention_dispatchers_raise_on_unknown_force():
    from sparknet_tpu.ops.pallas_kernels import (
        flash_attention,
        paged_attention,
    )

    q = jnp.ones((1, 1, 8, 4), jnp.float32)
    with pytest.raises(ValueError, match="unknown attention impl"):
        flash_attention(q, q, q, force="flash")
    qd = jnp.ones((1, 1, 4), jnp.float32)
    pool = jnp.ones((2, 2, 1, 4), jnp.float32)
    with pytest.raises(ValueError, match="unknown paged attention impl"):
        paged_attention(qd, pool, pool, jnp.zeros((1, 1), jnp.int32),
                        jnp.zeros((1,), jnp.int32), force="gather")
