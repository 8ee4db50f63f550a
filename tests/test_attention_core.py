"""Which kernel the attention core takes (``ops/attention.py core_kernel``:
read off the backend and the shapes, no flag), what the fence says of it,
and the splash kernels' equal-head arm against the XLA formulation in
Pallas's interpreter."""

from __future__ import annotations

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import models
from sparknet_tpu.ops import attention
from sparknet_tpu.solvers.solver import Solver

# the four decoder configurations' cores as their cells run them:
# q, k and v shapes [B, heads, S, width] and the window
OLMOE = ((4, 16, 4096, 128),) * 3 + (0,)
OURO = ((1, 16, 4096, 128),) * 3 + (0,)
MLA = ((1, 32, 4096, 192), (1, 32, 4096, 192), (1, 32, 4096, 128), 0)
HYBRID = ((1, 40, 2048, 64), (1, 20, 2048, 64), (1, 10, 2048, 128))


def with_seq(shapes, S):
    return tuple((b, h, S, d) for b, h, _, d in shapes[:3]) + shapes[3:]


@pytest.mark.parametrize("name, backend, shapes, causal, want", [
    ("olmoe", "tpu", OLMOE, True, "splash"),
    ("ouro", "tpu", OURO, True, "splash"),
    ("joyai_mla", "tpu", MLA, True, "splash"),
    ("hybrid_full", "tpu", HYBRID + (0,), True, "splash"),
    ("hybrid_window", "tpu", HYBRID + (512,), True, "splash"),
    ("s2560_tiles_in_512", "tpu", with_seq(OURO, 2560), True, "splash"),
    ("short", "tpu", with_seq(OLMOE, 1024), True, "xla"),
    ("short_mla", "tpu", with_seq(MLA, 1536), True, "xla"),
    ("s_not_in_blocks", "tpu", with_seq(OURO, 4096 + 128), True, "xla"),
    ("keys_of_96", "tpu", ((1, 16, 4096, 96),) * 3 + (0,), True, "xla"),
    ("values_of_64", "tpu", ((1, 16, 4096, 64),) * 3 + (0,), True, "xla"),
    ("not_causal", "tpu", OLMOE, False, "xla"),
    ("olmoe_on_cpu", "cpu", OLMOE, True, "xla"),
    ("ouro_on_gpu", "gpu", OURO, True, "xla"),
    ("hybrid_on_cpu", "cpu", HYBRID + (512,), True, "xla"),
])
def test_the_kernel_is_read_off_the_backend_and_the_shapes(
        monkeypatch, name, backend, shapes, causal, want):
    """``core_kernel`` names it, and ``attention_core`` takes that path
    with blocks read off S and the window: nothing else chooses."""
    q, k, v, window = shapes
    assert attention.core_kernel(backend, q[2], q[3], v[3], causal) == want
    taken = []

    def path(name):
        def stub(q, k, v, *rest, **kw):
            taken.append((name,) + rest)
            return jnp.zeros(q.shape[:3] + v.shape[3:], q.dtype)
        return stub

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(attention, "_splash_causal", path("splash"))
    monkeypatch.setattr(attention, "_attention_xla", path("xla"))
    monkeypatch.setattr(attention, "flash_attention", path("xla"))
    out = jax.eval_shape(
        lambda *x: attention.attention_core(*x, causal, window),
        *(jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (q, k, v)))
    assert out.shape == q[:3] + v[3:]
    assert [t[0] for t in taken] == [want]
    if want == "splash":
        block = 512 if window or q[2] % 1024 else 1024
        assert taken[0][1:] == (block, window, False)


def test_one_kernel_family_and_no_switch():
    """The module imports jax's splash kernels and no other attention
    kernel of jax's, and no environment variable chooses the core."""
    src = inspect.getsource(attention)
    assert "ops.tpu.splash_attention" in src
    assert "ops.tpu import flash_attention" not in src
    assert "os.environ" not in src and "getenv" not in src


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("S, block, scaled", [
    (256, 128, False), (512, 256, False), (256, 128, True)])
def test_splash_equal_heads_against_xla_in_the_interpreter(S, block, scaled):
    """The arm OLMoE's and Ouro's cores take on the chip, forward and the
    three gradients, bf16: rel <= 2e-2 of the XLA formulation; also with
    the scores' scale already on q (``scaled``: the kernel multiplies
    nothing, the caller's chain rule carries the scale to dq)."""
    ks = jax.random.split(jax.random.key(S), 4)
    shape = (2, 2, S, 128)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16) for key in ks[:3])
    do = jax.random.normal(ks[3], shape, jnp.float32)

    def both(core):
        def loss(q, k, v):
            o = core(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * do), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o,) + grads

    def splash(q, k, v):
        if scaled:
            q = (q.astype(jnp.float32) * 128 ** -0.5).astype(q.dtype)
        return attention._splash_causal(q, k, v, block, scaled=scaled,
                                        interpret=True)

    got = both(splash)
    want = both(lambda q, k, v: attention._attention_xla(q, k, v, True, 0))
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert rel(g, w) <= 2e-2, name


def olmoe_solver():
    return Solver(dataclasses.replace(models.olmoe_solver(), random_seed=3),
                  models.olmoe(batch=2, seq_len=32, vocab=97, hidden=64,
                               heads=4, experts=8, top_k=2, expert_dim=32,
                               layers=2))


def ouro_solver():
    return Solver(dataclasses.replace(models.ouro_solver(), random_seed=3),
                  models.ouro(batch=2, seq_len=32, vocab=97, hidden=64,
                              heads=4, mlp_dim=96, layers=2, ut_steps=3))


@pytest.mark.parametrize("make, layers", [(olmoe_solver, 2), (ouro_solver, 2)])
def test_the_fence_counts_the_cores_and_their_kernels(make, layers):
    """``attn_core_layers``: the attention layers (a looped layer once,
    not once a pass); ``attn_kernel_layers``: those whose last trace ran
    the splash kernels, none on the CPU, as ``ssm_kernel_layers``."""
    solver = make()
    cores = [l for l in solver.train_net.layers
             if isinstance(l, attention.AttentionLayer)]
    assert len(cores) == layers
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 97, (2, 33)).astype(np.int32)
    solver.step(1, lambda it: {"data": ids[:, :-1], "label": ids[:, 1:]})
    stats = solver._fence_stats()
    assert stats["attn_core_layers"] == layers
    assert stats["attn_kernel_layers"] == 0
    assert {l.kernel for l in cores} == {"xla"}


def test_rope_carries_a_scale_inside_its_f32_product():
    x = jax.random.normal(jax.random.key(0), (1, 2, 16, 8), jnp.float32)
    for interleave in (False, True):
        np.testing.assert_allclose(
            attention.rope(x, 1e4, interleave, scale=0.25),
            0.25 * attention.rope(x, 1e4, interleave), rtol=1e-6, atol=1e-7)
    # one rounding: the bf16 result is the rounded f32 product
    xb = x.astype(jnp.bfloat16)
    want = (attention.rope(xb.astype(jnp.float32)) * 8 ** -0.5)
    assert jnp.array_equal(attention.rope(xb, scale=8 ** -0.5),
                           want.astype(jnp.bfloat16))


@pytest.mark.parametrize("with_rope", [True, False])
def test_a_layer_keeps_what_its_trace_took_and_folds_the_scale(
        monkeypatch, with_rope):
    """On a TPU at a long causal shape the layer's ``kernel`` reads
    ``splash`` and the fence counts it (the choice at trace time, not a
    constant of the layer).  A layer with RoPE hands the kernel a q that
    carries the scores' scale already (``scaled``), one without leaves
    the scaling to ``_splash_causal``; either way the layer computes what
    it computes on the CPU."""
    solver = Solver(
        dataclasses.replace(models.ouro_solver(), random_seed=3),
        models.ouro(batch=1, seq_len=2048, vocab=97, hidden=256, heads=2,
                    mlp_dim=64, layers=1, ut_steps=2))
    layer = next(l for l in solver.train_net.layers
                 if isinstance(l, attention.AttentionLayer))
    layer.rope = with_rope
    params = [p.astype(jnp.float32)
              for p in solver.variables.params[layer.name]]
    x = jax.random.normal(jax.random.key(1), (1, 2048, 256), jnp.float32)
    apply = lambda: layer.apply(params, {}, [x], train=True).outputs[0]
    want = apply()
    assert layer.kernel == "xla"
    calls = []

    def splash(q, k, v, block, window=0, scaled=False):
        calls.append((block, window, scaled))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
        s = s if scaled else s * q.shape[-1] ** -0.5
        s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "_splash_causal", splash)
    got = apply()
    assert calls == [(1024, 0, with_rope)] and layer.kernel == "splash"
    assert rel(got, want) <= 1e-5
    assert solver._fence_stats()["attn_kernel_layers"] == 1


def test_the_xla_formulation_refuses_a_scaled_q():
    q = jnp.zeros((1, 2, 64, 128), jnp.float32)
    with pytest.raises(ValueError, match="scales the scores itself"):
        attention.attention_core(q, q, q, True, scaled=True)


def mha_layer(attention_param: str):
    from sparknet_tpu.common import Phase
    from sparknet_tpu.ops.registry import create_layer
    from sparknet_tpu.proto.text_format import parse

    return create_layer(parse(
        'layer { name: "a" type: "MultiHeadAttention" bottom: "x" top: "y" '
        f"attention_param {{ {attention_param} }} }}").get_all("layer")[0],
        Phase.TRAIN)


def token_major_layer(layer, params, x):
    """``MultiHeadAttentionLayer.apply`` as it was until PR 44, written
    out: ONE token-major projection to [B, S, 3E], bias and QK-norm on the
    E-wide values, then ``reshape(B, S, H, D).transpose(0, 2, 1, 3)`` for
    q, k and v and the transpose back for o."""
    from sparknet_tpu.ops.blocks import rms_norm

    if layer.bias_term:
        w_qkv, b_qkv, w_out, b_out = params[:4]
    else:
        (w_qkv, w_out), b_qkv, b_out = params[:2], 0.0, 0.0
    B, S, E = x.shape
    H = layer.num_heads
    q, k, v = jnp.split(jnp.einsum("bse,fe->bsf", x, w_qkv) + b_qkv, 3, -1)
    if layer.qk_norm:
        q = rms_norm(q, params[-2], layer.qk_norm_eps)
        k = rms_norm(k, params[-1], layer.qk_norm_eps)
    q, k, v = (t.reshape(B, S, H, E // H).transpose(0, 2, 1, 3)
               for t in (q, k, v))
    if layer.rope:
        q = attention.rope(q, layer.rope_theta)
        k = attention.rope(k, layer.rope_theta)
    o = attention.attention_core(q, k, v, layer.causal)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, E)
    return jnp.einsum("bse,fe->bsf", o, w_out) + b_out


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("with_rope", [True, False], ids=["rope", "norope"])
@pytest.mark.parametrize("qk_norm", [True, False], ids=["qknorm", "nonorm"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "nobias"])
def test_the_head_major_projections_are_the_token_major_layer(
        bias, qk_norm, with_rope, causal):
    """The head split and merge ride the projection matmuls (PR 44): the
    same products in the same sums as the token-major layer, so outputs
    and the gradients of x and of every blob agree to f32 rounding, and
    the blobs keep their shapes (every wire format reads them)."""
    B, S, E, H = 2, 24, 48, 4
    flag = lambda b: "true" if b else "false"
    layer = mha_layer(
        f"num_heads: {H} causal: {flag(causal)} rope: {flag(with_rope)} "
        f"rope_theta: 500.0 bias_term: {flag(bias)} qk_norm: {flag(qk_norm)}")
    params, _ = layer.init(jax.random.key(0), [(B, S, E)])
    shapes = [(3 * E, E)] + [(3 * E,)] * bias + [(E, E)] + [(E,)] * bias
    assert [p.shape for p in params] == shapes + [(E,), (E,)] * qk_norm
    # off their initial zeros and ones, so every blob's gradient is live
    keys = jax.random.split(jax.random.key(1), len(params) + 2)
    params = [p + 0.3 * jax.random.normal(k, p.shape, jnp.float32)
              if p.ndim == 1 else p for p, k in zip(params, keys)]
    x = jax.random.normal(keys[-2], (B, S, E), jnp.float32)
    dy = jax.random.normal(keys[-1], (B, S, E), jnp.float32)

    def both(fn):
        y, vjp = jax.vjp(fn, params, x)
        return (y,) + tuple(jax.tree_util.tree_leaves(vjp(dy)))

    got = both(lambda p, x: layer.apply(p, {}, [x], train=True).outputs[0])
    want = both(lambda p, x: token_major_layer(layer, p, x))
    assert len(got) == len(params) + 2
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert rel(g, w) <= 1e-5


def test_no_value_of_the_layer_is_token_major_by_heads():
    """No [B, S, H, D] value in the layer's forward jaxpr: q, k, v leave
    the projections as [B, H, S, D] and o enters the output projection
    so."""
    layer = mha_layer("num_heads: 4 causal: true rope: true qk_norm: true "
                      "bias_term: false")
    params, _ = layer.init(jax.random.key(0), [(2, 16, 32)])
    jaxpr = jax.make_jaxpr(
        lambda p, x: layer.apply(p, {}, [x], train=True).outputs[0])(
        params, jnp.zeros((2, 16, 32)))
    shapes = {tuple(v.aval.shape) for e in jaxpr.eqns for v in e.outvars}
    assert (2, 16, 4, 8) not in shapes
    assert "transpose(0, 2, 1, 3)" not in inspect.getsource(
        attention.MultiHeadAttentionLayer)
