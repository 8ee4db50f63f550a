"""Which kernel the attention core takes (``ops/attention.py core_kernel``:
read off the backend and the shapes, no flag), what the fence says of it,
the splash kernels' equal-head arm against the XLA formulation in
Pallas's interpreter, and the windowed core: every head grouping under
windows narrower than, equal to and wider than a block, the window's
edge exactly, and what its gradient's program holds."""

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
    # the backward's form is read off S and the window alone: the band
    # under a window that hides some key, the fused kernel elsewhere
    assert attention.band_backward(q[2], window) == bool(window)
    assert not attention.band_backward(q[2], q[2])


def test_one_kernel_family_and_no_switch():
    """The module imports jax's splash kernels and no other attention
    kernel of jax's, beside them the repo's own banded pair (which
    imports none of jax's), and no environment variable chooses the core
    or its form."""
    from sparknet_tpu.ops import band_attention

    src = inspect.getsource(attention)
    assert "ops.tpu.splash_attention" in src
    assert "ops.tpu import flash_attention" not in src
    assert "ops.band_attention import band_core" in src
    band = inspect.getsource(band_attention)
    assert "pallas.ops.tpu" not in band
    for text in (src, band):
        assert "os.environ" not in text and "getenv" not in text
    # ONE windowed form: jax's separate dq and dkv kernels are not built
    assert "block_q_dq" not in src + band


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
    both = lambda core: both_ways(core, q, k, v, do)

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


def both_ways(core, q, k, v, do):
    """(o, dq, dk, dv) of ``core`` under the cotangent ``do``."""
    def loss(q, k, v):
        o = core(q, k, v)
        return jnp.sum(o.astype(jnp.float32) * do), o
    (_, o), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    return (o,) + grads


# [query, key, value] heads: equal; 8 query heads a key head (Laguna's
# sliding layers); value heads fewer than key heads (the hybrid's)
GROUPINGS = {"equal": (2, 2, 2), "grouped_8": (8, 1, 1),
             "fewer_values": (4, 2, 1)}


@pytest.mark.parametrize("window", [0, 64, 128, 200],
                         ids=["fused", "narrower", "a_block", "wider"])
@pytest.mark.parametrize("heads", GROUPINGS.values(), ids=GROUPINGS)
def test_the_windowed_core_against_xla_in_the_interpreter(heads, window):
    """Forward and the three gradients, bf16, rel <= 2e-2 of the XLA
    formulation, at S = 4 key blocks of 128 so that under every window
    some block is out of reach; window 0 is the rule's other arm (the
    fused backward, as every core without a window runs)."""
    S, block = 512, 128
    assert attention.band_backward(S, window) == bool(window)
    ks = jax.random.split(jax.random.key(window + heads[0]), 4)
    q, k, v = (jax.random.normal(key, (1, h, S, 128), jnp.bfloat16)
               for key, h in zip(ks, heads))
    do = jax.random.normal(ks[3], q.shape, jnp.float32)
    got = both_ways(lambda q, k, v: attention._splash_causal(
        q, k, v, block, window, interpret=True), q, k, v, do)
    want = both_ways(lambda q, k, v: attention._attention_xla(
        q, k, v, True, window), q, k, v, do)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert rel(g, w) <= 2e-2, name


@pytest.mark.parametrize("window", [512, 700])
def test_the_cells_own_blocks_in_the_interpreter(window):
    """The same at the width the cells run, 512-wide blocks cut into two
    sub-blocks of 256 rows, 8 query heads a key head, S = 4 key blocks:
    a window of one block back and of two."""
    S = 2048
    block = attention.core_block(S, window)
    ks = jax.random.split(jax.random.key(window), 4)
    q, k, v = (jax.random.normal(key, (1, h, S, 128), jnp.bfloat16)
               for key, h in zip(ks, (8, 1, 1)))
    do = jax.random.normal(ks[3], q.shape, jnp.float32)
    got = both_ways(lambda q, k, v: attention._splash_causal(
        q, k, v, block, window, interpret=True), q, k, v, do)
    want = both_ways(lambda q, k, v: attention._attention_xla(
        q, k, v, True, window), q, k, v, do)
    for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert rel(g, w) <= 2e-2, name


def test_the_windows_edge_is_exact_through_the_band():
    """f32 inputs, grouped heads, the band-following backward: key
    t - window + 1 moves query t's output and takes a dk and a dv from
    it, key t - window does neither, to the bit."""
    S, block, window, t = 512, 128, 128, 300
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(key, (1, h, S, 128), jnp.float32)
               for key, h in zip(ks, (4, 2, 2)))
    core = lambda q, k, v: attention._splash_causal(
        q, k, v, block, window, interpret=True)
    # the cotangent lives on query t alone: dk, dv say which keys it saw
    do = jnp.zeros(q.shape, jnp.float32).at[:, :, t].set(1.0)
    o, dq, dk, dv = both_ways(core, q, k, v, do)
    saw = lambda g: np.asarray(jnp.any(g != 0, axis=(0, 1, 3)))
    inside = np.zeros(S, bool)
    inside[t - window + 1:t + 1] = True
    assert np.array_equal(saw(dk), inside)
    assert np.array_equal(saw(dv), inside)
    assert np.array_equal(saw(dq), np.arange(S) == t)
    # and the output: moving one key's value moves the queries that see it
    for key, moved in ((t - window + 1, True), (t - window, False)):
        o2 = jax.jit(core)(q, k, v.at[:, :, key].add(1.0))
        assert bool(jnp.any(o2[:, :, t] != o[:, :, t])) == moved
        rows = np.asarray(jnp.any(o2 != o, axis=(0, 1, 3)))
        assert np.array_equal(rows, (np.arange(S) >= key)
                              & (np.arange(S) < key + window))


@pytest.mark.parametrize("window", [100, 128, 300])
def test_the_band_forward_keeps_the_scores_logsumexp(window):
    """What the banded backward reads beside o: a query's logsumexp over
    the keys its window shows, f32, one lane a query."""
    from sparknet_tpu.ops.band_attention import band_fwd

    S, block = 512, 128
    ks = jax.random.split(jax.random.key(window), 3)
    q = jax.random.normal(ks[0], (1, 2, 2, S, 128), jnp.float32)
    k, v = (jax.random.normal(key, (1, 2, S, 128), jnp.float32)
            for key in ks[1:])
    o, lse = jax.jit(lambda q, k, v: band_fwd(
        q, k, v, block, window, interpret=True))(q, k, v)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k)
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    s = jnp.where((ahead >= 0) & (ahead < window), s, -jnp.inf)
    assert lse.shape == (1, 2, 2, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, axis=-1), rtol=1e-5)
    want = jnp.einsum("bhgqk,bhkd->bhgqd", jax.nn.softmax(s, axis=-1), v)
    assert rel(o, want) <= 1e-5


def walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (pallas kernels' bodies aside: their values live in VMEM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from walk(sub)


@pytest.mark.parametrize("name, shapes, window", [
    ("laguna_sliding", ((1, 64, 8192, 128), (1, 8, 8192, 128),
                        (1, 8, 8192, 128)), 512),
    ("hybrid_window", HYBRID, 512),
    ("window_of_three_blocks", ((1, 16, 4096, 128),) * 3, 1500),
    ("ouro_no_window", OURO[:3], 0),
])
def test_what_the_cores_gradient_holds(monkeypatch, name, shapes, window):
    """Chip-free, at the cells' own shapes (traced, nothing runs): under
    a window the gradient of ``attention_core`` holds NO array of
    ``S / block`` times q's size (the fused backward's dq partials, one a
    key block: ``bf16[8,16,8,8192,128]`` = 2.1 GB in Laguna's compiled
    step until PR 51), nothing of more elements than q or o at all, and
    both kernels' grids are the band: a step a query block and head (the
    backward ``reach`` more to flush its ring) that holds the key blocks
    the window reaches, so per head no more (query block, key block)
    pairs than ``window_blocks`` counts, padded to whole rows.  Without a window the fused kernel and
    its partials are there: the test sees what it looks for."""
    from sparknet_tpu.ops.band_attention import reach

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    S = q.shape[2]
    block = attention.core_block(S, window)
    n = S // block

    def loss(q, k, v):
        return jnp.sum(attention.attention_core(q, k, v, True, window)
                       .astype(jnp.float32))

    eqns = list(walk(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr))
    sizes = [int(np.prod(var.aval.shape)) for e in eqns for var in e.outvars]
    q_size = int(np.prod(q.shape))
    grids = {e.params["name"]: e.params["grid_mapping"].grid
             for e in eqns if e.primitive.name == "pallas_call"}
    assert len(grids) == 2, grids  # the forward and ONE backward kernel
    if not window:
        assert n * q_size in sizes
        assert not any("band" in kernel for kernel in grids)
        return
    o_size = q_size // q.shape[3] * v.shape[3]
    assert max(sizes) <= max(q_size, o_size), max(sizes) / q_size
    visited, causal = attention.window_blocks(S, window)
    held = reach(window, block) + 1  # key blocks a query block reaches
    group = q.shape[1] // k.shape[1]
    # a step a query block and head, ``held`` key blocks in VMEM each
    assert grids == {
        "band_attention_fwd": (q.shape[0], k.shape[1], n, group),
        "band_attention_bwd": (q.shape[0], k.shape[1], n + held - 1, group)}
    # whole rows: the first blocks' rows are short of ``held`` by a triangle
    assert n * held - visited == held * (held - 1) // 2 and n * held < causal


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
