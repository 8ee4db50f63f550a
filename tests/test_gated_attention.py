"""What PR 50 added to ``ops/attention.py``: YaRN's frequency table
against the formulas written out, the factor on cos and sin, ``rope_at``'s
bitwise contract with ``rope`` under both, the window's edge through the
grouped splash form in Pallas's interpreter at 64 query heads over 8, the
head-wise gate, and the Qwen3-Next layer as it was before the class
learned its new fields: same blobs, same output to the bit."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.common import Phase
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.layers_dsl import GatedAttentionLayer, NetParam, RDDLayer
from sparknet_tpu.models.zoo import LAGUNA_ROPE_PARAMETERS, _gauss
from sparknet_tpu.ops import attention
from sparknet_tpu.ops.attention import rope, rope_at, yarn_inv_freq
from sparknet_tpu.ops.blocks import rms_norm

FULL = LAGUNA_ROPE_PARAMETERS["full_attention"]
FACTOR = FULL["attention_factor"]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def published_table():
    return yarn_inv_freq(64, FULL["rope_theta"], FULL["factor"],
                         FULL["original_max_position_embeddings"],
                         FULL["beta_fast"], FULL["beta_slow"])


# ------------------------------------------------------------------- YaRN
def test_the_ramp_bounds_at_the_published_sizes():
    """r = 64, b = 500,000, L = 4,096: dim(64 turns) = 5.66, dim(1 turn) =
    15.80, so pairs 0..5 keep their frequency and pairs 16..31 are
    interpolated by 64."""
    low, high = attention.yarn_ramp_bounds(64, 500000, 4096, 64, 1)
    dim = lambda n: 64 * math.log(4096 / (2 * math.pi * n)) / (
        2 * math.log(500000))
    assert (low, high) == (5.0, 16.0)
    assert math.floor(dim(64)) == 5 and math.ceil(dim(1)) == 16
    # clipped to the span; a ramp of no width is kept from dividing by 0
    assert attention.yarn_ramp_bounds(8, 100, 16, 1e6, 1e-6) == (0.0, 7.0)
    assert attention.yarn_ramp_bounds(8, 100, 64, 4, 4) == (0.0, 1.0)
    assert attention.yarn_ramp_bounds(8, 1e9, 6, 1, 1) == (0.0, 0.001)


@pytest.mark.parametrize("i", range(32))
def test_the_table_is_the_formulas_written_out(i):
    """inv_freq_i = ramp_i / (f pos_i) + (1 - ramp_i) / pos_i, pair by
    pair, in Python floats."""
    pos = 500000.0 ** (2 * i / 64)
    ramp = min(max((i - 5) / (16 - 5), 0.0), 1.0)
    want = ramp / (64 * pos) + (1 - ramp) / pos
    got = published_table()
    assert got.dtype == np.float32 and got.shape == (32,)
    assert got[i] == np.float32(want)
    if i <= 5:
        assert got[i] == np.float32(1 / pos)        # extrapolated: kept
    if i >= 16:
        assert got[i] == np.float32(1 / (64 * pos))  # interpolated


def test_the_attention_factor_is_the_published_one():
    assert FACTOR == 0.1 * math.log(64) + 1 == 1.4158883083359672
    layer = Network(NetParam("t", RDDLayer("x", shape=[1, 8, 24]),
                             GatedAttentionLayer(
        "a", ["x"], 4, 2, 16, rotary_dim=8, rope_theta=100.0,
        rope_scaling={"factor": 64, "original_max_position_embeddings": 64})),
        Phase.TRAIN).layers[-1]
    assert layer.rope_scale == FACTOR  # the default: 0.1 ln(factor) + 1
    assert layer.inv_freq.shape == (4,)


def test_the_factor_rides_cos_and_sin():
    """``rope(x, scale=a, inv_freq=t)`` is a times the turn by the table's
    angles: checked against cos and sin written out."""
    table = published_table()
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 3, 40, 64)),
                    jnp.float32)
    ang = np.arange(40, dtype=np.float32)[:, None] * table[None, :]
    cos, sin = np.cos(ang) * np.float32(FACTOR), np.sin(ang) * np.float32(FACTOR)
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:])
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    got = rope(x, scale=FACTOR, inv_freq=table)
    assert rel(got, want) < 1e-6
    # so a score of two turned vectors carries the factor's square
    plain = rope(x, inv_freq=table)
    assert rel(jnp.sum(got * got, -1), FACTOR ** 2 * jnp.sum(plain * plain, -1)
               ) < 1e-6
    # the table replaces the base: position 1 turns pair i by table[i]
    assert rel(got[0, 0, 1, :32] / FACTOR,
               x1[0, 0, 1] * np.cos(table) - x2[0, 0, 1] * np.sin(table)) < 1e-6
    with pytest.raises(ValueError, match="inv_freq"):
        rope(x, inv_freq=table[:16])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("yarn", [False, True])
def test_rope_at_is_bitwise_rope(yarn, dtype):
    """The decode-path twin at positions 0..S-1 is ``rope`` bit for bit,
    under the table and the factor too."""
    kw = dict(scale=FACTOR, inv_freq=published_table()) if yarn else {}
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 3, 50, 64)),
                    dtype)
    whole = rope(x, 500000.0, **kw)
    positions = jnp.broadcast_to(jnp.arange(50, dtype=jnp.int32), (2, 50))
    assert np.array_equal(np.asarray(rope_at(x, positions, 500000.0, **kw)),
                          np.asarray(whole))
    # and one token at a time, at its absolute position
    for t in (0, 7, 49):
        one = rope_at(x[:, :, t:t + 1], jnp.full((2, 1), t, jnp.int32),
                      500000.0, **kw)
        assert np.array_equal(np.asarray(one), np.asarray(whole[:, :, t:t + 1]))


# ------------------------------------------------------- the window's edge
def explicit(q, k, v, window):
    """Query t sees keys t - window + 1 .. t: the mask written out."""
    H, S, D = q.shape[1:]
    k, v = (jnp.repeat(t, H // t.shape[1], axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * D ** -0.5
    t = np.arange(S)
    seen = (t[None, :] <= t[:, None]) & (t[None, :] >= t[:, None] - window + 1)
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                      v.astype(jnp.float32))


@pytest.fixture(scope="module")
def windowed():
    """64 query heads over 8 key heads of 128 through the grouped splash
    form under a window of 512, in Pallas's interpreter: S = 1,024 in
    512-wide blocks, so the second query block has its whole window."""
    ks = jax.random.split(jax.random.key(50), 3)
    q = jax.random.normal(ks[0], (1, 64, 1024, 128), jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, 8, 1024, 128), jnp.bfloat16)
            for key in ks[1:])
    got = jax.jit(lambda q, k, v: attention._splash_causal(
        q, k, v, 512, 512, interpret=True))(q, k, v)
    return q, k, v, got


@pytest.mark.parametrize("window,same", [(512, True), (511, False),
                                         (513, False)])
def test_the_windows_edge_through_the_grouped_splash_form(windowed, window,
                                                          same):
    q, k, v, got = windowed
    assert got.shape == (1, 64, 1024, 128) and got.dtype == jnp.bfloat16
    want = explicit(q, k, v, window)
    late = slice(512, None)  # queries whose window is whole
    if same:
        assert rel(got, want) < 1e-2
        assert rel(got[:, :, late], want[:, :, late]) < 1e-2
    else:  # one key more or fewer among ~512 random ones: ~4 %
        assert rel(got[:, :, late], want[:, :, late]) > 2e-2
        # before the edge the three masks are one
        assert rel(got[:, :, :511], want[:, :, :511]) < 1e-2


def test_the_xla_formulation_has_the_same_edge():
    q, k, v = (jnp.asarray(np.random.default_rng(i).standard_normal(
        (1, h, 40, 16)), jnp.float32) for i, h in ((0, 8), (1, 2), (2, 2)))
    for window in (1, 7, 8, 9):
        got = attention.attention_core(q, k, v, True, window)
        assert rel(got, explicit(q, k, v, window)) < 1e-5
    assert rel(attention.attention_core(q, k, v, True, 8),
               explicit(q, k, v, 9)) > 1e-2


# ------------------------------------------------------------- the layers
def one_layer(msg, shape=(2, 32, 24), seed=0):
    net = Network(NetParam("t", RDDLayer("x", shape=list(shape)), msg),
                  Phase.TRAIN)
    variables = net.init(jax.random.key(seed), None, None)
    name = msg.get_str("name")
    blobs = [b + 0.05 * jax.random.normal(jax.random.key(i), b.shape)
             for i, b in enumerate(variables.params[name])]
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                    jnp.float32)

    def run(blobs, x):
        import dataclasses
        v = dataclasses.replace(variables, params={name: blobs})
        return net.apply(v, {"x": x})[0][name]

    return net.layers[-1], run, blobs, x


def test_the_head_wise_gate():
    """W_g (H, E) is a blob of its own behind W_o; W_q holds the queries
    alone; no norm blobs; o_h is multiplied by ONE sigmoid a head and
    token."""
    msg = GatedAttentionLayer(
        "a", ["x"], num_heads=8, num_kv_heads=2, head_dim=16, rotary_dim=16,
        weight_filler=_gauss(0.3), qk_norm=False, head_gate=True, window=8)
    layer, run, blobs, x = one_layer(msg)
    assert [b.shape for b in blobs] == [
        (128, 24), (32, 24), (32, 24), (24, 128), (8, 24)]
    w_q, w_k, w_v, w_o, w_g = blobs
    B, S, E = x.shape
    heads = lambda t, n: t.reshape(B, S, n, 16).transpose(0, 2, 1, 3)
    q, k = rope(heads(x @ w_q.T, 8)), rope(heads(x @ w_k.T, 2))
    o = explicit(q, k, heads(x @ w_v.T, 2), 8)
    gate = jax.nn.sigmoid(x @ w_g.T).transpose(0, 2, 1)[..., None]  # [B,H,S,1]
    want = (o * gate).transpose(0, 2, 1, 3).reshape(B, S, 128) @ w_o.T
    assert rel(run(blobs, x), want) < 1e-5
    # a gate of ones is no gate: the blob matters
    assert rel(run([w_q, w_k, w_v, w_o, jnp.zeros_like(w_g)], x),
               0.5 * want / gate.mean()) > 1e-2
    assert layer.visited == (1, 1) and layer.window == 8


@pytest.mark.parametrize("field,text", [
    ("window", "window: -1"), ("rope_scaling", 'rope_scaling { type: "ntk" }'),
])
def test_the_layer_refuses_what_it_cannot_compute(field, text):
    from sparknet_tpu.proto.text_format import parse

    with pytest.raises(ValueError, match=field):
        Network(parse(
            'layer { name: "x" type: "Input" top: "x" input_param { shape { '
            'dim: 1 dim: 8 dim: 24 } } } layer { name: "a" type: '
            '"GatedAttention" bottom: "x" top: "a" attention_param { '
            f'num_heads: 4 num_kv_heads: 2 head_dim: 16 {text} }} }}'),
            Phase.TRAIN)


def qwen3_next_layer_as_it_was(params, x, H=4, Hk=2, D=16, r=4, theta=1e7,
                               eps=1e-6):
    """``GatedAttentionLayer.apply`` as PR 47 wrote it, before the class
    had a field for the gate's width, the norm, a window or a table."""
    w_q, w_k, w_v, w_o, q_norm, k_norm = params
    E = x.shape[-1]
    turn = lambda t: jnp.concatenate(
        [rope(t[..., :r], theta), t[..., r:]], axis=-1)
    q, gate = jnp.einsum("bse,hgde->gbhsd", x, w_q.reshape(H, 2, D, E))
    k, v = (jnp.einsum("bse,hde->bhsd", x, w.reshape(Hk, D, E))
            for w in (w_k, w_v))
    q = turn(rms_norm(q, 1.0 + q_norm, eps))
    k = turn(rms_norm(k, 1.0 + k_norm, eps))
    o = attention.attention_core(q, k, v, True)
    return jnp.einsum("bhsd,fhd->bsf", o * jax.nn.sigmoid(gate),
                      w_o.reshape(E, H, D))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_qwen3_next_layer_is_what_it_was(dtype):
    """Same blobs in the same order, and on the CPU the same output and
    the same gradients to the bit."""
    msg = GatedAttentionLayer("a", ["x"], num_heads=4, num_kv_heads=2,
                              head_dim=16, rotary_dim=4, rope_theta=1e7,
                              weight_filler=_gauss(0.3))
    layer, run, blobs, x = one_layer(msg)
    assert [b.shape for b in blobs] == [
        (128, 24), (32, 24), (32, 24), (24, 64), (16,), (16,)]
    assert (layer.qk_norm, layer.head_gate, layer.window, layer.inv_freq,
            layer.rope_scale) == (True, False, 0, None, 1.0)
    blobs = [b.astype(dtype) for b in blobs]
    x = x.astype(dtype)
    got = jax.jit(run)(blobs, x)
    want = jax.jit(qwen3_next_layer_as_it_was)(blobs, x)
    assert got.dtype == want.dtype == dtype
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    w = jnp.asarray(np.random.default_rng(9).standard_normal(x.shape), dtype)
    grads = lambda f: jax.jit(jax.grad(
        lambda b, x: jnp.sum((f(b, x) * w).astype(jnp.float32)),
        argnums=(0, 1)))(blobs, x)
    for g, old in zip(jax.tree_util.tree_leaves(grads(run)),
                      jax.tree_util.tree_leaves(
                          grads(qwen3_next_layer_as_it_was))):
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(old, np.float32))
