"""The gated-DeltaNet core and the layers Qwen3-Next added, on the CPU.

The chunked core (``ops/linear_attention.py gated_delta_rule``: the WY
form a chunk, a scan over chunk-start states, a ``custom_vjp`` that
recomputes from them) is held to its definition
(``gated_delta_rule_steps``: one ``lax.scan`` over time under plain
autodiff), forward and all seven gradients, at lengths that are and are
not multiples of the chunk; then ``GatedDeltaNet``, ``GatedAttention`` and
the expert layer's gated shared expert each against the plain reference's
function (``benchmarks/reference/qwen3_next.py``), and the shares of an
expert layer against the uncut layer.  f32 on one backend: the two sides
differ by summation order only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as ref
from sparknet_tpu.common import Phase
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.layers_dsl import (
    GatedAttentionLayer, GatedDeltaNetLayer, MoELayer, NetParam, RDDLayer)
from sparknet_tpu.models.zoo import _gauss
from sparknet_tpu.ops import linear_attention as la
from sparknet_tpu.ops.attention import rope

TOL = 2e-5
NAMES = ("q", "k", "v", "a", "b", "A_log", "dt_bias")


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def core_inputs(seq, seed=0, batch=2, hk=2, hv=4, dk=8, dv=16):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    a_log = jnp.log(jnp.asarray(rng.uniform(0.05, 16.0, hv), jnp.float32))
    return (f(batch, seq, hk, dk), f(batch, seq, hk, dk),
            f(batch, seq, hv, dv), f(batch, seq, hv), f(batch, seq, hv),
            a_log, f(hv) - 2.0)


# (tokens, chunk): whole chunks, a ragged last chunk, one padded chunk,
# the shipped chunk of 64 over two and a half chunks
CASES = [(32, 8), (40, 16), (24, 64), (160, 64)]


@pytest.fixture(scope="module", params=CASES, ids=lambda c: f"S{c[0]}-C{c[1]}")
def core(request):
    seq, chunk = request.param
    args = core_inputs(seq)
    weight = jnp.asarray(np.random.default_rng(1).standard_normal(
        args[2].shape), jnp.float32)
    out = {}
    for name, rule in (
            ("chunked", lambda *x: la.gated_delta_rule(*x, chunk=chunk)),
            ("steps", la.gated_delta_rule_steps)):
        loss = lambda *x: jnp.sum(rule(*x) * weight)
        out[name] = (rule(*args), jax.grad(loss, argnums=range(7))(*args))
    return out


def test_chunked_forward_is_the_definition(core):
    assert rel(core["chunked"][0], core["steps"][0]) < TOL


@pytest.mark.parametrize("i", range(7), ids=NAMES)
def test_chunked_gradient_is_the_definitions(core, i):
    want = core["steps"][1][i]
    assert float(jnp.linalg.norm(want)) > 0
    assert rel(core["chunked"][1][i], want) < TOL


def test_the_definition_is_the_four_assignments():
    """One value head, two tokens, by hand."""
    q, k, v, a, b, a_log, dt = core_inputs(2, batch=1, hk=1, hv=1, dk=2, dv=3)
    got = np.asarray(la.gated_delta_rule_steps(q, k, v, a, b, a_log, dt))
    unit = lambda x: x / np.sqrt(np.sum(x * x) + 1e-6)
    s = np.zeros((2, 3))
    for t in range(2):
        g = -np.exp(float(a_log[0])) * np.log1p(np.exp(float(a[0, t, 0] + dt[0])))
        beta = 1 / (1 + np.exp(-float(b[0, t, 0])))
        kt, qt = unit(np.asarray(k[0, t, 0])), unit(np.asarray(q[0, t, 0])) * 2 ** -0.5
        s = np.exp(g) * s
        u = beta * (np.asarray(v[0, t, 0]) - s.T @ kt)
        s = s + np.outer(kt, u)
        assert np.allclose(got[0, t, 0], s.T @ qt, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [1, 8, 16, 64])
def test_the_inverse_of_a_unit_lower_triangle(n):
    """Whole-matrix matmuls only, and exact where keys repeat: identical
    keys at beta = 1 make every entry below the diagonal 1."""
    rng = np.random.default_rng(n)
    lower = np.tril(np.ones((n, n)), -1)
    for a in (rng.uniform(-1, 1, (3, n, n)) * lower, lower[None]):
        a = jnp.asarray(a, jnp.float32)
        got = la._unit_lower_inverse(a)
        want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
        assert np.abs(np.asarray(got) - want).max() < 1e-5 * np.abs(want).max()
    a = jnp.asarray(rng.uniform(-1, 1, (n, n)) * lower, jnp.float32)
    w = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    got = jax.grad(lambda a: jnp.sum(la._unit_lower_inverse(a) * w))(a)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(jnp.eye(n) + a) * w))(a)
    assert rel(got * lower, want * lower) < 1e-4


def test_what_the_forward_keeps_is_chunk_states_never_a_state_a_token():
    assert la.chunking(4096) == (64, 64) and la.chunking(40, 16) == (16, 3)
    assert la.chunking(24) == (32, 1)  # one padded chunk, a power of two
    with pytest.raises(ValueError, match="power of two"):
        la.chunking(4096, 48)
    # 134 MB a layer at the cell's size, against 8.6 GB of states a token
    assert la.saved_state_bytes(1, 4096, 32, 128, 128) == 134_217_728
    q, k, v, a, b, a_log, dt = core_inputs(64, batch=1)
    _, residuals = la._rule_vjp_fwd(q, k, v, a, b, a_log, dt, 16)
    assert [x.shape for x in residuals[:7]] == [
        x.shape for x in (q, k, v, a, b, a_log, dt)]
    # [chunks, B, H_k, H_v / H_k, d_k, d_v]: the state at each chunk's start
    assert residuals[7].shape == (4, 1, 2, 2, 8, 16) and len(residuals) == 8
    with pytest.raises(ValueError, match="key heads"):
        la.gated_delta_rule(q, k, v[:, :, :3], a, b, a_log, dt)


# ---------------------------------------------------------------- the layers
def one_layer(layer_msg, shape, seed=0):
    net = Network(NetParam("t", RDDLayer("x", shape=list(shape)), layer_msg),
                  Phase.TRAIN)
    variables = net.init(jax.random.key(seed), None, None)
    rng = np.random.default_rng(seed + 1)
    name = layer_msg.get_str("name")
    # ones and zeros would hide a swapped norm or a dropped gate
    blobs = [w + jnp.asarray(0.1 * rng.standard_normal(w.shape), jnp.float32)
             if w.ndim == 1 else w for w in variables.params[name]]
    variables.params[name] = blobs
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def run(blobs, x):
        variables.params[name] = blobs
        return net.apply(variables, {"x": x}, rng=None)[0]

    return run, blobs, x, name


def grads_match(run, blobs, x, top, ref_fn, tol=TOL):
    weight = jnp.asarray(np.random.default_rng(9).standard_normal(x.shape),
                         jnp.float32)
    got = jax.grad(lambda b, x: jnp.sum(run(b, x)[top] * weight),
                   argnums=(0, 1))(blobs, x)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda b, x: jnp.sum(ref_fn(b, x) * weight),
                        argnums=(0, 1))(blobs, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(w)) > 0
        assert rel(g, w) < tol


GDN_CFG = dict(lk_heads=2, lv_heads=4, lk_dim=8, lv_dim=16, eps=1e-6)


def test_gated_delta_net_layer_is_the_references():
    msg = GatedDeltaNetLayer("gdn", ["x"], 2, 4, 8, 16,
                             weight_filler=_gauss(0.3))
    run, blobs, x, _ = one_layer(msg, (2, 40, 24))
    assert [b.shape for b in blobs] == [
        (160, 24), (8, 24), (96, 4), (4,), (4,), (16,), (24, 64)]
    ref_fn = lambda b, x: jnp.stack(
        [ref.gated_delta_net(b, x[n], GDN_CFG) for n in range(x.shape[0])])
    with jax.default_matmul_precision("highest"):
        assert rel(run(blobs, x)["gdn"], ref_fn(blobs, x)) < TOL
    grads_match(run, blobs, x, "gdn", ref_fn)


ATT_CFG = dict(heads=4, kv_heads=2, head_dim=16, rotary=4, theta=1e7, eps=1e-6)


def test_gated_attention_layer_is_the_references():
    """Grouped heads (4 over 2) of a width that is not hidden / heads,
    per-head zero-centred norms, the gate, RoPE on 4 of 16 features."""
    msg = GatedAttentionLayer("attn", ["x"], num_heads=4, num_kv_heads=2,
                              head_dim=16, rotary_dim=4, rope_theta=1e7,
                              weight_filler=_gauss(0.3))
    run, blobs, x, _ = one_layer(msg, (2, 32, 24))
    assert [b.shape for b in blobs] == [
        (128, 24), (32, 24), (32, 24), (24, 64), (16,), (16,)]
    ref_fn = lambda b, x: jnp.stack(
        [ref.gated_attention(b, x[n], ATT_CFG) for n in range(x.shape[0])])
    with jax.default_matmul_precision("highest"):
        assert rel(run(blobs, x)["attn"], ref_fn(blobs, x)) < TOL
    grads_match(run, blobs, x, "attn", ref_fn)


def test_partial_rope_leaves_the_rest_of_a_head_unrotated():
    msg = GatedAttentionLayer("attn", ["x"], num_heads=4, num_kv_heads=2,
                              head_dim=16, rotary_dim=4, rope_theta=1e7)
    net = Network(NetParam("t", RDDLayer("x", shape=[1, 8, 24]), msg),
                  Phase.TRAIN)
    layer = net.layers[-1]
    t = jnp.asarray(np.random.default_rng(0).standard_normal((1, 4, 8, 16)),
                    jnp.float32)
    turned = layer._turn(t)
    assert np.array_equal(turned[..., 4:], t[..., 4:])
    assert np.allclose(turned[..., :4], rope(t[..., :4], 1e7))
    assert not np.allclose(turned[:, :, 1:, :4], t[:, :, 1:, :4])
    with pytest.raises(ValueError, match="rotary_dim"):
        Network(NetParam("t", RDDLayer("x", shape=[1, 8, 24]),
                         GatedAttentionLayer("a", ["x"], 4, 2, 16,
                                             rotary_dim=5)), Phase.TRAIN)


def expert_layer(held=None, first=0, shared_gate=True):
    return MoELayer("moe", ["x"], num_experts=16, hidden_dim=24, top_k=3,
                    expert_act="swiglu", norm_topk_prob=True,
                    shared_hidden_dim=20, shared_gate=shared_gate,
                    experts_held=held, first_expert=first,
                    weight_filler=_gauss(0.3), loss_tops=(("lb", 0.001),))


def test_gated_shared_expert_and_aux_loss_are_the_references():
    run, blobs, x, _ = one_layer(expert_layer(held=4, first=4), (2, 32, 24))
    assert [b.shape for b in blobs][-4:] == [(20, 24), (20, 24), (24, 20),
                                             (1, 24)]
    cfg = dict(top_k=3, first_expert=4)
    flat = lambda x: x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        y, aux, _, _ = ref.moe(blobs, flat(x), cfg)
        got = run(blobs, x)
        assert rel(got["moe"], y.reshape(x.shape)) < TOL
        assert float(got["lb"]) == pytest.approx(float(aux), rel=1e-5)
        # the gate is there: without it the shared expert is not scaled
        shared = ref.gated_mlp(blobs[4:7], flat(x))
        gate = jax.nn.sigmoid(flat(x) @ blobs[7].T)
        assert rel(ref.shared_expert(blobs[4:], flat(x)), shared * gate) < 1e-6
        assert rel(shared * gate, shared) > 0.1
    grads_match(run, blobs, x, "moe",
                lambda b, x: ref.moe(b, flat(x), cfg)[0].reshape(x.shape))
    with pytest.raises(ValueError, match="shared_gate"):
        Network(NetParam("t", RDDLayer("x", shape=[1, 8, 24]), MoELayer(
            "m", ["x"], num_experts=4, shared_gate=True)), Phase.TRAIN)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Guide section 4: at 16 experts, 4 held, the four shares' routed
    parts plus the gated shared expert counted once equal the uncut
    reference's layer output."""
    run, blobs, x, _ = one_layer(expert_layer(), (2, 32, 24))
    flat = x.reshape(-1, 24)
    with jax.default_matmul_precision("highest"):
        whole, _, _, _ = ref.moe(blobs, flat, dict(top_k=3, first_expert=0))
        shared = ref.shared_expert(blobs[4:], flat)
        total = shared
        for first in (0, 4, 8, 12):
            share_run, _, _, _ = one_layer(expert_layer(4, first),
                                           (2, 32, 24))
            share = [blobs[0]] + [w[first:first + 4] for w in blobs[1:4]] + \
                blobs[4:]
            part = share_run(share, x)["moe"].reshape(-1, 24)
            # the reference's share, the same way
            want, _, _, _ = ref.moe(share, flat,
                                    dict(top_k=3, first_expert=first))
            assert rel(part, want) < TOL
            total = total + (part - shared)
        assert rel(total, whole) < TOL
        assert rel(run(blobs, x)["moe"].reshape(-1, 24), whole) < TOL
