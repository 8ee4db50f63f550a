"""JoyAI-LLM-Flash through the front door, held to its plain reference on
the CPU.

Tiny preset (hidden 64, 4 heads with keys of 16 + 8 and values of 16,
latent ranks 48 / 32, one dense block of width 96 and two expert blocks
with 16 routed experts top-4 of which this "chip" holds [4, 8), a shared
expert of 32, vocab 97, S 32), float32: the program (`models.joyai_flash`
through `compiler/graph.py`, `Solver.step`, the `tokens:` feed) against
`benchmarks/reference/joyai_flash.py` on seeded weights, with every norm
weight and every selection bias moved off its initial value.  At f32 on
one backend the two differ only by summation order, so the limit is 1e-5
(rel-L2 for arrays, relative for scalars).  A rotate-half RoPE, a softmax
router, weights that are not renormalised or not scaled, a bias that
reaches the weights, a label shifted the wrong way or an expert outside
the share that still adds move these by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_flash as ref
from sparknet_tpu import models
from sparknet_tpu.common import Phase, step_key
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.ops import moe
from sparknet_tpu.ops.attention import rope
from sparknet_tpu.proto.text_format import parse, serialize
from sparknet_tpu.solvers.solver import Solver

TINY = dict(batch=2, seq_len=32, vocab=97, hidden=64, heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, dense_dim=96, experts=16, top_k=4, expert_dim=32,
            shared_dim=32, layers=3, experts_held=4, first_expert=4)
CFG = dict(heads=4, nope=16, rope=8, v=16, eps=1e-6, theta=32e6, top_k=4,
           scale=2.5, layers=3, dense_layers=1, first_expert=4,
           mtp_weight=0.3)
TOL = 1e-5
MOE_LAYERS = ("moe2", "moe3", "mtp_moe")
_BLOCK = (("norm{}a", 1), ("attn{}", 7), ("norm{}b", 1))
LEAVES = [("embed", 0), ("norm_f", 0), ("lm_head", 0), ("mlp1", 0),
          ("mlp1", 1), ("mlp1", 2), ("mtp_norm_h", 0), ("mtp_norm_e", 0),
          ("mtp_proj", 0), ("mtp_norm_f", 0)] + [
    (name.format(i), b) for i in (1, 2, 3) for name, n in _BLOCK
    for b in range(n)] + [
    (name, b) for name, n in (("mtp_norm_a", 1), ("mtp_attn", 7),
                              ("mtp_norm_b", 1), *((m, 7) for m in MOE_LAYERS))
    for b in range(n)]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def make_solver(seed=3, **over):
    cfg = dataclasses.replace(models.joyai_flash_solver(), random_seed=seed,
                              **over)
    return Solver(cfg, models.joyai_flash(**TINY))


def batch_of(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab"], (TINY["batch"], TINY["seq_len"] + 1))
    return {"data": ids[:, :-1].astype(np.int32),
            "label": ids[:, 1:].astype(np.int32)}


@pytest.fixture(scope="module")
def both():
    """One forward/backward of the program and of the reference on the
    same weights, biases and batch, and one AdamW step of each."""
    solver = make_solver()
    rng = np.random.default_rng(5)
    for blobs in solver.variables.params.values():
        for i, w in enumerate(blobs):
            if w.ndim == 1 and w.size:  # ones would hide a swapped norm
                blobs[i] = jnp.asarray(
                    1.0 + 0.1 * rng.standard_normal(w.shape), jnp.float32)
    # a bias of the size of the score gaps: it changes who is chosen
    for name in MOE_LAYERS:
        solver.variables.state[name]["bias"] = jnp.asarray(
            0.05 * rng.standard_normal(TINY["experts"]), jnp.float32)
    feeds = batch_of()
    net = solver.train_net
    params = jax.tree_util.tree_map(jnp.array, solver.variables.params)
    bias = {n: solver.variables.state[n]["bias"] for n in MOE_LAYERS}

    def prog_loss(p):
        v = dataclasses.replace(solver.variables, params=p)
        blobs, state, loss = net.apply(v, feeds, rng=step_key(solver._key, 0))
        return loss, (blobs, state)

    (p_loss, (blobs, state)), p_grads = jax.value_and_grad(
        prog_loss, has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        (r_loss, (terms, (r_logits, r_mtp_logits, routing))), r_grads = \
            jax.value_and_grad(ref.loss, has_aux=True)(
                params, bias, feeds["data"], feeds["label"], CFG)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    stepped, _, _ = fn(variables, slots, 0, feeds, key)
    return dict(solver=solver, params=params, bias=bias, blobs=blobs,
                state=state, p_loss=p_loss, p_grads=p_grads, r_loss=r_loss,
                terms=terms, r_logits=r_logits, r_mtp_logits=r_mtp_logits,
                routing=routing, r_grads=r_grads, stepped=stepped,
                feeds=feeds)


@pytest.mark.parametrize("term", ["total", "main", "mtp"])
def test_loss_terms_match_reference(both, term):
    main, mtp = both["terms"]
    got, want = {"total": (both["p_loss"], both["r_loss"]),
                 "main": (both["blobs"]["loss"], main),
                 "mtp": (both["blobs"]["mtp_loss"], mtp)}[term]
    assert abs(float(got) - float(want)) <= TOL * abs(float(want))
    assert float(mtp) > 1.0  # a real term, weighted 0.3 into the total


@pytest.mark.parametrize("head", ["lm_head", "mtp_head"])
def test_logits_of_both_heads_match_reference(both, head):
    want = both["r_logits"] if head == "lm_head" else both["r_mtp_logits"]
    assert both["blobs"][head].shape == want.shape
    assert rel(both["blobs"][head], want) <= TOL


@pytest.mark.parametrize("layer", MOE_LAYERS)
def test_top_k_sets_and_load_match_reference(both, layer):
    """The layer's own routing on its own input chooses the reference's
    experts, token by token, with the bias in the selection; its counter
    counts them over ALL router outputs, held or not."""
    _, chosen = both["routing"][layer]
    chosen = np.asarray(chosen)
    norm = "mtp_norm_b" if layer == "mtp_moe" else f"norm{layer[3:]}b"
    x = both["blobs"][norm].reshape(-1, TINY["hidden"])
    _, _, weights, experts = moe.route(
        both["params"][layer][0], x, 4, True, scoring="sigmoid",
        select_bias=both["bias"][layer], scale=2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), axis=-1),
                                  np.sort(chosen, axis=-1))
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)
    want = np.bincount(chosen.reshape(-1), minlength=TINY["experts"])
    np.testing.assert_array_equal(np.asarray(both["state"][layer]["load"]),
                                  want)
    # the bias decided some of it: without it other experts are chosen
    _, _, _, unbiased = moe.route(both["params"][layer][0], x, 4, True,
                                  scoring="sigmoid")
    assert (np.sort(np.asarray(unbiased), -1) != np.sort(chosen, -1)).any()


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_gradient_matches_reference(both, leaf):
    layer, i = leaf
    assert rel(both["p_grads"][layer][i], both["r_grads"][layer][i]) <= TOL


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_adamw_step_matches_reference(both, leaf):
    """One ``Solver`` step (clip at global norm 1, AdamW, decoupled decay)
    against the reference's gradients through the reference's rule,
    compared as the CHANGE of the leaf on the entries whose gradient is
    clear of f32 noise (the first Adam step is ~lr * sign(g))."""
    layer, i = leaf
    c = both["solver"].config
    scale = ref.clip_scale(both["r_grads"], c.clip_gradients)
    w0 = both["params"][layer][i]
    w1, _, _ = ref.adamw_step(
        w0, both["r_grads"][layer][i] * scale, 0.0, 0.0, 1, lr=c.base_lr,
        beta1=c.momentum, beta2=c.momentum2, eps=c.delta,
        weight_decay=c.weight_decay)
    got = np.asarray(both["stepped"].params[layer][i]) - np.asarray(w0)
    g = np.abs(np.asarray(both["r_grads"][layer][i] * scale))
    sure = (g > 1e-4 * g.max()) | (g == 0)
    assert sure.mean() > 0.75
    assert rel(got[sure], (np.asarray(w1) - np.asarray(w0))[sure]) <= 1e-4


def test_shared_blobs_live_at_their_owner_only(both):
    """The MTP module's embedding and head ARE the main model's: a 0-size
    placeholder at the alias, both uses' gradients summed at the owner."""
    p = both["params"]
    assert p["mtp_embed"][0].size == 0 and p["mtp_head"][0].size == 0
    only_main = jax.grad(lambda p: ref.loss(
        p, both["bias"], both["feeds"]["data"], both["feeds"]["label"],
        dict(CFG, mtp_weight=0.0))[0])(p)
    assert rel(both["p_grads"]["lm_head"][0], only_main["lm_head"][0]) > 1e-2


@pytest.mark.parametrize("layer", MOE_LAYERS)
def test_bias_moves_by_the_balancing_rule_after_the_step(both, layer):
    _, chosen = both["routing"][layer]
    want = ref.bias_step(both["bias"][layer],
                         ref.load_of(chosen, TINY["experts"]), 0.001)
    got = np.asarray(both["stepped"].state[layer]["bias"])
    np.testing.assert_allclose(got, np.asarray(want), rtol=0, atol=1e-7)
    moved = got - np.asarray(both["bias"][layer])
    size = np.abs(moved)
    assert (np.isclose(size, 0.001, atol=1e-6) | (size == 0)).all()
    assert (moved > 0).any() and (moved < 0).any()


def test_bias_stays_put_outside_training():
    net = Network(models.joyai_flash(**TINY), Phase.TEST)
    v = net.init(jax.random.key(0))
    _, state, _ = net.apply(v, batch_of())
    for layer in MOE_LAYERS:
        np.testing.assert_array_equal(np.asarray(state[layer]["bias"]), 0.0)


@pytest.mark.parametrize("impl", ["program", "reference"])
def test_interleaved_rope_is_a_complex_rotation(impl):
    """Features (2i, 2i + 1) are the real and imaginary part of a number
    that position t turns by t * theta^(-2i/d)."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 3, 16, 8)).astype(np.float32)  # [B, H, S, d]
    theta = 32e6
    if impl == "program":
        got = np.asarray(rope(jnp.asarray(x), theta, interleave=True))
    else:
        got = np.asarray(jnp.stack([
            ref.rope_interleaved(jnp.asarray(x[b].transpose(1, 0, 2)), theta)
            for b in range(2)])).transpose(0, 2, 1, 3)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    ang = np.arange(16)[:, None] * theta ** (-np.arange(4) / 4)[None, :]
    turned = z * np.exp(1j * ang)
    want = np.stack([turned.real, turned.imag], -1).reshape(x.shape)
    assert rel(got, want) <= 1e-6
    # and it is not the rotate-half pairing
    assert rel(np.asarray(rope(jnp.asarray(x), theta)), want) > 0.1


def test_mtp_predicts_the_token_after_next(both):
    """Position i of the module sees h_i and the embedding of label_i and
    is scored against label_{i+1}; the last position has no target."""
    logits = np.asarray(both["blobs"]["mtp_head"], np.float64)
    label = both["feeds"]["label"]
    assert logits.shape[:2] == (TINY["batch"], TINY["seq_len"] - 1)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -np.take_along_axis(logp, label[:, 1:, None], -1).mean()
    assert abs(float(both["blobs"]["mtp_loss"]) - want) <= 1e-5 * want
    # the last label is only ever a TARGET of the module (of position S-2):
    # its embedding would feed position S-1, which is cut
    feeds = {k: np.array(v) for k, v in both["feeds"].items()}
    feeds["label"][:, -1] = (feeds["label"][:, -1] + 1) % TINY["vocab"]
    solver = both["solver"]
    v = dataclasses.replace(solver.variables, params=both["params"])
    blobs, _, _ = solver.train_net.apply(v, feeds,
                                         rng=step_key(solver._key, 0))
    np.testing.assert_array_equal(np.asarray(blobs["mtp_head"]),
                                  np.asarray(both["blobs"]["mtp_head"]))
    assert float(blobs["mtp_loss"]) != float(both["blobs"]["mtp_loss"])
    # an earlier label is an INPUT of every later position of the module
    feeds["label"][:, 3] = (feeds["label"][:, 3] + 1) % TINY["vocab"]
    blobs, _, _ = solver.train_net.apply(v, feeds,
                                         rng=step_key(solver._key, 0))
    moved = np.abs(np.asarray(blobs["mtp_head"])
                   - np.asarray(both["blobs"]["mtp_head"])).max(axis=(0, 2))
    assert (moved[:3] == 0).all() and (moved[3:] > 0).all()


# ------------------------------------------------------------- the share
def _layer_params(rng, e, d, h, hs):
    shapes = ((e, d), (e, h, d), (e, h, d), (e, d, h), (hs, d), (hs, d),
              (d, hs))
    return [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
            for s in shapes]


def _share_of(params, first, n):
    return [params[0], *(w[first:first + n] for w in params[1:4]),
            *params[4:]]


def _share_layer(first, n, d=16):
    return Network(parse(
        'layer { name: "x" type: "Input" top: "x" '
        f'input_param {{ shape {{ dim: 3 dim: 8 dim: {d} }} }} }} '
        'layer { name: "m" type: "MoE" bottom: "x" top: "y" '
        'moe_param { num_experts: 16 hidden_dim: 24 top_k: 4 '
        'expert_act: "swiglu" norm_topk_prob: true scoring_func: "sigmoid" '
        'routed_scaling_factor: 2.5 bias_update_rate: 0.001 '
        f'shared_hidden_dim: 20 experts_held: {n} first_expert: {first} '
        '} }'), Phase.TRAIN)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4: the routed parts of the four shares,
    with the shared expert (which every chip computes alike) counted once,
    are the uncut layer's output as the reference gives it."""
    rng = np.random.default_rng(7)
    params = _layer_params(rng, 16, 16, 24, 20)
    bias = jnp.asarray(0.05 * rng.standard_normal(16), jnp.float32)
    x = jnp.asarray(rng.standard_normal((3, 8, 16)), jnp.float32)
    cfg = dict(top_k=4, scale=2.5, first_expert=0)
    with jax.default_matmul_precision("highest"):
        whole, _, chosen = ref.moe(params, x.reshape(-1, 16), bias, cfg)
        shared = ref.gated_mlp(params[4:], x.reshape(-1, 16))
    total = -3.0 * shared
    held_pairs = 0
    for first in (0, 4, 8, 12):
        net = _share_layer(first, 4)
        v = net.init(jax.random.key(0))
        assert [p.shape[0] for p in v.params["m"][:4]] == [16, 4, 4, 4]
        v = dataclasses.replace(
            v, params={"m": _share_of(params, first, 4)},
            state={"m": dict(v.state["m"], bias=bias)})
        blobs, state, _ = net.apply(v, {"x": x})
        total = total + blobs["y"].reshape(-1, 16)
        load = np.asarray(state["m"]["load"])
        np.testing.assert_array_equal(
            load, np.bincount(np.asarray(chosen).reshape(-1), minlength=16))
        held_pairs += load[first:first + 4].sum()
        # one share alone is NOT the layer
        assert rel(blobs["y"].reshape(-1, 16), whole) > 0.1
    assert rel(total, whole) <= TOL
    assert held_pairs == 3 * 8 * 4  # every pair landed on exactly one share


@pytest.mark.parametrize("first", [0, 4, 12])
def test_a_share_matches_the_reference_given_the_same_share(first):
    """Output and every gradient (router, held experts, shared expert,
    input) of one share, against the reference told the same share; the
    rows of pairs that are not held reach no sum."""
    rng = np.random.default_rng(13 + first)
    params = _share_of(_layer_params(rng, 16, 16, 24, 20), first, 4)
    bias = jnp.asarray(0.05 * rng.standard_normal(16), jnp.float32)
    x = jnp.asarray(rng.standard_normal((37, 16)), jnp.float32)
    cfg = dict(top_k=4, scale=2.5, first_expert=first)

    def prog(p, x):
        y, *_ = moe.moe_dropless(
            p[:4], x, top_k=4, expert_act="swiglu", norm_topk_prob=True,
            scoring="sigmoid", select_bias=bias, scale=2.5,
            first_expert=first)
        return y + moe.gated_mlp(x, *p[4:])

    assert rel(prog(params, x), ref.moe(params, x, bias, cfg)[0]) <= TOL
    g = jax.grad(lambda p, x: jnp.sum(prog(p, x) ** 2), (0, 1))(params, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(ref.moe(p, x, bias, cfg)[0] ** 2),
                     (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        assert np.isfinite(np.asarray(a)).all()
        assert rel(a, b) <= TOL


def test_a_share_outside_the_router_is_refused():
    with pytest.raises(ValueError, match="not among"):
        _share_layer(14, 4)


# ------------------------------------------------------- defaults unchanged
def test_route_without_the_new_options_is_what_it_was(rng):
    """softmax -> top-k, weights the chosen probabilities as they are."""
    w = jnp.asarray(rng.randn(8, 16), jnp.float32)
    x = jnp.asarray(rng.randn(12, 16), jnp.float32)
    logits, probs, weights, experts = moe.route(w, x, 2)
    want_p = jax.nn.softmax(jnp.dot(x, w.T), axis=-1)
    want_w, want_e = jax.lax.top_k(want_p, 2)
    np.testing.assert_array_equal(np.asarray(probs), np.asarray(want_p))
    np.testing.assert_array_equal(np.asarray(weights), np.asarray(want_w))
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(want_e))


def test_gated_mlp_layer_is_the_swiglu_formula(rng):
    net = Network(parse(
        'layer { name: "x" type: "Input" top: "x" '
        'input_param { shape { dim: 2 dim: 5 dim: 16 } } } '
        'layer { name: "m" type: "GatedMLP" bottom: "x" top: "y" '
        'gated_mlp_param { hidden_dim: 24 } }'), Phase.TRAIN)
    v = net.init(jax.random.key(0))
    w_g, w_u, w_d = v.params["m"]
    assert [w.shape for w in v.params["m"]] == [(24, 16), (24, 16), (16, 24)]
    x = jnp.asarray(rng.randn(2, 5, 16), jnp.float32)
    want = (jax.nn.silu(x @ w_g.T) * (x @ w_u.T)) @ w_d.T
    assert rel(net.apply(v, {"x": x})[0]["y"], want) <= 1e-6


def test_decode_spec_refuses_latent_attention():
    """The cached decode step replays ``MultiHeadAttention`` only: MLA's
    latent cache and absorbed decode path are not built."""
    net = Network(models.joyai_flash(**TINY), Phase.TEST)
    with pytest.raises(ValueError, match="no cached decode twin"):
        models.zoo.decode_spec(net, end="lm_head")


def test_published_sizes_are_the_default():
    """491,696,128 parameters in the benchmark's cut (5 blocks, 8 of 256
    experts held, 16,160 rows), counted without building them."""
    net = Network(models.joyai_flash(layers=5, experts_held=8, vocab=16160),
                  Phase.TRAIN)
    shapes = jax.eval_shape(lambda k: net.init(k, None, None).params,
                            jax.random.key(0))
    count = lambda blobs: sum(int(np.prod(a.shape)) for a in blobs)
    assert count(shapes["attn1"]) == 26_345_472 + 2_048
    assert count(shapes["mlp1"]) == 3 * 7168 * 2048
    assert [a.shape for a in shapes["moe2"]][:2] == [(256, 2048),
                                                    (8, 768, 2048)]
    assert sum(count(b) for b in shapes.values()) == 491_696_128


# ---------------------------------------------------------- the front door
def test_tpunet_train_trains_joyai_from_prototxt_and_a_token_file(tmp_path):
    """``tpunet train --solver x.prototxt --data tokens:<file> --prefetch
    3`` on the serialized net, shared ``param { name }`` and all."""
    import glob

    from sparknet_tpu import cli

    rng = np.random.default_rng(2)
    path = tmp_path / "tokens.bin"
    rng.integers(0, TINY["vocab"], 33 * 7 + 5).astype(np.uint16).tofile(path)
    (tmp_path / "net.prototxt").write_text(
        serialize(models.joyai_flash(**TINY)))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path}/net.prototxt"\ntype: "AdamW"\nbase_lr: 0.00022\n'
        'lr_policy: "fixed"\nmomentum: 0.9\nmomentum2: 0.95\ndelta: 1e-8\n'
        'weight_decay: 0.1\nclip_gradients: 1.0\nmax_iter: 4\ndisplay: 0\n')
    out = str(tmp_path / "final")
    rc = cli.main(["train", "--solver", str(tmp_path / "solver.prototxt"),
                   "--data", f"tokens:{path}", "--prefetch", "3",
                   "--iterations", "3", "--seed", "7", "--output", out])
    assert rc == 0
    assert glob.glob(out + "*")


def test_the_fence_carries_the_new_counters():
    """After ``Solver.step``: pairs on held experts over the layers, the
    sorted rows those layers moved (whole tiles of their held pairs) and
    how many of them moved fewer than all their pairs, the bias's
    extremes, the MTP term of the loss (kept in its layer's state)."""
    solver = make_solver()
    solver.step(2, lambda it: batch_of(it))
    stats = solver._fence_stats()
    pairs = TINY["batch"] * TINY["seq_len"] * TINY["top_k"]
    assert stats["moe_pairs"] == pairs and stats["moe_experts"] == 16
    assert stats["moe_layers"] == 3
    assert 0 < stats["moe_pairs_held"] < 3 * pairs
    assert stats["moe_bias_min"] == pytest.approx(-0.002)
    assert stats["moe_bias_max"] == pytest.approx(0.002)
    assert 3.0 < stats["mtp_loss"] < 6.0  # ~ln(97) at initialisation
    # 256 pairs a layer: one tile of 512 rows holds whatever a layer holds
    # and is no fewer rows than its pairs, so no layer counts as compact
    assert stats["moe_rows_moved"] == 3 * 512
    assert stats["moe_compact_layers"] == 0


def test_the_fence_span_counts_the_rows_the_loops_walked():
    """4 of 128 experts on 1,024 pairs a layer: ``moe_rows_moved`` and
    ``moe_compact_layers`` on the ``sn.step.fence`` span are what the
    device's own count (``ops/moe.py live_tiles``) says of the ``load``
    the stepped solver holds."""
    from sparknet_tpu.obs import recorder

    wide = Solver(models.joyai_flash_solver(), models.joyai_flash(**dict(
        TINY, seq_len=128, experts=128)))
    wide.step(1, lambda it: {k: np.tile(v, (1, 4)) for k, v in
                             batch_of(it).items()})
    layers = [l for l in wide.train_net.layers if l.type == "MoE"]
    held = [int(np.asarray(wide.variables.state[l.name]["load"])[4:8].sum())
            for l in layers]
    assert len(held) == 3 and all(0 < n < 1024 for n in held)
    moved = [512 * int(moe.live_tiles(n)) for n in held]
    fence = [s for s in recorder.flight()[0] if s[0] == "sn.step.fence"][-1]
    stats = fence[4]
    assert stats["it"] == wide.iter == 1
    assert stats["moe_pairs"] == 1024 and stats["moe_layers"] == 3
    assert stats["moe_pairs_held"] == sum(held)
    assert stats["moe_rows_moved"] == sum(moved)
    assert stats["moe_compact_layers"] == sum(rows < 1024 for rows in moved)
    assert stats["moe_compact_layers"] >= 1
    assert stats == {"it": 1, **wide._fence_stats()}
    # and the benchmark's reader, as it stands, reads this fence
    from benchmarks.harness import load_by_name
    share = load_by_name("metrics", "moe.compact_share").read(
        {"decoder_scopes": {"scope_s": {}, "fences": [stats]}}, {})
    assert share == 100.0 * stats["moe_compact_layers"] / 3


def test_a_whole_layer_keeps_to_the_counters_it_had():
    # a whole layer without a bias: no share, so neither new counter
    plain = Solver(models.olmoe_solver(), models.olmoe(
        batch=2, seq_len=32, vocab=97, hidden=64, heads=4, experts=8,
        top_k=2, expert_dim=32, layers=1))
    assert set(plain._fence_stats()) == {
        "moe_load_max", "moe_pairs", "moe_experts", "attn_core_layers",
        "attn_kernel_layers"}
