"""Laguna-XS.2 through the front door, held to its plain reference on the
CPU.

Tiny preset (hidden 64; attention over 2 key/value heads of 16 with a
head-wise gate: the full layers 4 query heads and YaRN over 8 of 16
features, the sliding layers 8 query heads, a window of 8 and plain RoPE
over the whole head; a dense SwiGLU of 96 in layer 0, then 16 experts of
width 24, 3 a token, experts [4, 8) held, a shared expert of 24; vocab
97, S 32; five blocks = the leading dense layer and one period), float32:
the program (`models.laguna` through `compiler/graph.py`, `Solver.step`,
the `tokens:` feed) against `benchmarks/reference/laguna.py` on seeded
weights, with every norm moved off its initial value.  At f32 on one
backend the two differ only by summation order, so the limit is 2e-5
(rel-L2 for arrays, relative for scalars).  A dropped gate, a window one
key off, plain RoPE where YaRN belongs, an ``attention_factor`` of 1 or
another key head for a query head move these by 1e-3 or more (the
wrong-program tests below).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from sparknet_tpu import models
from sparknet_tpu.common import Phase, step_key
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.proto.text_format import parse, serialize
from sparknet_tpu.solvers.solver import Solver

ROPES = {
    "full_attention": {
        "rope_theta": 100, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 64, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1},
}
TINY = dict(batch=2, seq_len=32, vocab=97, hidden=64, layers=5,
            heads_per_layer=(4, 8, 8, 8), kv_heads=2, head_dim=16, window=8,
            rope_parameters=ROPES, dense_dim=96, experts=16, top_k=3,
            expert_dim=24, shared_dim=24, experts_held=4, first_expert=4)
KINDS = ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)


def hashable(ropes):
    return tuple((kind, tuple(sorted(group.items())))
                 for kind, group in ropes.items())


CFG = dict(kinds=KINDS, heads=(4, 8, 8, 8, 4), dense=(True,) + (False,) * 4,
           kv_heads=2, head_dim=16, window=8, ropes=hashable(ROPES),
           eps=1e-6, top_k=3, scale=2.5, first_expert=4, layers=5,
           aux_coef=0.001)
TOL = 2e-5
LEAVES = [("embed", 0), ("norm_f", 0), ("lm_head", 0)] + [
    (name, b) for i in range(5)
    for name, n in ((f"norm{i}a", 1), (f"attn{i}", 5), (f"norm{i}b", 1),
                    (f"moe{i}", 7) if i else ("mlp0", 3))
    for b in range(n)]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def make_solver(seed=3, **over):
    cfg = dataclasses.replace(models.laguna_solver(), random_seed=seed)
    return Solver(cfg, models.laguna(**{**TINY, **over}))


def batch_of(seed=0, seq_len=TINY["seq_len"], batch=TINY["batch"]):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab"], (batch, seq_len + 1))
    return {"data": ids[:, :-1].astype(np.int32),
            "label": ids[:, 1:].astype(np.int32)}


def shake_vectors(solver, seed=5):
    """Ones would hide a swapped norm."""
    rng = np.random.default_rng(seed)
    for blobs in solver.variables.params.values():
        for i, w in enumerate(blobs):
            if w.ndim == 1 and w.size:
                blobs[i] = w + jnp.asarray(
                    0.1 * rng.standard_normal(w.shape), jnp.float32)


def program_loss(solver, params, feeds):
    v = dataclasses.replace(solver.variables, params=params)
    blobs, _, loss = solver.train_net.apply(
        v, feeds, rng=step_key(solver._key, 0))
    return loss, blobs


@pytest.fixture(scope="module")
def both():
    """One forward/backward of the program and of the reference on the
    same weights and batch, and one AdamW step of the program."""
    solver = make_solver()
    shake_vectors(solver)
    feeds = batch_of()
    params = jax.tree_util.tree_map(jnp.array, solver.variables.params)
    (p_loss, blobs), p_grads = jax.value_and_grad(
        lambda p: program_loss(solver, p, feeds), has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        (r_loss, r_aux), r_grads = jax.value_and_grad(
            ref.loss, has_aux=True)(params, feeds["data"], feeds["label"], CFG)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    stepped, _, _ = fn(variables, slots, 0, feeds, key)
    return dict(solver=solver, params=params, blobs=blobs, p_loss=p_loss,
                p_grads=p_grads, r_loss=r_loss, r_aux=r_aux, r_grads=r_grads,
                stepped=stepped, feeds=feeds)


def test_loss_terms_match_reference(both):
    (main, aux), _ = both["r_aux"]
    got, want = float(both["p_loss"]), float(both["r_loss"])
    assert abs(got - want) <= TOL * abs(want)
    assert float(both["blobs"]["loss"]) == pytest.approx(float(main), rel=TOL)
    lb = sum(float(both["blobs"][f"lb{i}"]) for i in range(1, 5))
    assert lb == pytest.approx(float(aux), rel=TOL)
    assert got == pytest.approx(float(main) + 0.001 * float(aux), rel=1e-6)
    assert 4.0 < float(main) < 5.5  # ~ln(97) at initialisation
    # sigmoid scores near 1/2, not normalised: E * k / 2 = 24 a layer
    assert 4 * 20.0 < float(aux) < 4 * 28.0


def test_logits_and_routing_match_reference(both):
    _, (logits, routing, mixed) = both["r_aux"]
    assert sorted(mixed) == [f"attn{i}" for i in range(5)]
    for name, want in mixed.items():
        assert rel(both["blobs"][name], want) <= TOL, name
    assert both["blobs"]["lm_head"].shape == logits.shape
    assert rel(both["blobs"]["lm_head"], logits) <= TOL
    assert sorted(routing) == [f"moe{i}" for i in range(1, 5)]
    state = both["stepped"].state
    for name, (_, chosen) in routing.items():
        load = np.bincount(np.asarray(chosen).reshape(-1), minlength=16)
        assert np.array_equal(np.asarray(state[name]["load"]), load)


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_gradient_matches_reference(both, leaf):
    layer, i = leaf
    want = both["r_grads"][layer][i]
    assert float(jnp.linalg.norm(want)) > 0
    assert rel(both["p_grads"][layer][i], want) <= TOL


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
    assert rel(got[sure], (np.asarray(w1) - np.asarray(w0))[sure]) <= 3e-4


@pytest.mark.parametrize("leaf", [("loss", None)] + LEAVES,
                         ids=lambda l: f"{l[0]}.{l[1]}")
def test_the_reference_walked_by_block_is_the_reference(both, leaf):
    """``loss_and_grads_by_block`` (what the benchmark's check runs: one
    compiled block of each kind) against ``value_and_grad(loss)`` traced
    whole."""
    if "walked" not in both:
        with jax.default_matmul_precision("highest"):
            both["walked"] = ref.loss_and_grads_by_block(
                both["params"], both["feeds"]["data"],
                both["feeds"]["label"], CFG)
    (loss, ((main, aux), (logits, routing, mixed))), grads = both["walked"]
    name, i = leaf
    if name == "loss":
        (r_main, r_aux), (r_logits, r_routing, r_mixed) = both["r_aux"]
        for layer, want in r_mixed.items():
            assert rel(mixed[layer], want) < TOL
        assert abs(float(loss) / float(both["r_loss"]) - 1) < TOL
        assert abs(float(main) / float(r_main) - 1) < TOL
        assert abs(float(aux) / float(r_aux) - 1) < TOL
        assert rel(logits, r_logits) < TOL
        for layer in r_routing:
            assert np.array_equal(routing[layer][1], r_routing[layer][1])
    else:
        assert rel(grads[name][i], both["r_grads"][name][i]) < TOL


# ------------------------------------------------- wrong programs are seen
def _wrong(text_edit):
    """The tiny net with its prototxt edited: a program that computes
    something else under the same blobs."""
    text = text_edit(serialize(models.laguna(**TINY)))
    cfg = dataclasses.replace(models.laguna_solver(), random_seed=3)
    return Solver(cfg, parse(text))


@pytest.mark.parametrize("name,edit", [
    ("window-7", lambda t: t.replace("window: 8", "window: 7")),
    ("window-9", lambda t: t.replace("window: 8", "window: 9")),
    ("plain-rope-for-yarn",
     lambda t: re.sub(r"\s*rope_scaling \{[^}]*\}", "", t)),
    ("attention-factor-1",
     lambda t: t.replace("attention_factor: 1.4158883083359672",
                         "attention_factor: 1.0")),
    ("whole-head-turned", lambda t: t.replace("rotary_dim: 8\n",
                                              "rotary_dim: 16\n")),
])
def test_a_wrong_program_leaves_the_reference(both, name, edit):
    """The same blobs under another mask, frequency table, factor or
    rotary span: the logits leave the reference by fifty times the limit
    the right program is held to, and more."""
    solver = _wrong(edit)
    loss, blobs = program_loss(solver, both["params"], both["feeds"])
    _, (logits, _, _) = both["r_aux"]
    assert rel(blobs["lm_head"], logits) > 50 * TOL, name
    assert np.isfinite(float(loss))


# ------------------------------------------------------------ the builder
def _count(shapes):
    return {k: sum(int(np.prod(a.shape)) for a in v)
            for k, v in shapes.items()}


@pytest.mark.parametrize("kwargs,total", [
    ({"layers": 5, "experts_held": 16, "vocab": 12544}, 490_297_344),
    ({}, 33_442_596_864),
], ids=["the-benchmarks-cut", "published"])
def test_published_sizes_are_the_default(kwargs, total):
    """490.3 M parameters in the benchmark's cut (the dense layer and one
    period, 16 of 256 experts a layer, 12,544 rows), 33.44 B in all 40
    layers (the model's card says 33.4B: the count that fixes the gate at
    one scalar a head), counted without building them."""
    net = Network(models.laguna(**kwargs), Phase.TRAIN)
    n = _count(jax.eval_shape(lambda k: net.init(k, None, None).params,
                              jax.random.key(0)))
    # q + k + v + o + the head-wise gate
    assert n["attn0"] == n["attn4"] == 2 * 48 * 128 * 2048 + 2 * 8 * 128 * 2048 + 48 * 2048
    assert n["attn1"] == n["attn3"] == 2 * 64 * 128 * 2048 + 2 * 8 * 128 * 2048 + 64 * 2048
    assert n["mlp0"] == 3 * 2048 * 8192 and "moe0" not in n
    held = 16 if kwargs else 256
    assert n["moe1"] == 256 * 2048 + (held + 1) * 3 * 512 * 2048
    assert n["norm0a"] == n["norm_f"] == 2_048
    assert sum(n.values()) == total
    layers = [l for l in net.layers if l.type == "GatedAttention"]
    assert [l.window for l in layers[:5]] == [0, 512, 512, 512, 0]
    assert [l.num_heads for l in layers[:5]] == [48, 64, 64, 64, 48]
    assert [l.rotary_dim for l in layers[:5]] == [64, 128, 128, 128, 64]
    assert [l.inv_freq is not None for l in layers[:5]] == [
        True, False, False, False, True]
    assert layers[0].rope_scale == 1.4158883083359672
    assert layers[0].rope_theta == 500000.0 and layers[1].rope_theta == 1e4
    assert not any(l.qk_norm for l in layers) and all(
        l.head_gate for l in layers)
    if not kwargs:
        assert sum(l.window > 0 for l in layers) * 4 == 3 * len(layers) == 120


def test_whole_model_lists_beside_a_cut_depth_are_read_from_their_start():
    """The configuration's file keeps the published 40-entry lists; the
    builder reads the first ``layers`` of them."""
    whole = dict(
        layer_types=(("full_attention",) + ("sliding_attention",) * 3) * 10,
        heads_per_layer=(4, 8, 8, 8) * 10,
        mlp_layer_types=("dense",) + ("sparse",) * 39)
    assert serialize(models.laguna(**{**TINY, **whole})) == serialize(
        models.laguna(**TINY))
    with pytest.raises(ValueError, match="layer_types"):
        models.laguna(**{**TINY, "layer_types": ("linear_attention",)})


def test_the_prototxt_round_trips_and_names_the_new_fields():
    text = serialize(models.laguna(**TINY))
    assert serialize(parse(text)) == text
    assert text.count('type: "GatedAttention"') == 5
    assert text.count("qk_norm: false") == 5
    assert text.count("head_gate: true") == 5
    assert text.count("window: 8") == 3
    assert text.count("rope_scaling {") == 2
    for field in ('type: "yarn"', "factor: 64",
                  "original_max_position_embeddings: 64", "beta_fast: 4",
                  "beta_slow: 1", "attention_factor: 1.4158883083359672"):
        assert text.count(field) == 2, field
    assert text.count("rotary_dim: 8\n") == 2
    assert text.count("rotary_dim: 16\n") == 3
    assert text.count('scoring_func: "sigmoid"') == 4
    assert text.count("routed_scaling_factor: 2.5") == 4
    assert "bias_update_rate" not in text and "zero_centered" not in text
    # the auxiliary loss rides the expert layers' second top
    assert text.count("loss_weight: 0.001") == 4
    # the Qwen3-Next layer's prototxt names none of the new fields
    other = serialize(models.qwen3_next(
        batch=1, seq_len=32, vocab=97, hidden=64, layers=4, heads=4,
        kv_heads=2, head_dim=16, linear_k_heads=2, linear_v_heads=4,
        linear_k_dim=8, linear_v_dim=16, experts=16, top_k=3, expert_dim=24,
        shared_dim=24))
    for field in ("qk_norm", "head_gate", "window", "rope_scaling"):
        assert field not in other


# ---------------------------------------------------------- the front door
def test_tpunet_train_trains_laguna_from_prototxt_and_a_token_file(tmp_path):
    """``tpunet train --solver x.prototxt --data tokens:<file> --prefetch
    3`` on the serialized net: the new fields cross the prototxt."""
    import glob

    from sparknet_tpu import cli

    rng = np.random.default_rng(2)
    path = tmp_path / "tokens.bin"
    rng.integers(0, TINY["vocab"], 33 * 7 + 5).astype(np.uint16).tofile(path)
    (tmp_path / "net.prototxt").write_text(serialize(models.laguna(**TINY)))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path}/net.prototxt"\ntype: "AdamW"\nbase_lr: 0.0003\n'
        'lr_policy: "fixed"\nmomentum: 0.9\nmomentum2: 0.95\ndelta: 1e-8\n'
        'weight_decay: 0.1\nclip_gradients: 1.0\nmax_iter: 4\ndisplay: 0\n')
    out = str(tmp_path / "final")
    rc = cli.main(["train", "--solver", str(tmp_path / "solver.prototxt"),
                   "--data", f"tokens:{path}", "--prefetch", "3",
                   "--iterations", "3", "--seed", "7", "--output", out])
    assert rc == 0
    assert glob.glob(out + "*")


def test_training_lowers_the_loss():
    solver = make_solver()
    feeds = batch_of()
    first = solver.step(1, lambda it: feeds)
    last = solver.step(12, lambda it: feeds)
    assert np.isfinite(last) and last < first - 0.5


def test_the_fence_carries_the_window_counters():
    """After ``Solver.step``: how many gated attention layers have a
    window (the window itself and how many see every key are the layers'
    own since PR 52, not the fence's), and the share of the causal
    block pairs the windowed cores' mask reaches, at the width the core
    hands its kernels (one block at 32 tokens: all of it; 31 of 136
    512-wide ones at 8,192 under 512), and how many of the windowed
    layers' backward walked only those blocks (none where the core runs
    in the XLA formulation, as here; ``tests/test_window_band.py`` has a
    layer traced as on the chip), beside the attention and expert
    counters under their present names."""
    from sparknet_tpu.ops.attention import core_block, window_blocks

    solver = make_solver()
    solver.step(2, lambda it: batch_of(it))
    stats = solver._fence_stats()
    assert {k: stats[k] for k in stats if not k.startswith("moe_")} == {
        "attn_core_layers": 5, "attn_kernel_layers": 0,
        "swa_window_layers": 3, "swa_band_layers": 0,
        "swa_block_share": 100.0}
    gated = [l for l in solver.train_net.layers if l.type == "GatedAttention"]
    assert sorted(l.window for l in gated) == [0, 0, 8, 8, 8]
    assert stats["moe_layers"] == 4 and stats["moe_experts"] == 16
    assert stats["moe_pairs"] == 2 * 32 * 3
    assert 0 <= stats["moe_pairs_held"] <= 4 * stats["moe_pairs"]
    assert window_blocks(8192, 512) == (31, 136)
    # the blocks counted are the blocks attention_core passes on: 1024
    # wide without a window where they tile, 512 under one
    assert (core_block(8192), core_block(8192, 512), core_block(2560)) == (
        1024, 512, 512)
    assert window_blocks(8192, 0) == window_blocks(8192, 8192) == (36, 36)
    assert window_blocks(2048, 513) == (7, 10) and window_blocks(
        2048, 514) == (9, 10)
    # a net whose gated attention has no window keeps to its counters
    plain = Solver(models.qwen3_next_solver(), models.qwen3_next(
        batch=1, seq_len=32, vocab=97, hidden=64, layers=4, heads=4,
        kv_heads=2, head_dim=16, linear_k_heads=2, linear_v_heads=4,
        linear_k_dim=8, linear_v_dim=16, experts=16, top_k=3, expert_dim=24,
        shared_dim=24))
    assert not any(k.startswith("swa_") for k in plain._fence_stats())


def test_the_new_scopes_are_in_the_cache_key_and_the_step():
    from sparknet_tpu import common
    from sparknet_tpu.ops import attention

    assert (attention.ROPE_SCOPE, attention.GATE_SCOPE) == ("A.rope", "A.gate")
    assert "A.rope" in common.CACHE_SCOPES and "A.gate" in common.CACHE_SCOPES
    solver = make_solver(batch=1)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    hlo = fn.lower(variables, slots, 0, batch_of(batch=1),
                   key).compile().as_text()  # op_name holds the whole path
    for layer in ("attn0", "attn1"):
        for scope in ("A.rope", "A.core", "A.gate"):
            # forward under jvp(L.<layer>), backward under transpose(jvp(..))
            for side in (rf"/jvp\(L\.{layer}\)/",
                         rf"transpose\(jvp\(L\.{layer}\)\)/"):
                assert re.search(side + re.escape(scope) + "/", hlo), (
                    layer, scope, side)
    assert re.search(r"jvp\(L\.moe4\)/M\.shared/", hlo)


def test_no_scores_of_a_whole_sequence_exist_where_the_kernels_run():
    """The compiled CPU step at S = 32 materialises [B, H, S, S] scores
    (the XLA formulation); the path the chip takes at 8,192 is named by
    ``core_kernel`` and holds none."""
    from sparknet_tpu.ops.attention import core_kernel

    assert core_kernel("tpu", 8192, 128, 128, True) == "splash"
    assert core_kernel("cpu", 8192, 128, 128, True) == "xla"


def test_decode_spec_refuses_the_new_layers():
    """The cached decode step holds one head count, no window and no
    frequency table: it says which layer it cannot replay."""
    net = Network(models.laguna(**TINY), Phase.TEST)
    with pytest.raises(ValueError, match="has no cached decode twin"):
        models.zoo.decode_spec(net, end="lm_head")


# ------------------------------------------------------------- the share
def _layer_params(rng, e, d, h, hs):
    shapes = ((e, d), (e, h, d), (e, h, d), (e, d, h), (hs, d), (hs, d),
              (d, hs))
    return [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
            for s in shapes]


def _share_layer(first, n, d=16):
    return Network(parse(
        'layer { name: "x" type: "Input" top: "x" '
        f'input_param {{ shape {{ dim: 3 dim: 8 dim: {d} }} }} }} '
        'layer { name: "m" type: "MoE" bottom: "x" top: "y" '
        'moe_param { num_experts: 16 hidden_dim: 24 top_k: 4 '
        'expert_act: "swiglu" norm_topk_prob: true scoring_func: "sigmoid" '
        'routed_scaling_factor: 2.5 '
        f'shared_hidden_dim: 20 experts_held: {n} first_expert: {first} '
        '} }'), Phase.TRAIN)


@pytest.mark.parametrize("held", [4, 8])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """16 experts in 16 / n shares of n: the routed parts of the shares,
    with the shared expert (which every chip computes alike) counted
    once, are the uncut layer's output as the reference gives it."""
    rng = np.random.default_rng(7)
    params = _layer_params(rng, 16, 16, 24, 20)
    x = jnp.asarray(rng.standard_normal((3, 8, 16)), jnp.float32)
    cfg = dict(top_k=4, scale=2.5, first_expert=0)
    with jax.default_matmul_precision("highest"):
        whole, _, _, chosen = ref.moe(params, x.reshape(-1, 16), cfg)
        shared = ref.gated_mlp(params[4:], x.reshape(-1, 16))
    shares = 16 // held
    total = -(shares - 1.0) * shared
    held_pairs = 0
    for first in range(0, 16, held):
        net = _share_layer(first, held)
        v = net.init(jax.random.key(0))
        assert [p.shape[0] for p in v.params["m"][:4]] == [16] + [held] * 3
        assert "bias" not in v.state["m"]  # no selection bias here
        v = dataclasses.replace(v, params={"m": [
            params[0], *(w[first:first + held] for w in params[1:4]),
            *params[4:]]})
        blobs, state, _ = net.apply(v, {"x": x})
        total = total + blobs["y"].reshape(-1, 16)
        load = np.asarray(state["m"]["load"])
        np.testing.assert_array_equal(
            load, np.bincount(np.asarray(chosen).reshape(-1), minlength=16))
        held_pairs += load[first:first + held].sum()
        # one share alone is NOT the layer
        assert rel(blobs["y"].reshape(-1, 16), whole) > 0.1
    assert rel(total, whole) <= TOL
    assert held_pairs == 3 * 8 * 4  # every pair landed on exactly one share


# -- the benchmark's check of this model (benchmarks/harness/window_check.py)
CHECK_CONFIG = dict(
    layer_types=list(KINDS), num_attention_heads_per_layer=[4, 8, 8, 8, 4],
    mlp_layer_types=["dense"] + ["sparse"] * 4, num_key_value_heads=2,
    head_dim=16, sliding_window=8, rope_parameters=ROPES, rms_norm_eps=1e-6,
    num_experts_per_tok=3, moe_routed_scaling_factor=2.5, first_expert=4,
    num_experts=4, num_hidden_layers=5, router_aux_loss_coef=0.001)
# N(0, 0.12) weights: scores of unit size, as the published widths give at
# N(0, 0.02) (0.02 * sqrt(2048) = 0.9 a query feature); at 0.02 over 64
# inputs a softmax is level and sees no frequency table
CHECK_STD = 0.12
WRONG = {
    "window-7": lambda t: t.replace("window: 8", "window: 7"),
    "window-9": lambda t: t.replace("window: 8", "window: 9"),
    "plain-rope-for-yarn":
        lambda t: re.sub(r"\s*rope_scaling \{[^}]*\}", "", t),
    "attention-factor-1":
        lambda t: t.replace("attention_factor: 1.4158883083359672",
                            "attention_factor: 1.0"),
}


def _check_solver(edit=lambda t: t):
    text = edit(serialize(models.laguna(
        **{**TINY, "batch": 1, "init_std": CHECK_STD})))
    cfg = dataclasses.replace(models.laguna_solver(), random_seed=3)
    return Solver(cfg, parse(text))


@pytest.fixture(scope="module")
def checked():
    """The check as the cell runs it, on one tiny sequence: the program's
    facts, the reference computed entirely in bf16 (parameters, moments
    and update too) against the reference proper, and the facts of four
    wrong programs from the same initial parameters."""
    from benchmarks.harness import window_check as chk

    feeds = batch_of()
    ids, labels = feeds["data"][:1], feeds["label"][:1]
    solver = _check_solver()
    runs = {
        name: jax.tree_util.tree_map(np.asarray, chk.run_reference(
            ref, solver.variables.params, jnp.asarray(ids),
            jnp.asarray(labels), chk.reference_config(CHECK_CONFIG),
            solver.config, chk.leaves(CHECK_CONFIG), dtype))
        for name, dtype in (("f32", None), ("bf16", jnp.bfloat16))}
    out = dict(chk=chk, solver=solver,
               twin=chk.compare(runs["bf16"], runs["f32"]), wrong={})
    for name, edit in [("right", lambda t: t), *WRONG.items()]:
        s = solver if name == "right" else _check_solver(edit)
        facts, problems = chk.check_step(
            s, ref, CHECK_CONFIG, ids, labels, chk.tolerances(rehearse=True),
            chk.forward_program(s), runs["f32"])
        if name == "right":
            out.update(facts=facts, problems=problems)
        else:
            out["wrong"][name] = facts
    return out


def test_the_check_passes_the_program_and_reads_every_limit(checked):
    assert checked["problems"] == []
    full = checked["chk"].tolerances()
    for name, limit in full.items():
        assert np.isfinite(checked["facts"][name]), name
        # f32 on the CPU: the timed step's numbers lie on the reference's,
        # far inside the limits the chip's bf16 run is held to
        assert checked["facts"][name] <= limit / 10, name
    assert checked["facts"]["topk_sets_differ"] == 0
    assert checked["facts"]["held_pair_share"] > 0
    assert set(checked["chk"].TOL_REHEARSE) == set(full)


@pytest.mark.parametrize("name,limit", [
    ("window-7", "window_edge"), ("window-9", "window_edge"),
    ("plain-rope-for-yarn", "mixed_rel.full"),
    ("attention-factor-1", "mixed_rel.full")])
def test_the_check_refuses_a_wrong_program(checked, name, limit):
    """A window one key off, plain RoPE where YaRN belongs, an
    ``attention_factor`` of 1: each breaks the FULL-SIZE limit on the
    output of the attention layer it changes, and the other kind of
    layer, which it does not touch, still reads as the right program's."""
    chk, facts = checked["chk"], checked["wrong"][name]
    if limit == "window_edge":
        # all of the departure lies along what that wrong window does
        assert facts[limit] == pytest.approx(1.0, abs=1e-3)
        # attn0 lies before every sliding layer: it reads as it did
        assert facts["mixed_rel.full"] == checked["facts"]["mixed_rel.full"]
    else:
        assert facts[limit] > 2 * chk.tolerances()[limit]


@pytest.mark.parametrize("name", list(WRONG))
def test_the_timed_steps_own_leaves_see_a_wrong_program(checked, name):
    """``mixed_rel.*`` and ``window_edge`` come from a forward of the
    check's own, in the first layer of each kind.  The TIMED step's own
    numbers see each wrong program too, at this size: the first AdamW
    change of the last full layer's W_q + W_k (behind every sliding
    layer) and of the first sliding layer's W_k + W_v leaves the
    reference's by ten times its full-size limit and more (0.099 to 1.09
    against 1e-2).  What they read at the published widths, where one
    key is one of 512 and not of 8, is PERF.md section 2's."""
    chk, facts = checked["chk"], checked["wrong"][name]
    for leaf in ("update_rel.full_qk", "update_rel.window_kv"):
        assert facts[leaf] > 5 * chk.tolerances()[leaf], leaf
        assert checked["facts"][leaf] < chk.tolerances()[leaf] / 10, leaf


@pytest.mark.parametrize("leaf", ["gate_w", "full_qk", "full_out",
                                  "window_out", "window_kv", "dense_up"])
def test_a_program_without_f32_master_weights_breaks_the_update_limit(
        checked, leaf):
    """The all-bf16 reading fails the FULL-SIZE limit of every matrix
    leaf whatever the data: a weight of size ~0.1 moves in bf16 steps of
    4.9e-4 where the first change is 3e-4."""
    name = "update_rel." + leaf
    limit = checked["chk"].tolerances()[name]
    assert checked["twin"][name] > limit
    assert checked["facts"][name] < limit


def test_no_limit_is_read_over_fewer_than_64_entries_at_full_size():
    """The leaves at the published widths: the tenth of each has at least
    64 entries."""
    import json
    import os

    from benchmarks.harness import window_check as chk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "laguna-xs2-l5-v8-bf16.json")) as f:
        config = json.load(f)
    net = Network(models.laguna(layers=5, experts_held=16, vocab=12544),
                  Phase.TRAIN)
    shapes = jax.eval_shape(lambda k: net.init(k, None, None).params,
                            jax.random.key(0))
    zeros = {k: [np.zeros(a.shape, np.int8) for a in v] if k in (
        "attn1", "attn3", "attn4", "mlp0", "moe4", "norm_f") else None
        for k, v in shapes.items()}
    sizes = {name: chk._leaf(zeros, spec, np).size
             for name, spec in chk.leaves(config).items()}
    assert sizes == {
        "gate_w": 64 * 2048, "full_qk": (48 + 8) * 128 * 2048,
        "full_out": 2048 * 48 * 128, "window_out": 2048 * 64 * 128,
        "window_kv": 2 * 8 * 128 * 2048, "dense_up": 8192 * 2048,
        "router": 256 * 2048, "held_gate": 16 * 512 * 2048,
        "final_norm": 2048}
    assert all(n // 10 >= 64 for n in sizes.values())
    assert {k.split(".")[1] for k in chk.TOL if k.startswith("update")} == \
        set(sizes)
    assert chk.mixed_layers(config) == {"full": "attn0", "window": "attn1"}


def test_the_routing_hooks_read_the_layers_counters(checked):
    chk, solver = checked["chk"], checked["solver"]
    assert chk.settle_bias(solver, None, None, None) == [1.0]
    fullest, held = chk.routing_now(solver, CHECK_CONFIG)
    assert fullest >= 1.0 and 0.0 <= held <= 100.0
