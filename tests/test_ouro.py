"""Ouro, a looped language model, through the front door, held to its
plain reference on the CPU.

Tiny preset (hidden 64, 4 heads of 16, MLP 96, 2 blocks run 3 times,
vocab 97, S 32), float32: the program (`models.ouro` through the looped
region of `compiler/graph.py`, `ops/loss.py ExitWeightedLoss`,
`Solver.step`, the `tokens:` feed) against `benchmarks/reference/ouro.py`
on seeded weights with every vector moved off its initial value, and
against the same net WRITTEN OUT (the region's layers three times over
with shared ``param { name }``: `benchmarks/scratch/looped_written_out.py`,
built from the same builder).  At f32 on one backend the forms differ
only by summation order, so the limit is 1e-5 (rel-L2 for arrays,
relative for scalars).  A pass that reads the un-normed state, a gate
applied to the wrong pass, a gradient that keeps one pass of three or a
product of probabilities in place of the sum of logs move these by 1e-2
or more.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ouro as ref
from benchmarks.scratch.looped_written_out import written_out
from sparknet_tpu import models
from sparknet_tpu.common import Phase, step_key
from sparknet_tpu.compiler.graph import Network, NetVars
from sparknet_tpu.layers_dsl import SoftmaxWithLoss
from sparknet_tpu.ops.loss import exit_distribution
from sparknet_tpu.proto.text_format import parse, serialize
from sparknet_tpu.solvers.solver import Solver

TINY = dict(batch=2, seq_len=32, vocab=97, hidden=64, heads=4, mlp_dim=96,
            layers=2, ut_steps=3)
CFG = dict(heads=4, eps=1e-6, theta=1e6, layers=2, ut_steps=3,
           entropy_weight=0.1)
TOL = 1e-5
BLOCK = (("norm{}a", 1), ("attn{}", 2), ("norm{}b", 1), ("norm{}c", 1),
         ("mlp{}", 3), ("norm{}d", 1))
LEAVES = [("embed", 0), ("norm_f", 0), ("exit_gate", 0), ("exit_gate", 1),
          ("lm_head", 0)] + [
    (name.format(i), b) for i in range(TINY["layers"]) for name, n in BLOCK
    for b in range(n)]
LOOPED = [leaf for leaf in LEAVES
          if leaf[0] not in ("embed", "exit_gate", "lm_head")]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def make_solver(seed=3, net=None, **over):
    cfg = dataclasses.replace(models.ouro_solver(), random_seed=seed)
    return Solver(cfg, net or models.ouro(**{**TINY, **over}))


def batch_of(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab"], (TINY["batch"], TINY["seq_len"] + 1))
    return {"data": ids[:, :-1].astype(np.int32),
            "label": ids[:, 1:].astype(np.int32)}


def shake(solver, seed=5):
    """Ones would hide a swapped norm, a zero bias a dropped one; a gate
    of N(0, 0.02) on a normed state leaves every lambda at a half."""
    rng = np.random.default_rng(seed)
    for name, blobs in solver.variables.params.items():
        for i, w in enumerate(blobs):
            if (w.ndim == 1 or name == "exit_gate") and w.size:
                blobs[i] = w + jnp.asarray(
                    0.1 * rng.standard_normal(w.shape), jnp.float32)


def program(solver, feeds):
    """params -> (loss, blobs) of the solver's own net."""
    net = solver.train_net

    def loss(p):
        v = dataclasses.replace(solver.variables, params=p)
        blobs, _, total = net.apply(v, feeds, rng=step_key(solver._key, 0))
        return total, blobs

    return loss


@pytest.fixture(scope="module")
def both():
    """One forward/backward of the program and of the reference on the
    same weights and batch, and one AdamW step of the program."""
    solver = make_solver()
    shake(solver)
    feeds = batch_of()
    params = jax.tree_util.tree_map(jnp.array, solver.variables.params)
    (p_loss, blobs), p_grads = jax.value_and_grad(
        program(solver, feeds), has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        (r_loss, r_aux), r_grads = ref.loss_and_grads(
            params, feeds["data"], feeds["label"], CFG)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    stepped, _, _ = fn(variables, slots, 0, feeds, key)
    return dict(solver=solver, params=params, blobs=blobs, p_loss=p_loss,
                p_grads=p_grads, r_loss=r_loss, r_aux=r_aux, r_grads=r_grads,
                stepped=stepped, feeds=feeds)


# ------------------------------------------- (a) against the reference
def test_loss_matches_reference(both):
    got, want = float(both["p_loss"]), float(both["r_loss"])
    assert abs(got - want) <= TOL * abs(want)
    assert float(both["blobs"]["loss"]) == pytest.approx(got, rel=1e-6)
    assert 4.0 < want < 5.5  # ~ln(97) - 0.1 H at initialisation


def test_last_logits_match_reference(both):
    z = both["blobs"]["lm_head"].reshape(
        TINY["ut_steps"], TINY["batch"], TINY["seq_len"], TINY["vocab"])
    assert rel(z[-1], both["r_aux"][0]) <= TOL
    with jax.default_matmul_precision("highest"):
        z_t = ref.forward(both["params"], both["feeds"]["data"], CFG)
        z_all, p = ref.forward(both["params"], both["feeds"]["data"], CFG,
                               every_step=True)
    assert rel(z[-1], z_t) <= TOL and rel(z, z_all) <= TOL
    np.testing.assert_allclose(p, both["r_aux"][1], rtol=1e-6)


def test_exit_distribution_matches_reference(both):
    gate = both["blobs"]["exit_gate"].reshape(
        TINY["ut_steps"], TINY["batch"], TINY["seq_len"])
    p = np.exp(np.asarray(exit_distribution(gate)))
    want = np.asarray(both["r_aux"][1])
    assert np.abs(p - want).max() <= 1e-6
    assert want.std() > 1e-3  # the shaken gate tells the steps apart
    assert rel(both["blobs"]["step_loss"], both["r_aux"][2]) <= TOL
    assert float(both["blobs"]["exit_mean_step"]) == pytest.approx(
        float(both["r_aux"][3]), rel=1e-5)


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


# --------------------------------- (b) the region against the written-out net
@pytest.fixture(scope="module")
def flat(both):
    """The same net written out (3 x the region's layers, shared names),
    on the region net's parameters."""
    solver = make_solver(net=written_out(models.ouro(**TINY)))
    owned = {k: v for k, v in solver.variables.params.items()
             if any(a.size for a in v)}
    assert set(owned) == set(both["params"])
    params = {k: (both["params"][k] if k in owned else v)
              for k, v in solver.variables.params.items()}
    (loss, blobs), grads = jax.value_and_grad(
        program(solver, both["feeds"]), has_aux=True)(params)
    return dict(solver=solver, loss=loss, blobs=blobs, grads=grads,
                owned=owned)


def test_the_region_equals_the_written_out_net(both, flat):
    assert float(flat["loss"]) == pytest.approx(float(both["p_loss"]),
                                                rel=TOL)
    assert rel(both["blobs"]["lm_head"], flat["blobs"]["lm_head"]) <= TOL
    assert rel(both["blobs"]["states"], flat["blobs"]["states"]) <= TOL


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_the_regions_gradient_is_the_written_out_nets(both, flat, leaf):
    layer, i = leaf
    assert rel(both["p_grads"][layer][i], flat["grads"][layer][i]) <= TOL


def test_each_looped_blob_is_there_once(both, flat):
    """The region's parameters are the written-out net's OWNED ones, name
    for name and shape for shape; the prototxt holds each block once."""
    shapes = lambda tree: {k: [a.shape for a in v] for k, v in tree.items()}
    assert shapes(both["params"]) == shapes(flat["owned"])
    text = serialize(models.ouro(**TINY))
    assert text.count('type: "MultiHeadAttention"') == TINY["layers"]
    assert text.count('type: "GatedMLP"') == TINY["layers"]
    flat_text = serialize(written_out(models.ouro(**TINY)))
    assert flat_text.count('type: "GatedMLP"') == 3 * TINY["layers"]
    n = sum(int(a.size) for v in both["params"].values() for a in v)
    d, f, v = TINY["hidden"], TINY["mlp_dim"], TINY["vocab"]
    assert n == 2 * (4 * d * d + 3 * d * f + 4 * d) + 2 * v * d + d + d + 1


def test_a_looped_gradient_is_the_sum_over_the_passes(both, flat):
    """Each pass of the written-out net alone (its own copy of one blob,
    unshared): the region's gradient is their sum, not any one of them."""
    solver, feeds = both["solver"], both["feeds"]
    net = Network(written_out_unshared(), Phase.TRAIN)
    variables = net.init(jax.random.key(0))
    passes = ["attn0", "attn0@2", "attn0@3"]
    params = {k: (both["params"][k.split("@")[0]]
                  if k.split("@")[0] in both["params"] else v)
              for k, v in variables.params.items()}
    grads = jax.grad(lambda p: net.apply(
        NetVars(p, variables.state), feeds,
        rng=step_key(solver._key, 0))[2])(params)
    parts = [grads[name][0] for name in passes]
    assert rel(sum(parts), both["p_grads"]["attn0"][0]) <= TOL
    far = [rel(part, both["p_grads"]["attn0"][0]) for part in parts]
    assert min(far) > 0.15, far  # no single pass is the sum


def written_out_unshared():
    """The written-out net with ``attn0``'s sharing dropped: three blobs."""
    net = written_out(models.ouro(**TINY))
    for layer in net.get_all("layer"):
        if layer.get_str("name").split("@")[0] == "attn0":
            layer.fields.pop("param")
    return net


# ------------------------------------------------ (c) one pass is the plain net
def test_one_step_is_the_plain_net():
    """``ut_steps: 1``: p_1 = 1, H = 0, and the loss is the plain
    decoder's cross-entropy; the gate gets no gradient."""
    looped = make_solver(ut_steps=1)
    shake(looped)
    msg = models.ouro(**{**TINY, "ut_steps": 1})
    plain = parse(serialize(msg))
    plain.fields.pop("loop")
    layers = [l for l in plain.get_all("layer")
              if l.get_str("name") not in ("exit_gate", "loss")]
    for l in layers:
        if l.get_str("name") == "lm_head":
            l.fields["bottom"] = ["norm_f"]
    plain.fields["layer"] = layers + [
        SoftmaxWithLoss("loss", ["lm_head", "label"], axis=2)]
    solver = make_solver(net=plain)
    feeds = batch_of()
    params = {k: v for k, v in looped.variables.params.items()
              if k != "exit_gate"}
    (want, _), g_plain = jax.value_and_grad(
        program(solver, feeds), has_aux=True)(params)
    (got, blobs), g_loop = jax.value_and_grad(
        program(looped, feeds), has_aux=True)(looped.variables.params)
    assert float(got) == pytest.approx(float(want), rel=TOL)
    assert float(blobs["exit_mean_step"]) == 1.0
    for name in params:
        for a, b in zip(g_loop[name], g_plain[name]):
            assert rel(a, b) <= TOL
    assert all(float(jnp.abs(g).max()) == 0.0 for g in g_loop["exit_gate"])


# ------------------------------------------------------ (d) the loss's own tops
def test_the_exit_distribution_sums_to_one():
    rng = np.random.default_rng(0)
    gate = jnp.asarray(8.0 * rng.standard_normal((4, 3, 50)), jnp.float32)
    logp = exit_distribution(gate)
    np.testing.assert_allclose(np.exp(logp).sum(0), 1.0, atol=1e-6)
    lam = 1.0 / (1.0 + np.exp(-np.asarray(gate, np.float64)))
    want = np.stack([lam[0], lam[1] * (1 - lam[0]),
                     lam[2] * (1 - lam[0]) * (1 - lam[1]),
                     (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    np.testing.assert_allclose(np.exp(logp), want, rtol=1e-4, atol=1e-30)
    # a gate shut at every step sends everything to the last
    shut = exit_distribution(jnp.full((4, 2), -200.0))
    np.testing.assert_allclose(np.exp(shut), [[0, 0]] * 3 + [[1, 1]],
                               atol=1e-30)
    assert np.isfinite(np.asarray(shut)[-1]).all()


def test_the_loss_top_is_its_parts(both):
    """loss = mean_tokens[ sum_t p_t L_t - beta H ], recomputed from the
    logits and the gate; the weight-0 tops are the per-step means."""
    b = both["blobs"]
    t, n, s, v = TINY["ut_steps"], TINY["batch"], TINY["seq_len"], TINY["vocab"]
    z = np.asarray(b["lm_head"], np.float64).reshape(t, n, s, v)
    z = z - z.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    lab = np.broadcast_to(both["feeds"]["label"], (t, n, s))
    nll = -np.take_along_axis(logp, lab[..., None], -1)[..., 0]
    p = np.exp(np.asarray(exit_distribution(
        b["exit_gate"].reshape(t, n, s)), np.float64))
    entropy = -(p * np.log(p)).sum(0)
    want = ((p * nll).sum(0) - CFG["entropy_weight"] * entropy).mean()
    assert float(b["loss"]) == pytest.approx(want, rel=1e-6)
    np.testing.assert_allclose(b["step_loss"], nll.mean((1, 2)), rtol=1e-6)
    steps = np.arange(1, t + 1)[:, None, None]
    assert float(b["exit_mean_step"]) == pytest.approx(
        (steps * p).sum(0).mean(), rel=1e-6)
    # the read-outs carry no weight: the total is the loss top alone
    assert float(both["p_loss"]) == pytest.approx(float(b["loss"]), rel=1e-7)


# -------------------------------------------------------- (e) the prototxt
def test_the_prototxt_round_trips_to_the_same_program():
    net = models.ouro(**TINY)
    text = serialize(net)
    assert serialize(parse(text)) == text
    assert text.count("loop {") == 1 and "collect {" in text
    feeds = batch_of()
    lowered = []
    for msg in (net, parse(text)):
        n = Network(msg, Phase.TRAIN)
        v = n.init(jax.random.key(1))
        lowered.append(jax.jit(
            lambda p, n=n, v=v: n.apply(NetVars(p, v.state), feeds)[2]
        ).lower(v.params).as_text())
    assert lowered[0] == lowered[1]


def test_the_region_is_expanded_when_the_net_is_built():
    """Declared once, traced once a pass: the lowered forward holds the
    written-out net's matmuls, ``count`` times a single pass's, under the
    region's scope; no loop is left in the program (PERF.md section 6,
    PR 41: one ``lax.scan`` over the passes did not fit the chip)."""
    feeds = batch_of()

    def lowered(msg):
        n = Network(msg, Phase.TRAIN)
        v = n.init(jax.random.key(1))
        return jax.jit(lambda p: n.apply(NetVars(p, v.state), feeds)[2]
                       ).lower(v.params).as_text(debug_info=True)

    dots = lambda text: text.count("stablehlo.dot_general")
    looped = lowered(models.ouro(**TINY))
    once = lowered(models.ouro(**{**TINY, "ut_steps": 1}))
    outside = 2  # the gate and the head, once over all the passes' rows
    assert dots(looped) - outside == 3 * (dots(once) - outside)
    assert dots(looped) == dots(lowered(written_out(models.ouro(**TINY))))
    assert "stablehlo.while" not in looped
    assert "LOOP.ut/L.attn0/A.core" in looped
    assert "LOOP.ut/L.lm_head" not in looped and "L.lm_head" in looped


@pytest.mark.parametrize("change,match", [
    (dict(first="norm0b", carry_in="norm0a"), None),
    (dict(first="nowhere"), "must each name one layer"),
    (dict(count=0), "count >= 1"),
    (dict(carry_out="embed"), "a top of the region"),
    (dict(last="loss"), "input and loss layers stay outside"),
    (dict(carry_out="mlp0_unknown"), "a top of the region"),
], ids=["a-shorter-region", "unknown-layer", "count-0", "carry-out-outside",
        "a-loss-inside", "unknown-blob"])
def test_a_region_is_checked_when_the_net_is_built(change, match):
    net = parse(serialize(models.ouro(**TINY)))
    for key, value in change.items():
        net.get_msg("loop").set(key, value)
    if match is None:  # norm0a runs once, the rest three times
        assert Network(net, Phase.TRAIN).loops[0].first == 5
        return
    with pytest.raises(ValueError, match=match):
        Network(net, Phase.TRAIN)


def test_a_carry_of_another_shape_and_a_stateful_layer_are_refused():
    net = parse(serialize(models.ouro(**TINY)))
    net.get_msg("loop").set("carry_out", "mlp0").set("last", "mlp0")
    net.get_msg("loop").fields.pop("collect")
    layers = net.get_all("layer")
    mlp0 = next(l for l in layers if l.get_str("name") == "mlp0")
    net.fields["layer"] = layers[:layers.index(mlp0) + 1]
    n = Network(net, Phase.TRAIN)
    n.init(jax.random.key(0))  # [2, 32, 64] -> [2, 32, 64]: fine
    mlp0.set("type", "InnerProduct").set("inner_product_param", parse(
        "num_output: 48 axis: 2"))
    with pytest.raises(ValueError, match="must have the shape of carry_in"):
        Network(net, Phase.TRAIN).init(jax.random.key(0))
    net = parse(serialize(models.ouro(**TINY)))
    bn = parse('name: "bn" type: "BatchNorm" bottom: "norm0a" top: "bn"')
    layers = net.get_all("layer")
    net.fields["layer"] = layers[:4] + [bn] + layers[4:]
    with pytest.raises(ValueError, match="keeps state and lies in the looped"):
        Network(net, Phase.TRAIN).init(jax.random.key(0))


def test_a_partial_run_may_not_cut_a_region():
    solver = make_solver()
    net, feeds = solver.train_net, batch_of()
    with pytest.raises(ValueError, match="inside the looped region"):
        net.apply(solver.variables, feeds, end="attn0")
    with pytest.raises(ValueError, match="inside the looped region"):
        net.apply(solver.variables, {"norm0a": jnp.zeros((2, 32, 64))},
                  start="attn0")
    blobs, _, _ = net.apply(solver.variables, feeds, end="norm_f")
    assert blobs["states"].shape == (3 * 2, 32, 64)
    np.testing.assert_array_equal(blobs["states"][4:], blobs["norm_f"])


# --------------------------- (f) the solver sees each looped blob once
def test_clip_norm_and_decay_see_each_looped_blob_once(both):
    """The clip's global norm is over one gradient a blob (the sum over
    the passes, once), and the decay pulls each blob once: a step on
    gradients scaled to nothing is pure decay, lr * wd * w."""
    c = both["solver"].config
    norm = float(jnp.sqrt(sum(jnp.sum(g * g) for v in both["p_grads"].values()
                              for g in v)))
    assert norm > c.clip_gradients  # the clip is live at this size
    scale = float(ref.clip_scale(both["r_grads"], c.clip_gradients))
    assert scale == pytest.approx(c.clip_gradients / norm, rel=1e-5)
    # three times the blob in the norm would read sqrt(3) of it
    counted = {k for k, v in both["p_grads"].items() for _ in v}
    assert counted == set(both["params"])
    from sparknet_tpu.solvers.updates import apply_update

    zero = jax.tree_util.tree_map(jnp.zeros_like, both["params"])
    solver = both["solver"]
    new, _ = apply_update(c, both["params"], zero, solver.slots,
                          solver._specs, c.base_lr, 0)
    for layer, i in LOOPED:
        w0 = np.asarray(both["params"][layer][i])
        np.testing.assert_allclose(
            np.asarray(new[layer][i]) - w0,
            -c.base_lr * c.weight_decay * w0, rtol=1e-4, atol=1.5e-7)


def test_a_snapshot_holds_each_looped_blob_once_and_restores(tmp_path):
    solver = make_solver()
    solver.step(2, lambda it: batch_of(it))
    path = solver.save(str(tmp_path / "snap"))
    with np.load(path if path.endswith(".npz") else path + ".solverstate.npz") as z:
        keys = [k for k in z.files if "attn0" in k and "param" in k.lower()]
    assert keys and not any("@" in k for k in keys)
    again = make_solver(seed=11)
    again.restore(path)
    for name, blobs in solver.variables.params.items():
        for a, b in zip(blobs, again.variables.params[name]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    feeds = batch_of(7)
    assert again.step(1, lambda it: feeds) == pytest.approx(
        solver.step(1, lambda it: feeds), rel=1e-6)


def test_the_fence_carries_the_loop(both):
    solver = make_solver()
    solver.step(2, lambda it: batch_of(it))
    stats = solver._fence_stats()
    assert set(stats) == {"ut_steps", "ut_loss_1", "ut_loss_2", "ut_loss_3",
                          "exit_mean_step", "attn_core_layers",
                          "attn_kernel_layers"}
    assert stats["ut_steps"] == 3 and 1.0 < stats["exit_mean_step"] < 3.0
    assert all(4.0 < stats[f"ut_loss_{t}"] < 5.5 for t in (1, 2, 3))
    # a net without a region prints none of these keys
    plain = Solver(models.olmoe_solver(), models.olmoe(
        batch=2, seq_len=32, vocab=97, hidden=64, heads=4, experts=8,
        top_k=2, expert_dim=32, layers=1))
    assert set(plain._fence_stats()) == {
        "moe_load_max", "moe_pairs", "moe_experts", "attn_core_layers",
        "attn_kernel_layers"}


def test_training_lowers_the_loss_and_parallel_replicas_agree():
    solver = make_solver()
    feeds = batch_of()
    first = solver.step(1, lambda it: feeds)
    last = solver.step(12, lambda it: feeds)
    assert np.isfinite(last) and last < first - 0.5


def test_scanned_steps_equal_single_steps():
    """``--scan``: the region's scan inside the solver's own."""
    a, b = make_solver(), make_solver()
    one = [a.step(1, lambda it: batch_of(it)) for _ in range(4)][-1]
    assert b.step(4, lambda it: batch_of(it), scan_chunk=4) == pytest.approx(
        one, rel=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(a.variables.params),
                    jax.tree_util.tree_leaves(b.variables.params)):
        assert rel(x, y) <= 1e-5


# ---------------------------------------------------------- the front door
def test_published_sizes_are_the_default():
    """2.6 B parameters in 48 blocks; 230,723,585 in the benchmark's cut
    (4 blocks, 6,144 rows), counted without building them."""
    def count(**kw):
        net = Network(models.ouro(**kw), Phase.TRAIN)
        shapes = jax.eval_shape(lambda k: net.init(k, None, None).params,
                                jax.random.key(0))
        return {k: sum(int(np.prod(a.shape)) for a in v)
                for k, v in shapes.items()}

    n = count(layers=4, vocab=6144)
    block = sum(n[name.format(0)] for name, _ in BLOCK)
    assert block == 51_388_416 and n["lm_head"] == n["embed"] == 12_582_912
    assert n["exit_gate"] == 2049 and sum(n.values()) == 230_723_585
    whole = count()
    assert sum(whole.values()) == 48 * block + 2 * 49152 * 2048 + 2048 + 2049


def test_tpunet_train_trains_ouro_from_prototxt_and_a_token_file(tmp_path):
    """``tpunet train --solver x.prototxt --data tokens:<file> --prefetch
    3`` on the serialized net: the region crosses the prototxt."""
    import glob

    from sparknet_tpu import cli

    rng = np.random.default_rng(2)
    path = tmp_path / "tokens.bin"
    rng.integers(0, TINY["vocab"], 33 * 7 + 5).astype(np.uint16).tofile(path)
    (tmp_path / "net.prototxt").write_text(serialize(models.ouro(**TINY)))
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


def test_decode_spec_refuses_a_looped_net():
    net = Network(models.ouro(**TINY), Phase.TRAIN)
    with pytest.raises(ValueError, match="looped region"):
        models.zoo.decode_spec(net, end="lm_head")


# ------------------------------------------------------- (g) the flop rows
def _load(kind, name):
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmarks", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_flop_rows_count_the_region_ut_steps_times():
    """Against a hand count at the tiny preset: hidden 64, 4 heads of 16,
    MLP 96, 2 blocks, 3 passes, 97 rows, 32 tokens."""
    counts = _load("harness", "looped_flops")
    config = {"hidden_size": 64, "num_attention_heads": 4,
              "intermediate_size": 96, "num_hidden_layers": 2,
              "total_ut_steps": 3, "vocab_rows": 97}
    parts = {r["name"]: r for r in counts.parts(config, 1, 32)}
    t, d, f, v, steps = 32, 64, 96, 97, 3
    assert parts["attn0.proj"]["macs"] == steps * t * 4 * d * d
    assert parts["mlp1"]["macs"] == steps * t * 3 * d * f
    assert parts["lm_head"]["macs"] == steps * t * d * v
    assert parts["exit_gate"]["macs"] == steps * t * d
    # causal core: query q sees q + 1 keys; 2 d_head multiply-adds a pair
    assert parts["attn1.core"]["macs"] == steps * (32 * 33 // 2) * 4 * 2 * 16
    rows = {r["name"]: r for r in counts.layer_rows(list(parts.values()))}
    assert set(rows) == {"attn0", "mlp0", "attn1", "mlp1", "lm_head",
                         "exit_gate"}
    assert rows["attn0"]["macs"] == (parts["attn0.proj"]["macs"]
                                     + parts["attn0.core"]["macs"])
    # a weight is read once a pass, whatever the count says of its size
    assert parts["mlp0"]["weight_elems"] == steps * 3 * d * f
    once = {r["name"]: r for r in counts.parts(
        {**config, "total_ut_steps": 1}, 1, 32)}
    assert all(parts[k]["macs"] == steps * once[k]["macs"] for k in parts)


# ------------------------------------------------ the benchmark's four readers
def _trace(loop=True):
    """A neutral-form trace of one chip (``harness/trace.py``): a region's
    ops under ``LOOP.ut``, the exit path and the update outside it."""
    pre = "jit(train_step)/jit(main)/"
    fwd = pre + "LOOP.ut/" if loop else pre
    bwd = pre + "transpose(jvp(LOOP.ut))/" if loop else pre + "transpose(jvp("
    rows = [
        [0, 300, "fusion.1", fwd + "L.mlp0/dot_general"],
        [300, 100, "flash_fwd", fwd + "L.attn0/A.core/pallas_call"],
        [400, 100, "fusion.2", pre + "L.lm_head/dot_general"],
        [500, 50, "fusion.3", pre + "L.loss/reduce"],
        [550, 50, "fusion.4", pre + "transpose(jvp(L.exit_gate))/dot_general"],
        [600, 200, "flash_bwd", bwd + "transpose(jvp(L.attn0))/A.core/x"],
        [800, 200, "fusion.5", pre + "S.update/sub"],
    ]
    return {"window": [0, 1000], "chips": {"0": rows}, "host": []}


def test_the_readers_read_the_loop_and_fall_silent_without_it():
    from benchmarks.harness import trace

    scopes = _load("metrics", "_loop_scopes")
    fences = [{"start_ns": 10, "stats": {"ut_steps": 4, "exit_mean_step": 1.9}},
              {"start_ns": 20, "stats": {"exit_mean_step": "2.1"}},
              {"start_ns": 5000, "stats": {"exit_mean_step": 9.0}},
              {"start_ns": 30, "stats": {"moe_load_max": 3}}]
    tr = _trace()
    summary = dict(trace.summarize(tr), loop_scopes=scopes.reduce(tr, fences))
    assert scopes.body_share(summary) == pytest.approx(60.0)
    assert scopes.exit_share(summary) == pytest.approx(20.0)
    assert scopes.mean_exit_step(summary) == pytest.approx(2.0)
    for name, want in (("loop.body_share", 60.0), ("loop.exit_share", 20.0),
                       ("loop.mean_exit_step", 2.0)):
        assert _load("metrics", name).read(summary, {}) == pytest.approx(want)
    # a program without the scope or the counter (the parent, another
    # cell): every reader returns None and raises nowhere
    tr = _trace(loop=False)
    plain = dict(trace.summarize(tr), loop_scopes=scopes.reduce(tr, fences[3:]),
                 hybrid_scopes={"layer_scope_s": {}})
    for name in ("loop.body_share", "loop.exit_share", "loop.mean_exit_step",
                 "loop.attn_core_roofline"):
        assert _load("metrics", name).read(plain, {}) is None
        assert _load("metrics", name).read(None, {}) is None


def test_the_core_roofline_reads_the_looped_cores():
    counts = _load("harness", "looped_flops")
    config = {"hidden_size": 2048, "num_attention_heads": 16,
              "intermediate_size": 5632, "num_hidden_layers": 4,
              "total_ut_steps": 4, "vocab_rows": 6144}
    parts = counts.parts(config, 1, 4096)
    run = {"decoder_parts": parts, "steps_traced": 2,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    cores = [r for r in parts if r["kind"] == "loop_core"]
    assert len(cores) == 4
    floor = sum(2 * r["macs"] * 3 / 197e12 for r in cores)  # compute-bound
    summary = {"hybrid_scopes": {"layer_scope_s": {
        f"attn{i}/A.core": 2 * floor / 4 / 0.5 for i in range(4)}}}
    got = _load("metrics", "loop.attn_core_roofline").read(summary, run)
    assert got == pytest.approx(50.0)
    # the configuration's totals: 24.74 TFLOP a step, the region 95 % of it
    from benchmarks.harness import flops

    rows = counts.layer_rows(parts)
    assert flops.step_flops(rows) == 6 * 4096 * 1_006_673_920
    region = sum(r["macs"] for r in rows if r["name"][:4] in ("attn", "mlp0",
                 "mlp1", "mlp2", "mlp3"))
    assert 0.94 < region / sum(r["macs"] for r in rows) < 0.96


# -- the benchmark's check of this model (benchmarks/harness/looped_check.py)
@pytest.fixture(scope="module")
def checked():
    """The check as the cell runs it, on one tiny sequence: the program's
    facts, and the reference computed entirely in bf16 (parameters,
    moments and update too) against the reference proper."""
    from benchmarks.harness import looped_check as chk

    solver = make_solver(batch=1)
    feeds = batch_of()
    ids, labels = feeds["data"][:1], feeds["label"][:1]
    config = dict(num_attention_heads=4, rms_norm_eps=1e-6, rope_theta=1e6,
                  num_hidden_layers=2, total_ut_steps=3, entropy_weight=0.1)
    runs = {
        name: jax.tree_util.tree_map(np.asarray, chk.run_reference(
            ref, solver.variables.params, jnp.asarray(ids),
            jnp.asarray(labels), chk.reference_config(config), solver.config,
            chk.leaves(config), dtype))
        for name, dtype in (("f32", None), ("bf16", jnp.bfloat16))}
    facts, problems = chk.check_step(
        solver, ref, config, ids, labels, chk.tolerances(rehearse=True),
        chk.forward_program(solver), runs["f32"])
    return dict(chk=chk, facts=facts, problems=problems,
                twin=chk.compare(runs["bf16"], runs["f32"]))


def test_the_check_passes_the_program_and_reads_every_limit(checked):
    assert checked["problems"] == []
    for name in checked["chk"].tolerances():
        assert np.isfinite(checked["facts"][name]), name
    # f32 on the CPU: the timed step's own numbers lie on the reference's
    for name in ("loss_rel", "step_loss_rel", "exit_step_rel", "exit_p_abs"):
        assert checked["facts"][name] < 1e-4, name


@pytest.mark.parametrize("leaf", ["qkv_first", "down_last", "final_norm"])
def test_a_program_without_f32_master_weights_breaks_the_update_limit(
        checked, leaf):
    """The all-bf16 reading fails the FULL-SIZE limit of these leaves
    whatever the data: a bf16 weight near 0.02 moves in steps of 1.2e-4
    where the first AdamW change is 3e-4, and a norm weight of 1.0 cannot
    move at all."""
    name = f"update_rel.{leaf}"
    limit = checked["chk"].tolerances()[name]
    assert checked["twin"][name] > 5 * limit or checked["twin"][name] == 1.0
    assert checked["facts"][name] < limit


@pytest.mark.parametrize("leaf", [("loss", None)] + LEAVES,
                         ids=lambda l: f"{l[0]}.{l[1]}")
def test_the_reference_walked_by_block_is_the_reference(both, leaf):
    """``loss_and_grads_by_block`` (what the benchmark's check runs: one
    compiled block) against ``loss_and_grads`` traced whole."""
    if "walked" not in both:
        with jax.default_matmul_precision("highest"):
            both["walked"] = ref.loss_and_grads_by_block(
                both["params"], both["feeds"]["data"],
                both["feeds"]["label"], CFG)
    (loss, aux), grads = both["walked"]
    name, i = leaf
    if name == "loss":
        assert abs(float(loss) / float(both["r_loss"]) - 1) < TOL
        for got, want in zip(aux, both["r_aux"]):
            assert rel(got, want) < TOL
    else:
        assert rel(grads[name][i], both["r_grads"][name][i]) < TOL
