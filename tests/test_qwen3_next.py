"""Qwen3-Next-80B-A3B through the front door, held to its plain reference
on the CPU.

Tiny preset (hidden 64; DeltaNet 2 key and 4 value heads of 8 / 16, conv
4; attention 4 query heads over 2 key/value heads of 16, RoPE on 4 of 16
features; 16 experts of width 24, 3 a token, experts [4, 8) held, a gated
shared expert of 24; vocab 97, S 32; four blocks = one period: three
DeltaNet and one attention), float32: the program (`models.qwen3_next`
through `compiler/graph.py`, `Solver.step`, the `tokens:` feed) against
`benchmarks/reference/qwen3_next.py` on seeded weights, with every vector
(norms, dt_bias, A_log) moved off its initial value.  At f32 on one
backend the two differ only by summation order, so the limit is 2e-5
(rel-L2 for arrays, relative for scalars).  A dropped gate, a norm that
is not zero-centred, RoPE over the whole head, another key head for a
value head or a chunk started from the wrong state move these by 1e-2 or
more.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as ref
from sparknet_tpu import models
from sparknet_tpu.common import Phase, get_config, set_config, step_key
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.ops import linear_attention as la
from sparknet_tpu.proto.text_format import parse, serialize
from sparknet_tpu.solvers.solver import Solver

TINY = dict(batch=2, seq_len=32, vocab=97, hidden=64, layers=4, heads=4,
            kv_heads=2, head_dim=16, linear_k_heads=2, linear_v_heads=4,
            linear_k_dim=8, linear_v_dim=16, experts=16, top_k=3,
            expert_dim=24, shared_dim=24, experts_held=4, first_expert=4)
CFG = dict(heads=4, kv_heads=2, head_dim=16, rotary=4, theta=1e7, eps=1e-6,
           lk_heads=2, lv_heads=4, lk_dim=8, lv_dim=16, top_k=3,
           first_expert=4, layers=4, interval=4, aux_coef=0.001)
TOL = 2e-5
MIXERS = ["gdn0", "gdn1", "gdn2", "attn3"]
LEAVES = [("embed", 0), ("norm_f", 0), ("lm_head", 0)] + [
    (name, b) for i, mixer in enumerate(MIXERS)
    for name, n in ((f"norm{i}a", 1), (mixer, 6 if "attn" in mixer else 7),
                    (f"norm{i}b", 1), (f"moe{i}", 8))
    for b in range(n)]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def make_solver(seed=3, **over):
    cfg = dataclasses.replace(models.qwen3_next_solver(), random_seed=seed)
    return Solver(cfg, models.qwen3_next(**{**TINY, **over}))


def batch_of(seed=0, seq_len=TINY["seq_len"], batch=TINY["batch"]):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab"], (batch, seq_len + 1))
    return {"data": ids[:, :-1].astype(np.int32),
            "label": ids[:, 1:].astype(np.int32)}


def shake_vectors(solver, seed=5):
    """Ones and zeros would hide a swapped norm or a weight that is not
    1 + w."""
    rng = np.random.default_rng(seed)
    for blobs in solver.variables.params.values():
        for i, w in enumerate(blobs):
            if w.ndim == 1 and w.size:
                blobs[i] = w + jnp.asarray(
                    0.1 * rng.standard_normal(w.shape), jnp.float32)


@pytest.fixture(scope="module")
def both():
    """One forward/backward of the program and of the reference on the
    same weights and batch, and one AdamW step of the program."""
    solver = make_solver()
    shake_vectors(solver)
    feeds = batch_of()
    net = solver.train_net
    params = jax.tree_util.tree_map(jnp.array, solver.variables.params)

    def prog_loss(p):
        v = dataclasses.replace(solver.variables, params=p)
        blobs, _, loss = net.apply(v, feeds, rng=step_key(solver._key, 0))
        return loss, blobs

    (p_loss, blobs), p_grads = jax.value_and_grad(
        prog_loss, has_aux=True)(params)
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
    lb = sum(float(both["blobs"][f"lb{i}"]) for i in range(4))
    assert lb == pytest.approx(float(aux), rel=TOL)
    assert got == pytest.approx(float(main) + 0.001 * float(aux), rel=1e-6)
    assert 4.0 < float(main) < 5.5  # ~ln(97) at initialisation
    assert 4 * 2.5 < float(aux) < 4 * 4.0  # k = 3 a layer when level


def test_logits_and_routing_match_reference(both):
    _, (logits, routing) = both["r_aux"]
    assert both["blobs"]["lm_head"].shape == logits.shape
    assert rel(both["blobs"]["lm_head"], logits) <= TOL
    assert sorted(routing) == [f"moe{i}" for i in range(4)]
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
    (loss, ((main, aux), (logits, routing))), grads = both["walked"]
    name, i = leaf
    if name == "loss":
        (r_main, r_aux), (r_logits, r_routing) = both["r_aux"]
        assert abs(float(loss) / float(both["r_loss"]) - 1) < TOL
        assert abs(float(main) / float(r_main) - 1) < TOL
        assert abs(float(aux) / float(r_aux) - 1) < TOL
        assert rel(logits, r_logits) < TOL
        for layer in r_routing:
            assert np.array_equal(routing[layer][1], r_routing[layer][1])
    else:
        assert rel(grads[name][i], both["r_grads"][name][i]) < TOL


# ------------------------------------------------------------ the builder
def _count(shapes):
    return {k: sum(int(np.prod(a.shape)) for a in v)
            for k, v in shapes.items()}


@pytest.mark.parametrize("kwargs,total", [
    ({"layers": 4, "experts_held": 32, "vocab": 18992}, 625_667_136),
    ({}, 79_674_391_296),
], ids=["the-benchmarks-cut", "published"])
def test_published_sizes_are_the_default(kwargs, total):
    """625.7 M parameters in the benchmark's cut (one period, 32 of 512
    experts a layer, 18,992 rows), 79.7 B in all 48 layers (the model's
    card says 80B; the MTP module is not built), counted without building
    them."""
    net = Network(models.qwen3_next(**kwargs), Phase.TRAIN)
    n = _count(jax.eval_shape(lambda k: net.init(k, None, None).params,
                              jax.random.key(0)))
    assert n["gdn0"] == n["gdn2"] == 33_718_464
    assert n["attn3"] == 27_263_488
    assert n["norm0a"] == n["norm_f"] == 2_048
    held = 32 if kwargs else 512
    assert n["moe0"] == 1_048_576 + held * 3_145_728 + 3_145_728 + 2_048
    assert sum(n.values()) == total
    mixers = [l.type for l in net.layers
              if l.type in ("GatedDeltaNet", "GatedAttention")]
    assert mixers[:4] == ["GatedDeltaNet"] * 3 + ["GatedAttention"]
    assert mixers.count("GatedAttention") * 4 == len(mixers)


def test_the_prototxt_round_trips_and_names_the_new_fields():
    net = models.qwen3_next(**TINY)
    text = serialize(net)
    assert serialize(parse(text)) == text
    assert text.count('type: "GatedDeltaNet"') == 3
    assert text.count('type: "GatedAttention"') == 1
    assert text.count("zero_centered: true") == 9
    assert text.count("shared_gate: true") == 4
    assert "rotary_dim: 4" in text and "num_kv_heads: 2" in text
    # the auxiliary loss rides the expert layers' second top
    assert text.count("loss_weight: 0.001") == 4


def test_the_norms_are_zero_centred(both):
    """w from zero, weight 1 + w: the blob of a fresh net is zeros, and
    the layer's output is the reference's with 1 + w."""
    fresh = make_solver()
    assert float(jnp.abs(fresh.variables.params["norm0a"][0]).max()) == 0.0
    assert float(jnp.abs(fresh.variables.params["attn3"][4]).max()) == 0.0
    assert float(fresh.variables.params["gdn0"][5].min()) == 1.0  # gated norm
    x, w = both["blobs"]["embed"], both["params"]["norm0a"][0]
    assert rel(both["blobs"]["norm0a"], ref.rms_norm(x, 1.0 + w, 1e-6)) <= TOL
    plain = Network(parse(serialize(models.qwen3_next(**TINY)).replace(
        "zero_centered: true", "zero_centered: false")), Phase.TRAIN)
    ones = plain.init(jax.random.key(0), None, None).params["norm0a"][0]
    assert float(ones.min()) == 1.0


def test_the_delta_layers_keep_their_vectors_in_f32_under_bf16():
    """dt_bias and A_log reach their layer in the parameter dtype when the
    compute dtype is bf16; the matrices do not."""
    seen = {}
    before = get_config().compute_dtype
    set_config(compute_dtype=jnp.bfloat16)
    try:
        net = Network(models.qwen3_next(**TINY), Phase.TRAIN)
        variables = net.init(jax.random.key(0), None, None)
        layer = net.layer_by_name("gdn0")
        inner = layer.apply

        def spy(params, *a, **k):
            seen["gdn0"] = [p.dtype for p in params]
            return inner(params, *a, **k)

        layer.apply = spy
        _, _, loss = net.apply(variables, batch_of(), rng=jax.random.key(1))
    finally:
        set_config(compute_dtype=before)
    assert loss.dtype == jnp.float32 and np.isfinite(float(loss))
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert seen["gdn0"] == [bf16] * 3 + [f32] * 2 + [bf16] * 2


def test_the_compiled_step_holds_no_state_a_token():
    """No f32 array of the compiled train step has the state's two axes
    (d_k = 8, d_v = 16 a head) and a whole sequence's states: S = 128 in
    chunks of 64 keeps [2, B, H_k, R, 8, 16]."""
    seq = 128
    solver = make_solver(batch=1, seq_len=seq)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    feeds = batch_of(seq_len=seq, batch=1)
    text = fn.lower(variables, slots, 0, feeds, key).compile().as_text()
    shapes = {tuple(int(x) for x in dims.split(","))
              for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    state_like = {s for s in shapes if s[-2:] == (8, 16)}
    assert (2, 1, 2, 2, 8, 16) in state_like  # the test sees them
    assert max(int(np.prod(s)) for s in state_like) < seq * 4 * 8 * 16


# ---------------------------------------------------------- the front door
def test_tpunet_train_trains_qwen3_next_from_prototxt_and_a_token_file(tmp_path):
    """``tpunet train --solver x.prototxt --data tokens:<file> --prefetch
    3`` on the serialized net: the new layer types and fields cross the
    prototxt."""
    import glob

    from sparknet_tpu import cli

    rng = np.random.default_rng(2)
    path = tmp_path / "tokens.bin"
    rng.integers(0, TINY["vocab"], 33 * 7 + 5).astype(np.uint16).tofile(path)
    (tmp_path / "net.prototxt").write_text(
        serialize(models.qwen3_next(**TINY)))
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


def test_the_fence_carries_the_new_counters():
    """After ``Solver.step``: the DeltaNet layers, how many of them took
    the Pallas kernels (none on the CPU, nor at these 8 x 16 heads
    anywhere), beside the attention and expert counters under their
    present names.  The tokens of a chunk and the bytes of the kept
    chunk-start states (f32 [chunks, B, H_v, d_k, d_v]) are the layers'
    own since PR 52, not the fence's."""
    solver = make_solver()
    solver.step(2, lambda it: batch_of(it))
    stats = solver._fence_stats()
    assert {k: stats[k] for k in stats if not k.startswith("moe_")} == {
        "gdn_layers": 3, "gdn_kernel_layers": 0,
        "attn_core_layers": 1, "attn_kernel_layers": 0}
    deltas = [l for l in solver.train_net.layers if l.type == "GatedDeltaNet"]
    assert [l.chunk for l in deltas] == [32, 32, 32]
    assert sum(l.saved_bytes for l in deltas) == 3 * 1 * 2 * 4 * 8 * 16 * 4
    assert stats["moe_layers"] == 4 and stats["moe_experts"] == 16
    assert stats["moe_pairs"] == 2 * 32 * 3
    assert 0 <= stats["moe_pairs_held"] <= 4 * stats["moe_pairs"]
    # a net without a DeltaNet layer keeps to the counters it had
    plain = Solver(models.olmoe_solver(), models.olmoe(
        batch=2, seq_len=32, vocab=97, hidden=64, heads=4, experts=8,
        top_k=2, expert_dim=32, layers=1))
    assert set(plain._fence_stats()) == {
        "moe_load_max", "moe_pairs", "moe_experts", "attn_core_layers",
        "attn_kernel_layers"}


def test_the_new_scope_is_in_the_cache_key_and_the_step():
    from sparknet_tpu import common

    assert la.DELTA_SCOPE == "D.delta" and "D.delta" in common.CACHE_SCOPES
    solver = make_solver(batch=1)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    hlo = fn.lower(variables, slots, 0, batch_of(batch=1),
                   key).compile().as_text()  # op_name holds the whole path
    for layer, scope in (("gdn0", "D.delta"), ("attn3", "A.core"),
                         ("moe3", "M.shared"), ("moe0", "M.route")):
        # forward under jvp(L.<layer>), backward under transpose(jvp(..))
        for side in (rf"/jvp\(L\.{layer}\)/", rf"transpose\(jvp\(L\.{layer}\)\)/"):
            assert re.search(side + re.escape(scope) + "/", hlo), (layer, scope)
    # the convolution and the gated norm lie outside the core's scope
    assert re.search(r"jvp\(L\.gdn0\)/jit\(silu\)", hlo)


def test_decode_spec_refuses_the_new_layers():
    """The cached decode step holds no matrix state beside keys and
    values: it says which layer it cannot replay."""
    net = Network(models.qwen3_next(**TINY), Phase.TEST)
    with pytest.raises(ValueError, match="has no cached decode twin"):
        models.zoo.decode_spec(net, end="lm_head")


# -- the benchmark's check of this model (benchmarks/harness/linear_check.py)
@pytest.fixture(scope="module")
def checked():
    """The check as the cell runs it, on one tiny sequence: the program's
    facts, and the reference computed entirely in bf16 (parameters,
    moments and update too) against the reference proper."""
    from benchmarks.harness import linear_check as chk

    solver = make_solver(batch=1)
    feeds = batch_of()
    ids, labels = feeds["data"][:1], feeds["label"][:1]
    config = dict(
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=8, linear_value_head_dim=16,
        num_experts_per_tok=3, first_expert=4, num_experts=4,
        num_hidden_layers=4, full_attention_interval=4,
        router_aux_loss_coef=0.001)
    runs = {
        name: jax.tree_util.tree_map(np.asarray, chk.run_reference(
            ref, solver.variables.params, jnp.asarray(ids),
            jnp.asarray(labels), chk.reference_config(config), solver.config,
            chk.leaves(config), dtype))
        for name, dtype in (("f32", None), ("bf16", jnp.bfloat16))}
    facts, problems = chk.check_step(
        solver, ref, config, ids, labels, chk.tolerances(rehearse=True),
        chk.forward_program(solver), runs["f32"])
    return dict(chk=chk, facts=facts, problems=problems, config=config,
                solver=solver, twin=chk.compare(runs["bf16"], runs["f32"]))


def test_the_check_passes_the_program_and_reads_every_limit(checked):
    assert checked["problems"] == []
    for name in checked["chk"].tolerances():
        assert np.isfinite(checked["facts"][name]), name
    # f32 on the CPU: the timed step's own numbers lie on the reference's
    for name in ("total_rel", "main_rel", "aux_rel", "logits_rel"):
        assert checked["facts"][name] < 1e-4, name
    assert checked["facts"]["topk_sets_differ"] == 0
    assert checked["facts"]["update_rel_all.decay"] < 1e-3
    assert checked["facts"]["held_pair_share"] > 0


def test_no_limit_is_read_over_fewer_than_64_entries_at_full_size():
    """The leaves at the published widths: the tenth of each has at least
    64 entries, and the decay's leaves are read over all 192."""
    import json
    import os

    from benchmarks.harness import linear_check as chk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "qwen3-next-80b-a3b-l4-ep16-v8-bf16.json")) as f:
        config = json.load(f)
    net = Network(models.qwen3_next(layers=4, experts_held=32, vocab=18992),
                  Phase.TRAIN)
    shapes = jax.eval_shape(lambda k: net.init(k, None, None).params,
                            jax.random.key(0))
    zeros = {k: [np.zeros(a.shape, np.int8) for a in v] if k in (
        "gdn0", "gdn1", "gdn2", "attn3", "moe3", "norm_f") else None
        for k, v in shapes.items()}
    sizes = {name: chk._leaf(zeros, spec, np).size
             for name, spec in chk.leaves(config).items()}
    assert sizes == {"qkvz_k": 2048 * 2048, "gdn_out": 2048 * 4096,
                     "attn_kv": 2 * 512 * 2048, "attn_gate": 4096 * 2048,
                     "router": 512 * 2048, "held_gate": 32 * 512 * 2048,
                     "shared_gate": 2048, "final_norm": 2048, "decay": 192}
    for name, n in sizes.items():
        assert (n if name in chk.WHOLE else n // 10) >= 64, name
    assert {k.split(".")[1] for k in chk.TOL if k.startswith("update")} == \
        set(sizes)


@pytest.mark.parametrize("leaf", ["decay", "gdn_out", "qkvz_k"])
def test_a_program_without_f32_master_weights_breaks_the_update_limit(
        checked, leaf):
    """The all-bf16 reading fails the FULL-SIZE limit of these leaves
    whatever the data: A_log (0.1 to 2.8) and dt_bias (-6.9 to -2.2)
    cannot move by 3e-4 in bf16, and a weight near 0.02 moves in steps of
    1.2e-4."""
    name = ("update_rel_all." if leaf in checked["chk"].WHOLE
            else "update_rel.") + leaf
    limit = checked["chk"].tolerances()[name]
    assert checked["twin"][name] > limit
    assert checked["facts"][name] < limit


def test_the_routing_hooks_read_the_layers_counters(checked):
    chk, solver = checked["chk"], checked["solver"]
    assert chk.settle_bias(solver, None, None, None) == [1.0]
    fullest, held = chk.routing_now(solver, checked["config"])
    assert fullest >= 1.0 and 0.0 <= held <= 100.0
