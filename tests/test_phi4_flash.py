"""Phi-4-mini-flash-reasoning through the front door, held to its plain
reference on the CPU.

Tiny preset (hidden 64, 4 query and 2 key/value heads of 16, MLP 96,
window 8, Mamba state 4 over d_inner 128, dt_rank 4, vocab 97, S 32; 12
published layers of which {0, 1, 6, 7, 8, 9, 10, 11} are kept: one
self-decoder period, the memory's Mamba layer, the full attention layer,
and TWO cross-decoder periods that read the memory and the keys and
values), float32: the program (`models.phi4_flash` through
`compiler/graph.py`, `Solver.step`, the `tokens:` feed) against
`benchmarks/reference/phi4_flash.py` on seeded weights, with every vector
(norms, biases, lambdas, D) moved off its initial value.  At f32 on one
backend the two differ only by summation order, so the limit is 1e-5
(rel-L2 for arrays, relative for scalars).  A dropped lambda term, another
head pairing, a window off by one, a memory taken after the gate or a
scan that starts a chunk from the wrong state move these by 1e-2 or more.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4_flash as ref
from sparknet_tpu import models
from sparknet_tpu.common import Phase, get_config, set_config, step_key
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.ops import ssm
from sparknet_tpu.ops.attention import attention_core
from sparknet_tpu.ops.blocks import rms_norm
from sparknet_tpu.ops.pallas_kernels import attention_xla
from sparknet_tpu.proto.text_format import parse, serialize
from sparknet_tpu.solvers.solver import Solver

KEPT = (0, 1, 6, 7, 8, 9, 10, 11)
ROLES = ("mamba", "window", "memory", "full", "gmu", "cross", "gmu", "cross")
TINY = dict(batch=2, seq_len=32, vocab=97, hidden=64, heads=4, kv_heads=2,
            mlp_dim=96, layers=12, window=8, d_state=4, kept_layers=KEPT)
CFG = dict(heads=4, kv_heads=2, eps=1e-5, window=8, layers=12,
           mb_per_layer=2, kept=KEPT)
TOL = 1e-5
# a lambda vector's gradient is ONE scalar (d loss / d lambda, a sum over
# every pair, position and feature in which the terms cancel) times the
# other vector: f32 summation order shows at 1e-5 to 2e-5, a dropped or
# misplaced term at 1
TOL_LAMBDA = 1e-4
MIXERS = {"mamba": ("mamba{}", 9), "memory": ("mamba{}", 9),
          "window": ("attn{}", 7), "full": ("attn{}", 7),
          "gmu": ("gmu{}", 2), "cross": ("xattn{}", 7)}
LEAVES = [("embed", 0), ("norm_f", 0), ("norm_f", 1)] + [
    (name.format(i), b) for i, kind in zip(KEPT, ROLES)
    for name, n in (("norm{}a", 2), MIXERS[kind], ("norm{}b", 2),
                    ("mlp{}", 3))
    for b in range(n)]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def make_solver(seed=3, **over):
    cfg = dataclasses.replace(models.phi4_flash_solver(), random_seed=seed)
    return Solver(cfg, models.phi4_flash(**{**TINY, **over}))


def batch_of(seed=0, seq_len=TINY["seq_len"]):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab"], (TINY["batch"], seq_len + 1))
    return {"data": ids[:, :-1].astype(np.int32),
            "label": ids[:, 1:].astype(np.int32)}


def shake_vectors(solver, seed=5):
    """Ones and zeros would hide a swapped norm, a dropped bias or D."""
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
        (r_loss, r_logits), r_grads = jax.value_and_grad(
            ref.loss, has_aux=True)(params, feeds["data"], feeds["label"], CFG)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    stepped, _, _ = fn(variables, slots, 0, feeds, key)
    return dict(solver=solver, params=params, blobs=blobs, p_loss=p_loss,
                p_grads=p_grads, r_loss=r_loss, r_logits=r_logits,
                r_grads=r_grads, stepped=stepped, feeds=feeds)


def test_loss_matches_reference(both):
    got, want = float(both["p_loss"]), float(both["r_loss"])
    assert abs(got - want) <= TOL * abs(want)
    assert float(both["blobs"]["loss"]) == pytest.approx(got, rel=1e-6)
    assert 4.0 < want < 5.5  # ~ln(97) at initialisation


def test_logits_match_reference(both):
    assert both["blobs"]["lm_head"].shape == both["r_logits"].shape
    assert rel(both["blobs"]["lm_head"], both["r_logits"]) <= TOL


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_gradient_matches_reference(both, leaf):
    layer, i = leaf
    want = both["r_grads"][layer][i]
    assert float(jnp.linalg.norm(want)) > 0
    lam = "attn" in layer and 2 <= i <= 5
    assert rel(both["p_grads"][layer][i], want) <= (TOL_LAMBDA if lam else TOL)


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_adamw_step_matches_reference(both, leaf):
    """One ``Solver`` step (clip at global norm 1, AdamW, decoupled decay)
    against the reference's gradients through the reference's rule,
    compared as the CHANGE of the leaf on the entries whose gradient is
    clear of f32 noise (the first Adam step is ~lr * sign(g)).  The limit
    is what f32 holds of a change of 4e-4 in a weight of 0.5 to 2.8 (W_dt,
    A_log, the norms: half an ulp of 3e-8 to 1.2e-7 an entry)."""
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


# ------------------------------------------------------------- the scan
def scan_inputs(seed=0, batch=2, seq=32, d=24, n=4):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(batch, seq, d), f(batch, seq, d) - 1.0, f(batch, seq, n),
            f(batch, seq, n), jnp.asarray(rng.uniform(-1, 1.5, (d, n)),
                                          jnp.float32), f(d))


@pytest.mark.parametrize("chunk", [8, 5, 12, 32, 64],
                         ids=lambda c: f"chunk{c}")
def test_chunked_scan_matches_the_time_step_scan(chunk):
    """Forward and every gradient, for chunk lengths that divide S = 32,
    that do not (5, 12: the last chunk is padded), S itself and more."""
    args = scan_inputs()
    weight = jnp.asarray(np.random.default_rng(1).standard_normal(
        args[0].shape), jnp.float32)
    loss = lambda scan: lambda *a: jnp.sum(scan(*a) * weight)
    chunked = lambda *a: ssm.selective_scan(*a, chunk=chunk)
    assert rel(chunked(*args), ssm.selective_scan_steps(*args)) <= TOL
    got = jax.grad(loss(chunked), argnums=tuple(range(6)))(*args)
    want = jax.grad(loss(ssm.selective_scan_steps),
                    argnums=tuple(range(6)))(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel(g, w) <= TOL


def test_the_scan_matches_the_reference_recurrence():
    """``selective_scan`` on one sequence is the reference's Mamba
    recurrence with the projections taken out."""
    c, dt, b, cm, a_log, d = scan_inputs(batch=1)
    a = -np.exp(np.asarray(a_log, np.float64))
    delta = np.log1p(np.exp(np.asarray(dt[0], np.float64)))
    h, ys = np.zeros(a.shape), []
    for t in range(c.shape[1]):
        h = (np.exp(delta[t][:, None] * a) * h
             + (delta[t] * np.asarray(c[0, t]))[:, None] * np.asarray(b[0, t]))
        ys.append(h @ np.asarray(cm[0, t]) + np.asarray(d) * np.asarray(c[0, t]))
    assert rel(ssm.selective_scan(c, dt, b, cm, a_log, d)[0], np.stack(ys)) <= TOL


def test_the_compiled_step_holds_no_whole_sequence_of_states():
    """No array of the compiled train step has the state's two axes
    (N = 6, d_inner = 128) and a whole sequence's elements: S = 128 in
    chunks of 64 keeps [2, B, 6, 128] and walks [64, B, 6, 128]."""
    seq, n, d_inner = 128, 6, 128
    solver = make_solver(batch=1, seq_len=seq, d_state=n, kept_layers=(0, 6))
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    feeds = {k: v[:1] for k, v in batch_of(seq_len=seq).items()}
    text = fn.lower(variables, slots, 0, feeds, key).compile().as_text()
    shapes = {tuple(int(x) for x in dims.split(","))
              for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    state_like = {s for s in shapes if n in s and d_inner in s}
    assert (ssm.CHUNK, 1, n, d_inner) in state_like  # the test sees them
    assert max(int(np.prod(s)) for s in state_like) < seq * n * d_inner


def test_the_scan_keeps_its_vectors_in_f32_under_bf16():
    """b_dt, A_log, D and the lambdas reach their layer in the parameter
    dtype when the compute dtype is bf16; the matrices do not."""
    seen = {}
    before = get_config().compute_dtype
    set_config(compute_dtype=jnp.bfloat16)
    try:
        net = Network(models.phi4_flash(**TINY), Phase.TRAIN)
        variables = net.init(jax.random.key(0), None, None)
        for name in ("mamba0", "attn1"):
            layer = net.layer_by_name(name)
            inner = layer.apply

            def spy(params, *a, _inner=inner, _name=name, **k):
                seen[_name] = [p.dtype for p in params]
                return _inner(params, *a, **k)

            layer.apply = spy
        _, _, loss = net.apply(variables, batch_of(), rng=jax.random.key(1))
    finally:
        set_config(compute_dtype=before)
    assert loss.dtype == jnp.float32 and np.isfinite(float(loss))
    f32, bf16 = jnp.float32, jnp.bfloat16
    assert seen["mamba0"] == [bf16] * 5 + [f32] * 3 + [bf16]
    assert seen["attn1"] == [bf16] * 2 + [f32] * 4 + [bf16]


# -------------------------------------------------------- the attention
def qkv(seed=0, b=2, h=4, hk=2, hv=1, s=16, d=8, dv=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    return f(b, h, s, d), f(b, hk, s, d), f(b, hv, s, dv)


@pytest.mark.parametrize("window", [16, 17, 1000])
def test_a_window_no_shorter_than_the_sequence_is_causal(window):
    q, k, v = qkv()
    assert rel(attention_core(q, k, v, True, window),
               attention_core(q, k, v, True)) <= 1e-6


@pytest.mark.parametrize("window", [1, 3, 8])
def test_a_window_sees_exactly_its_keys(window):
    """Query t attends to keys t - window + 1 .. t: against a softmax over
    just those."""
    q, k, v = qkv(h=2, hk=2, hv=2)
    got = np.asarray(attention_core(q, k, v, True, window))
    for t in (0, 5, 15):
        lo = max(0, t - window + 1)
        s = np.einsum("bhd,bhkd->bhk", q[:, :, t], k[:, :, lo:t + 1]) / 8 ** 0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("bhk,bhkd->bhd", p / p.sum(-1, keepdims=True),
                         v[:, :, lo:t + 1])
        assert rel(got[:, :, t], want) <= 1e-5


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 5), (False, 0)])
def test_grouped_heads_are_repeated_heads(causal, window):
    q, k, v = qkv()
    want = attention_core(q, jnp.repeat(k, 2, 1), jnp.repeat(v, 4, 1),
                          causal, window)
    assert rel(attention_core(q, k, v, causal, window), want) <= 1e-6
    plain = attention_xla(q, jnp.repeat(k, 2, 1),
                          jnp.repeat(v[..., :8], 4, 1), causal)
    if not window:
        assert rel(want[..., :8], plain) <= 1e-6


def test_head_counts_that_do_not_nest_are_refused():
    q, k, v = qkv(h=4, hk=3, hv=1)
    with pytest.raises(ValueError, match="must divide"):
        attention_core(q, k, v, True)
    with pytest.raises(ValueError, match="window is causal"):
        attention_core(*qkv(), False, 4)


def test_lambda_zero_with_equal_halves_is_ordinary_attention():
    """With lambda = 0 (zero lambda vectors, lambda_init 0) and the two
    halves of every pair equal, the layer is ordinary causal attention of
    H / 2 heads over the doubled values, RMS-normalised."""
    from sparknet_tpu.layers_dsl import (DifferentialAttentionLayer,
                                         NetParam, RDDLayer)

    B, S, E, H, Hk, D = 2, 16, 32, 4, 2, 8
    net = Network(NetParam(
        "t", RDDLayer("x", shape=[B, S, E]),
        DifferentialAttentionLayer("a", ["x"], H, Hk, lambda_init=0.0)),
        Phase.TRAIN)
    v = net.init(jax.random.key(0), None, None)
    rng = np.random.default_rng(0)
    w = np.array(v.params["a"][0]).reshape(H + 2 * Hk, D, E)
    w[1], w[3], w[5] = w[0], w[2], w[4]  # q2_j = q1_j, k2 = k1
    w_o = v.params["a"][1]
    subln = jnp.asarray(1 + 0.1 * rng.standard_normal(2 * D), jnp.float32)
    v.params["a"] = [jnp.asarray(w.reshape(-1, E)), w_o,
                     *(jnp.zeros((D,), jnp.float32),) * 4, subln]
    x = jnp.asarray(rng.standard_normal((B, S, E)), jnp.float32)
    got = net.apply(v, {"x": x})[0]["a"]
    proj = (x @ w.reshape(-1, E).T).reshape(B, S, H + 2 * Hk, D)
    q = proj[:, :, [0, 2]].transpose(0, 2, 1, 3)      # the two pairs' q1
    k = jnp.repeat(proj[:, :, 4:5].transpose(0, 2, 1, 3), 2, 1)  # k1_0
    vals = jnp.repeat(proj[:, :, 6:].reshape(B, S, 1, 2 * D)
                      .transpose(0, 2, 1, 3), 2, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / D ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vals)
    want = rms_norm(a, subln, 1e-5).transpose(0, 2, 1, 3).reshape(B, S, E)
    assert rel(got, want @ w_o.T) <= 1e-5


# ------------------------------------------------- blobs with far readers
def test_the_tied_blobs_gradient_is_the_embeddings_plus_the_heads(both):
    """``embed`` and ``lm_head`` share one array (``param { name }``): a
    0-size placeholder at the alias, and at the owner the sum of what the
    same net gives its embedding and its head when they are two arrays."""
    assert both["params"]["lm_head"][0].size == 0
    text = serialize(models.phi4_flash(**TINY))
    assert text.count('name: "embed_w"') == 2
    untied = Network(parse(text.replace('name: "embed_w"', 'name: ""')),
                     Phase.TRAIN)
    params = dict(both["params"])
    params["lm_head"] = [params["embed"][0]]
    variables = dataclasses.replace(both["solver"].variables, params=params)

    def loss(p):
        return untied.apply(dataclasses.replace(variables, params=p),
                            both["feeds"], rng=jax.random.key(0))[2]

    g = jax.grad(loss)(params)
    assert rel(g["embed"][0] + g["lm_head"][0],
               both["p_grads"]["embed"][0]) <= TOL
    assert rel(g["lm_head"][0], both["p_grads"]["embed"][0]) > 1e-2


def _split_readers(text: str, second_reader: str, blobs) -> str:
    """The prototxt with the bottoms ``blobs`` of layer ``second_reader``
    renamed ``<blob>_b``."""
    head, sep, tail = text.partition(f'name: "{second_reader}"')
    assert sep
    body, brace, rest = tail.partition("attention_param" if "xattn" in
                                       second_reader else "gmu_param")
    for b in blobs:
        assert f'bottom: "{b}"' in body
        body = body.replace(f'bottom: "{b}"', f'bottom: "{b}_b"')
    return head + sep + body + brace + rest


@pytest.mark.parametrize("blobs,second", [(("memory",), "gmu10"),
                                          (("yoco_k", "yoco_v"), "xattn11")])
def test_a_far_blobs_gradient_is_the_sum_over_its_readers(both, blobs, second):
    """The memory (read by gmu8 and gmu10) and the kept keys and values
    (read by xattn9 and xattn11): the cross-decoder run from the blobs it
    is fed, once as built and once with the second reader on a copy; the
    gradient of the shared blob is the sum of the two copies'."""
    text = serialize(models.phi4_flash(**TINY))
    shared = Network(parse(text), Phase.TRAIN)
    split = Network(parse(_split_readers(text, second, blobs)), Phase.TRAIN)
    variables = dataclasses.replace(both["solver"].variables,
                                    params=both["params"])
    fed = {k: both["blobs"][k] for k in ("res7b", "memory", "yoco_k",
                                         "yoco_v")}
    fed["label"] = jnp.asarray(both["feeds"]["label"])

    def run(net, extra):
        return net.apply(variables, {**fed, **extra}, start="norm8a",
                         rng=jax.random.key(0))[2]

    together = jax.grad(lambda x: run(shared, dict(zip(blobs, x))))(
        [fed[b] for b in blobs])
    first, copy = jax.grad(
        lambda x, y: run(split, {**dict(zip(blobs, x)),
                                 **{b + "_b": v for b, v in zip(blobs, y)}}),
        argnums=(0, 1))([fed[b] for b in blobs], [fed[b] for b in blobs])
    for t, a, b in zip(together, first, copy):
        assert float(jnp.linalg.norm(a)) > 0 and float(jnp.linalg.norm(b)) > 0
        assert rel(a + b, t) <= TOL


def test_the_memory_is_the_scan_output_before_the_gate(both):
    """``memory`` is y of the memory layer, not y * silu(z): the layer's
    own output is W_out of the gated product."""
    p = both["params"]["mamba6"]
    x = both["blobs"]["norm6a"]
    z = (x @ p[0].T)[..., 128:]
    want = (both["blobs"]["memory"] * jax.nn.silu(z)) @ p[8].T
    assert both["blobs"]["memory"].shape == (2, 32, 128)
    assert rel(both["blobs"]["mamba6"], want) <= TOL


# ------------------------------------------------------------ the builder
@pytest.mark.parametrize("i,kind", list(zip(KEPT, ROLES)))
def test_kept_layers_keep_their_published_role_and_lambda(i, kind):
    net = models.phi4_flash(**TINY)
    assert models.phi4_flash_role(i, 12) == kind == ref.role(i, 12)
    name = MIXERS[kind][0].format(i)
    layer = next(l for l in net.get_all("layer") if l.get_str("name") == name)
    tops = [str(t) for t in layer.get_all("top")]
    bottoms = [str(b) for b in layer.get_all("bottom")]
    assert tops == {"memory": [name, "memory"],
                    "full": [name, "yoco_k", "yoco_v"]}.get(kind, [name])
    assert bottoms == {"gmu": [f"norm{i}a", "memory"],
                       "cross": [f"norm{i}a", "yoco_k", "yoco_v"]}.get(
        kind, [f"norm{i}a"])
    if kind in ("window", "full", "cross"):
        p = layer.get_msg("attention_param")
        assert p.get_float("lambda_init") == pytest.approx(
            0.8 - 0.6 * np.exp(-0.3 * i), abs=1e-12)
        assert p.get_float("lambda_init") == ref.lambda_init(i)
        assert p.get_int("window", 0) == (8 if kind == "window" else 0)


@pytest.mark.parametrize("i,kind", [(0, "mamba"), (15, "window"),
                                    (16, "memory"), (17, "full"),
                                    (18, "gmu"), (31, "cross")])
def test_the_published_32_layers_have_their_roles(i, kind):
    assert models.phi4_flash_role(i, 32) == kind == ref.role(i, 32)


def test_a_reader_without_its_producer_is_refused():
    with pytest.raises(ValueError, match="without layer 6"):
        models.phi4_flash(**{**TINY, "kept_layers": (0, 1, 7, 8, 9)})
    with pytest.raises(ValueError, match="without layer 7"):
        models.phi4_flash(**{**TINY, "kept_layers": (0, 1, 6, 8, 9)})
    with pytest.raises(ValueError, match="must ascend"):
        models.phi4_flash(**{**TINY, "kept_layers": (1, 0)})
    with pytest.raises(ValueError, match="multiple of mb_per_layer"):
        models.phi4_flash_role(0, 10)


def _count(shapes):
    return {k: sum(int(np.prod(a.shape)) for a in v)
            for k, v in shapes.items()}


@pytest.mark.parametrize("kwargs,total", [
    ({}, 3_852_457_984),
    ({"vocab": 25008, "kept_layers": (0, 1, 16, 17, 18, 19)}, 697_073_792),
], ids=["published", "the-benchmarks-cut"])
def test_published_sizes_are_the_default(kwargs, total):
    """3.85 B parameters in all 32 layers, 697.1 M in the benchmark's cut
    (six layers, 25,008 rows), counted without building them."""
    net = Network(models.phi4_flash(**kwargs), Phase.TRAIN)
    n = _count(jax.eval_shape(lambda k: net.init(k, None, None).params,
                              jax.random.key(0)))
    assert n["mamba0"] == 41_241_600 and n["mlp0"] == 78_643_200
    assert n["attn1"] == n["attn17"] == 19_661_184
    assert n["gmu18"] == 26_214_400 and n["xattn19"] == 13_107_584
    assert n["norm0a"] == n["norm_f"] == 5_120 and n["lm_head"] == 0
    assert sum(n.values()) == total


# ---------------------------------------------------------- the front door
def test_tpunet_train_trains_phi4_flash_from_prototxt_and_a_token_file(tmp_path):
    """``tpunet train --solver x.prototxt --data tokens:<file> --prefetch
    3`` on the serialized net: the tied blob, the memory and the kept keys
    and values all cross the prototxt."""
    import glob

    from sparknet_tpu import cli

    rng = np.random.default_rng(2)
    path = tmp_path / "tokens.bin"
    rng.integers(0, TINY["vocab"], 33 * 7 + 5).astype(np.uint16).tofile(path)
    (tmp_path / "net.prototxt").write_text(
        serialize(models.phi4_flash(**TINY)))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path}/net.prototxt"\ntype: "AdamW"\nbase_lr: 0.0004\n'
        'lr_policy: "fixed"\nmomentum: 0.9\nmomentum2: 0.95\ndelta: 1e-8\n'
        'weight_decay: 0.1\nclip_gradients: 1.0\nmax_iter: 4\ndisplay: 0\n')
    out = str(tmp_path / "final")
    rc = cli.main(["train", "--solver", str(tmp_path / "solver.prototxt"),
                   "--data", f"tokens:{path}", "--prefetch", "3",
                   "--iterations", "3", "--seed", "7", "--output", out])
    assert rc == 0
    assert glob.glob(out + "*")


def test_the_prototxt_round_trips():
    net = models.phi4_flash(**TINY)
    assert serialize(parse(serialize(net))) == serialize(net)


def test_training_lowers_the_loss():
    solver = make_solver()
    feeds = batch_of()
    first = solver.step(1, lambda it: feeds)
    last = solver.step(12, lambda it: feeds)
    assert np.isfinite(last) and last < first - 0.5


def test_the_fence_carries_the_new_counters():
    """After ``Solver.step``: the scan layers and how many of them run
    the kernels (none on the CPU).  The steps between two kept states and
    the bytes of those states (f32 [chunks, B, N, d_inner]) are the
    layers' own since PR 52, not the fence's."""
    solver = make_solver()
    solver.step(2, lambda it: batch_of(it))
    stats = solver._fence_stats()
    assert stats == {"ssm_layers": 2, "ssm_kernel_layers": 0,
                     "attn_core_layers": 4, "attn_kernel_layers": 0}
    scans = [l for l in solver.train_net.layers if l.type == "Mamba"]
    assert [l.chunk for l in scans] == [32, 32]
    assert sum(l.saved_bytes for l in scans) == 2 * 1 * 2 * 4 * 128 * 4
    assert ssm.chunking(2048) == (64, 32)
    assert ssm.saved_state_bytes(1, 2048, 5120, 16) == 32 * 16 * 5120 * 4
    # a net without a scan layer keeps to the counters it had
    plain = Solver(models.olmoe_solver(), models.olmoe(
        batch=2, seq_len=32, vocab=97, hidden=64, heads=4, experts=8,
        top_k=2, expert_dim=32, layers=1))
    assert set(plain._fence_stats()) == {
        "moe_load_max", "moe_pairs", "moe_experts", "attn_core_layers",
        "attn_kernel_layers"}


def test_decode_spec_refuses_the_new_layers():
    """The cached decode step has no recurrent state, no windowed block
    pool and no kept keys and values: it says which layer it cannot
    replay."""
    net = Network(models.phi4_flash(**TINY), Phase.TEST)
    with pytest.raises(ValueError, match=r"\(LayerNorm\) has no cached "
                                         "decode twin"):
        models.zoo.decode_spec(net, end="lm_head")
