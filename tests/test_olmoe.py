"""OLMoE through the front door, held to its plain reference on the CPU.

Tiny preset (hidden 64, 4 heads, 8 experts top-2, expert width 32, vocab
97, S 32, 2 layers), float32: the program (`models.olmoe` through
`compiler/graph.py`, `Solver.step`, the `tokens:` feed, `ParallelTrainer`)
against `benchmarks/reference/olmoe.py` on seeded weights.  At f32 on one
backend the two differ only by summation order, so the limit is 1e-5
everywhere (rel-L2 for arrays, relative for scalars): measured 1e-7 to
3e-6.  A renormalised top-k, a dropped z-loss, L2-style decay or a wrong
RoPE pairing moves these by 1e-2 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmoe as ref
from sparknet_tpu import models
from sparknet_tpu.common import step_key
from sparknet_tpu.ops import moe
from sparknet_tpu.proto.text_format import parse
from sparknet_tpu.solvers.solver import Solver

TINY = dict(batch=2, seq_len=32, vocab=97, hidden=64, heads=4, experts=8,
            top_k=2, expert_dim=32, layers=2)
CFG = dict(heads=4, top_k=2, layers=2, eps=1e-5, theta=10000.0,
           lb_weight=0.01, z_weight=0.001)
TOL = 1e-5
LEAVES = [("embed", 0), ("norm_f", 0), ("lm_head", 0)] + [
    (f"{kind}{i}{sfx}", b)
    for i in (1, 2)
    for kind, sfx, n in (("norm", "a", 1), ("attn", "", 4), ("norm", "b", 1),
                         ("moe", "", 4))
    for b in range(n)]


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def make_solver(seed=3, **over):
    cfg = dataclasses.replace(models.olmoe_solver(), random_seed=seed, **over)
    return Solver(cfg, models.olmoe(**TINY))


def batch_of(seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, TINY["vocab"], (TINY["batch"], TINY["seq_len"] + 1))
    return {"data": ids[:, :-1].astype(np.int32),
            "label": ids[:, 1:].astype(np.int32)}


@pytest.fixture(scope="module")
def both():
    """One forward/backward of the program and of the reference on the
    same weights and batch, and one AdamW step of each."""
    solver = make_solver()
    # ones for every RMSNorm weight would hide a swapped q/k norm
    rng = np.random.default_rng(5)
    for name, blobs in solver.variables.params.items():
        for i, w in enumerate(blobs):
            if w.ndim == 1:
                blobs[i] = jnp.asarray(
                    1.0 + 0.1 * rng.standard_normal(w.shape), jnp.float32)
    feeds = batch_of()
    net = solver.train_net
    params = jax.tree_util.tree_map(jnp.array, solver.variables.params)

    def prog_loss(p):
        v = dataclasses.replace(solver.variables, params=p)
        blobs, _, loss = net.apply(v, feeds, rng=step_key(solver._key, 0))
        return loss, blobs

    (p_loss, blobs), p_grads = jax.value_and_grad(prog_loss, has_aux=True)(
        params)
    with jax.default_matmul_precision("highest"):
        (r_loss, ((ce, lb, z), (r_logits, _, r_chosen))), r_grads = \
            jax.value_and_grad(ref.loss, has_aux=True)(
                params, feeds["data"], feeds["label"], CFG)
    fn, variables, slots, key = solver.jitted_train_step(donate=False)
    stepped, _, _ = fn(variables, slots, 0, feeds, key)
    return dict(solver=solver, params=params, blobs=blobs, p_loss=p_loss,
                p_grads=p_grads, r_loss=r_loss, terms=(ce, lb, z),
                r_logits=r_logits, r_chosen=r_chosen, r_grads=r_grads,
                stepped=stepped.params)


@pytest.mark.parametrize("term", ["total", "cross_entropy", "load_balancing",
                                  "z_loss"])
def test_loss_terms_match_reference(both, term):
    ce, lb, z = both["terms"]
    b = both["blobs"]
    got, want = {
        "total": (both["p_loss"], both["r_loss"]),
        "cross_entropy": (b["loss"], ce),
        "load_balancing": ((b["lb1"] + b["lb2"]) / 2, lb),
        "z_loss": ((b["z1"] + b["z2"]) / 2, z),
    }[term]
    assert abs(float(got) - float(want)) <= TOL * abs(float(want))


def test_logits_match_reference(both):
    assert rel(both["blobs"]["lm_head"], both["r_logits"]) <= TOL


@pytest.mark.parametrize("layer", [1, 2])
def test_top_k_sets_and_load_match_reference(both, layer):
    """The program's tokens per expert are the reference's chosen sets
    counted; an identical multiset per expert with identical logits is an
    identical top-k set per token (a swap between two tokens would move
    the logits)."""
    chosen = np.asarray(both["r_chosen"][layer - 1])
    want = np.bincount(chosen.reshape(-1), minlength=TINY["experts"])
    np.testing.assert_array_equal(
        np.asarray(both["blobs"][f"load{layer}"]), want)
    # and token by token, through the layer's own routing on its input
    x = both["blobs"][f"norm{layer}b"].reshape(-1, TINY["hidden"])
    _, _, _, experts = moe.route(both["params"][f"moe{layer}"][0], x, 2)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), axis=-1),
                                  np.sort(chosen, axis=-1))


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_gradient_matches_reference(both, leaf):
    layer, i = leaf
    assert rel(both["p_grads"][layer][i], both["r_grads"][layer][i]) <= TOL


@pytest.mark.parametrize("leaf", LEAVES, ids=lambda l: f"{l[0]}.{l[1]}")
def test_adamw_step_matches_reference(both, leaf):
    """One ``Solver`` step (clip at global norm 1, AdamW, decoupled
    decay 0.1) against the reference's gradients through the reference's
    rule.  The first Adam step is ~lr·sign(g): compared as the CHANGE of
    the leaf, with eps inside, so a gradient entry near zero counts with
    its true size."""
    layer, i = leaf
    c = both["solver"].config
    scale = ref.clip_scale(both["r_grads"], c.clip_gradients)
    w0 = both["params"][layer][i]
    w1, _, _ = ref.adamw_step(
        w0, both["r_grads"][layer][i] * scale, 0.0, 0.0, 1, lr=c.base_lr,
        beta1=c.momentum, beta2=c.momentum2, eps=c.delta,
        weight_decay=c.weight_decay)
    got = np.asarray(both["stepped"][layer][i]) - np.asarray(w0)
    # entries whose gradient is within f32 noise of zero flip sign freely;
    # an exact zero (an embedding row no token used) is decay alone
    g = np.abs(np.asarray(both["r_grads"][layer][i] * scale))
    sure = (g > 1e-4 * g.max()) | (g == 0)
    assert sure.mean() > 0.9
    assert rel(got[sure], (np.asarray(w1) - np.asarray(w0))[sure]) <= 1e-4


def test_adamw_decay_is_decoupled():
    """A zero gradient leaves AdamW's moments at zero and the weight
    shrunk by lr·wd·w exactly; Caffe's Adam with the decay in the gradient
    would move it by ~lr·sign(w)."""
    from sparknet_tpu.ops.base import ParamSpec
    from sparknet_tpu.solvers.updates import apply_update, init_slots

    cfg = dataclasses.replace(models.olmoe_solver(), clip_gradients=-1.0)
    w = {"l": [jnp.asarray([[1.0, -2.0], [0.5, 4.0]], jnp.float32)]}
    g = jax.tree_util.tree_map(jnp.zeros_like, w)
    new, slots = apply_update(cfg, w, g, init_slots("AdamW", w),
                              {"l": [ParamSpec()]}, jnp.float32(cfg.base_lr), 0)
    np.testing.assert_allclose(
        np.asarray(new["l"][0]),
        np.asarray(w["l"][0]) * (1 - cfg.base_lr * cfg.weight_decay),
        rtol=1e-7)
    assert all(float(jnp.abs(s).max()) == 0.0 for s in slots["l"][0])


# ---------------------------------------------------------------- dispatch
def _swiglu_params(rng, e, d, h):
    return [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
            for s in ((e, d), (e, h, d), (e, h, d), (e, d, h))]


@pytest.mark.parametrize("case", ["an_expert_with_no_token",
                                  "every_token_on_one_expert",
                                  "pairs_not_a_multiple_of_any_tile"])
def test_dispatch_edge_cases_match_reference(case):
    rng = np.random.default_rng(11)
    e, d, h, k, t = 8, 16, 24, 2, 48
    params = _swiglu_params(rng, e, d, h)
    if case == "an_expert_with_no_token":
        params[0] = params[0].at[3].set(-50.0 * jnp.ones(d))  # never chosen
        x = jnp.abs(jnp.asarray(rng.standard_normal((t, d)), jnp.float32))
    elif case == "every_token_on_one_expert":
        k = 1
        params[0] = params[0].at[5].set(50.0 * jnp.ones(d))
        x = jnp.abs(jnp.asarray(rng.standard_normal((t, d)), jnp.float32))
    else:
        t, k = 37, 3  # 111 pairs
        x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    cfg = dict(top_k=k)

    def prog(p, x):
        return moe.moe_dropless(p, x, top_k=k, expert_act="swiglu")

    y, _, _, _, load = prog(params, x)
    y_ref, _, chosen = ref.moe(params, x, cfg)
    assert rel(y, y_ref) <= TOL
    want = np.bincount(np.asarray(chosen).reshape(-1), minlength=e)
    np.testing.assert_array_equal(np.asarray(load), want)
    if case == "an_expert_with_no_token":
        assert want[3] == 0
    if case == "every_token_on_one_expert":
        assert want[5] == t
    g = jax.grad(lambda p, x: jnp.sum(prog(p, x)[0] ** 2), (0, 1))(params, x)
    g_ref = jax.grad(lambda p, x: jnp.sum(ref.moe(p, x, cfg)[0] ** 2),
                     (0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        assert rel(a, b) <= TOL


# ------------------------------------------------------- defaults unchanged
def test_moe_default_is_the_top1_relu_switch_layer(rng):
    """Without the new options the layer is what it was: five blobs,
    top-1, ReLU experts with biases, equal to the ``moe_dense`` oracle."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network

    net = Network(parse(
        'layer { name: "x" type: "Input" top: "x" '
        'input_param { shape { dim: 2 dim: 12 dim: 16 } } } '
        'layer { name: "m" type: "MoE" bottom: "x" top: "y" '
        'moe_param { num_experts: 4 hidden_dim: 24 } }'), Phase.TRAIN)
    v = net.init(jax.random.key(0))
    params = [p + 0.1 * jnp.asarray(rng.randn(*p.shape), jnp.float32)
              for p in v.params["m"]]  # biases off zero
    assert [p.shape for p in params] == [
        (4, 16), (4, 24, 16), (4, 24), (4, 16, 24), (4, 16)]
    x = jnp.asarray(rng.randn(2, 12, 16), jnp.float32)
    v = dataclasses.replace(v, params={"m": params})
    y = net.apply(v, {"x": x})[0]["y"]
    want = moe.moe_dense(params, x.reshape(-1, 16)).reshape(x.shape)
    assert rel(y, want) <= TOL


def test_attention_without_the_new_options_is_the_layer_before(rng):
    """The layer's arithmetic before PR 26's options, written out: fused
    biased QKV, head split, rope at base 10000, the XLA attention core,
    biased output projection.  Equal to f32 rounding since PR 44 (bit
    for bit until then): the layer's projections write q, k, v head-major
    and read o so, the same products summed in the matmul's own order."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.ops.attention import rope
    from sparknet_tpu.ops.pallas_kernels import attention_xla

    net = Network(parse(
        'layer { name: "x" type: "Input" top: "x" '
        'input_param { shape { dim: 2 dim: 16 dim: 32 } } } '
        'layer { name: "a" type: "MultiHeadAttention" bottom: "x" top: "y" '
        'attention_param { num_heads: 4 causal: true rope: true } }'),
        Phase.TRAIN)
    v = net.init(jax.random.key(1))
    w_qkv, b_qkv, w_out, b_out = v.params["a"]
    assert len(v.params["a"]) == 4
    b_qkv = b_qkv + jnp.asarray(rng.randn(*b_qkv.shape), jnp.float32)
    b_out = b_out + jnp.asarray(rng.randn(*b_out.shape), jnp.float32)
    v = dataclasses.replace(v, params={"a": [w_qkv, b_qkv, w_out, b_out]})
    x = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    qkv = jnp.einsum("bse,fe->bsf", x, w_qkv) + b_qkv
    q, k, vv = (t.reshape(2, 16, 4, 8).transpose(0, 2, 1, 3)
                for t in jnp.split(qkv, 3, axis=-1))
    o = attention_xla(rope(q), rope(k), vv, True)
    want = jnp.einsum("bse,fe->bsf",
                      o.transpose(0, 2, 1, 3).reshape(2, 16, 32), w_out) + b_out
    np.testing.assert_allclose(np.asarray(net.apply(v, {"x": x})[0]["y"]),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ tokens: feed
@pytest.fixture
def token_file(tmp_path):
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, TINY["vocab"], 33 * 7 + 5).astype(np.uint16)
    path = tmp_path / "tokens.bin"
    tokens.tofile(path)
    return str(path), tokens


def test_token_windows_are_the_files_slices(token_file):
    from sparknet_tpu.data.prefetch import fresh_bytes
    from sparknet_tpu.data.text import token_windows

    path, tokens = token_file
    fn = token_windows(path, batch=2, seq_len=32)
    assert fn.takes_out
    ring = {"data": np.empty((2, 32), np.int32),
            "label": np.empty((2, 32), np.int32)}
    for it in range(5):  # 7 whole windows: batch 3 wraps around
        feeds = fn(it) if it == 0 else fn(it, out=ring)
        assert fresh_bytes(feeds, None if it == 0 else ring) == (
            2 * 2 * 32 * 4 if it == 0 else 0)
        for row in range(2):
            lo = ((it * 2 + row) % 7) * 33
            np.testing.assert_array_equal(feeds["data"][row],
                                          tokens[lo:lo + 32])
            np.testing.assert_array_equal(feeds["label"][row],
                                          tokens[lo + 1:lo + 33])
        assert feeds["data"].dtype == np.int32


def test_token_windows_refuse_a_short_file_and_a_wrong_destination(token_file):
    from sparknet_tpu.data.text import token_windows

    path, _ = token_file
    with pytest.raises(ValueError, match="one window needs"):
        token_windows(path, batch=2, seq_len=4096)
    fn = token_windows(path, batch=2, seq_len=32)
    with pytest.raises(ValueError, match="destination"):
        fn(0, out={"data": np.empty((3, 32), np.int32),
                   "label": np.empty((3, 32), np.int32)})


def _write_solver(tmp_path, net_msg):
    from sparknet_tpu.proto.text_format import serialize as to_text

    (tmp_path / "net.prototxt").write_text(to_text(net_msg))
    (tmp_path / "solver.prototxt").write_text(
        f'net: "{tmp_path}/net.prototxt"\ntype: "AdamW"\nbase_lr: 0.0004\n'
        'lr_policy: "fixed"\nmomentum: 0.9\nmomentum2: 0.95\ndelta: 1e-8\n'
        'weight_decay: 0.1\nclip_gradients: 1.0\nmax_iter: 4\ndisplay: 0\n')
    return str(tmp_path / "solver.prototxt")


def test_tpunet_train_trains_olmoe_from_prototxt_and_a_token_file(
        token_file, tmp_path, capsys):
    """The front door: ``tpunet train --solver x.prototxt --data
    tokens:<file> --prefetch 3``, and the feed's spans on the way."""
    from sparknet_tpu import cli

    path, _ = token_file
    solver = _write_solver(tmp_path, models.olmoe(**TINY))
    out = str(tmp_path / "final")
    rc = cli.main(["train", "--solver", solver, "--data", f"tokens:{path}",
                   "--prefetch", "3", "--iterations", "3", "--seed", "7",
                   "--output", out])
    assert rc == 0
    import glob

    assert glob.glob(out + "*")


def test_tokens_feed_carries_its_spans(token_file, monkeypatch):
    """``sn.feed.read`` on the ``tokens:`` path: images = sequences, a
    ``tokens`` stat, ``alloc_bytes`` 0 once the batch goes into the
    caller's arrays."""
    import argparse

    from sparknet_tpu import cli
    from sparknet_tpu.obs import recorder

    seen = []

    class Ann:
        def __init__(self, name, **kw):
            self.row = {"name": name, **kw}
            seen.append(self.row)

        def set_metadata(self, **kw):
            self.row.update(kw)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(recorder, "_trace_annotation", lambda: Ann)
    path, _ = token_file
    solver = make_solver()
    args = argparse.Namespace(data=f"tokens:{path}", augment="host")
    train_fn, _ = cli._data_fns(args, solver.train_net)
    assert train_fn.takes_out
    first = train_fn(0)
    ring = {k: np.empty_like(v) for k, v in first.items()}
    train_fn(1, out=ring)
    reads = [r for r in seen if r["name"] == "sn.feed.read"]
    assert [r["images"] for r in reads] == [2, 2]
    assert [r["tokens"] for r in reads] == [64, 64]
    assert reads[0]["alloc_bytes"] > 0 and reads[1]["alloc_bytes"] == 0


# ------------------------------------------------------------ tau-averaging
def test_parallel_trainer_tau2_is_the_mean_of_two_local_runs():
    """``ParallelTrainer(tau=2)`` takes the net unchanged: after one round
    on two virtual devices every leaf is the mean over the workers of two
    local ``Solver`` steps on that worker's batches."""
    from sparknet_tpu.parallel.mesh import data_parallel_mesh
    from sparknet_tpu.parallel.trainer import ParallelTrainer

    solver = make_solver()
    start = jax.tree_util.tree_map(np.asarray, solver.variables.params)
    trainer = ParallelTrainer(solver, mesh=data_parallel_mesh(2), tau=2)
    b = [[batch_of(10 * t + w) for w in range(2)] for t in range(2)]
    feeds = {k: np.stack([np.concatenate([b[t][w][k] for w in range(2)])
                          for t in range(2)]) for k in ("data", "label")}
    trainer.train_round(lambda it: feeds)
    finals = []
    for w in range(2):
        local = make_solver()
        local.variables = dataclasses.replace(
            local.variables,
            params=jax.tree_util.tree_map(jnp.array, start))
        local.step(2, lambda it: b[it][w])
        finals.append(local.variables.params)
    for layer, i in LEAVES:
        mean = (np.asarray(finals[0][layer][i])
                + np.asarray(finals[1][layer][i])) / 2
        got = np.asarray(trainer.variables.params[layer][i][0])
        assert rel(got - start[layer][i], mean - start[layer][i]) <= 1e-4, layer
