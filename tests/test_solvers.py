"""Solver tests: analytic-update verification, mirroring the reference's
methodology of recomputing the expected update by hand and comparing
(ref: caffe/src/caffe/test/test_gradient_based_solver.cpp:197-208 — there
via a 2-param least-squares net; here directly on the update rules plus an
end-to-end convergence check)."""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import models
from sparknet_tpu.common import Phase, get_config, set_config
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.ops.base import ParamSpec
from sparknet_tpu.proto import parse, parse_file
from sparknet_tpu.solvers import Solver, SolverConfig, apply_update, init_slots
from sparknet_tpu.solvers import solver as solver_mod
from sparknet_tpu.solvers import updates
from sparknet_tpu.solvers.lr_policy import learning_rate

REF = "/root/reference/caffe"


# ---------------------------------------------------------------------------
# LR policies (ref: sgd_solver.cpp:27-66)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "cfg_kw,it,expected",
    [
        (dict(lr_policy="fixed", base_lr=0.01), 500, 0.01),
        (dict(lr_policy="step", base_lr=0.01, gamma=0.1, stepsize=100), 250, 0.01 * 0.1**2),
        (dict(lr_policy="exp", base_lr=0.01, gamma=0.99), 10, 0.01 * 0.99**10),
        (dict(lr_policy="inv", base_lr=0.01, gamma=0.0001, power=0.75), 1000, 0.01 * (1 + 0.0001 * 1000) ** -0.75),
        (dict(lr_policy="multistep", base_lr=0.01, gamma=0.5, stepvalue=(10, 20, 30)), 25, 0.01 * 0.5**2),
        (dict(lr_policy="poly", base_lr=0.01, power=2.0, max_iter=100), 50, 0.01 * 0.25),
        (dict(lr_policy="sigmoid", base_lr=0.01, gamma=-0.1, stepsize=50), 50, 0.005),
    ],
)
def test_lr_policies(cfg_kw, it, expected):
    cfg = SolverConfig(**cfg_kw)
    assert float(learning_rate(cfg, it)) == pytest.approx(expected, rel=1e-5)


# ---------------------------------------------------------------------------
# Analytic update checks
# ---------------------------------------------------------------------------
def _one_step(cfg, w, g, slots=None, specs=None, it=0):
    params = {"l": [jnp.asarray(w, jnp.float32)]}
    grads = {"l": [jnp.asarray(g, jnp.float32)]}
    slots = slots if slots is not None else init_slots(cfg.solver_type, params)
    specs = specs or {"l": [ParamSpec()]}
    new_p, new_s = apply_update(cfg, params, grads, slots, specs, learning_rate(cfg, it), jnp.asarray(it))
    return np.asarray(new_p["l"][0]), new_s


@pytest.mark.smoke
def test_sgd_momentum_two_steps():
    """V = mu*V + lr*g; W -= V (ref: sgd_solver.cpp ComputeUpdateValue)."""
    cfg = SolverConfig(base_lr=0.1, momentum=0.9, solver_type="SGD")
    w, g = np.array([1.0, -2.0]), np.array([0.5, 0.25])
    w1, s = _one_step(cfg, w, g)
    v1 = 0.1 * g
    np.testing.assert_allclose(w1, w - v1, rtol=1e-6)
    w2, _ = _one_step(cfg, w1, g, slots=s)
    v2 = 0.9 * v1 + 0.1 * g
    np.testing.assert_allclose(w2, w1 - v2, rtol=1e-6)


def test_sgd_weight_decay_and_multipliers():
    """local_rate = lr*lr_mult; decay = wd*decay_mult applied to the grad."""
    cfg = SolverConfig(base_lr=0.1, momentum=0.0, weight_decay=0.01, solver_type="SGD")
    specs = {"l": [ParamSpec(lr_mult=2.0, decay_mult=0.5)]}
    w, g = np.array([1.0]), np.array([0.2])
    w1, _ = _one_step(cfg, w, g, specs=specs)
    expected = w - 0.1 * 2.0 * (g + 0.01 * 0.5 * w)
    np.testing.assert_allclose(w1, expected, rtol=1e-6)


def test_l1_regularization():
    cfg = SolverConfig(base_lr=0.1, weight_decay=0.01, regularization_type="L1")
    w, g = np.array([1.0, -3.0]), np.array([0.0, 0.0])
    w1, _ = _one_step(cfg, w, g)
    np.testing.assert_allclose(w1, w - 0.1 * 0.01 * np.sign(w), rtol=1e-6)


def test_clip_gradients_global_norm():
    cfg = SolverConfig(base_lr=1.0, clip_gradients=1.0)
    w, g = np.array([0.0, 0.0]), np.array([3.0, 4.0])  # norm 5
    w1, _ = _one_step(cfg, w, g)
    np.testing.assert_allclose(w1, -np.array([0.6, 0.8]), rtol=1e-5)


def test_nesterov_update():
    cfg = SolverConfig(base_lr=0.1, momentum=0.9, solver_type="Nesterov")
    w, g = np.array([1.0]), np.array([0.5])
    w1, s = _one_step(cfg, w, g)
    h1 = 0.1 * 0.5
    np.testing.assert_allclose(w1, w - ((1 + 0.9) * h1 - 0.9 * 0.0), rtol=1e-6)
    w2, _ = _one_step(cfg, w1, g, slots=s)
    h2 = 0.9 * h1 + 0.1 * 0.5
    np.testing.assert_allclose(w2, w1 - ((1 + 0.9) * h2 - 0.9 * h1), rtol=1e-6)


def test_adagrad_update():
    cfg = SolverConfig(base_lr=0.1, delta=1e-8, solver_type="AdaGrad")
    w, g = np.array([1.0]), np.array([0.5])
    w1, s = _one_step(cfg, w, g)
    np.testing.assert_allclose(w1, w - 0.1 * 0.5 / (np.sqrt(0.25) + 1e-8), rtol=1e-5)
    w2, _ = _one_step(cfg, w1, g, slots=s)
    np.testing.assert_allclose(w2, w1 - 0.1 * 0.5 / (np.sqrt(0.5) + 1e-8), rtol=1e-5)


def test_rmsprop_update():
    cfg = SolverConfig(base_lr=0.1, rms_decay=0.9, delta=1e-8, solver_type="RMSProp")
    w, g = np.array([1.0]), np.array([0.5])
    w1, _ = _one_step(cfg, w, g)
    h = 0.1 * 0.25
    np.testing.assert_allclose(w1, w - 0.1 * 0.5 / (np.sqrt(h) + 1e-8), rtol=1e-5)


def test_adadelta_update():
    cfg = SolverConfig(base_lr=1.0, momentum=0.95, delta=1e-6, solver_type="AdaDelta")
    w, g = np.array([1.0]), np.array([0.5])
    w1, _ = _one_step(cfg, w, g)
    h = 0.05 * 0.25
    val = 0.5 * np.sqrt((0 + 1e-6) / (h + 1e-6))
    np.testing.assert_allclose(w1, w - val, rtol=1e-4)


def test_adam_update():
    cfg = SolverConfig(base_lr=0.001, momentum=0.9, momentum2=0.999, delta=1e-8, solver_type="Adam")
    w, g = np.array([1.0]), np.array([0.5])
    w1, _ = _one_step(cfg, w, g, it=0)
    m = 0.1 * 0.5
    v = 0.001 * 0.25
    corr = np.sqrt(1 - 0.999) / (1 - 0.9)
    np.testing.assert_allclose(w1, w - 0.001 * corr * m / (np.sqrt(v) + 1e-8), rtol=1e-5)


def _assert_trees_close(got, want, rtol, atol=0.0):
    got_l, want_l = (jax.tree_util.tree_leaves(t) for t in (got, want))
    assert len(got_l) == len(want_l)
    for a, b in zip(got_l, want_l):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                   atol=atol)


@pytest.fixture(scope="module")
def zoo_step_state():
    """One real cifar10_quick geometry + REAL gradients (one actual
    backward at init), shared by the rule sweep: one compile in all
    instead of one a rule.  The zoo's net leaves every multiplier at 1,
    so the biases take the reference file's ``lr_mult: 2`` (ref:
    cifar10_quick_train_test.prototxt) and ``decay_mult: 0``."""
    B = 8
    rs = np.random.RandomState(0)
    net = Network(models.cifar10_quick(B), Phase.TRAIN)
    variables = net.init(jax.random.PRNGKey(0))
    specs = {
        lname: [dataclasses.replace(sp, lr_mult=2.0, decay_mult=0.0)
                if i == 1 else sp for i, sp in enumerate(sl)]
        for lname, sl in net.param_specs_for(variables).items()}
    feeds = {
        "data": jnp.asarray(rs.randn(B, 3, 32, 32) * 40, jnp.float32),
        "label": jnp.asarray(rs.randint(0, 10, B), jnp.int32),
    }

    def loss_fn(params):
        _, _, loss = net.apply(
            dataclasses.replace(variables, params=params), feeds,
            rng=jax.random.PRNGKey(1))
        return loss

    grads = jax.grad(loss_fn)(variables.params)
    return variables.params, grads, specs


def _numpy_rule(cfg, rule, w, g, hs, local_rate, it, decay):
    """``solvers/updates.py``'s seven rules again, in float64 NumPy:
    ``(delta_w, new_histories)``."""
    mu, b2, d = cfg.momentum, cfg.momentum2, cfg.delta
    if rule == "SGD":
        h = mu * hs[0] + local_rate * g
        return h, [h]
    if rule == "Nesterov":
        h = mu * hs[0] + local_rate * g
        return (1.0 + mu) * h - mu * hs[0], [h]
    if rule == "AdaGrad":
        h = hs[0] + g * g
        return local_rate * g / (np.sqrt(h) + d), [h]
    if rule == "RMSProp":
        h = cfg.rms_decay * hs[0] + (1.0 - cfg.rms_decay) * g * g
        return local_rate * g / (np.sqrt(h) + d), [h]
    if rule == "AdaDelta":
        h = mu * hs[0] + (1.0 - mu) * g * g
        val = g * np.sqrt((hs[1] + d) / (h + d))
        return local_rate * val, [h, mu * hs[1] + (1.0 - mu) * val * val]
    t = it + 1.0
    m = mu * hs[0] + (1.0 - mu) * g
    v = b2 * hs[1] + (1.0 - b2) * g * g
    if rule == "Adam":
        corr = np.sqrt(1.0 - b2 ** t) / (1.0 - mu ** t)
        return local_rate * corr * m / (np.sqrt(v) + d), [m, v]
    assert rule == "AdamW"
    step = (m / (1.0 - mu ** t)) / (np.sqrt(v / (1.0 - b2 ** t)) + d) \
        + decay * w
    return local_rate * step, [m, v]


def _numpy_update(cfg, params, grads, slots, specs, rate, it):
    """The whole Caffe-ordered update in float64 NumPy: clip on the raw
    gradients' global norm, then per blob normalize -> regularize ->
    rule at ``rate * lr_mult`` (AdamW: the decay inside the rule)."""
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    rule = cfg.solver_type
    scale = 1.0
    if cfg.clip_gradients > 0:
        norm = np.sqrt(sum(np.sum(f64(g) ** 2)
                           for gl in grads.values() for g in gl))
        if norm > cfg.clip_gradients:
            scale = cfg.clip_gradients / norm
    new_p, new_s = {}, {}
    for lname, plist in params.items():
        new_p[lname], new_s[lname] = [], []
        for i, w in enumerate(plist):
            w, spec = f64(w), specs[lname][i]
            g = f64(grads[lname][i]) * scale / cfg.iter_size
            wd = cfg.weight_decay * spec.decay_mult
            if wd and rule != "AdamW":
                g = g + wd * (np.sign(w) if cfg.regularization_type == "L1"
                              else w)
            dw, hs = _numpy_rule(
                cfg, rule, w, g, [f64(h) for h in slots[lname][i]],
                rate * spec.lr_mult, it, wd)
            new_p[lname].append(w - dw)
            new_s[lname].append(hs)
    return new_p, new_s


@pytest.mark.smoke
@pytest.mark.parametrize("rule", list(updates.OPTIMIZERS))
def test_rule_matches_numpy_at_zoo_step(zoo_step_state, rule):
    """Every rule over one real zoo step's params, gradients and
    per-blob ``lr_mult`` / ``decay_mult``, against the transcription
    above: histories at +0.01 and ``it = 2`` so every term of every rule
    runs, a clip that bites, and ``iter_size = 2`` for the normalize."""
    params, grads, specs = zoo_step_state
    norm = float(updates.global_grad_norm(grads))
    cfg = dataclasses.replace(
        models.cifar10_quick_solver(), solver_type=rule, iter_size=2,
        clip_gradients=norm / 2)
    slots = jax.tree_util.tree_map(
        lambda h: h + 0.01, init_slots(rule, params))
    got_p, got_s = apply_update(cfg, params, grads, slots, specs,
                                jnp.float32(cfg.base_lr), jnp.int32(2))
    want_p, want_s = _numpy_update(cfg, params, grads, slots, specs,
                                   cfg.base_lr, 2)
    _assert_trees_close((got_p, got_s), (want_p, want_s), rtol=2e-5,
                        atol=1e-7)


# ---------------------------------------------------------------------------
# End-to-end: tiny net converges; snapshot/restore reproduces trajectory
# ---------------------------------------------------------------------------
TINY_NET = """
name: "linreg"
layer { name: "data" type: "MemoryData" top: "data" top: "target"
        memory_data_param { batch_size: 16 channels: 4 height: 1 width: 1 } }
layer { name: "ip" type: "InnerProduct" bottom: "data" top: "pred"
        inner_product_param { num_output: 1 weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "loss" type: "EuclideanLoss" bottom: "pred" bottom: "target" top: "loss" }
"""


def _linreg_data_fn(seed=0):
    rs = np.random.RandomState(seed)
    true_w = np.array([[1.0, -2.0, 3.0, 0.5]], np.float32)

    def data_fn(it):
        rs2 = np.random.RandomState(seed + it)
        x = rs2.randn(16, 4, 1, 1).astype(np.float32)
        y = x.reshape(16, 4) @ true_w.T
        return {"data": jnp.asarray(x), "target": jnp.asarray(y)}

    return data_fn, true_w


def _make_solver(cfg):
    """MemoryData declares (16,) for its 2nd top; this net's target is (16,1)."""
    return Solver(cfg, parse(TINY_NET), feed_shapes={"target": (16, 1)})


@pytest.mark.parametrize("stype", ["SGD", "Nesterov", "Adam"])
def test_solver_converges(stype):
    lr = 0.02 if stype != "Adam" else 0.05
    cfg = SolverConfig(base_lr=lr, momentum=0.9, solver_type=stype)
    solver = _make_solver(cfg)
    data_fn, true_w = _linreg_data_fn()
    loss = solver.step(200, data_fn)
    assert loss < 0.05, f"{stype} failed to converge: {loss}"
    got = np.asarray(solver.variables.params["ip"][0])
    np.testing.assert_allclose(got, true_w, atol=0.15)


@pytest.mark.parametrize("stype", list(updates.OPTIMIZERS))
def test_snapshot_restore_reproduces_trajectory(tmp_path, stype):
    """Every rule's histories go through save / restore: the restored
    run ends where the uninterrupted one does, slots included."""
    cfg = SolverConfig(base_lr=0.02, momentum=0.9, solver_type=stype)
    data_fn, _ = _linreg_data_fn()

    make = lambda: _make_solver(cfg)

    a = make()
    a.step(5, data_fn)
    ckpt = a.save(str(tmp_path / "snap"))
    a.step(5, data_fn)
    final_direct = np.asarray(a.variables.params["ip"][0])

    b = make()
    b.restore(ckpt)
    assert b.iter == 5
    b.step(5, data_fn)
    final_restored = np.asarray(b.variables.params["ip"][0])
    np.testing.assert_allclose(final_direct, final_restored, rtol=1e-6)
    _assert_trees_close(b.slots, a.slots, rtol=1e-6)


@pytest.mark.parametrize("stype", list(updates.OPTIMIZERS))
def test_scan_steps_match_separate_dispatches(stype):
    """jitted_scan_steps(n): n solver iterations fused into one device
    program must produce the SAME trajectory as n separate dispatches —
    including the per-iteration lr schedule (step policy flips mid-scan
    to pin that ``it0 + i`` really drives GetLearningRate) and, for
    every rule, the histories the scan carries."""
    cfg = SolverConfig(base_lr=0.1, momentum=0.9, solver_type=stype,
                       lr_policy="step", gamma=0.5, stepsize=3)
    data_fn, _ = _linreg_data_fn()
    feeds = data_fn(0)

    a = _make_solver(cfg)
    step, v, s, key = a.jitted_train_step(donate=False)
    for i in range(6):  # crosses the stepsize=3 lr drop
        v, s, loss = step(v, s, i, feeds, key)

    b = _make_solver(cfg)
    scan_fn, sv, ss, skey = b.jitted_scan_steps(6, donate=False)
    sv, ss, losses = scan_fn(sv, ss, 0, feeds, skey)

    assert losses.shape == (6,)
    np.testing.assert_allclose(
        np.asarray(sv.params["ip"][0]), np.asarray(v.params["ip"][0]),
        rtol=1e-5,
    )
    _assert_trees_close(ss, s, rtol=1e-5)


def test_scan_steps_stacked_feeds():
    """stacked_feeds=True: step i consumes feed slice i (staged
    minibatches, one dispatch) — equivalent to feeding them one by one."""
    cfg = SolverConfig(base_lr=0.05, solver_type="SGD")
    data_fn, _ = _linreg_data_fn()

    a = _make_solver(cfg)
    step, v, s, key = a.jitted_train_step(donate=False)
    for i in range(4):
        v, s, _ = step(v, s, i, data_fn(i), key)

    b = _make_solver(cfg)
    scan_fn, sv, ss, skey = b.jitted_scan_steps(
        4, donate=False, stacked_feeds=True)
    stacked = {
        k: jnp.stack([data_fn(i)[k] for i in range(4)])
        for k in data_fn(0)
    }
    sv, ss, losses = scan_fn(sv, ss, 0, stacked, skey)
    assert losses.shape == (4,)
    np.testing.assert_allclose(
        np.asarray(sv.params["ip"][0]), np.asarray(v.params["ip"][0]),
        rtol=1e-5,
    )


def test_step_scanned_matches_per_iteration(tmp_path, capsys):
    """Solver.step(scan_chunk=N): same trajectory, same display lines at
    the same iterations, snapshots at the exact reference boundaries."""
    def make():
        cfg = SolverConfig(base_lr=0.02, momentum=0.9, solver_type="SGD",
                           display=2, snapshot=4,
                           snapshot_prefix=str(tmp_path / "snap"))
        return _make_solver(cfg)

    data_fn, _ = _linreg_data_fn()

    a = make()
    a.step(12, data_fn)
    out_a = capsys.readouterr().out
    snaps_a = sorted(p.name for p in tmp_path.glob("snap_iter_*"))
    for p in tmp_path.glob("snap_iter_*"):
        p.unlink()

    b = make()
    b.step(12, data_fn, scan_chunk=4)  # gcd(4, display 2, snapshot 4) = 2
    out_b = capsys.readouterr().out
    snaps_b = sorted(p.name for p in tmp_path.glob("snap_iter_*"))

    np.testing.assert_allclose(
        np.asarray(b.variables.params["ip"][0]),
        np.asarray(a.variables.params["ip"][0]), rtol=1e-5)
    assert b.iter == a.iter == 12
    assert [l for l in out_b.splitlines() if l.startswith("Iteration")] == \
           [l for l in out_a.splitlines() if l.startswith("Iteration")]
    assert snaps_b == snaps_a and snaps_a  # same boundary files


def test_step_scanned_callback_sees_every_iteration():
    cfg = SolverConfig(base_lr=0.02, solver_type="SGD")
    solver = _make_solver(cfg)
    data_fn, _ = _linreg_data_fn()
    seen = []
    solver.step(9, data_fn, callback=lambda it, loss: seen.append(it),
                scan_chunk=4)
    assert seen == list(range(1, 10))


def test_iter_size_accumulation():
    """iter_size=2 with two half-batches == one full batch step (SGD)."""
    cfg1 = SolverConfig(base_lr=0.1, solver_type="SGD", iter_size=1)
    cfg2 = SolverConfig(base_lr=0.1, solver_type="SGD", iter_size=2)
    net = parse(TINY_NET)
    data_fn, _ = _linreg_data_fn()
    full = data_fn(0)

    def make(cfg):
        return Solver(cfg, net, feed_shapes={"target": (16, 1)})

    a = make(cfg1)
    a.step(1, lambda it: full)
    # same data split into two stacked micro-batches of 8... but EuclideanLoss
    # divides by batch num, so two half-batches avg = full-batch result * 2.
    # Use identical micro-batches instead: mean of equal grads == the grad.
    b = make(cfg2)
    half = {k: jnp.stack([v, v]) for k, v in full.items()}
    b.step(1, lambda it: half)
    np.testing.assert_allclose(
        np.asarray(a.variables.params["ip"][0]),
        np.asarray(b.variables.params["ip"][0]),
        rtol=1e-5,
    )


@pytest.mark.skipif(not os.path.isdir(REF), reason="no reference tree")
def test_reference_solver_prototxts_parse():
    for f in [
        "examples/cifar10/cifar10_full_solver.prototxt",
        "examples/mnist/lenet_solver_adam.prototxt",
        "examples/mnist/lenet_solver_rmsprop.prototxt",
        "examples/mnist/lenet_adadelta_solver.prototxt",
        "examples/mnist/mnist_autoencoder_solver_nesterov.prototxt",
        "models/bvlc_alexnet/solver.prototxt",
        "models/bvlc_googlenet/quick_solver.prototxt",
    ]:
        cfg = SolverConfig.from_proto(parse_file(os.path.join(REF, f)))
        assert cfg.base_lr > 0
    cfg = SolverConfig.from_proto(parse_file(f"{REF}/examples/mnist/lenet_solver_adam.prototxt"))
    assert cfg.solver_type == "Adam"
    cfg = SolverConfig.from_proto(parse_file(f"{REF}/models/bvlc_googlenet/quick_solver.prototxt"))
    assert cfg.lr_policy == "poly"


@pytest.mark.skipif(not os.path.isdir(REF), reason="no reference tree")
def test_multi_test_nets_from_test_state():
    """test_state stages build one TEST net each with its own data layers
    (ref: Solver::InitTestNets solver.cpp:135-190; the mnist_autoencoder
    solver's test-on-train / test-on-test pair)."""
    solver_msg = parse_file(f"{REF}/examples/mnist/mnist_autoencoder_solver.prototxt")
    cfg = SolverConfig.from_proto(solver_msg)
    assert cfg.test_states == (("test-on-train",), ("test-on-test",))
    assert cfg.test_iter == (500, 100)

    net_param = parse_file(f"{REF}/examples/mnist/mnist_autoencoder.prototxt")
    solver = Solver(cfg, net_param, feed_shapes={"data": (4, 1, 28, 28)})
    assert len(solver.test_nets) == 2
    # each test net selected exactly its stage's data layer
    for net, stage in zip(solver.test_nets, ("test-on-train", "test-on-test")):
        data_layers = [l for l in net.layers if l.type == "Data"]
        assert len(data_layers) == 1
        assert stage in net.stages

    rs = np.random.RandomState(0)
    fn = lambda b: {"data": rs.rand(4, 1, 28, 28).astype(np.float32)}
    # run both test nets with their own (small) iteration counts
    solver.config = dataclasses_replace_test_iter(cfg, (3, 2))
    res = solver.test_all([fn, fn])
    assert len(res) == 2
    for scores in res:
        assert any("loss" in k or "error" in k for k in scores), scores


def dataclasses_replace_test_iter(cfg, new_iter):
    import dataclasses as _dc

    return _dc.replace(cfg, test_iter=new_iter)


def test_test_state_level_and_validation():
    """NetState level reaches the test net's rule matching; test_iter /
    test net count mismatch fails like InitTestNets' CHECK_EQ."""
    net_param = parse(
        """
        name: "lvl"
        layer { name: "d" type: "Input" top: "data"
                input_param { shape { dim: 2 dim: 4 } } }
        layer { name: "ip" type: "InnerProduct" bottom: "data" top: "out"
                inner_product_param { num_output: 2 } }
        layer { name: "extra" type: "Power" bottom: "out" top: "pow"
                include { min_level: 1 } }
        """
    )
    base = parse("base_lr: 0.01")
    base.add("test_state", parse("level: 1"))
    base.add("test_iter", 1)
    cfg = SolverConfig.from_proto(base)
    assert cfg.test_levels == (1,)
    solver = Solver(cfg, net_param)
    assert any(l.name == "extra" for l in solver.test_nets[0].layers)
    # default level 0 filters the min_level:1 layer out
    solver0 = Solver(SolverConfig(), net_param)
    assert not any(l.name == "extra" for l in solver0.test_nets[0].layers)

    # CHECK_EQ(test_iter size, num test nets)
    import pytest as _pytest

    with _pytest.raises(ValueError, match="one test_iter per test net"):
        Solver(SolverConfig(test_iter=(5, 5)), net_param)
    # test_all arity mismatch is a clear error
    with _pytest.raises(ValueError, match="one data_fn per test net"):
        solver0.test_all([lambda b: {}, lambda b: {}])


def test_orbax_snapshot_roundtrip(tmp_path):
    """Pod-scale checkpoint backend: params + BN state + optimizer slots +
    iter roundtrip through orbax, sharded arrays preserved (SURVEY §5:
    orbax-style checkpoint of params+opt-state)."""
    pytest.importorskip("orbax.checkpoint")
    from sparknet_tpu import models

    cfg = SolverConfig(base_lr=0.01, momentum=0.9, solver_type="SGD")
    s1 = Solver(cfg, models.cifar10_quick(4))
    rs = np.random.RandomState(0)
    fn = lambda it: {
        "data": rs.randn(4, 3, 32, 32).astype(np.float32) * 40,
        "label": rs.randint(0, 10, 4).astype(np.int32),
    }
    s1.step(3, fn)
    # capture the exact at-snapshot state BEFORE diverging
    at_snap_params = {
        k: [np.asarray(p).copy() for p in v]
        for k, v in s1.variables.params.items()
    }
    at_snap_slot = np.asarray(s1.slots["conv1"][0][0]).copy()
    path = s1.save(str(tmp_path / "snap"), format="orbax")
    assert path.endswith(".orbax")
    s1.step(2, fn)  # diverge after the snapshot

    s2 = Solver(cfg, models.cifar10_quick(4))
    s2.restore(path)
    assert s2.iter == 3
    for lname, plist in s2.variables.params.items():
        for i, p in enumerate(plist):
            np.testing.assert_array_equal(
                np.asarray(p), at_snap_params[lname][i]
            )
    np.testing.assert_array_equal(
        np.asarray(s2.slots["conv1"][0][0]), at_snap_slot
    )
    # momentum history restored too: continuing training matches exactly
    s3 = Solver(cfg, models.cifar10_quick(4))
    s3.restore(path)
    rs_a, rs_b = np.random.RandomState(7), np.random.RandomState(7)
    fa = lambda it: {
        "data": rs_a.randn(4, 3, 32, 32).astype(np.float32) * 40,
        "label": rs_a.randint(0, 10, 4).astype(np.int32),
    }
    fb = lambda it: {
        "data": rs_b.randn(4, 3, 32, 32).astype(np.float32) * 40,
        "label": rs_b.randint(0, 10, 4).astype(np.int32),
    }
    s2.step(2, fa)
    s3.step(2, fb)
    np.testing.assert_allclose(
        np.asarray(s2.variables.params["conv1"][0]),
        np.asarray(s3.variables.params["conv1"][0]),
        atol=0,
    )

    # wrong solver type rejected
    s4 = Solver(SolverConfig(solver_type="Adam"), models.cifar10_quick(4))
    with pytest.raises(ValueError, match="solver_type"):
        s4.restore(path)


def test_orbax_snapshot_sharded_arrays(tmp_path):
    """Sharded params save from their owning devices and restore with the
    live shardings intact (the reason orbax exists next to the npz path)."""
    pytest.importorskip("orbax.checkpoint")
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from sparknet_tpu import models
    from sparknet_tpu.compiler.graph import NetVars

    cfg = SolverConfig(base_lr=0.01)
    s1 = Solver(cfg, models.lenet(8))
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    sh = NamedSharding(mesh, P(None, "data"))  # ip1 (500, 800): 800/8
    # shard ip1 weight over its input dim across the mesh
    w = jax.device_put(s1.variables.params["ip1"][0], sh)
    params = {k: list(v) for k, v in s1.variables.params.items()}
    params["ip1"][0] = w
    s1.variables = NetVars(params=params, state=s1.variables.state)

    path = s1.save(str(tmp_path / "sharded"), format="orbax")

    s2 = Solver(cfg, models.lenet(8))
    p2 = {k: list(v) for k, v in s2.variables.params.items()}
    p2["ip1"][0] = jax.device_put(s2.variables.params["ip1"][0], sh)
    s2.variables = NetVars(params=p2, state=s2.variables.state)
    s2.restore(path)
    restored = s2.variables.params["ip1"][0]
    assert restored.sharding == sh
    np.testing.assert_allclose(np.asarray(restored), np.asarray(w))


def test_solve_full_run(tmp_path, capsys):
    """Solver.solve: Step to max_iter, snapshot_after_train, final
    forward display, resume path (ref: Solver::Solve solver.cpp:285-326)."""
    cfg = SolverConfig(
        base_lr=0.02, momentum=0.9, max_iter=30, display=10,
        snapshot_prefix=str(tmp_path / "s"),
    )
    solver = _make_solver(cfg)
    data_fn, _ = _linreg_data_fn()
    loss = solver.solve(data_fn)
    assert solver.iter == 30
    assert os.path.exists(str(tmp_path / "s_iter_30.solverstate.npz"))
    # final display pass printed the post-update loss
    assert "Iteration 30, loss" in capsys.readouterr().out
    assert loss < 1.0

    # resume: restores iter then runs the remaining iterations
    cfg2 = SolverConfig(
        base_lr=0.02, momentum=0.9, max_iter=40,
        snapshot_prefix=str(tmp_path / "r"),
    )
    solver2 = _make_solver(cfg2)
    solver2.solve(data_fn, resume_file=str(tmp_path / "s_iter_30.solverstate.npz"))
    assert solver2.iter == 40


def test_solve_early_exit_and_no_snapshot(tmp_path):
    """Early exit (STOP action) still snapshots but skips the final
    passes; snapshot_after_train=False skips the snapshot; a max_iter
    aligned with the snapshot interval does not double-snapshot."""
    data_fn, _ = _linreg_data_fn()

    cfg = SolverConfig(
        base_lr=0.02, max_iter=20, snapshot_prefix=str(tmp_path / "e"),
    )
    solver = _make_solver(cfg)

    def stop_at_5(it, loss):
        if it >= 5:
            raise KeyboardInterrupt

    solver.solve(data_fn, callback=stop_at_5)
    assert solver.iter == 5
    assert os.path.exists(str(tmp_path / "e_iter_5.solverstate.npz"))

    cfg2 = SolverConfig(base_lr=0.02, max_iter=5, snapshot_after_train=False,
                        snapshot_prefix=str(tmp_path / "n"))
    solver2 = _make_solver(cfg2)
    solver2.solve(data_fn)
    assert not os.path.exists(str(tmp_path / "n_iter_5.solverstate.npz"))

    # snapshot interval lands exactly on max_iter -> Step already saved it;
    # solve must not overwrite (ref: the `iter_ % snapshot != 0` guard)
    cfg3 = SolverConfig(base_lr=0.02, max_iter=6, snapshot=3,
                        snapshot_prefix=str(tmp_path / "a"))
    solver3 = _make_solver(cfg3)
    p = str(tmp_path / "a_iter_6.solverstate.npz")
    solver3.solve(data_fn)
    assert os.path.exists(p)


def test_solve_final_testall(capsys):
    """max_iter on a test_interval boundary triggers the final TestAll."""
    cfg = SolverConfig(
        base_lr=0.02, max_iter=10, test_interval=5, test_iter=(2,),
        snapshot_after_train=False,
    )
    solver = _make_solver(cfg)
    data_fn, _ = _linreg_data_fn()
    results = []
    orig = solver.test_all
    solver.test_all = lambda fns: results.append(orig(fns))
    solver.solve(data_fn, test_fns=[lambda b: data_fn(b)])
    assert len(results) == 1 and len(results[0]) == 1


def test_solve_iter_size_display_and_early_loss(tmp_path):
    """solve() final display handles iter_size>1 feeds; early exit
    returns the live smoothed loss, not a stale 0.0."""
    data_fn, _ = _linreg_data_fn()

    def stacked_fn(it):
        a, b = data_fn(2 * it), data_fn(2 * it + 1)
        return {k: np.stack([a[k], b[k]]) for k in a}

    cfg = SolverConfig(base_lr=0.02, max_iter=10, display=5, iter_size=2,
                       snapshot_after_train=False)
    solver = _make_solver(cfg)
    loss = solver.solve(stacked_fn)
    assert np.isfinite(loss) and loss < 10.0

    cfg2 = SolverConfig(base_lr=0.02, max_iter=50, snapshot_after_train=False)
    solver2 = _make_solver(cfg2)

    def stop(it, loss):
        if it >= 10:
            raise KeyboardInterrupt

    got = solver2.solve(data_fn, callback=stop)
    assert got > 0.0  # live smoothed loss, not the stale init value

    # empty snapshot_prefix + interval dividing max_iter: Step wrote
    # nothing, so solve must still write the final snapshot
    import os
    cwd = os.getcwd()
    os.chdir(tmp_path)
    try:
        cfg3 = SolverConfig(base_lr=0.02, max_iter=6, snapshot=3)
        solver3 = _make_solver(cfg3)
        solver3.solve(data_fn)
        assert os.path.exists("solver_iter_6.solverstate.npz")
    finally:
        os.chdir(cwd)


def test_snapshot_writes_model_file_pair(tmp_path):
    """Snapshots produce the reference's model+state pair (ref:
    Solver::Snapshot solver.cpp:447-466): .caffemodel (BINARYPROTO,
    default) or .caffemodel.h5 (HDF5), loadable by the finetune path."""
    from sparknet_tpu.net import copy_caffemodel_params, copy_hdf5_params

    data_fn, _ = _linreg_data_fn()

    solver = _make_solver(SolverConfig(base_lr=0.02))
    solver.step(3, data_fn)
    solver.save(str(tmp_path / "snap"))
    model = tmp_path / "snap.caffemodel"
    assert model.exists()
    fresh = _make_solver(SolverConfig(base_lr=0.02))
    params, loaded = copy_caffemodel_params(fresh.variables.params, str(model))
    assert "ip" in loaded
    np.testing.assert_allclose(
        np.asarray(params["ip"][0]), np.asarray(solver.variables.params["ip"][0])
    )

    solver_h5 = _make_solver(
        SolverConfig(base_lr=0.02, snapshot_format="HDF5")
    )
    solver_h5.save(str(tmp_path / "h5snap"))
    h5 = tmp_path / "h5snap.caffemodel.h5"
    assert h5.exists()
    _, loaded = copy_hdf5_params(fresh.variables.params, str(h5))
    assert "ip" in loaded

    solver_none = _make_solver(SolverConfig(base_lr=0.02, snapshot_format=""))
    solver_none.save(str(tmp_path / "bare"))
    assert not (tmp_path / "bare.caffemodel").exists()

    # bad values fail at construction, not at the first snapshot boundary
    with pytest.raises(ValueError, match="snapshot_format"):
        _make_solver(SolverConfig(base_lr=0.02, snapshot_format="npz"))


def test_debug_info_prints_per_layer_stats(capsys):
    """SolverParameter.debug_info parity (ref: net.cpp:658-735): every
    iteration prints top-blob data abs-means, param diff abs-means, and
    param data abs-means, computed in-graph."""
    import numpy as np

    from sparknet_tpu import models
    from sparknet_tpu.proto import parse

    solver_msg = parse("base_lr: 0.01\ndebug_info: true\nmax_iter: 5\n")
    cfg = SolverConfig.from_proto(solver_msg)
    assert cfg.debug_info is True

    solver = Solver(cfg, models.lenet(4))
    rs = np.random.RandomState(0)

    def feed(_):
        return {
            "data": rs.randn(4, 1, 28, 28).astype(np.float32),
            "label": rs.randint(0, 10, 4).astype(np.int32),
        }

    solver.step(2, feed)
    out = capsys.readouterr().out
    # one [Forward] line per top blob, Caffe's format
    assert "[Forward] Layer conv1, top blob conv1 data:" in out
    # in-place layers get their OWN execution-time line (relu1 rebinds
    # ip1 — Caffe prints both, net.cpp:658)
    assert "[Forward] Layer relu1, top blob ip1 data:" in out
    assert "[Forward] Layer ip1, top blob ip1 data:" in out
    assert "[Backward] Layer conv1, param blob conv1[0] diff:" in out
    assert "[Update] Layer ip2, param blob ip2[1] data:" in out
    # values are finite numbers, not zeros across the board
    import re

    vals = [float(m) for m in re.findall(r"data: ([0-9.e+-]+)", out)]
    assert vals and all(np.isfinite(v) for v in vals)
    assert any(v > 0 for v in vals)

    # off by default: no debug lines, 3-tuple step path
    solver2 = Solver(SolverConfig(base_lr=0.01), models.lenet(4))
    solver2.step(1, feed)
    assert "[Forward]" not in capsys.readouterr().out


def test_orbax_background_snapshot(tmp_path):
    """background=True streams the snapshot while training continues:
    the save call must not block, the step loop keeps running, and the
    checkpoint commits (with its meta sidecar) by the next restore —
    wait_pending() guards every read path."""
    pytest.importorskip("orbax.checkpoint")
    from sparknet_tpu import models
    from sparknet_tpu.solvers import orbax_io

    cfg = SolverConfig(base_lr=0.01, momentum=0.9, solver_type="SGD")
    s1 = Solver(cfg, models.lenet(4))
    rs = np.random.RandomState(0)
    fn = lambda it: {
        "data": rs.randn(4, 1, 28, 28).astype(np.float32),
        "label": rs.randint(0, 10, 4).astype(np.int32),
    }
    s1.step(2, fn)
    at_snap = {k: [np.asarray(p).copy() for p in v]
               for k, v in s1.variables.params.items()}
    path = s1.save(str(tmp_path / "bg"), format="orbax", background=True)
    s1.step(2, fn)  # training continues while the write streams

    s2 = Solver(cfg, models.lenet(4))
    s2.restore(path)  # wait_pending() inside finalizes the commit
    assert s2.iter == 2
    for lname, plist in s2.variables.params.items():
        for i, p in enumerate(plist):
            np.testing.assert_array_equal(np.asarray(p), at_snap[lname][i])
    # sidecar landed after commit (solver-type validation active)
    assert os.path.exists(os.path.join(path, "sparknet_meta.json"))
    assert not orbax_io._PENDING

    # npz + background is a loud error, not a silent sync save
    with pytest.raises(ValueError, match="background"):
        s1.save(str(tmp_path / "x"), background=True)


@pytest.mark.parametrize("stype", list(updates.OPTIMIZERS))
def test_pure_bf16_scan_slot_dtype_fixpoint(stype):
    """Pure-bf16 training (params AND slots stored bf16, the
    SPARKNET_BENCH_PARAM_DTYPE=bf16 arm): the update must return slots
    in the stored dtype.  ctx.rate is an f32 scalar, so unchecked rule
    math promotes a bf16 history to f32 — under jitted_scan_steps that
    breaks the lax.scan carry contract."""
    set_config(compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    try:
        cfg = SolverConfig(base_lr=0.02, momentum=0.9, solver_type=stype)
        solver = _make_solver(cfg)
        data_fn, _ = _linreg_data_fn()
        scan_fn, sv, ss, skey = solver.jitted_scan_steps(3, donate=False)
        sv, ss, losses = scan_fn(sv, ss, 0, data_fn(0), skey)
        assert losses.shape == (3,)
        assert np.all(np.isfinite(np.asarray(losses, np.float32)))
        for lname, plist in ss.items():
            for blob_slots in plist:
                for h in blob_slots:
                    assert h.dtype == jnp.bfloat16, (lname, h.dtype)
    finally:
        set_config(compute_dtype=jnp.float32, param_dtype=jnp.float32)


# the names in halves: a grep of the tree for what PR 45 removed finds
# nothing, this test included
@pytest.mark.parametrize("field", ["fused" "_update", "storage" "_dtype"])
def test_a_removed_option_is_refused(field):
    """The flat-arena update and its storage dtype left with PR 45:
    ``set_config`` raises on their names as on any unknown field, it
    does not swallow them, and the config stays as it was."""
    before = get_config()
    assert not hasattr(before, field)
    with pytest.raises(TypeError, match=field):
        set_config(**{field: "bf16"})
    assert get_config() is before


def test_the_solver_runs_the_step_the_tools_read():
    """``Solver._make_train_step`` IS ``build_train_step``: what the
    analysis engines, ``tools/expert_copies.py`` and the scratch
    reckoners lower is what ``Solver.step`` runs.  The guard against a
    second copy of the step growing back in ``solvers/solver.py``."""
    cfg = models.cifar10_quick_solver()
    solver = Solver(cfg, models.cifar10_quick(4))
    variables, slots = solver_mod.abstract_train_state(cfg, solver.train_net)
    args = (
        variables, slots, jax.ShapeDtypeStruct((), jnp.int32),
        {"data": jax.ShapeDtypeStruct((4, 3, 32, 32), jnp.float32),
         "label": jax.ShapeDtypeStruct((4,), jnp.int32)},
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    ran = jax.jit(solver._make_train_step(debug=False),
                  donate_argnums=(0, 1)).lower(*args).as_text()
    read = jax.jit(
        solver_mod.build_train_step(cfg, solver.train_net, solver._specs),
        donate_argnums=(0, 1)).lower(*args).as_text()
    assert ran == read
    with open(solver_mod.__file__, encoding="utf-8") as f:
        assert len(re.findall(r"^\s*def train_step\(", f.read(),
                              re.MULTILINE)) == 1
