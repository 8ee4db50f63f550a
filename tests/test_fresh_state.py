"""A solver is born in one program (``solvers/solver.py
fresh_train_state``): the fillers and the optimizer's slots as ONE jitted
executable keyed by the net's shape and read by the PRNG key.

The eager composition ``net.init(key, ...)`` + ``init_slots(...)`` the
``Solver`` ran before stays here as the oracle: the jitted state equals it
leaf by leaf, to the bit wherever the CPU backend lets it (the known
places where it does not are held to a few ulps, with the reason).
"""

from __future__ import annotations

import dataclasses
import importlib

import jax
import numpy as np
import pytest

from sparknet_tpu import models
from sparknet_tpu.common import Phase
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.obs.recorder import flight
from sparknet_tpu.proto.text_format import parse
from sparknet_tpu.solvers import solver as solver_mod
from sparknet_tpu.solvers.solver import (Solver, SolverConfig,
                                         abstract_train_state,
                                         fresh_train_state)
from sparknet_tpu.solvers.updates import init_slots

FILLERS = {
    "constant": 'type: "constant" value: 0.25',
    # (a width of exactly 1 off zero, say [-0.3, 0.7], is the one uniform
    # that differs: its scale gone, XLA adds the sampler's own -1 and the
    # minimum as one constant)
    "uniform": 'type: "uniform" min: -0.3 max: 0.9',
    "gaussian": 'type: "gaussian" std: 0.01',
    "gaussian-sparse": 'type: "gaussian" std: 0.5 sparse: 3',
    "xavier": 'type: "xavier"',
    "xavier-average": 'type: "xavier" variance_norm: AVERAGE',
    "msra": 'type: "msra"',
    "msra-fan_out": 'type: "msra" variance_norm: FAN_OUT',
    "bilinear": 'type: "bilinear"',
}


def one_conv_net(filler: str, outputs: int = 6):
    """A net of one convolution whose weight (a square 4-D blob, so that
    ``bilinear`` fits) takes ``filler``."""
    return parse(f"""
        name: "one_conv"
        layer {{ name: "data" type: "Input" top: "data"
                 input_param {{ shape {{ dim: 2 dim: 5 dim: 9 dim: 9 }} }} }}
        layer {{ name: "conv" type: "Convolution" bottom: "data" top: "conv"
                 convolution_param {{ num_output: {outputs} kernel_size: 4
                   weight_filler {{ {filler} }}
                   bias_filler {{ type: "constant" value: 0.1 }} }} }}
    """)


def eager_state(solver_type, net_param, key):
    """The oracle: what ``Solver.__init__`` ran before, a program a
    filler and shape."""
    variables = Network(net_param, Phase.TRAIN).init(key)
    return variables, init_slots(solver_type, variables.params)


def named_leaves(state):
    """``(name, leaf)`` of a ``(variables, slots)`` pair, in tree order."""
    variables, slots = state
    for part, tree in (("params", variables.params),
                       ("state", variables.state), ("slots", slots)):
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
            yield part + jax.tree_util.keystr(path), leaf


def differing(oracle, state):
    """Names of the leaves whose bytes differ, after checking that the
    trees, shapes and dtypes are the oracle's."""
    assert (jax.tree_util.tree_structure(oracle)
            == jax.tree_util.tree_structure(state))
    out = []
    for (name, a), (_, b) in zip(named_leaves(oracle), named_leaves(state)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        if np.asarray(a).tobytes() != np.asarray(b).tobytes():
            out.append(name)
    return out


@pytest.mark.parametrize("name", list(FILLERS))
def test_every_filler_comes_out_bit_equal(name):
    net_param = one_conv_net(FILLERS[name])
    key = jax.random.key(11)
    state = fresh_train_state(SolverConfig(solver_type="Adam"),
                              Network(net_param, Phase.TRAIN))(key)
    oracle = eager_state("Adam", net_param, key)
    assert differing(oracle, state) == []
    weight = np.asarray(state[0].params["conv"][0])
    if name != "constant":
        assert np.unique(weight).size >= 3  # a sampler ran, or the kernel


# where one program cannot be the eager call to the bit on the CPU: XLA
# takes the barrier out before it fuses, and inside one fused loop (a)
# LLVM contracts a product and a sum into ONE fused multiply-add, the
# product's rounding gone (a gaussian WITH a mean; no zoo net has one),
# (b) a row sum fused with its sampler adds in another order
# (``positive_unitball``; no zoo net has one)
NEAR = {
    "gaussian-mean": ('type: "gaussian" mean: 0.5 std: 0.02', 1),
    "positive_unitball": ('type: "positive_unitball"', 4),
}


@pytest.mark.parametrize("name", list(NEAR))
def test_a_filler_the_cpu_fuses_is_within_ulps(name):
    filler, ulps = NEAR[name]
    net_param = one_conv_net(filler)
    key = jax.random.key(11)
    state = fresh_train_state(SolverConfig(),
                              Network(net_param, Phase.TRAIN))(key)
    oracle = eager_state("SGD", net_param, key)
    assert set(differing(oracle, state)) <= {"params['conv'][0]"}
    np.testing.assert_array_max_ulp(
        np.asarray(oracle[0].params["conv"][0]),
        np.asarray(state[0].params["conv"][0]), maxulp=ulps)


def tiny(test_module: str):
    return importlib.import_module(test_module).TINY


NETS = {
    "lenet": lambda: (models.lenet(4), models.lenet_solver()),
    "cifar10_quick": lambda: (models.cifar10_quick(4),
                              models.cifar10_quick_solver()),
    "mnist_autoencoder": lambda: (models.mnist_autoencoder(4),
                                  models.lenet_solver()),
    "olmoe": lambda: (models.olmoe(**tiny("test_olmoe")),
                      models.olmoe_solver()),
    "ouro": lambda: (models.ouro(**tiny("test_ouro")),
                     models.ouro_solver()),
}


@pytest.mark.parametrize("name", list(NETS))
def test_a_solver_holds_the_eager_state_to_the_bit(name):
    """Through the front door: gaussian and xavier CNNs, the sparse
    gaussians of the autoencoder, a decoder's experts and a looped one."""
    net_param, solver_param = NETS[name]()
    solver = Solver(solver_param, net_param)
    oracle = eager_state(solver.config.solver_type, net_param, solver._key)
    assert differing(oracle, (solver.variables, solver.slots)) == []
    # in the net's layer order, as ``net.init`` builds it (a jitted call
    # alone hands its dicts back sorted by key)
    for eager, held in ((oracle[0].params, solver.variables.params),
                        (oracle[0].state, solver.variables.state),
                        (oracle[1], solver.slots)):
        assert list(eager) == list(held)
    slots = jax.tree_util.tree_leaves(solver.slots)
    assert slots and all(not np.asarray(s).any() for s in slots)


# the step bias of a state-space or delta-rule mixer starts as
# exp(u * a + b) of a uniform u (``ops/ssm.py``, ``ops/linear_attention.py``
# ``init``): the same product-and-sum the CPU backend contracts inside one
# program (then the inverse softplus on top of it: two ulps at most);
# every other leaf is the eager call's to the bit
STEP_BIAS = {
    "phi4_flash": ("test_phi4_flash", "mamba", 5),
    "qwen3_next": ("test_qwen3_next", "gdn", 3),
}


@pytest.mark.parametrize("name", list(STEP_BIAS))
def test_a_mixer_s_step_bias_is_within_ulps_and_the_rest_bit_equal(name):
    module, layer, blob = STEP_BIAS[name]
    net_param = getattr(models, name)(**tiny(module))
    solver = Solver(getattr(models, name + "_solver")(), net_param)
    oracle = eager_state(solver.config.solver_type, net_param, solver._key)
    off = differing(oracle, (solver.variables, solver.slots))
    assert all(p.startswith(f"params['{layer}") and p.endswith(f"[{blob}]")
               for p in off), off
    for lname, blobs in oracle[0].params.items():
        if lname.startswith(layer):
            np.testing.assert_array_max_ulp(
                np.asarray(blobs[blob]),
                np.asarray(solver.variables.params[lname][blob]), maxulp=2)


def test_two_seeds_share_one_executable():
    net_param = one_conv_net(FILLERS["gaussian"], outputs=7)
    cfg = SolverConfig(random_seed=3)
    first = Solver(cfg, net_param)
    second = Solver(dataclasses.replace(cfg, random_seed=4), net_param)
    jitted = fresh_train_state(cfg, first.train_net)
    assert jitted is fresh_train_state(cfg, second.train_net)
    assert jitted._cache_size() == 1
    a, b = (np.asarray(s.variables.params["conv"][0]) for s in (first, second))
    assert a.shape == b.shape and not np.array_equal(a, b)
    # the second net's layers learnt their shapes from a trace of their own
    assert second.train_net.blob_info().keys() == \
        first.train_net.blob_info().keys()
    # and a seed gives its state again
    again = Solver(cfg, net_param)
    assert differing((first.variables, first.slots),
                     (again.variables, again.slots)) == []


@pytest.mark.parametrize("change", ["solver_type", "batch", "feed_shape",
                                    "filler", "phase"])
def test_another_state_is_another_callable(change):
    """The signature holds what a fresh state depends on."""
    cfg = SolverConfig(solver_type="SGD")
    net_param = one_conv_net(FILLERS["xavier"], outputs=8)
    net = Network(net_param, Phase.TRAIN)
    base = fresh_train_state(cfg, net)
    feed_shapes = None
    if change == "solver_type":
        cfg = SolverConfig(solver_type="Adam")
    elif change == "batch":
        net = Network(net_param, Phase.TRAIN, batch_override=3)
    elif change == "feed_shape":
        feed_shapes = {"data": (2, 5, 12, 12)}
    elif change == "filler":
        net = Network(one_conv_net(FILLERS["msra"], outputs=8), Phase.TRAIN)
    elif change == "phase":
        net = Network(net_param, Phase.TEST)
    other = fresh_train_state(cfg, net, feed_shapes)
    assert other is not base
    variables, slots = jax.eval_shape(other, jax.random.key(0))
    n_slots = {"SGD": 1, "Adam": 2}[cfg.solver_type]
    assert len(slots["conv"][0]) == n_slots
    assert variables.params["conv"][0].shape == (8, 5, 4, 4)


@pytest.mark.parametrize("name", ["lenet", "cifar10_quick", "olmoe"])
def test_the_abstract_state_is_the_solver_s(name):
    net_param, solver_param = NETS[name]()
    solver = Solver(solver_param, net_param)
    jitted = fresh_train_state(solver.config, solver.train_net)
    traces = jitted._cache_size()
    variables, slots = abstract_train_state(solver.config, solver.train_net)
    assert jitted._cache_size() == traces == 1  # the same callable's trace
    held = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        (solver.variables, solver.slots))
    assert (variables, slots) == held
    # and of a net no solver has met, nothing materializes
    fresh = Network(one_conv_net(FILLERS["xavier"], outputs=9), Phase.TRAIN)
    leaves = jax.tree_util.tree_leaves(
        abstract_train_state(solver.config, fresh))
    assert leaves and all(
        isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)


def init_spans(since: int):
    return [s[4] for s in flight()[0][since:] if s[0] == "sn.solver.init"]


@pytest.mark.parametrize("build", ["first", "second"])
def test_the_init_span_counts_its_one_program(build):
    """``programs`` = 1 on every build; the first build of a net compiles
    that one, the second compiles nothing (the jit cache's hit)."""
    outputs = {"first": 10, "second": 11}[build]
    net_param = one_conv_net(FILLERS["msra"], outputs=outputs)
    cfg = SolverConfig(solver_type="Nesterov", random_seed=5)
    if build == "second":
        Solver(cfg, net_param)
    since = len(flight()[0])
    Solver(dataclasses.replace(cfg, random_seed=6), net_param)
    (span,) = init_spans(since)
    assert span["programs"] == 1
    assert span["params"] == outputs * 5 * 4 * 4 + outputs
    if build == "first":
        assert span["compiles"] >= 1
    else:
        assert span.get("compiles", 0) == 0


def test_the_held_callables_are_bounded():
    cfg = SolverConfig()
    for outputs in range(12, 12 + solver_mod._FRESH_STATES_HELD + 2):
        fresh_train_state(
            cfg, Network(one_conv_net(FILLERS["constant"], outputs),
                         Phase.TRAIN))
    assert len(solver_mod._FRESH_STATES) == solver_mod._FRESH_STATES_HELD


def test_an_init_that_raises_raises_through_the_program():
    """A net whose shapes do not fit fails at construction with the
    layer's own error, as the eager init did."""
    bad = parse("""
        name: "bad"
        layer { name: "data" type: "Input" top: "data"
                input_param { shape { dim: 2 dim: 3 } } }
        layer { name: "conv" type: "Convolution" bottom: "data" top: "conv"
                convolution_param { num_output: 2 kernel_size: 3 } }
    """)
    with pytest.raises(Exception) as eager:
        Network(bad, Phase.TRAIN).init(jax.random.key(0))
    with pytest.raises(type(eager.value)):
        Solver(SolverConfig(), bad)
