"""A layer that holds a share of its experts dispatches at a capacity read
off its shapes when the step's held pairs fit it, and over all T·k rows
when they do not (``ops/moe.py``, PR 35): the same layer either way.

128 sigmoid-routed experts, top-4, of which this "chip" holds [8, 12):
``capacity`` is 6 x the level share of the pairs in whole tiles of 512
rows, so 512 at the sizes here.  A select bias of 10 on m held and 4 - m
other outputs sends every token's pairs to exactly those, which puts
m·T pairs on the held experts: 0, fewer than the capacity, exactly the
capacity, one more, and all of them.  Each case against the path over all
rows (the layer as it was: ``capacity`` patched to 0, which is also what
a layer that holds every expert reads) and against the plain reference
(``benchmarks/reference/joyai_flash.py``), f32 on the CPU: the same
products, summed in another order.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_flash as ref
from sparknet_tpu.ops import moe

E, HELD, FIRST, K, D, H = 128, 4, 8, 4, 16, 24
TOL = 1e-5
# name -> (tokens, held experts among each token's four; None: the
# router's own choice under a small bias)
CASES = {"none_held": (512, 0), "routers_own": (300, None),
         "under": (300, 1), "exactly": (512, 1), "one_more": (171, 3),
         "all_held": (171, 4)}
LEAVES = ("x", "router", "w_gate", "w_up", "w_down")


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def layer(case, act="swiglu"):
    """-> (params, x, bias) of one seeded layer under ``case``'s routing."""
    tokens, m = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
    params = [draw(E, D), draw(HELD, H, D)]
    params += ([draw(HELD, H, D), draw(HELD, D, H)] if act == "swiglu" else
               [draw(HELD, H), draw(HELD, D, H), draw(HELD, D)])
    bias = 0.05 * rng.standard_normal(E)
    if m is not None:
        bias[list(range(FIRST, FIRST + m))
             + list(range(E // 2, E // 2 + K - m))] = 10.0
    return params, draw(tokens, D) / 0.3, jnp.asarray(bias, jnp.float32)


def run(params, x, bias, act="swiglu"):
    """-> (y, load, gradients to x and every blob of a loss over y)."""
    def loss(params, x):
        y, _, _, _, load = moe.moe_dropless(
            params, x, top_k=K, expert_act=act, norm_topk_prob=True,
            scoring="sigmoid", select_bias=bias, scale=2.5,
            first_expert=FIRST)
        return jnp.sum(y ** 2), (y, load)

    (_, (y, load)), (g_params, g_x) = jax.jit(jax.value_and_grad(
        loss, (0, 1), has_aux=True))(params, x)
    return y, load, [g_x, *g_params]


@contextlib.contextmanager
def one_path():
    """The layer as it was: no capacity, the path over all rows alone."""
    real, moe.capacity = moe.capacity, lambda *a: 0
    try:
        yield
    finally:
        moe.capacity = real


@pytest.fixture(scope="module")
def results():
    """Per case: the layer as it is, as it was (one path over all rows),
    and the reference told the same share."""
    out = {}
    for case in CASES:
        params, x, bias = layer(case)
        now = run(params, x, bias)
        with one_path():
            was = run(params, x, bias)
        cfg = dict(top_k=K, scale=2.5, first_expert=FIRST)
        with jax.default_matmul_precision("highest"):
            want, g_want = jax.value_and_grad(
                lambda p, x: jnp.sum(ref.moe(p, x, bias, cfg)[0] ** 2),
                (0, 1))(params + [jnp.zeros((1, D))] * 2
                        + [jnp.zeros((D, 1))], x)
        out[case] = dict(now=now, was=was,
                         ref=[g_want[1], *g_want[0][:4]], loss=want)
    return out


def held_pairs(load):
    return int(np.asarray(load)[FIRST:FIRST + HELD].sum())


@pytest.mark.parametrize("pairs, held, experts, rows", [
    (32768, 8, 256, 6144),  # the JoyAI cell
    (131072, 64, 64, 0),  # OLMoE: every expert held, one path
    (256, 4, 16, 0),  # a large share of few experts: nothing to gain
    (2048, 4, 128, 512), (1200, 4, 128, 512), (684, 4, 128, 512),
    (4096, 8, 128, 1536), (4104, 8, 128, 2048)])
def test_capacity_is_read_off_the_shapes(pairs, held, experts, rows):
    assert moe.capacity(pairs, held, experts) == rows
    assert rows % moe.CAPACITY_TILE == 0 and rows < pairs


@pytest.mark.parametrize("case, fits", [
    ("none_held", True), ("routers_own", True), ("under", True),
    ("exactly", True), ("one_more", False), ("all_held", False)])
def test_the_cases_lie_where_their_names_say(results, case, fits):
    tokens, m = CASES[case]
    cap = moe.capacity(tokens * K, HELD, E)
    held = held_pairs(results[case]["now"][1])
    assert cap == 512
    assert bool(moe.takes_compact(held, cap)) is fits
    want = {"none_held": 0, "exactly": cap, "one_more": cap + 1,
            "all_held": tokens * K}.get(case)
    assert held == want if want is not None else 0 < held < cap


@pytest.mark.parametrize("case", CASES)
def test_output_and_load_are_those_of_the_path_over_all_rows(results, case):
    (y, load, _), (y_was, load_was, _) = (results[case][k]
                                          for k in ("now", "was"))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_was))
    assert int(np.asarray(load).sum()) == CASES[case][0] * K
    if held_pairs(load):
        assert rel(y, y_was) <= TOL
    else:
        assert not np.asarray(y).any() and not np.asarray(y_was).any()


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("case", CASES)
def test_gradients_are_those_of_the_path_over_all_rows(results, case, leaf):
    i = LEAVES.index(leaf)
    got, was = results[case]["now"][2][i], results[case]["was"][2][i]
    assert np.isfinite(np.asarray(got)).all()
    if np.asarray(was).any():
        assert rel(got, was) <= TOL
    else:
        assert not np.asarray(got).any()


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("case", ["routers_own", "exactly", "one_more"])
def test_gradients_match_the_reference_given_the_same_share(results, case,
                                                            leaf):
    i = LEAVES.index(leaf)
    assert rel(results[case]["now"][2][i], results[case]["ref"][i]) <= TOL


@pytest.mark.parametrize("case", CASES)
def test_the_step_runs_the_path_the_predicate_names(case, monkeypatch):
    """The other path is poisoned: whatever it computes never shows, in
    the output or in a gradient, so one predicate picked both passes."""
    params, x, bias = layer(case)
    want = run(params, x, bias)
    tokens, _ = CASES[case]
    fits = bool(moe.takes_compact(held_pairs(want[1]),
                                  moe.capacity(tokens * K, HELD, E)))
    other = "_all_rows" if fits else "_capacity_rows"
    real = getattr(moe, other)
    monkeypatch.setattr(moe, other, lambda x, w, rest, *a, **kw: jnp.nan * (
        real(x, w, rest, *a, **kw)))
    jax.clear_caches()
    got = run(params, x, bias)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["under", "one_more"])
def test_experts_with_biases_take_both_paths_too(case):
    params, x, bias = layer(case, act="relu")
    y, load, grads = run(params, x, bias, act="relu")
    with one_path():
        y_was, load_was, grads_was = run(params, x, bias, act="relu")
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_was))
    assert rel(y, y_was) <= TOL
    for a, b in zip(grads, grads_was):
        assert rel(a, b) <= TOL


def sub_jaxprs(eqn):
    """(parameter, position, jaxpr) of every jaxpr an equation carries."""
    for key, v in eqn.params.items():
        for i, sub in enumerate(v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield key, i, sub


def all_eqns(jaxpr):
    """Every equation of ``jaxpr``, at any depth."""
    for eqn in jaxpr.eqns:
        yield eqn
        for _, _, sub in sub_jaxprs(eqn):
            yield from all_eqns(sub)


@pytest.mark.parametrize("act, shapes, eqns", [
    ("swiglu", ((8, 16), (8, 24, 16), (8, 24, 16), (8, 16, 24)), 146),
    ("relu", ((8, 16), (8, 24, 16), (8, 24), (8, 16, 24), (8, 16)), 158),
    ("relu", ((8, 16), (8, 24, 16), (8, 16, 24)), 135)],
    ids=["swiglu", "relu_biases", "relu"])
def test_a_layer_that_holds_every_expert_lowers_as_before(act, shapes, eqns):
    """No ``cond``, and the equations of forward + backward counted on the
    commit before PR 35 (whose StableHLO the layer's still equals, byte
    for byte: PERF.md section 6)."""
    rng = np.random.default_rng(0)
    params = [jnp.asarray(rng.standard_normal(s), jnp.float32)
              for s in shapes]
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(moe.moe_dropless(
        p, x, top_k=2, expert_act=act)[0] ** 2), (0, 1)))(params, x)
    names = [e.primitive.name for e in all_eqns(jaxpr.jaxpr)]
    assert len(names) == eqns
    assert "cond" not in names


def branch_arrays(jaxpr, elements, found=None, inside=None):
    """{branch index: arrays of at least ``elements`` elements made inside
    that branch of any ``cond``, at any depth}."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if inside is not None:
            found.setdefault(inside, []).extend(
                v.aval.shape for v in eqn.outvars
                if hasattr(v.aval, "shape")
                and int(np.prod(v.aval.shape)) >= elements)
        for key, i, sub in sub_jaxprs(eqn):
            branch = (eqn.primitive.name == "cond" and key == "branches"
                      and inside is None)
            branch_arrays(sub, elements, found, i if branch else inside)
    return found


def test_the_compact_branch_makes_no_array_of_all_pairs():
    """Forward and backward of a share at its capacity: in BOTH ``cond``s
    (the forward's and the ``custom_vjp`` backward's) the branch that
    ``takes_compact`` picks (index 1) makes no array of T·k rows of the
    narrower width, the other one does (so the count sees them), and no
    such array leaves the forward for the backward."""
    params, x, bias = layer("exactly")
    tokens = x.shape[0]
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(moe.moe_dropless(
        p, x, top_k=K, expert_act="swiglu", scoring="sigmoid",
        select_bias=bias, first_expert=FIRST)[0] ** 2), (0, 1)))(params, x)
    conds = [e for e in all_eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]
    assert len(conds) == 2  # one a pass
    found = branch_arrays(jaxpr.jaxpr, tokens * K * min(D, H))
    assert found.get(1, []) == []
    assert len(found[0]) >= 8  # rows, h, out, per_pair and their cotangents
    wide = [v.aval.shape for e in conds for v in e.outvars
            if int(np.prod(v.aval.shape)) >= tokens * K * min(D, H)]
    assert wide == []


# ------------------------------------------- the benchmark's reader of it
@pytest.mark.parametrize("fences, want", [
    ([{"moe_layers": 5, "moe_compact_layers": 4, "moe_pairs_held": 9},
      {"moe_layers": 5, "moe_compact_layers": 5, "moe_pairs_held": 7}], 90.0),
    ([{"moe_layers": 5, "moe_compact_layers": 0, "moe_pairs_held": 9}], 0.0),
    # the parent of PR 35: held pairs counted, no layer counted at a capacity
    ([{"moe_layers": 5, "moe_pairs_held": 9}], None),
    ([], None)], ids=["mean_over_fences", "never", "parent", "no_fence"])
def test_compact_share_reads_the_fences_counter(fences, want):
    from benchmarks.harness import load_by_name

    reader = load_by_name("metrics", "moe.compact_share")
    summary = {"decoder_scopes": {"scope_s": {}, "fences": fences}}
    assert reader.read(summary, {}) == want
    assert reader.read(None, {}) is None
