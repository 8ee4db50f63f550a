"""A layer that holds a share of its experts moves only its live rows
(``ops/moe.py``, PR 49): ``gather_live`` / ``combine_live`` walk
``live_tiles(held pairs)`` tiles of 512 sorted rows, a trip count read on
the device, at ANY routing: one path, the same layer whatever it holds.

128 sigmoid-routed experts, top-4, of which this "chip" holds [8, 12);
1,200 tokens, so 4,800 pairs in 10 tiles of 512 (the last one short).  A
select bias a token and an expert (10 on the outputs a token shall take)
puts an exact number of pairs on the held experts: 0, 1, 511, 512, 513,
the router's own, 1,024 (the capacity PR 35 gave these shapes), one more,
and all of them.  Each case against the path over all rows (``_all_rows``
on a layer that holds EVERY expert, the absent ones' matrices zero: the
same products, summed in another order) and against the plain reference
(``benchmarks/reference/joyai_flash.py``), f32 on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_flash as ref
from sparknet_tpu.ops import moe

E, HELD, FIRST, K, D, H, T = 128, 4, 8, 4, 16, 24, 1200
TOL = 1e-5
OLD_CAPACITY = 1024  # 6 x the level share of 4,800 pairs, in tiles of 512
# name -> pairs on the held experts (None: the router's own choice)
CASES = {"none_held": 0, "one": 1, "tile_less_one": 511, "tile": 512,
         "tile_and_one": 513, "routers_own": None,
         "old_capacity": OLD_CAPACITY, "one_more": OLD_CAPACITY + 1,
         "all_held": T * K}
LEAVES = ("x", "router", "w_gate", "w_up", "w_down")


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def layer(case, act="swiglu"):
    """-> (params, x, bias [T, E]) of one seeded layer under ``case``'s
    routing."""
    pairs = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    draw = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), jnp.float32)
    params = [draw(E, D), draw(HELD, H, D)]
    params += ([draw(HELD, H, D), draw(HELD, D, H)] if act == "swiglu" else
               [draw(HELD, H), draw(HELD, D, H), draw(HELD, D)])
    bias = np.tile(0.05 * rng.standard_normal(E), (T, 1))
    if pairs is not None:
        # token t takes ``held[t]`` held outputs and K - held[t] others
        held = pairs // T + (np.arange(T) < pairs % T)
        slot = np.arange(K)[None, :]
        chosen = np.where(slot < held[:, None],
                          FIRST + (np.arange(T)[:, None] + slot) % HELD,
                          E // 2 + slot)
        np.put_along_axis(bias, chosen, 10.0, axis=1)
    return params, draw(T, D) / 0.3, jnp.asarray(bias, jnp.float32)


def run(params, x, bias, act="swiglu", first=FIRST):
    """-> (y, load, gradients to x and every blob of a loss over y)."""
    def loss(params, x):
        y, _, _, _, load = moe.moe_dropless(
            params, x, top_k=K, expert_act=act, norm_topk_prob=True,
            scoring="sigmoid", select_bias=bias, scale=2.5,
            first_expert=first)
        return jnp.sum(y ** 2), (y, load)

    (_, (y, load)), (g_params, g_x) = jax.jit(jax.value_and_grad(
        loss, (0, 1), has_aux=True))(params, x)
    return y, load, [g_x, *g_params]


def over_all_rows(params, x, bias, act="swiglu"):
    """The same layer through ``_all_rows``: a layer that holds EVERY
    expert, the absent ones' blobs zero (they add nothing, as the pairs
    of an absent expert add nothing); the gradients cut to the share."""
    whole = [params[0]] + [
        jnp.zeros((E,) + p.shape[1:], p.dtype).at[FIRST:FIRST + HELD].set(p)
        for p in params[1:]]
    y, load, grads = run(whole, x, bias, act, first=0)
    return y, load, grads[:2] + [g[FIRST:FIRST + HELD] for g in grads[2:]]


@pytest.fixture(scope="module")
def results():
    """Per case: the layer as it is, the path over all rows, and the
    reference told the same share."""
    out = {}
    for case in CASES:
        params, x, bias = layer(case)
        cfg = dict(top_k=K, scale=2.5, first_expert=FIRST)
        with jax.default_matmul_precision("highest"):
            want, g_want = jax.value_and_grad(
                lambda p, x: jnp.sum(ref.moe(p, x, bias, cfg)[0] ** 2),
                (0, 1))(params + [jnp.zeros((1, D))] * 2
                        + [jnp.zeros((D, 1))], x)
        out[case] = dict(now=run(params, x, bias),
                         was=over_all_rows(params, x, bias),
                         ref=[g_want[1], *g_want[0][:4]], loss=want)
    return out


def held_pairs(load):
    return int(np.asarray(load)[FIRST:FIRST + HELD].sum())


@pytest.mark.parametrize("pairs, tiles", [
    (0, 0), (1, 1), (511, 1), (512, 1), (513, 2),
    (2560, 5),  # the level share of Qwen3-Next's 40,960 pairs
    (6144, 12), (15360, 30),  # the capacities PR 35's rule gave the cells
    (16384, 32), (32768, 64), (40960, 80)])
def test_live_tiles_is_one_count_for_the_device_and_the_host(pairs, tiles):
    assert moe.live_tiles(pairs) == tiles
    assert int(jax.jit(moe.live_tiles)(jnp.int32(pairs))) == tiles


@pytest.mark.parametrize("case", CASES)
def test_the_cases_lie_where_their_names_say(results, case):
    held = held_pairs(results[case]["now"][1])
    want = CASES[case]
    assert held == want if want is not None else 0 < held < 512
    assert T * K % moe.CAPACITY_TILE  # the last tile of the pairs is short


@pytest.mark.parametrize("case", CASES)
def test_output_and_load_are_those_of_the_path_over_all_rows(results, case):
    (y, load, _), (y_was, load_was, _) = (results[case][k]
                                          for k in ("now", "was"))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_was))
    assert int(np.asarray(load).sum()) == T * K
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
@pytest.mark.parametrize("case", [c for c in CASES if CASES[c] != 0])
def test_gradients_match_the_reference_given_the_same_share(results, case,
                                                            leaf):
    i = LEAVES.index(leaf)
    assert rel(results[case]["now"][2][i], results[case]["ref"][i]) <= TOL


@pytest.mark.parametrize("case", CASES)
def test_rows_past_the_last_live_tile_reach_nothing(results, case,
                                                    monkeypatch):
    """The buffers the walk fills tile by tile start as NaN (on the chip
    they start as whatever the memory held): not a bit of y, of ``load``
    or of a gradient moves, so no sum and no product read a dead row."""
    params, x, bias = layer(case)
    made = []

    def poisoned(shape, dtype):
        made.append(shape)
        return jnp.full(
            shape, jnp.nan if jnp.issubdtype(dtype, jnp.floating) else 0,
            dtype)

    monkeypatch.setattr(moe, "_buffer", poisoned)
    jax.clear_caches()
    got = run(params, x, bias)
    jax.clear_caches()
    assert (5120, D) in made  # rows and d out: all the pairs, whole tiles
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(results[case]["now"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("case", ["one", "tile_and_one", "routers_own",
                                  "one_more", "all_held"])
def test_experts_with_biases_move_their_live_rows_too(case, monkeypatch):
    params, x, bias = layer(case, act="relu")
    want = run(params, x, bias, act="relu")
    y_was, load_was, grads_was = over_all_rows(params, x, bias, act="relu")
    np.testing.assert_array_equal(np.asarray(want[1]), np.asarray(load_was))
    assert rel(want[0], y_was) <= TOL
    for a, b in zip(want[2], grads_was):
        assert rel(a, b) <= TOL
    monkeypatch.setattr(moe, "_buffer", lambda shape, dtype: jnp.full(
        shape, jnp.nan if jnp.issubdtype(dtype, jnp.floating) else 0, dtype))
    jax.clear_caches()
    got = run(params, x, bias, act="relu")
    jax.clear_caches()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def sub_jaxprs(eqn):
    """(parameter, position, jaxpr) of every jaxpr an equation carries."""
    for key, v in eqn.params.items():
        for i, sub in enumerate(v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield key, i, sub


def all_eqns(jaxpr, in_loop=False):
    """(equation, whether a ``while`` holds it) of ``jaxpr``, any depth."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        for _, _, sub in sub_jaxprs(eqn):
            yield from all_eqns(sub, in_loop or eqn.primitive.name == "while")


@pytest.mark.parametrize("act, shapes, eqns", [
    ("swiglu", ((8, 16), (8, 24, 16), (8, 24, 16), (8, 16, 24)), 146),
    ("relu", ((8, 16), (8, 24, 16), (8, 24), (8, 16, 24), (8, 16)), 158),
    ("relu", ((8, 16), (8, 24, 16), (8, 16, 24)), 135)],
    ids=["swiglu", "relu_biases", "relu"])
def test_a_layer_that_holds_every_expert_lowers_as_before(act, shapes, eqns):
    """No loop, no ``cond``, and the equations of forward + backward
    counted on the commit before PR 35 (whose StableHLO the layer's still
    equals, byte for byte: PERF.md section 6)."""
    rng = np.random.default_rng(0)
    params = [jnp.asarray(rng.standard_normal(s), jnp.float32)
              for s in shapes]
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: jnp.sum(moe.moe_dropless(
        p, x, top_k=2, expert_act=act)[0] ** 2), (0, 1)))(params, x)
    names = [e.primitive.name for e, _ in all_eqns(jaxpr.jaxpr)]
    assert len(names) == eqns
    assert "cond" not in names and "while" not in names


def moved_rows(eqn):
    """Rows a gather or a scatter moves: its indices' leading extent."""
    if eqn.primitive.name == "gather":
        return eqn.outvars[0].aval.shape[0] if eqn.outvars[0].aval.shape else 1
    updates = eqn.invars[2].aval.shape
    return updates[0] if updates else 1


@pytest.mark.parametrize("act", ["swiglu", "relu"])
def test_no_row_mover_of_the_share_is_wider_than_a_tile(act):
    """Forward and backward of the share-holding path (``_held_rows``, on
    pairs sorted already): one path (no ``cond``), every gather and every
    scatter inside a ``while`` whose trip count is traced, each moving
    one tile of 512 rows, none outside the loops at all, and outside the
    loops no elementwise equation over the [R, ·] arrays either."""
    params, x, bias = layer("one_more", act)
    weights = jnp.full((T, K), 0.25, jnp.float32)
    flat, order, group_sizes, _ = moe.sort_pairs(
        jnp.asarray(np.random.default_rng(0).integers(0, E, (T, K)),
                    jnp.int32), E, HELD, FIRST)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, w, rest: jnp.sum(moe._held_rows(
        x, w, rest, flat, order, group_sizes, act) ** 2), (0, 1, 2)))(
        x, weights, tuple(params[1:]))
    eqns = list(all_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e, _ in eqns]
    assert "cond" not in names
    movers = [(e, inside) for e, inside in eqns
              if e.primitive.name in ("gather", "scatter", "scatter-add")]
    assert movers and all(inside for _, inside in movers)
    assert {moved_rows(e) for e, _ in movers} == {moe.CAPACITY_TILE}
    wide = [e for e, inside in eqns if not inside
            and e.primitive.name in ("add", "add_any", "mul", "logistic",
                                     "max", "select_n", "convert_element_type")
            and e.outvars[0].aval.shape[:1] == (5120,)
            and e.outvars[0].aval.ndim == 2]
    assert wide == []
    loops = [e for e, _ in eqns if e.primitive.name == "while"]
    # forward: gather, activation, combine; backward: the three again (the
    # second combine feeds nothing: XLA drops it) and four cotangents
    # (combine's, the activation's, the sum over the rows' two readers,
    # the gather's); with biases seven forward (three gathers of theirs,
    # two elementwise passes), those again and six cotangents
    assert len(loops) == (10 if act == "swiglu" else 20)
    for loop in loops:  # carry (i, trips, ...): the trips a value, no literal
        trips = loop.invars[loop.params["cond_nconsts"]
                            + loop.params["body_nconsts"] + 1]
        assert trips.aval.shape == () and not hasattr(trips, "val")


# ------------------------------------------- the benchmark's reader of it
@pytest.mark.parametrize("fences, want", [
    ([{"moe_layers": 5, "moe_compact_layers": 4, "moe_pairs_held": 9},
      {"moe_layers": 5, "moe_compact_layers": 5, "moe_pairs_held": 7}], 90.0),
    ([{"moe_layers": 5, "moe_compact_layers": 0, "moe_pairs_held": 9}], 0.0),
    # this PR's fences: one more counter beside those the reader takes
    ([{"moe_layers": 4, "moe_compact_layers": 4, "moe_pairs_held": 9,
       "moe_rows_moved": 2048}], 100.0),
    # the parent of PR 35: held pairs counted, no layer counted at a capacity
    ([{"moe_layers": 5, "moe_pairs_held": 9}], None),
    ([], None)], ids=["mean_over_fences", "never", "rows_moved_beside_it",
                      "parent", "no_fence"])
def test_compact_share_reads_the_fences_counter(fences, want):
    from benchmarks.harness import load_by_name

    reader = load_by_name("metrics", "moe.compact_share")
    summary = {"decoder_scopes": {"scope_s": {}, "fences": fences}}
    assert reader.read(summary, {}) == want
    assert reader.read(None, {}) is None
