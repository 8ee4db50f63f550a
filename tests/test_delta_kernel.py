"""The gated delta rule's Pallas kernels (``ops/linear_attention.py``, PR 48)
in interpret mode on the CPU against the definition
``gated_delta_rule_steps`` (one ``lax.scan`` over time under plain
autodiff), and the rule that says which of the two paths
``gated_delta_rule`` takes.

Heads of 128, the narrowest the kernels tile.  Under f32 inputs every
operand is f32 and both sides differ by summation order only (the
kernels read 4e-7 to 4e-6, the XLA chunked form 4e-7 to 3e-6; the limit
is ``tests/test_linear_attention.py``'s).  Under bf16 inputs the matmul
operands are bf16 exactly where the XLA chunked form hands bf16 over, so
the kernels must lie no further from the un-rounded definition than that
form does on the same inputs: the output reads 4.24e-3 against 4.22e-3
(the inverse's three-pass products against ``HIGHEST``: 1.05 x is the
room), the seven gradients 2.6e-3 to 4.7e-3 against 4.5e-3 to 5.5e-3.
The compile for a described v5e at the cell's size is in
``tests/test_moe_grad_layout.py``, the one file that describes a
topology.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.ops import linear_attention as la

TOL = 2e-5
ROOM = 1.05
WHAT = ("o", "q", "k", "v", "a", "b", "A_log", "dt_bias")
D = 128


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def core_inputs(seq, dtype, hk=1, hv=2, batch=1, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    a_log = jnp.log(jnp.asarray(rng.uniform(0.05, 16.0, hv), jnp.float32))
    return (f(batch, seq, hk, D).astype(dtype), f(batch, seq, hk, D).astype(dtype),
            f(batch, seq, hv, D).astype(dtype), f(batch, seq, hv),
            f(batch, seq, hv), a_log, f(hv) - 2.0)


def kernel(*a):
    return la.gated_delta_rule_kernel(*a, interpret=True)


def output_and_gradients(rule, args):
    weight = jnp.asarray(np.random.default_rng(1).standard_normal(
        args[2].shape), jnp.float32)
    loss = lambda *x: jnp.sum(rule(*x).astype(jnp.float32) * weight)
    return (jax.jit(rule)(*args),) + jax.jit(
        jax.grad(loss, argnums=range(7)))(*args)


# (tokens, dtype, key heads, value heads, (HEAD_BLOCK, CHUNK_BLOCK)):
# whole chunks with two value heads a key head; a ragged last chunk; one
# padded chunk; two head blocks of two chunks a grid step, the last chunk
# all padding; bf16 operands, two key heads a block
CASES = [
    (128, jnp.float32, 1, 2, None), (100, jnp.float32, 1, 2, None),
    (24, jnp.float32, 1, 2, None), (160, jnp.float32, 2, 2, (1, 2)),
    (100, jnp.bfloat16, 2, 4, None)]


@pytest.fixture(scope="module", params=CASES, ids=lambda c: (
    f"S{c[0]}-{jnp.dtype(c[1]).name}-{c[2]}x{c[3]}" + ("-blocks" if c[4] else "")))
def core(request):
    seq, dtype, hk, hv, blocks = request.param
    args = core_inputs(seq, dtype, hk, hv)
    with pytest.MonkeyPatch.context() as patch:
        if blocks:  # shapes no other case has: the jitted entries are fresh
            patch.setattr(la, "HEAD_BLOCK", blocks[0])
            patch.setattr(la, "CHUNK_BLOCK", blocks[1])
        got = output_and_gradients(kernel, args)
    exact = tuple(x.astype(jnp.float32) for x in args)
    want = output_and_gradients(la.gated_delta_rule_steps, exact)
    chunked = None
    if dtype != jnp.float32:
        chunked = output_and_gradients(la.gated_delta_rule, args)
    return args, got, want, chunked


@pytest.mark.parametrize("i", range(8), ids=WHAT)
def test_kernels_match_the_definition(core, i):
    """The output and all seven gradients: f32 to the chunked form's own
    tolerance, bf16 no further from the definition than the XLA chunked
    form on the same inputs."""
    args, got, want, chunked = core
    like = args[2] if i == 0 else args[i - 1]
    assert got[i].shape == like.shape and got[i].dtype == like.dtype
    assert float(jnp.linalg.norm(want[i])) > 0
    if chunked is None:
        assert rel(got[i], want[i]) < TOL
    else:
        assert rel(got[i], want[i]) <= ROOM * rel(chunked[i], want[i]) < 3e-2


@pytest.mark.parametrize("case", ["random", "repeated keys", "nearly repeated"])
def test_the_inverse_by_joins_in_three_pass_products(case):
    """(I + A)^-1 from 2 x 2 blocks up, f32 operands as bf16 pairs: exact
    where keys repeat at beta = 1 (every entry below the diagonal 1),
    2e-5 of the largest entry elsewhere, and closer than the ten
    ``HIGHEST`` matmuls of the XLA form where keys nearly repeat (their
    16-row Neumann products cancel: 4e-4)."""
    n = la.CHUNK
    rng = np.random.default_rng(3)
    lower = np.tril(np.ones((n, n)), -1)
    a = {"random": rng.uniform(-1, 1, (2, n, n)) * lower,
         "repeated keys": lower[None],
         "nearly repeated": (1 - 0.01 * rng.uniform(0, 1, (2, n, n))) * lower
         }[case]
    a = jnp.asarray(a, jnp.float32)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    far = lambda x: np.abs(np.asarray(x) - want).max() / np.abs(want).max()
    assert far(la._join_inverse(a, la._mm32)) <= (
        0 if case == "repeated keys" else 4e-5)
    assert far(la._join_inverse(a, la._mm_exact)) <= 1e-6
    if case == "nearly repeated":
        assert far(la._join_inverse(a, la._mm32)) < far(
            la._unit_lower_inverse(a))


@pytest.mark.parametrize("d_k,d_v,chunk,tiles", [
    (128, 128, 64, True), (256, 128, 64, True), (128, 256, 64, True),
    (128, 128, 32, False), (128, 64, 64, False), (8, 16, 64, False),
    (192, 128, 64, False)])
def test_who_takes_the_kernels(d_k, d_v, chunk, tiles, monkeypatch):
    """A TPU, heads in whole lane groups, chunks of 64: read off the
    backend and the shapes.  The CPU never does."""
    assert jax.default_backend() == "cpu"
    assert not la.takes_kernel(d_k, d_v, chunk)
    assert la.kernel_tiles(d_k, d_v, chunk) == tiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert la.takes_kernel(d_k, d_v, chunk) == tiles


def test_the_cpu_takes_the_chunked_form_at_any_width(monkeypatch):
    """``gated_delta_rule`` on this backend never reaches a kernel, even
    at heads the kernels tile, and the kernels' entry refuses heads they
    do not."""
    monkeypatch.setattr(la.pl, "pallas_call", None)  # a kernel would raise
    args = core_inputs(64, jnp.float32, 1, 1)
    want = la.gated_delta_rule_steps(*args)
    assert rel(la.gated_delta_rule(*args), want) < TOL
    monkeypatch.undo()
    narrow = tuple(x[..., :64] if x.ndim == 4 else x for x in args)
    with pytest.raises(ValueError, match="do not tile"):
        kernel(*narrow)
    with pytest.raises(ValueError, match="key heads"):
        kernel(*core_inputs(64, jnp.float32, 2, 3))


def test_both_paths_give_the_same_numbers():
    """The chunked form and the kernels on one input: what a net trained
    on the CPU and continued on a TPU sees."""
    args = core_inputs(100, jnp.float32)
    assert rel(kernel(*args), la.gated_delta_rule(*args)) < TOL


def test_what_the_kernels_keep_is_chunk_states_never_a_state_a_token():
    """The residuals: the seven inputs and the state at each chunk's
    start, [B, chunks, H_v, d_k, d_v] f32, as many bytes as
    ``saved_state_bytes`` says."""
    args = core_inputs(100, jnp.float32)
    _, residuals = la._kernel_vjp_fwd(*args, True)
    assert len(residuals) == 8
    assert [x.shape for x in residuals[:7]] == [x.shape for x in args]
    starts = residuals[7]
    assert starts.shape == (1, 2, 2, D, D) and starts.dtype == jnp.float32
    assert starts.nbytes == la.saved_state_bytes(1, 100, 2, D, D)
    assert not np.asarray(starts[:, 0]).any()  # the rule starts from zero
    assert np.asarray(starts[:, 1]).any()


def test_a_layer_reports_the_path_it_takes(monkeypatch):
    """What ``Solver._fence_stats`` reads off a ``GatedDeltaNet`` layer
    (``gdn_kernel_layers``): false on the CPU, true on a TPU at heads the
    kernels tile, false there at the tests' 8 x 16 heads."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.layers_dsl import GatedDeltaNetLayer
    from sparknet_tpu.ops.registry import create_layer

    def made(backend, d_k, d_v):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        layer = create_layer(GatedDeltaNetLayer("g", ["x"], 2, 4, d_k, d_v),
                             Phase.TRAIN)
        layer.init(jax.random.key(0), [(1, 4096, 64)])
        return layer.kernel, layer.chunk, layer.saved_bytes

    kept = lambda d_k, d_v: 64 * 4 * d_k * d_v * 4
    assert made("cpu", D, D) == (False, 64, kept(D, D))
    assert made("tpu", D, D) == (True, 64, kept(D, D))
    assert made("tpu", 8, 16) == (False, 64, kept(8, 16))


def test_the_layer_hands_the_kernels_the_convolutions_output_in_place(
        monkeypatch):
    """``GatedDeltaNet`` on the kernels' path (told here that it is on a TPU,
    with the kernels interpreted): q, k and v are cut out of the
    convolution's one output by the index maps, the normalisation is the
    kernels', the cotangents come back side by side; the output and every
    gradient are the XLA path's."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.layers_dsl import GatedDeltaNetLayer, NetParam, RDDLayer

    shape = (1, 100, 32)
    net = Network(NetParam("t", RDDLayer("x", shape=list(shape)),
                           GatedDeltaNetLayer("g", ["x"], 1, 2, D, D)),
                  Phase.TRAIN)
    variables = net.init(jax.random.key(0), None, None)
    blobs = variables.params["g"]
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    weight = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def loss(blobs, x):
        variables.params["g"] = blobs
        return jnp.sum(net.apply(variables, {"x": x}, rng=None)[0]["g"]
                       * weight)

    # one jit a path: the second trace must see the patched module
    both = lambda: jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    want = both()(blobs, x)
    assert not net.layers[-1].kernel
    real = la.pl.pallas_call
    monkeypatch.setattr(la.pl, "pallas_call", lambda *a, **kw: real(
        *a, **{**kw, "interpret": True}))
    monkeypatch.setattr(la, "takes_kernel", lambda *a: la.kernel_tiles(*a))
    got = both()(blobs, x)
    assert net.layers[-1].kernel
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.linalg.norm(w)) > 0 and rel(g, w) < TOL
