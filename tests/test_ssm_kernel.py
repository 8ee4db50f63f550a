"""The selective scan's Pallas kernels (``ops/ssm.py``, PR 33) in interpret
mode on the CPU against the oracle ``selective_scan_steps`` (one
``lax.scan`` over time under plain autodiff), and the rule that says
which of the two paths ``selective_scan`` takes.

Same products in kernel and oracle, summed in another order: f32 inputs
read 0 to 4e-7 relative, bf16 ``c`` / B / C up to 3e-6 on d(c), which is
rounded to bf16 once in each (limit 1e-5; a kept state one step off, a
time block walked in the wrong order or a d-block's state handed to its
neighbour read 1e-2 or more).  The compile for a described v5e at the
cell's widths is in ``tests/test_moe_grad_layout.py``, the one file
that describes a topology.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.ops import ssm

TOL = 1e-5
# 3 d-blocks of 128 lanes, one f32 sublane group of state
D_INNER, D_STATE, BLOCK = 384, 8, 16


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def scan_inputs(batch, seq, dtype, d=D_INNER, n=D_STATE, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(batch, seq, d).astype(dtype), f(batch, seq, d) - 1.0,
            f(batch, seq, n).astype(dtype), f(batch, seq, n).astype(dtype),
            jnp.asarray(rng.uniform(-1, 1.5, (d, n)), jnp.float32), f(d))


def weighted(scan, shape):
    weight = jnp.asarray(np.random.default_rng(1).standard_normal(shape),
                         jnp.float32)
    return lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * weight)


def kernel(*a):
    return ssm.selective_scan_kernel(*a, block=BLOCK, interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("seq", [32, 40], ids=["whole", "padded"])
@pytest.mark.parametrize("batch", [1, 2], ids=["b1", "b2"])
def test_kernels_match_the_time_step_scan(batch, seq, dtype):
    """Forward and all six gradients: 2 and 3 time blocks of 16 steps (40
    = 2.5 blocks: the last is padded with steps that leave the state
    alone), 3 d-blocks, the state carried across time blocks per
    d-block and per sequence."""
    args = scan_inputs(batch, seq, dtype)
    assert ssm.time_block(seq, BLOCK) == BLOCK
    assert D_INNER // ssm._d_block(D_INNER) == 3
    got, want = kernel(*args), ssm.selective_scan_steps(*args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    assert rel(got, want) <= TOL
    every = tuple(range(6))
    got = jax.grad(weighted(kernel, args[0].shape), argnums=every)(*args)
    want = jax.grad(weighted(ssm.selective_scan_steps, args[0].shape),
                    argnums=every)(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel(g, w) <= TOL


def test_the_kernels_cotangents_have_the_primal_dtypes():
    """bf16 ``c`` / B / C beside f32 Δ, A_log and D, as the layer hands
    them over under a bf16 compute dtype."""
    args = scan_inputs(1, 32, jnp.bfloat16)
    grads = jax.grad(weighted(kernel, args[0].shape),
                     argnums=tuple(range(6)))(*args)
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    assert [g.shape for g in grads] == [a.shape for a in args]
    assert [a.dtype for a in args] == [jnp.bfloat16, jnp.float32] + [
        jnp.bfloat16] * 2 + [jnp.float32] * 2


@pytest.mark.parametrize("d,n", [(D_INNER, D_STATE), (24, 4), (128, 6)],
                         ids=["tiles", "odd", "odd-state"])
def test_the_cpu_and_odd_widths_take_the_loop_form(d, n, monkeypatch):
    """``selective_scan`` on this backend never reaches a kernel, whatever
    the widths, and gives the oracle's numbers; on a TPU the kernels run
    where they tile, and only there."""
    assert jax.default_backend() == "cpu"
    assert not ssm.takes_kernel(d, n)
    assert ssm.scan_tiles(d, n) == ((d, n) == (D_INNER, D_STATE))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.takes_kernel(d, n) == ssm.scan_tiles(d, n)
    monkeypatch.undo()
    monkeypatch.setattr(ssm.pl, "pallas_call", None)  # a kernel would raise
    args = scan_inputs(2, 32, jnp.float32, d, n)
    want = ssm.selective_scan_steps(*args)
    assert rel(ssm.selective_scan(*args), want) <= TOL
    got = jax.grad(weighted(ssm.selective_scan, want.shape),
                   argnums=tuple(range(6)))(*args)
    want = jax.grad(weighted(ssm.selective_scan_steps, want.shape),
                    argnums=tuple(range(6)))(*args)
    for g, w in zip(got, want):
        assert rel(g, w) <= TOL


def test_both_paths_give_the_same_numbers():
    """The loop form and the kernels on one input: what a net trained on
    the CPU and continued on a TPU sees."""
    args = scan_inputs(2, 40, jnp.float32)
    assert rel(kernel(*args), ssm.selective_scan(*args, chunk=BLOCK)) <= TOL


def test_widths_that_do_not_tile_are_refused_by_the_kernel_entry():
    with pytest.raises(ValueError, match="do not tile"):
        ssm.selective_scan_kernel(*scan_inputs(1, 16, jnp.float32, 24, 4),
                                  interpret=True)


@pytest.mark.parametrize("seq,chunk,steps", [
    (2048, None, 64), (2049, None, 64), (40, None, 48), (8, None, 16),
    (2048, 128, 128), (40, 10, 16)])
def test_a_time_block_is_whole_packed_sublane_groups(seq, chunk, steps):
    """bf16 rows come sixteen to a register, and a sequence shorter than
    a block is one block."""
    assert ssm.time_block(seq, chunk) == steps
    assert steps % ssm.PACKED == 0


def test_a_layer_reports_the_path_it_takes(monkeypatch):
    """What ``Solver._fence_stats`` reads off a ``Mamba`` layer: on the CPU
    the loop form's chunk; on a TPU at widths that tile, the kernels' time
    block and the states kept at that stride."""
    from sparknet_tpu.layers_dsl import MambaLayer
    from sparknet_tpu.ops.registry import create_layer
    from sparknet_tpu.common import Phase

    def made(backend):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        layer = create_layer(MambaLayer("m", ["x"], d_state=16), Phase.TRAIN)
        layer.init(jax.random.key(0), [(1, 2048, 64)])
        return layer.kernel, layer.chunk, layer.saved_bytes

    kept = lambda chunk: (2048 // chunk) * 16 * 128 * 4
    assert made("cpu") == (False, ssm.CHUNK, kept(ssm.CHUNK))
    assert made("tpu") == (True, ssm.TIME_BLOCK, kept(ssm.TIME_BLOCK))
