"""The grouped matmul's TPU branch hands its weight gradient over as the
weights are stored, ``[experts, out, in]`` (PR 31).

Two proofs, both without a chip.  (1) The branch itself
(`ops/moe._megablox_matmul`: jax's megablox kernels under a backward of
our own) in Pallas interpret mode on the CPU against ``jax.vjp`` of the
``ragged_dot_general`` branch, f32, at lane-aligned toy sizes: 500 rows
(the kernel needs 512: the pad), 4 groups of which one is EMPTY, 50 rows
past the groups' sum (a held share's dead rows).  Same products in both,
summed in another order: measured 0 to 3e-7 relative, limit 1e-4.
(2) A toy OLMoE train step compiled for a described v5e
(`tools/expert_copies.py`): no ``copy`` in the ENTRY computation has the
shape of an f32 expert matrix.  With megablox's own backward the same toy
step has 18 (each matrix and both AdamW moments, into the transposed
layout and back).  Both proofs live in this one file: the process that
describes the topology holds libtpu, and a second file could land on
another worker.  For the same reason the share-holding layer's two
compiled branches are counted here (PR 35), and the selective scan's kernels
(``ops/ssm.py``, PR 33) are compiled for that v5e here, at the hybrid
cell's widths: Mosaic refuses here what it would refuse on the chip; and
the solo feed's augment (``data/device_transform.py``, PR 39) at the two
CNN solo cells' shapes; and toy multi-head decoder steps hold no copy of
an activation between token-major and head-major (PR 44); and the gated
delta rule's kernels (``ops/linear_attention.py``, PR 48) at the
linear-attention cell's size.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu import models
from sparknet_tpu.common import Phase, get_config, set_config
from sparknet_tpu.compiler.graph import Network
from sparknet_tpu.ops import moe
from tools import delta_kernel, expert_copies, scan_kernel

ROWS, LIVE, SIZES = 500, 450, (200, 0, 150, 100)
G, D, H = len(SIZES), 128, 256
# SwiGLU's three matrices as the layer stores them: [G, out, in]
MATRICES = {"w_gate": (G, H, D), "w_up": (G, H, D), "w_down": (G, D, H)}
TOL = 1e-4


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def masked(matmul):
    """``matmul`` as ``moe_dropless`` calls it for a held share: nothing
    goes into a dead row and nothing comes out of one."""
    live = (jnp.arange(ROWS) < LIVE)[:, None]
    sizes = jnp.asarray(SIZES, jnp.int32)
    return lambda x, w: jnp.where(
        live, matmul(jnp.where(live, x, 0), w, sizes), 0)


@pytest.mark.parametrize("matrix", sorted(MATRICES))
def test_tpu_branch_matches_ragged_dot(matrix):
    shape = MATRICES[matrix]
    rng = np.random.default_rng(sorted(MATRICES).index(matrix))
    x = jnp.asarray(rng.standard_normal((ROWS, shape[2])), jnp.float32)
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    dy = jnp.asarray(rng.standard_normal((ROWS, shape[1])), jnp.float32)
    want, want_vjp = jax.vjp(masked(
        lambda x, w, s: jax.lax.ragged_dot_general(
            x, w, s, moe._OUT_IN, preferred_element_type=x.dtype)), x, w)
    got, got_vjp = jax.vjp(masked(
        lambda x, w, s: moe._megablox_matmul(x, w, s, interpret=True)), x, w)
    (want_dx, want_dw), (got_dx, got_dw) = want_vjp(dy), got_vjp(dy)
    assert got_dw.shape == w.shape and got_dw.dtype == w.dtype
    assert rel(got, want) <= TOL
    assert rel(got_dx, want_dx) <= TOL
    assert rel(got_dw, want_dw) <= TOL
    assert not np.asarray(got_dw[SIZES.index(0)]).any()  # the empty group


def test_backward_transposes_no_expert_matrix():
    """Between the kernel and whoever takes ``dw`` there is no transpose
    of a [G, ., .] array: that one was what XLA folded into the update's
    layout."""
    x = jax.ShapeDtypeStruct((512, D), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((G, H, D), jnp.bfloat16)
    sizes = jnp.asarray((212, 0, 150, 150), jnp.int32)

    def dw(x, w):
        return jax.grad(lambda w: moe._megablox_matmul(
            x, w, sizes, interpret=True).astype(jnp.float32).sum())(w)

    jaxpr = jax.make_jaxpr(dw)(x, w)
    assert jaxpr.out_avals[0].shape == w.shape
    rank3 = [e for e in jaxpr.eqns if e.primitive.name == "transpose"
             and len(e.invars[0].aval.shape) == 3]
    assert not rank3


# --- the compiled program, for a described v5e ------------------------------


@pytest.fixture(scope="module")
def v5e():
    try:
        return expert_copies.v5e_chip()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def bf16_compute():
    before = get_config().compute_dtype
    set_config(compute_dtype=jnp.bfloat16)
    yield
    set_config(compute_dtype=before)


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def test_compiled_step_copies_no_expert_matrix(v5e, bf16_compute,
                                               no_compile_cache):
    toy = dict(batch=2, seq_len=256, vocab=512, hidden=D, heads=1, experts=G,
               top_k=2, expert_dim=H, layers=1)
    net = Network(models.olmoe(**toy), Phase.TRAIN)
    cfg = dataclasses.replace(models.olmoe_solver(), display=0)
    compiled, variables = expert_copies.compile_step(
        cfg, net, (toy["batch"], toy["seq_len"]), v5e)
    shapes = expert_copies.expert_shapes(net, variables)
    assert shapes == {(G, H, D), (G, D, H)}
    text = compiled.as_text()
    # the step did take the kernels: 3 matmuls x (gmm, gmm, tgmm)
    assert text.count("tpu_custom_call") >= 9
    assert expert_copies.expert_copies(text, shapes) == []


def test_compiled_share_step_moves_its_rows_a_tile_at_a_time(
        v5e, bf16_compute, no_compile_cache):
    """A toy JoyAI step (4 of 128 experts held, 2,048 pairs a layer)
    compiled for the v5e: no ``conditional`` is left, each share-holding
    layer's movers are ``while`` loops whose every gather and scatter
    moves one tile of 512 rows, and outside the loops nothing moves more
    rows than the step's 512 tokens (the embedding's own), so none moves
    a layer's 2,048 pairs (PR 49; ``tools/expert_copies.py row_moves``).
    Its expert matrices are copied as little as OLMoE's."""
    toy = dict(batch=2, seq_len=256, vocab=512, hidden=D, heads=2,
               q_lora_rank=64, kv_lora_rank=64, qk_nope_head_dim=32,
               qk_rope_head_dim=16, v_head_dim=32, dense_dim=H, experts=128,
               top_k=4, expert_dim=D, shared_dim=D, layers=2, experts_held=4,
               first_expert=8)
    net = Network(models.joyai_flash(**toy), Phase.TRAIN)
    cfg = dataclasses.replace(models.joyai_flash_solver(), display=0)
    compiled, variables = expert_copies.compile_step(
        cfg, net, (toy["batch"], toy["seq_len"]), v5e)
    text = compiled.as_text()
    assert " conditional(" not in text
    tokens = toy["batch"] * toy["seq_len"]
    moves = expert_copies.row_moves(text)
    inside = [m for m in moves if m["in_loop"]]
    # a layer: x's rows gathered in both passes and dy's in the backward,
    # the weighted rows and x's cotangent added to their tokens
    assert sorted((m["op"], m["rows"], m["width"]) for m in inside) == (
        [("gather", moe.CAPACITY_TILE, D)] * 6
        + [("scatter", moe.CAPACITY_TILE, D)] * 4)
    assert max(m["rows"] for m in moves if not m["in_loop"]) == tokens
    shapes = expert_copies.expert_shapes(net, variables)
    assert shapes == {(4, D, D)}
    assert expert_copies.expert_copies(text, shapes) == []


def toy_olmoe(B, S, E, heads):
    return (models.olmoe(batch=B, seq_len=S, vocab=512, hidden=E, heads=heads,
                         experts=G, top_k=2, expert_dim=H, layers=1),
            models.olmoe_solver(), 1)


def toy_ouro(B, S, E, heads):
    return (models.ouro(batch=B, seq_len=S, vocab=512, hidden=E, heads=heads,
                        mlp_dim=512, layers=1, ut_steps=2),
            models.ouro_solver(), 2)


@pytest.mark.parametrize("toy", [toy_olmoe, toy_ouro],
                         ids=["qk_norm", "no_qk_norm_looped"])
def test_compiled_step_copies_no_activation_between_head_layouts(
        v5e, bf16_compute, no_compile_cache, toy):
    """Multi-head attention's head split and merge ride its projection
    matmuls (PR 44): a toy step at lane-aligned widths (2 heads of 128)
    and 2,048 tokens, whose core takes the splash kernels as the cells'
    do, compiled for the v5e holds NO ``copy`` of activation size shaped
    [B, S, E] or [B, S, H, D], with QK-norm (OLMoE's block) and without
    (Ouro's, looped twice).  With the token-major projections and the
    ``reshape(B, S, H, D).transpose(0, 2, 1, 3)`` around the core the same
    steps hold eight such copies a layer-pass (ten at the cells' widths)."""
    B, S, E, heads = 2, 2048, 256, 2
    net_param, solver, passes = toy(B, S, E, heads)
    net = Network(net_param, Phase.TRAIN)
    cfg = dataclasses.replace(solver, display=0)
    compiled, _ = expert_copies.compile_step(cfg, net, (B, S), v5e)
    text = compiled.as_text()
    # forward and the fused backward, once a pass
    assert text.count("tpu_custom_call") >= 2 * passes
    copies = expert_copies.activation_copies(text, B * S * E)
    token_major = {f"bf16[{B},{S},{E}]", f"bf16[{B},{S},{heads},{E // heads}]"}
    assert not token_major & set(copies), copies


@pytest.mark.parametrize("seq", [2048, 2000], ids=["whole", "padded"])
def test_the_scan_kernels_compile_at_the_cell_widths(v5e, no_compile_cache,
                                                     seq):
    """1 x 2,048 x 5120, state 16, bf16 c / B / C beside f32 Δ: forward and
    backward are one Mosaic kernel each, and between them they keep the
    state at every time block's start and no whole sequence of states."""
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.ops import ssm

    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=SingleDeviceSharding(v5e))
            for shape, dtype in scan_kernel.shapes(seq)]
    compiled = scan_kernel.both(ssm.selective_scan_kernel).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "while(" not in text
    d, n = scan_kernel.D_INNER, scan_kernel.D_STATE
    kept = ssm.saved_state_bytes(1, seq, d, n, ssm.TIME_BLOCK)
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert kept <= temps < seq * d * n * 4 // 4


@pytest.mark.parametrize("seq", [4096, 4000], ids=["whole", "padded"])
def test_the_delta_rule_kernels_compile_at_the_cell_size(v5e, no_compile_cache,
                                                         seq):
    """1 x 4,096 tokens, 16 key and 32 value heads of 128, bf16 q / k / v
    beside f32 gates: forward and backward are one Mosaic kernel each, no
    loop is left, and between them they keep the state at every chunk's
    start (134 MB) and nothing a [chunk-heads, 64, 64] f32 array would
    need (30 of them at 34 MB each, lane-padded, was the XLA form's way)."""
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.ops import linear_attention as la

    args = [jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=SingleDeviceSharding(v5e))
            for shape, dtype in delta_kernel.shapes(seq)]
    compiled = delta_kernel.both(la.gated_delta_rule_kernel).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "while(" not in text
    kept = la.saved_state_bytes(1, seq, delta_kernel.V_HEADS,
                                delta_kernel.HEAD_DIM, delta_kernel.HEAD_DIM)
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert kept <= temps < kept + 4 * 34 * 2 ** 20


@pytest.mark.parametrize("batch, crop", [(1024, 227), (256, 224)],
                         ids=["alexnet-solo", "resnet50-solo"])
def test_the_augment_is_one_kernel_and_no_copy(v5e, no_compile_cache, batch,
                                               crop):
    """The solo feed's one pass (``data/device_transform.py``, PR 39) at
    the two CNN solo cells' shapes: Mosaic takes the kernel, and because
    it writes the crop as the chip stores it (batch-minor) the program
    is that kernel and nothing that moves an array the crop's size: no
    ``copy``, under 1 MB of temporaries."""
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.data.device_transform import DeviceAugment, crop_tiles
    from sparknet_tpu.data.transform import TransformConfig

    aug = DeviceAugment(TransformConfig(crop_size=crop, mirror=True,
                                        mean_value=(104.0, 117.0, 123.0)),
                        layout="nchw")
    assert crop_tiles(batch, 256, 256, crop)
    chip = SingleDeviceSharding(v5e)
    compiled = jax.jit(lambda x, it: aug.fused(
        x, jax.random.fold_in(jax.random.key(1234), it))).lower(
        jax.ShapeDtypeStruct((batch, 3, 256, 256), jnp.uint8, sharding=chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    big = f"f32[{batch},3,{crop},{crop}]"
    assert not [line for line in text.splitlines()
                if " copy(" in line and big in line]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
