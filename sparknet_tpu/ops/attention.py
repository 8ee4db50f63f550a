"""In-graph multi-head self-attention — the long-context layer type.

The reference is CNN-only (SURVEY §5: attention/sequence work absent;
RNNs were future work, ROADMAP.md:12), but this framework treats
long-context as first-class: beyond the sequence-parallel primitives
(`parallel/ring_attention.py`, `parallel/ulysses.py`), this layer makes
attention available through the ordinary prototxt/DSL -> compiler path so
sequence models build, train, and snapshot exactly like the CNN zoo.

Prototxt surface::

    layer {
      name: "attn" type: "MultiHeadAttention" bottom: "x" top: "y"
      attention_param { num_heads: 8 causal: true }
    }

Input/output blobs are [B, S, E].  Params follow Caffe blob order:
[W_qkv (3E, E), b_qkv (3E), W_out (E, E), b_out (E)] — importable/
exportable through every weight path (caffemodel, HDF5, orbax).
``bias_term: false`` drops the two biases; ``qk_norm: true`` appends
[q_norm (E), k_norm (E)], RMSNorm weights applied to a token's whole
E-wide q and k projections (the OLMoE / OLMo-2 QK-norm,
``qk_norm_eps``); ``rope_theta`` is the rotary base.  The attention core
(:func:`attention_core`) is chosen by shape and platform: long causal
sequences on a TPU run jax's pallas splash kernels, the rest
:func:`flash_attention` (XLA by default; ``SPARKNET_ATTN_IMPL`` still
selects the repo's own pallas forward there).

Sequence parallelism composes here: under an active
:func:`sequence_parallel` context (a `ParallelTrainer` whose mesh has a
'seq' axis activates it automatically), the attention core runs ring or
Ulysses attention with the sequence dimension sharded over that axis —
the same prototxt model scales to long contexts with no model changes.
"""

from __future__ import annotations

import contextlib
import math
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from sparknet_tpu.common import get_config
from sparknet_tpu.ops.base import Layer, LayerOutput
from sparknet_tpu.ops.blocks import rms_norm
from sparknet_tpu.ops.fillers import fill, normal
from sparknet_tpu.ops.pallas_kernels import attention_xla, flash_attention
from sparknet_tpu.ops.registry import register
from sparknet_tpu.proto.text_format import Message

# ---------------------------------------------------------------------------
# Sequence-parallel dispatch.
#
# The SP primitives (`parallel/ring_attention.py`, `parallel/ulysses.py`)
# are mesh programs; a Layer is a mesh-oblivious pytree function.  The
# bridge is a TRACE-TIME context: a trainer whose mesh has a 'seq' axis
# activates `sequence_parallel(mesh, impl)` around its jitted-step trace,
# and every MultiHeadAttention layer traced inside routes its attention
# core through a shard_map over that axis (batch stays on 'data').  The
# context nests under jit: only tracing consults it, the compiled program
# keeps the collectives.
# ---------------------------------------------------------------------------

_SP = threading.local()


@contextlib.contextmanager
def sequence_parallel(mesh, impl: str = "ring"):
    """Route MultiHeadAttention layers traced in this context through
    sequence parallelism over ``mesh``'s 'seq' axis.

    ``impl``: 'ring' (ppermute K/V rotation — any head count) or
    'ulysses' (head-scatter all_to_all — needs num_heads divisible by the
    seq-axis size).
    """
    if impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    prev = getattr(_SP, "ctx", None)
    _SP.ctx = (mesh, impl)
    try:
        yield
    finally:
        _SP.ctx = prev


def active_sequence_parallel():
    """(mesh, impl) when a seq-parallel context with a real (>1) seq axis
    is active, else None."""
    ctx = getattr(_SP, "ctx", None)
    if ctx is None:
        return None
    mesh, impl = ctx
    from sparknet_tpu.parallel.mesh import mesh_seq_size

    if mesh_seq_size(mesh) <= 1:
        return None
    return mesh, impl


def _sp_attention(mesh, impl, q, k, v, causal):
    """Attention core over a (data?, seq) mesh: [B, H, S, D] inputs with
    B on 'data' and S on 'seq'; collectives ride the 'seq' axis only."""
    from sparknet_tpu.parallel.mesh import shard_map
    from sparknet_tpu.parallel.ring_attention import ring_attention
    from sparknet_tpu.parallel.ulysses import ulysses_attention

    cfg = get_config()
    sax = cfg.seq_axis
    dax = cfg.data_axis if mesh.shape.get(cfg.data_axis, 1) > 1 else None
    if impl == "ulysses" and q.shape[1] % mesh.shape[sax] != 0:
        raise ValueError(
            f"ulysses needs num_heads ({q.shape[1]}) divisible by the "
            f"'{sax}' mesh axis ({mesh.shape[sax]}); use impl='ring'"
        )
    core = ring_attention if impl == "ring" else ulysses_attention
    spec = jax.sharding.PartitionSpec(dax, None, sax, None)
    # ring's fully-masked-block skip is a lax.cond whose branches jax's
    # varying-axes checker mis-types (its own error text prescribes
    # disabling the check)
    return shard_map(
        partial(core, axis_name=sax, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)


def yarn_ramp_bounds(rotary_dim: int, base: float, original_max: int,
                     beta_fast: float, beta_slow: float) -> tuple[float, float]:
    """(low, high) of :func:`yarn_inv_freq`'s ramp, in feature pairs."""
    dim = lambda turns: (rotary_dim * math.log(
        original_max / (2 * math.pi * turns)) / (2 * math.log(base)))
    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), rotary_dim - 1)
    # the public code keeps the ramp from dividing by zero the same way
    return float(low), float(high) + (0.001 if low == high else 0.0)


def yarn_inv_freq(rotary_dim: int, base: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0):
    """YaRN's blended inverse frequencies over ``rotary_dim`` features
    (Peng et al. 2023, arXiv:2309.00071, as the public
    ``_compute_yarn_parameters`` computes them) -> numpy f32
    [rotary_dim / 2], for :func:`rope`'s ``inv_freq``.

    With pos_i = base^(2i / r), i in [0, r / 2): a feature pair that turns
    more than ``beta_fast`` times over the ``original_max`` positions
    keeps its frequency 1 / pos_i, one that turns fewer than ``beta_slow``
    times is interpolated to 1 / (factor pos_i), and between the two a
    linear ramp blends them: dim(n) = r ln(original_max / (2 pi n)) /
    (2 ln base), low = max(floor(dim(beta_fast)), 0), high =
    min(ceil(dim(beta_slow)), r - 1), ramp_i = clip((i - low) /
    (high - low), 0, 1), inv_freq_i = ramp_i / (factor pos_i) +
    (1 - ramp_i) / pos_i.  Computed in float64 on the host and rounded
    once; the ``attention_factor`` on cos and sin is :func:`rope`'s
    ``scale``."""
    low, high = yarn_ramp_bounds(rotary_dim, base, original_max, beta_fast,
                                 beta_slow)
    i = np.arange(rotary_dim // 2, dtype=np.float64)
    pos = float(base) ** (2.0 * i / rotary_dim)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (ramp / (factor * pos) + (1.0 - ramp) / pos).astype(np.float32)


def _rope_theta(half: int, base: float, inv_freq) -> jax.Array:
    """The angle a position turns feature pair i by: ``base^(-i / half)``,
    or the table ``inv_freq`` [half] (:func:`yarn_inv_freq`) as given."""
    if inv_freq is None:
        return base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if inv_freq.shape != (half,):
        raise ValueError(f"inv_freq {inv_freq.shape} for {half} feature pairs")
    return jnp.asarray(inv_freq, jnp.float32)


def rope(x: jax.Array, base: float = 10000.0,
         interleave: bool = False, scale: float = 1.0,
         inv_freq=None) -> jax.Array:
    """Rotary position embedding over ``x`` [B, H, S, D] (D even).

    Parameter-free absolute-position encoding with the relative-position
    dot-product property (RoFormer, Su et al. 2021 — public technique,
    PAPERS.md): position t rotates each head-dim pair (2i, 2i+1) by
    t·θ_i, θ_i = base^(-2i/D).  Applied to q and k only; attention
    scores then depend on t_q − t_k.  No new weight blobs, so every
    wire format (caffemodel/HDF5/orbax) is untouched.  Must run BEFORE
    any sequence-parallel split: positions here are global.

    The pair that angle θ_i turns is features (i, i + D/2) by default
    (rotate-half, the Llama / OLMoE weight layout) and features
    (2i, 2i + 1) with ``interleave`` (the DeepSeek-V3 family's published
    layout, ``rope_interleave``); each feature stays where it was.
    ``scale`` multiplies the result inside the f32 product, before its
    one rounding to ``x``'s dtype (:func:`attention_core`'s ``scaled``;
    YaRN's ``attention_factor`` on cos and sin).  ``inv_freq`` [D / 2]
    f32 replaces the θ_i (:func:`yarn_inv_freq`); ``base`` is then not
    read.
    """
    B, H, S, D = x.shape
    if D % 2:
        raise ValueError(f"rope needs an even head dim, got {D}")
    half = D // 2
    theta = _rope_theta(half, base, inv_freq)  # [half]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * theta[None, :]  # [S,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        ).reshape(x.shape).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]  # rotate-half convention
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def rope_at(x: jax.Array, positions: jax.Array,
            base: float = 10000.0, scale: float = 1.0,
            inv_freq=None) -> jax.Array:
    """:func:`rope` at explicit absolute positions — the decode-path
    twin.  ``x`` is [B, H, W, D] (W the proposed-token width, 1 for
    plain decode) and ``positions`` [B, W] int32 absolute positions.
    Bitwise contract with :func:`rope`: for ``positions[b, w] == t`` the
    rotation applied here is the SAME float expression :func:`rope`
    applies at sequence index t (identical theta/cos/sin/rotate-half
    arithmetic, under ``scale`` and ``inv_freq`` too), so a cached K
    written through this path equals the K the full-window forward
    computes at that row.
    """
    B, H, W, D = x.shape
    if D % 2:
        raise ValueError(f"rope needs an even head dim, got {D}")
    half = D // 2
    theta = _rope_theta(half, base, inv_freq)  # [half]
    ang = positions.astype(jnp.float32)[..., None] * theta  # [B, W, half]
    cos = jnp.cos(ang)[:, None]  # [B, 1, W, half] — broadcast over heads
    sin = jnp.sin(ang)[:, None]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# device scope of the attention core (scores, softmax, weighted sum) inside
# the layer's ``L.<name>`` scope; in common.CACHE_SCOPES
CORE_SCOPE = "A.core"


def core_kernel(backend: str, S: int, D: int, Dv: int, causal: bool) -> str:
    """The kernel :func:`attention_core` runs over S tokens with keys of D
    and values of Dv, read off the backend and the shapes: ``splash``
    (jax's pallas splash attention, which never holds the [B, H, S, S]
    scores: 1 GB per 4k sequence of 16 heads in f32) for a causal core
    from S = 2048 on a TPU whose blocks tile, else ``xla`` (the scores
    materialized).  Head counts change the grouping and a window the
    mask and the pair of kernels that walks it (:func:`band_backward`),
    not this name: ``splash`` says the core runs as Pallas kernels."""
    tiles = S % 512 == 0 and D % 64 == 0 and Dv % 128 == 0
    long_on_tpu = backend == "tpu" and S >= 2048
    return "splash" if long_on_tpu and causal and tiles else "xla"


def core_block(S: int, window: int = 0) -> int:
    """The width of the query and key blocks :func:`attention_core` hands
    its kernels: 1024, 512 where S is no multiple of 1024 or under a
    window (the blocks the window never reaches are skipped: a
    ``LocalMask`` in jax's kernels, the band's walk in the repo's own,
    which cut a query block into sub-blocks of 256 rows that take only
    the keys they can see).  :func:`window_blocks` counts the same blocks
    and :func:`band_backward` says which kernels walk them."""
    return 512 if window or S % 1024 else 1024


def band_backward(S: int, window: int = 0) -> bool:
    """Whether the core runs as the kernels that follow the window's band
    (``ops/band_attention.py``): a grid step a query block and head, the
    key blocks its window reaches held in VMEM (2 at a window of 512 in
    512-wide blocks), dq summed in f32 over them and written once, dK and
    dV summed in a VMEM ring over the query blocks that reach a key
    block.  The other arm is jax's fused backward, whose grid is every
    (key block, head, query block) whatever the mask and whose dq is one
    q-sized partial a KEY block, zeros where the block is masked, summed
    by XLA afterwards (16 x q at 8,192 tokens: 2.1 GB a sliding layer of
    64 heads).  The rule, read off S and the window alone: under a window
    that hides some key, the band (timed at both windowed shapes the
    cells run, :func:`attention_core`'s table); without one the fused
    kernel's one pass over the scores (PR 43's table)."""
    return 0 < window < S


def attention_core(q, k, v, causal: bool, window: int = 0,
                   scaled: bool = False):
    """Softmax attention over q [B, H, S, D], k [B, Hk, S, D] and v
    [B, Hv, S, Dv] -> [B, H, S, Dv], head-major in and out: the layout
    the kernels fix.  ``MultiHeadAttentionLayer``'s projections write q,
    k, v so and read o so; the two other layers still transpose around
    the core.  The scores are scaled by
    ``D ** -0.5``, D the key width.  Grouped heads: Hk divides H and Hv
    divides Hk, query head h reads key head ``h // (H / Hk)`` and value
    head ``h // (H / Hv)``.  ``window`` > 0 (causal only): query t sees
    keys t - window + 1 .. t.  ``scaled``: q carries the ``D ** -0.5``
    already (only where :func:`core_kernel` says ``splash``: those
    kernels score q kᵀ as given, so q is scaled before them and rounded
    once more, unless its layer folded the scale into an f32 value q
    passed through anyway, as RoPE's).

    ONE kernel family for every long causal core (:func:`core_kernel`):
    jax's splash kernels with the fused backward (dq, dk and dv from one
    pass over the scores), at equal widths (OLMoE, Ouro: 16 heads of
    128), with values narrower than keys (latent attention: keys of 192,
    values of 128) and in their grouped form (one key head and the query
    heads that read it a call, so that no key head is repeated in HBM).
    Blocks are 1024 wide, 512 where S is no multiple of 1024 or under a
    window.  UNDER A WINDOW (:func:`band_backward`; Laguna's sliding
    layers: 64 query heads on 8 key heads of 128 at 8,192 tokens;
    differential attention's windowed layer: 40 query heads of 64 on 20
    key and 10 value heads) the core is the repo's own pair of kernels
    that walk the window's band (``ops/band_attention.py``): no grid step
    for a (query block, key block) pair the window never reaches, dq
    summed in f32 and written once; the fused backward's grid is every
    pair whatever the mask and its dq is one q-sized partial a KEY block
    (16 x q at 8,192 tokens: 2.1 GB a sliding layer).  Timed alone on the
    v5e (TPU v5 lite), forward + backward, bf16, causal (PERF.md section
    6; ``tools/attn_core_kernel.py``, ``benchmarks/scratch/hybrid_kernels.py``):
    1 x 16 x 4096 x 128: 2.13 ms at 1024-wide blocks (2.36 at 512, 2.62
    with separate dq and dkv kernels; jax's three-kernel pallas
    ``flash_attention``, which this shape ran until PR 43, 3.26);
    4 x 16 x 4096 x 128: 8.96 ms (10.32, 11.12; 14.31).
    1 x 32 x 4096, keys 192, values 128: 7.41 ms (8.35 with separate
    kernels; an XLA loop over query blocks with remat 49.9).
    1 x 40 x 2048, keys 64, values 128, grouped: 1.75 ms (1.79 at 512; the
    XLA formulation 9.59); under a window of 512 1.09 ms as the band kernels (1.21 in
    sub-blocks of 128 rows, 1.20 of 512), where the fused splash form that
    ran until PR 51 takes 1.50 at 512-wide blocks (1.83 at 1024; separate
    dq and dkv kernels 1.51; XLA 9.58).
    1 x 64 over 8 x 8192 x 128 under a window of 512: 7.46 ms as the band
    kernels (8.23 in sub-blocks of 128, 8.15 of 512; 7.49 at 1024-wide
    blocks), where the fused splash form takes 17.43 (17.09 at 1024) and
    jax's separate dq and dkv kernels 11.36 at 512-wide blocks (16.07 at
    256, 33.02 at 128); the forward alone 2.90 in every form at 512.
    Everything else takes the XLA formulation, which materializes the
    scores (no cell has a long core that is not causal): through
    :func:`flash_attention` at equal widths and head counts without a
    window."""
    H, S, D = q.shape[1:]
    Dv = v.shape[3]
    if window and not causal:
        raise ValueError("a window is causal here: keys t - window + 1 .. t")
    if H % k.shape[1] or k.shape[1] % v.shape[1]:
        raise ValueError(f"{H} query heads over {k.shape[1]} key heads over "
                         f"{v.shape[1]} value heads: each must divide the "
                         "one before")
    window = 0 if window >= S else window
    if core_kernel(jax.default_backend(), S, D, Dv, causal) == "splash":
        return _splash_causal(q, k, v, core_block(S, window), window, scaled)
    if scaled:
        raise ValueError("the XLA formulation scales the scores itself")
    if D != Dv or window or not k.shape[1] == v.shape[1] == H:
        return _attention_xla(q, k, v, causal, window)
    return flash_attention(q, k, v, causal=causal)


def _attention_xla(q, k, v, causal: bool, window: int):
    """The XLA formulation with grouped heads and a window:
    :func:`attention_xla` where there is neither."""
    H, S = q.shape[1:3]
    if not window and k.shape[1] == H and v.shape[1] == H:
        return attention_xla(q, k, v, causal)
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * q.shape[-1] ** -0.5
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]  # query - key
    seen = ahead >= 0 if causal else jnp.ones((S, S), bool)
    if window:
        seen &= ahead < window
    s = jnp.where(seen, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32)).astype(q.dtype)


def _splash_causal(q, k, v, block: int, window: int = 0,
                   scaled: bool = False, interpret: bool = False):
    """Causal attention through jax's splash attention kernels at
    ``block``-wide query and key blocks, whatever the key and value
    widths; with fewer key heads than query heads, through their grouped
    form (one key head and the query heads that read it a call).  The
    backward is ONE fused kernel of jax's for dq, dk and dv, or under a
    window (:func:`band_backward`) the repo's own kernel that walks the
    band (``ops/band_attention.py``).  ``interpret`` runs them in
    Pallas's interpreter (the tests, on the CPU)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)

    B, H, S, D = q.shape
    if not band_backward(S, window):
        sizes = sk.BlockSizes(
            block_q=block, block_kv=block, block_kv_compute=block,
            block_q_dkv=block, block_kv_dkv=block,
            block_kv_dkv_compute=block, use_fused_bwd_kernel=True)
        return _splash(q, k, v, sizes, window, scaled, interpret)
    from sparknet_tpu.ops.band_attention import band_core

    Hk = k.shape[1]
    if not scaled:
        q = (q * D ** -0.5).astype(q.dtype)
    v = jnp.repeat(v, Hk // v.shape[1], axis=1)
    o = band_core(q.reshape(B, Hk, H // Hk, S, D), k, v, block, window,
                  interpret)
    return o.reshape(B, H, S, v.shape[3])


def _splash(q, k, v, sizes, window: int = 0, scaled: bool = False,
            interpret: bool = False):
    """jax's splash kernels at the ``BlockSizes`` given, under the causal
    mask or, with a window, a ``LocalMask``."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    H, S, D = q.shape[1:]
    Hk = k.shape[1]
    seen = (sm.LocalMask((S, S), (window - 1, 0), 0) if window
            else sm.CausalMask((S, S)))
    # the kernel takes [H, S, D] and scores q kᵀ as given: scale q first
    if not scaled:
        q = (q * D ** -0.5).astype(q.dtype)
    if Hk == H and v.shape[1] == H:
        kernel = sk.make_splash_mha_single_device(
            sm.MultiHeadMask([seen] * H), block_sizes=sizes,
            interpret=interpret)
        return jax.vmap(kernel)(q, k, v)
    # one key head and its H / Hk query heads a call
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([seen] * (H // Hk)), block_sizes=sizes,
        interpret=interpret)
    v = jnp.repeat(v, Hk // v.shape[1], axis=1)
    grouped = q.reshape((q.shape[0], Hk, H // Hk, S, D))
    o = jax.vmap(jax.vmap(kernel))(grouped, k, v)
    return o.reshape((q.shape[0], H, S, v.shape[3]))


class AttentionLayer(Layer):
    """A layer whose token mixer is :func:`attention_core`.  ``kernel`` is
    what its last trace ran the core as (:func:`core_kernel`'s name, or
    the sequence-parallel implementation's) and ``band`` whether those
    kernels' backward followed a window's band (:func:`band_backward`),
    for ``Solver._fence_stats``; empty and False until the layer is
    traced."""

    kernel = ""
    band = False

    def _core(self, q, k, v, causal: bool, window: int = 0):
        self.kernel = core_kernel(jax.default_backend(), q.shape[2],
                                  q.shape[3], v.shape[3], causal)
        self.band = (self.kernel == "splash"
                     and band_backward(q.shape[2], window))
        with jax.named_scope(CORE_SCOPE):
            return attention_core(q, k, v, causal, window)


@register
class MultiHeadAttentionLayer(AttentionLayer):
    """Multi-head self-attention, [B, S, E] -> [B, S, E] (the module
    docstring has the prototxt surface and the blobs).

    The head-major layout [B, H, S, D] the core takes is made BY THE
    PROJECTIONS (PR 44): x is contracted with the three [H, D, E] views
    of ``w_qkv`` as it is stored straight into q, k and v [B, H, S, D],
    and o [B, H, S, D] is contracted over (H, D) with ``w_out`` viewed
    [E, H, D].  Bias ([3, H, 1, D]), QK-norm (the mean of squares over
    axes (H, D): a token's whole projection; the weight [H, 1, D]) and
    RoPE act on the head-major value.  No token-major q, k, v or o
    ([B, S, E], [B, S, H, D]) exists, forward or backward: each was a
    full-size transposing ``copy`` around the kernels, ten a layer-pass
    (``tools/expert_copies.py activation_copies``).  One path for every
    backend, shape and option, the sequence-parallel branch included."""

    TYPE = "MultiHeadAttention"

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("attention_param")
        self.num_heads = p.get_int("num_heads", 1)
        self.causal = p.get_bool("causal", False)
        self.rope = p.get_bool("rope", False)
        self.rope_theta = p.get_float("rope_theta", 10000.0)
        self.bias_term = p.get_bool("bias_term", True)
        self.qk_norm = p.get_bool("qk_norm", False)
        self.qk_norm_eps = p.get_float("qk_norm_eps", 1e-5)
        self.weight_filler = (
            p.get_msg("weight_filler")
            if p.has("weight_filler")
            else Message().set("type", "xavier")
        )

    def init(self, key, in_shapes):
        (B, S, E) = in_shapes[0]
        if E % self.num_heads != 0:
            raise ValueError(
                f"attention embed dim ({E}) must be divisible by "
                f"num_heads ({self.num_heads})"
            )
        k1, k2 = jax.random.split(key)
        w_qkv = fill(self.weight_filler, k1, (3 * E, E))
        w_out = fill(self.weight_filler, k2, (E, E))
        if self.bias_term:
            params = [w_qkv, jnp.zeros((3 * E,), jnp.float32),
                      w_out, jnp.zeros((E,), jnp.float32)]
        else:
            params = [w_qkv, w_out]
        if self.qk_norm:
            params += [jnp.ones((E,), jnp.float32), jnp.ones((E,), jnp.float32)]
        return params, {}

    def apply(self, params, state, inputs, *, train, rng=None) -> LayerOutput:
        x = inputs[0]  # [B, S, E]
        # blobs: [w_qkv, (b_qkv), w_out, (b_out), (q_norm, k_norm)]
        if self.bias_term:
            w_qkv, b_qkv, w_out, b_out = params[:4]
        else:
            w_qkv, w_out = params[:2]
        S, E = x.shape[1:]
        H = self.num_heads
        D = E // H
        # the head split rides the projections: x against the [H, D, E]
        # views of w_qkv, straight into [B, H, S, D]
        q, k, v = (jnp.einsum("bse,hde->bhsd", x, w)
                   for w in w_qkv.reshape(3, H, D, E))
        if self.bias_term:
            q, k, v = (t + b for t, b in
                       zip((q, k, v), b_qkv.reshape(3, H, 1, D)))
        if self.qk_norm:
            # over a token's whole E-wide projection: axes (H, D) here
            q, k = (rms_norm(t, w.reshape(H, 1, D), self.qk_norm_eps, (1, 3))
                    for t, w in ((q, params[-2]), (k, params[-1])))
        sp = active_sequence_parallel()
        if sp is not None and S % sp[0].shape[get_config().seq_axis] != 0:
            # ring/Ulysses need equal sequence blocks; an indivisible S
            # runs locally instead (correct, just not sequence-parallel)
            import warnings

            warnings.warn(
                f"{self.name}: sequence length {S} not divisible by the "
                f"'seq' mesh axis ({sp[0].shape[get_config().seq_axis]}); "
                "attention runs without sequence parallelism",
                stacklevel=2,
            )
            sp = None
        self.kernel = sp[1] if sp is not None else core_kernel(
            jax.default_backend(), S, D, D, self.causal)
        # the splash kernels score q kᵀ as given: their scale rides RoPE's
        # f32 tables, where it costs q no rounding of its own
        fold = self.rope and self.kernel == "splash"
        if self.rope:
            # global positions — before any sequence-parallel split
            q = rope(q, self.rope_theta, scale=D ** -0.5 if fold else 1.0)
            k = rope(k, self.rope_theta)
        with jax.named_scope(CORE_SCOPE):
            if sp is not None:
                o = _sp_attention(sp[0], sp[1], q, k, v, self.causal)
            else:
                o = attention_core(q, k, v, self.causal, scaled=fold)
        # and the merge the output projection: over (H, D) of o as it is
        y = jnp.einsum("bhsd,fhd->bsf", o, w_out.reshape(E, H, D))
        if self.bias_term:
            y = y + b_out
        return LayerOutput(outputs=[y])


# device scope of latent attention's projections, inner norms, RoPE and
# the assembly of k (everything of the layer outside ``A.core``); in
# common.CACHE_SCOPES
LATENT_SCOPE = "A.latent"


@register
class LatentAttentionLayer(AttentionLayer):
    """Multi-head latent attention (MLA; DeepSeek-V2 arXiv:2405.04434
    section 2.1, as the DeepSeek-V3 family's ``config.json`` sizes it), the
    training form: the compressed latents are expanded to full heads and
    the core is ordinary causal attention with keys of
    ``qk_nope_head_dim + qk_rope_head_dim`` and values of ``v_head_dim``.

    ``attention_param { num_heads q_lora_rank kv_lora_rank
    qk_nope_head_dim qk_rope_head_dim v_head_dim rope_theta
    rope_interleave norm_eps causal }``.  [B, S, E] -> [B, S, E]; blobs,
    every matrix ``[out, in]``, no biases:

      W_dq (q_lora_rank, E), q_norm (q_lora_rank),
      W_uq (H·(nope + rope), q_lora_rank)        per head [nope ; rope]
      W_dkv (kv_lora_rank + rope, E)             [latent ; the ONE rotary key]
      kv_norm (kv_lora_rank),
      W_ukv (H·(nope + v), kv_lora_rank)         per head [k_nope ; v]
      W_o (E, H·v)

    RoPE (global positions) turns the rope part of every query head and
    the one rotary key all heads share; the scores are scaled by
    ``(nope + rope) ** -0.5``."""

    TYPE = "LatentAttention"

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("attention_param")
        self.num_heads = p.get_int("num_heads", 1)
        self.q_rank = p.get_int("q_lora_rank")
        self.kv_rank = p.get_int("kv_lora_rank")
        self.nope = p.get_int("qk_nope_head_dim")
        self.rope_dim = p.get_int("qk_rope_head_dim")
        self.v_dim = p.get_int("v_head_dim")
        self.causal = p.get_bool("causal", True)
        self.rope_theta = p.get_float("rope_theta", 10000.0)
        self.rope_interleave = p.get_bool("rope_interleave", False)
        self.norm_eps = p.get_float("norm_eps", 1e-6)
        self.weight_filler = (
            p.get_msg("weight_filler")
            if p.has("weight_filler")
            else Message().set("type", "xavier")
        )

    def init(self, key, in_shapes):
        E = in_shapes[0][-1]
        H, qk = self.num_heads, self.nope + self.rope_dim
        keys = jax.random.split(key, 5)
        shapes = [(self.q_rank, E), (H * qk, self.q_rank),
                  (self.kv_rank + self.rope_dim, E),
                  (H * (self.nope + self.v_dim), self.kv_rank),
                  (E, H * self.v_dim)]
        w_dq, w_uq, w_dkv, w_ukv, w_o = (
            fill(self.weight_filler, k, s) for k, s in zip(keys, shapes))
        return [w_dq, jnp.ones((self.q_rank,), jnp.float32), w_uq, w_dkv,
                jnp.ones((self.kv_rank,), jnp.float32), w_ukv, w_o], {}

    def apply(self, params, state, inputs, *, train, rng=None) -> LayerOutput:
        if active_sequence_parallel() is not None:
            raise NotImplementedError(
                f"{self.name}: latent attention has no sequence-parallel "
                "core (ring / Ulysses take one head width)")
        x = inputs[0]  # [B, S, E]
        w_dq, q_norm, w_uq, w_dkv, kv_norm, w_ukv, w_o = params
        B, S, _ = x.shape
        H, nope, rd, vd = self.num_heads, self.nope, self.rope_dim, self.v_dim
        heads = lambda t: t.reshape(B, S, H, -1).transpose(0, 2, 1, 3)
        turn = lambda t: rope(t, self.rope_theta, self.rope_interleave)
        with jax.named_scope(LATENT_SCOPE):
            c_q = rms_norm(x @ w_dq.T, q_norm, self.norm_eps)
            q = heads(c_q @ w_uq.T)  # [B, H, S, nope + rope]
            q = jnp.concatenate([q[..., :nope], turn(q[..., nope:])], -1)
            dkv = x @ w_dkv.T
            c_kv = rms_norm(dkv[..., :self.kv_rank], kv_norm, self.norm_eps)
            k_rope = turn(dkv[:, None, :, self.kv_rank:])  # [B, 1, S, rope]
            kv = heads(c_kv @ w_ukv.T)  # [B, H, S, nope + v]
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (B, H, S, rd))], -1)
            v = kv[..., nope:]
        # scaled by (nope + rd) ** -0.5, the whole key's width
        o = self._core(q, k, v, self.causal)
        with jax.named_scope(LATENT_SCOPE):
            y = o.transpose(0, 2, 1, 3).reshape(B, S, H * vd) @ w_o.T
        return LayerOutput(outputs=[y])


@register
class DifferentialAttentionLayer(AttentionLayer):
    """Differential attention with grouped heads (Ye et al. 2024,
    arXiv:2410.05258, in its two-maps-over-doubled-values form, as the
    SambaY decoder uses it, arXiv:2507.06607), self or cross.

    ``attention_param { num_heads num_kv_heads window lambda_init
    norm_eps }``; ``num_heads`` H query heads and ``num_kv_heads`` Hk key
    and value heads of D = E / H; ``window`` > 0: query t sees keys
    t - window + 1 .. t, else every key up to t.  No positional encoding,
    no biases.

    Self (one bottom [B, S, E]): blobs W_qkv ((H + 2 Hk) D, E) [q ; k ; v],
    W_o (E, H D), lambda_q1, lambda_k1, lambda_q2, lambda_k2 (D each),
    subln (2 D).  Tops [y] or [y, k, v]: k [B, Hk, S, D] and the paired
    values v [B, Hk / 2, S, 2 D], for a layer far down the net to read.
    Cross (bottoms [x, k, v]): blobs W_q (H D, E), W_o and the same five
    vectors; it projects no key and no value.

    Heads pair: query heads (2j, 2j + 1) are (q1_j, q2_j), key heads
    (2g, 2g + 1) are (k1_g, k2_g), value heads (2g, 2g + 1) side by side
    are v_g of width 2 D; query pair j reads pair g = j // (H / Hk).
    a^r_j = softmax(q^r_j k^r_gᵀ / sqrt(D) + mask) v_g for r = 1, 2;
    lambda = exp(lambda_q1 · lambda_k1) - exp(lambda_q2 · lambda_k2) +
    lambda_init; o_j = (1 - lambda_init) · RMSNorm_{2D}(a^1_j - lambda a^2_j)
    with weight subln; y = W_o [o_1 .. o_{H/2}].  Both maps of every pair
    go through ONE :func:`attention_core` call: H query heads of D over
    Hk key heads of D and Hk / 2 value heads of 2 D."""

    TYPE = "DifferentialAttention"
    F32_BLOBS = (2, 3, 4, 5)  # the four lambda vectors

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("attention_param")
        self.num_heads = p.get_int("num_heads")
        self.num_kv_heads = p.get_int("num_kv_heads", self.num_heads)
        self.window = p.get_int("window", 0)
        self.lambda_init = p.get_float("lambda_init", 0.8)
        self.norm_eps = p.get_float("norm_eps", 1e-5)
        self.cross = len(self.bottoms) == 3
        if len(self.bottoms) not in (1, 3) or len(self.tops) not in (1, 3):
            raise ValueError(
                f"{self.name}: bottoms are [x] or [x, k, v], tops [y] or "
                "[y, k, v]")
        self.weight_filler = (
            p.get_msg("weight_filler") if p.has("weight_filler")
            else Message().set("type", "xavier"))

    def init(self, key, in_shapes):
        E = in_shapes[0][-1]
        H, Hk = self.num_heads, self.num_kv_heads
        if E % H or H % Hk or Hk % 2:
            raise ValueError(
                f"{self.name}: {H} query heads must divide the width {E}, "
                f"{Hk} key heads must divide them and come in pairs")
        D = E // H
        k_in, k_out, k_lam = jax.random.split(key, 3)
        rows = H * D if self.cross else (H + 2 * Hk) * D
        lam = 0.1 * normal(k_lam, (4, D), jnp.float32)
        return [fill(self.weight_filler, k_in, (rows, E)),
                fill(self.weight_filler, k_out, (E, H * D)),
                *lam, jnp.ones((2 * D,), jnp.float32)], {}

    def apply(self, params, state, inputs, *, train, rng=None) -> LayerOutput:
        if active_sequence_parallel() is not None:
            raise NotImplementedError(
                f"{self.name}: differential attention has no "
                "sequence-parallel core (ring / Ulysses take one head "
                "width and one head count)")
        w_in, w_o, lq1, lk1, lq2, lk2, subln = params
        x = inputs[0]
        B, S, E = x.shape
        H, Hk = self.num_heads, self.num_kv_heads
        D, rep = E // H, H // Hk
        proj = x @ w_in.T
        if self.cross:
            k, v = inputs[1], inputs[2]
        else:
            heads = lambda t, n, d: t.reshape(B, S, n, d).transpose(0, 2, 1, 3)
            k = heads(proj[..., H * D:(H + Hk) * D], Hk, D)
            # value heads (2g, 2g + 1) side by side: one head of 2 D
            v = heads(proj[..., (H + Hk) * D:], Hk // 2, 2 * D)
        # query head 2j + r of pair j = g rep + i, half r -> core head
        # (g, r, i), which reads key head 2g + r and value head g
        q = proj[..., :H * D].reshape(B, S, Hk // 2, rep, 2, D)
        q = q.transpose(0, 2, 4, 3, 1, 5).reshape(B, H, S, D)
        a = self._core(q, k, v, True, self.window)
        a = a.reshape(B, Hk // 2, 2, rep, S, 2 * D).astype(jnp.float32)
        lam = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
               + self.lambda_init).astype(jnp.float32)
        o = rms_norm(a[:, :, 0] - lam * a[:, :, 1], subln, self.norm_eps)
        o = (o * (1.0 - self.lambda_init)).astype(x.dtype)
        # [B, g, i, S, 2D] -> [B, S, pairs (g, i), 2D]
        y = o.transpose(0, 3, 1, 2, 4).reshape(B, S, H * D) @ w_o.T
        return LayerOutput(outputs=[y, k, v][:len(self.tops)])


# device scopes of the rotary pass over q and k and of the output gate
# (its sigmoid and product; a head-wise gate's own projection too) inside
# the layer's ``L.<name>`` scope, beside ``A.core``; in common.CACHE_SCOPES
ROPE_SCOPE = "A.rope"
GATE_SCOPE = "A.gate"


def window_blocks(S: int, window: int) -> tuple[int, int]:
    """(visited, causal): of the (query block, key block) pairs at or
    below the diagonal of an S x S causal core, at the width
    :func:`core_block` gives the kernels, how many hold a key some query
    of theirs sees under ``window`` (all of them without one): the blocks
    a ``LocalMask`` leaves the splash kernels to visit."""
    window = 0 if window >= S else window
    block = core_block(S, window)
    n = -(-S // block)
    causal = n * (n + 1) // 2
    if not window:
        return causal, causal
    # block j < i is seen from block i when its last key lies inside the
    # window of block i's first query: (i - j - 1) * block + 1 < window
    reach = (window - 2) // block + 1
    return sum(min(i, reach) + 1 for i in range(n)), causal


@register
class GatedAttentionLayer(AttentionLayer):
    """Causal softmax attention with grouped heads of a width of their
    own and a sigmoid gate on its output; what else the layer does is
    said by its fields, so ONE class serves the Qwen3-Next family's
    full-attention layer (per-head QK-norm, RoPE over a quarter of a
    head, a gate as wide as the heads: the defaults) and both of the
    Laguna family's (no QK-norm, a gate of one scalar a head; the sliding
    kind under a window with plain RoPE over the whole head, the full
    kind with YaRN over half of one), each as its ``config.json`` sizes
    it.

    ``attention_param { num_heads num_kv_heads head_dim rotary_dim
    rope_theta norm_eps causal qk_norm head_gate window rope_scaling {
    type factor original_max_position_embeddings beta_fast beta_slow
    attention_factor } }``; H query heads and Hk key and value heads of
    D = ``head_dim`` (H D need not be E); ``rotary_dim`` (even, <= D;
    default D) leading features of every q and k head turn (rotate-half
    within them), the rest pass.  ``window`` > 0: query t sees keys
    t - window + 1 .. t (:func:`attention_core`'s ``LocalMask`` form),
    else every key up to t.  ``rope_scaling { type: "yarn" }``: the
    turned features' frequencies are :func:`yarn_inv_freq`'s and cos and
    sin carry ``attention_factor`` (default 0.1 ln(factor) + 1), so a
    score's turned part carries its square.  [B, S, E] -> [B, S, E];
    blobs, every matrix ``[out, in]``, no biases:

      W_q (2 H D, E)     per head [q (D) ; gate (D)], side by side; or
          (H D, E)       with ``head_gate``: the queries alone
      W_k (Hk D, E), W_v (Hk D, E)
      W_o (E, H D)
      q_norm (D), k_norm (D)   RMSNorm per head, weight 1 + w, w from 0;
                               absent with ``qk_norm: false``
      W_g (H, E)         with ``head_gate``: one gate a head and token
                         (the head-wise form of arXiv:2505.06708)

    q, k <- RMSNorm_D (zero-centred weight; ``qk_norm``) then RoPE
    (``A.rope``); o = the causal core over H heads on Hk
    (:func:`attention_core`'s grouped form, ``A.core``), scores scaled by
    D^-1/2; y = W_o (o * sigmoid(gate)) (``A.gate``), the gate [.., D]
    from W_q or [.., 1] from W_g.  Head-major in and out of the
    projections, as ``MultiHeadAttentionLayer``: x against the
    [H, 2, D, E] (or [H, D, E]), [Hk, D, E] and [H, E] views of the
    matrices straight into [B, H, S, D] and [B, H, S], and o contracted
    over (H, D) with W_o viewed [E, H, D]: no token-major q, gate, k, v
    or o exists.  ``visited`` is :func:`window_blocks` of the last trace,
    for ``Solver._fence_stats``."""

    TYPE = "GatedAttention"
    visited = (0, 0)

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("attention_param")
        self.num_heads = p.get_int("num_heads")
        self.num_kv_heads = p.get_int("num_kv_heads", self.num_heads)
        self.head_dim = p.get_int("head_dim")
        self.rotary_dim = p.get_int("rotary_dim", self.head_dim)
        self.rope_theta = p.get_float("rope_theta", 10000.0)
        self.norm_eps = p.get_float("norm_eps", 1e-6)
        self.causal = p.get_bool("causal", True)
        self.qk_norm = p.get_bool("qk_norm", True)
        self.head_gate = p.get_bool("head_gate", False)
        self.window = p.get_int("window", 0)
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.name}: {self.num_kv_heads} key heads must divide "
                f"{self.num_heads} query heads")
        if self.rotary_dim % 2 or not 0 <= self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"{self.name}: rotary_dim {self.rotary_dim} must be even "
                f"and at most head_dim {self.head_dim}")
        if self.window < 0 or (self.window and not self.causal):
            raise ValueError(
                f"{self.name}: window {self.window}: a window is causal "
                "here, keys t - window + 1 .. t")
        # the turned features' frequency table and the factor on cos, sin
        self.inv_freq, self.rope_scale = None, 1.0
        if p.has("rope_scaling"):
            r = p.get_msg("rope_scaling")
            kind = r.get_str("type", "yarn")
            if kind != "yarn" or not self.rotary_dim:
                raise ValueError(
                    f"{self.name}: rope_scaling type {kind!r} over "
                    f"{self.rotary_dim} features: yarn over a rotary span "
                    "is what there is")
            factor = r.get_float("factor")
            self.inv_freq = yarn_inv_freq(
                self.rotary_dim, self.rope_theta, factor,
                r.get_int("original_max_position_embeddings"),
                r.get_float("beta_fast", 32.0), r.get_float("beta_slow", 1.0))
            self.rope_scale = r.get_float(
                "attention_factor", 0.1 * math.log(factor) + 1.0)
        self.weight_filler = (
            p.get_msg("weight_filler") if p.has("weight_filler")
            else Message().set("type", "xavier"))

    def init(self, key, in_shapes):
        E = in_shapes[0][-1]
        H, Hk, D = self.num_heads, self.num_kv_heads, self.head_dim
        keys = jax.random.split(key, 4)
        shapes = [((1 if self.head_gate else 2) * H * D, E), (Hk * D, E),
                  (Hk * D, E), (E, H * D)]
        params = [fill(self.weight_filler, k, s) for k, s in zip(keys, shapes)]
        if self.qk_norm:
            params += [jnp.zeros((D,), jnp.float32),
                       jnp.zeros((D,), jnp.float32)]
        if self.head_gate:
            params.append(fill(self.weight_filler,
                               jax.random.fold_in(key, 4), (H, E)))
        return params, {}

    def _turn(self, t):
        """RoPE on the first ``rotary_dim`` features of every head."""
        r = self.rotary_dim
        if r == 0:
            return t
        turn = lambda u: rope(u, self.rope_theta, scale=self.rope_scale,
                              inv_freq=self.inv_freq)
        with jax.named_scope(ROPE_SCOPE):
            if r == t.shape[-1]:
                return turn(t)
            return jnp.concatenate([turn(t[..., :r]), t[..., r:]], axis=-1)

    def apply(self, params, state, inputs, *, train, rng=None) -> LayerOutput:
        if active_sequence_parallel() is not None:
            raise NotImplementedError(
                f"{self.name}: gated attention has no sequence-parallel "
                "core (ring / Ulysses take one head count)")
        w_q, w_k, w_v, w_o = params[:4]
        x = inputs[0]  # [B, S, E]
        S, E = x.shape[1:]
        H, Hk, D = self.num_heads, self.num_kv_heads, self.head_dim
        if self.head_gate:
            q = jnp.einsum("bse,hde->bhsd", x, w_q.reshape(H, D, E))
        else:
            q, gate = jnp.einsum("bse,hgde->gbhsd", x,
                                 w_q.reshape(H, 2, D, E))
        k, v = (jnp.einsum("bse,hde->bhsd", x, w.reshape(Hk, D, E))
                for w in (w_k, w_v))
        norms = params[4:6] if self.qk_norm else (None, None)
        q, k = (self._turn(t if w is None
                           else rms_norm(t, 1.0 + w, self.norm_eps))
                for t, w in zip((q, k), norms))
        self.visited = window_blocks(S, self.window)
        o = self._core(q, k, v, self.causal, self.window)
        with jax.named_scope(GATE_SCOPE):
            if self.head_gate:
                gate = jnp.einsum("bse,he->bhs", x, params[-1])[..., None]
            o = o * jax.nn.sigmoid(gate)
        y = jnp.einsum("bhsd,fhd->bsf", o, w_o.reshape(E, H, D))
        return LayerOutput(outputs=[y])
