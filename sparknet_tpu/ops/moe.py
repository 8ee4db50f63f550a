"""In-graph mixture-of-experts FFN — the expert layer type.

The reference has no MoE or expert parallelism (ref: SURVEY §2.3.5 —
its parallelism inventory ends at data parallelism); like
`MultiHeadAttention`, this is a TPU-first-class extra wired through the
ordinary prototxt/DSL -> compiler path so expert models build, train and
snapshot like the CNN zoo.

Prototxt surface::

    layer {
      name: "moe" type: "MoE" bottom: "x"
      top: "y" top: "lb_loss" top: "z_loss" top: "load"
      loss_weight: 0 loss_weight: 0.01 loss_weight: 0.001 loss_weight: 0
      moe_param { num_experts: 64 hidden_dim: 1024 top_k: 8
                  expert_act: "swiglu" bias_term: false }
    }

Input/output blobs are [..., D].  Routing (Switch / OLMoE, public
technique, PAPERS.md): router logits and their softmax over the experts
in f32, the ``top_k`` largest probabilities are the token's experts and
its combine weights (renormalised to sum 1 only with ``norm_topk_prob``).
Dispatch is DROPLESS: the T·k (token, slot) pairs are sorted by expert,
each expert runs on exactly its own rows through a grouped matmul over
the ragged groups, and the rows are un-sorted and summed per token.  No
capacity, no padding, no token dropped.

Tops, as many as the prototxt declares (1 to 4): ``y``; the
load-balancing loss ``E · Σ_e f_e · P_e`` (f_e: share of the T·k pairs
routed to e per slot, P_e: mean router probability); the router z-loss
``mean_t (logsumexp logits_t)²``; and the tokens per expert of this
step, which the layer also keeps in its state (``load``) for a reader
that looks only at fences.  The loss tops carry the prototxt's
``loss_weight`` like any Caffe loss top.

Params in Caffe blob order, every expert matrix ``[out, in]``:
  relu   (default): [W_router (E, D), W1 (E, H, D), b1 (E, H),
                     W2 (E, D, H), b2 (E, D)]   y = relu(x W1ᵀ+b1) W2ᵀ+b2
  swiglu:           [W_router (E, D), W_gate (E, H, D), W_up (E, H, D),
                     W_down (E, D, H)]  y = (silu(x W_gateᵀ) · x W_upᵀ) W_downᵀ
``bias_term: false`` drops b1/b2 (swiglu never has them).  The defaults
(top-1, relu, biases) are the switch layer `parallel/expert.py`
distributes; ``moe_dense`` below is that layer's dense oracle.

The DeepSeek-V3 family's layer (arXiv:2412.19437) is the same path with
further ``moe_param`` fields: ``scoring_func: "sigmoid"`` (independent
scores; default softmax), ``routed_scaling_factor`` (times the weights,
after ``norm_topk_prob``), ``bias_update_rate: γ`` (a per-expert bias
[E] f32 in the layer's state ``bias`` joins the scores for the SELECTION
only; after each training step ``b_e += γ · sign(mean(load) − load_e)``,
no gradient) and ``shared_hidden_dim`` (one shared SwiGLU expert every
token passes: three more blobs [Ws_gate (Hs, D), Ws_up (Hs, D),
Ws_down (D, Hs)] at the end, scope ``M.shared``).

The chip's SHARE of an expert-parallel layer: ``experts_held: n`` and
``first_expert: e0`` say that this layer holds experts [e0, e0 + n) of
the ``num_experts`` the router scores.  The router keeps its width, the
expert blobs' leading axis is n, and a (token, slot) pair whose expert
is not held adds nothing to ``y``; ``load`` still counts every router
output.  Nothing stands in for the absent chips or their exchange.

What a share-holding layer moves (PRs 35 and 49).  A deployment's
exchange would hand this chip only its own rows, so the layer touches
only those: the pairs are sorted with the held groups first, so the LIVE
rows are the first ``sum(group_sizes)`` sorted rows, and every per-row
operation walks them in tiles of ``CAPACITY_TILE`` = 512 rows,
``live_tiles`` = ceil(held pairs / 512) of them, a trip count READ ON THE
DEVICE from the ``group_sizes`` the layer already has.  Two movers, each
other's transpose, each a ``custom_vjp`` around ``lax.fori_loop``s (a loop
with a traced trip count has no reverse mode of its own): ``gather_live``
brings the live tiles' rows of ``x`` (its cotangent adds them back to
their tokens in f32), the grouped matmuls visit no other tile, and
``combine_live`` adds the weighted rows to their tokens in f32 (its
cotangents are ``dy[token] · w`` and the row sums of ``dy[token] · out``,
tile by tile).  What lies between the grouped matmuls walks the same
tiles (``_LiveRows``: the activation and the biases through ``map_live``,
the sum of the rows' two cotangents through ``_twice_live``).  The arrays
are [R, ·] for R = T·k in whole tiles, never initialised (``_buffer``)
and never passed over whole: the cost follows the live rows, about 2,500
of 40,960 at 32 of 512 experts under a level router, at ANY routing, all
pairs on held experts included.  So there is ONE path and no capacity
(PR 35's ``lax.cond`` between a path at 6 x the level share and a path
over all rows left with PR 49): the layer is dropless through it, the
forward keeps only its operands (``_held_rows``) and the backward runs the
path again.  ``Solver._fence_stats`` counts the rows the loops walked
(``moe_rows_moved``) with the same ``live_tiles`` from the same ``load``.
A layer that holds every expert has no dead row, takes ``_all_rows`` and
lowers as it did.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from sparknet_tpu.ops.base import Layer, LayerOutput
from sparknet_tpu.ops.blocks import gated_mlp
from sparknet_tpu.ops.fillers import fill
from sparknet_tpu.ops.registry import register
from sparknet_tpu.proto.text_format import Message

# device scopes inside the layer's own ``L.<name>`` scope (a trace reader
# finds the layer by ``L.``, the stage by these); in common.CACHE_SCOPES
ROUTE_SCOPE = "M.route"
DISPATCH_SCOPE = "M.dispatch"
EXPERTS_SCOPE = "M.experts"
COMBINE_SCOPE = "M.combine"
SHARED_SCOPE = "M.shared"  # the shared expert, which every token passes


def gate_top1(w_gate, x):
    """Softmax gate -> (expert index, gate probability) per token.

    ``x``: [T, D] tokens; returns ([T] int32, [T] float)."""
    logits = x @ w_gate.T  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    idx = jnp.argmax(logits, axis=-1)
    return idx, jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]


def expert_ffn(params_e, x):
    """One expert's FFN on its tokens: ReLU(x W1ᵀ + b1) W2ᵀ + b2.

    ``params_e``: (W1 [H, D], b1 [H], W2 [D, H], b2 [D]); ``x``: [T, D]."""
    w1, b1, w2, b2 = params_e
    return jax.nn.relu(x @ w1.T + b1) @ w2.T + b2


def moe_dense(params, x):
    """Dense top-1 MoE on [T, D] tokens: every expert computes every
    token, a one-hot combine keeps the chosen one.  The oracle for the
    expert-parallel dispatch (`parallel/expert.py`) and for the layer's
    top-1 ReLU default; nothing on a training path calls it."""
    w_gate, w1, b1, w2, b2 = params
    idx, prob = gate_top1(w_gate, x)
    # [E, T, D]: expert-major dense compute (MXU-friendly batched matmuls)
    h = jax.nn.relu(jnp.einsum("td,ehd->eth", x, w1) + b1[:, None, :])
    y_all = jnp.einsum("eth,edh->etd", h, w2) + b2[:, None, :]
    onehot = jax.nn.one_hot(idx, w1.shape[0], dtype=x.dtype)  # [T, E]
    return jnp.einsum("etd,te->td", y_all, onehot) * prob[:, None]


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def route(w_router, x, top_k: int, norm_topk_prob: bool = False, *,
          scoring: str = "softmax", select_bias=None, scale: float = 1.0):
    """Router on [T, D] tokens -> (logits [T, E] f32, scores [T, E] f32,
    weights [T, k] f32, experts [T, k] int32).  The matmul takes the
    operands as they come (bf16 under ``--dtype bf16``) and accumulates
    in f32; everything after it is f32.

    ``scoring``: ``softmax`` over the experts (Switch / OLMoE) or an
    independent ``sigmoid`` per expert (DeepSeek-V3).  ``select_bias``
    [E] f32 is added to the scores for the SELECTION only (the
    auxiliary-loss-free balancing of arXiv:2408.15664: no gradient
    reaches it, the weights are the unbiased scores of the chosen);
    ``scale`` multiplies the weights after the renormalisation."""
    logits = jnp.dot(x, w_router.T, preferred_element_type=jnp.float32)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    if select_bias is None:
        weights, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(select_bias), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return logits, scores, weights, experts.astype(jnp.int32)


def load_balancing_loss(probs, experts, num_experts: int):
    """``E · Σ_{slot, e} f_{slot,e} · P_e`` (Switch Transformer eq. 4 as
    HF ``load_balancing_loss_func`` computes it for top-k: the share of
    tokens whose slot s went to e, times the mean router probability of
    e, summed over slots and experts)."""
    mask = jax.nn.one_hot(experts, num_experts, dtype=jnp.float32)  # [T,k,E]
    return num_experts * jnp.sum(
        jnp.mean(mask, axis=0) * jnp.mean(probs, axis=0)[None, :])


def router_z_loss(logits):
    """``mean_t (log Σ_e exp logits_te)²`` (ST-MoE eq. 5)."""
    return jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))


# ---------------------------------------------------------------------------
# dropless dispatch: sort the (token, slot) pairs by expert, grouped
# matmul over the ragged groups, un-sort, weighted sum per token
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _take_rows(x, idx, inv):
    """``x[idx]`` for a PERMUTATION ``idx`` of the rows with inverse
    ``inv``: the cotangent is the inverse gather, never a scatter."""
    return x[idx]


def _take_rows_fwd(x, idx, inv):
    return x[idx], inv


def _take_rows_bwd(inv, g):
    return g[inv], None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _spread_rows(x, order, inv):
    """Row r of the result is token ``order[r] // k`` of ``x`` [T, D]
    (``order``: a permutation of the T·k pairs, ``inv`` its inverse).
    The cotangent un-sorts and sums each token's k rows: a gather and a
    reshape, never a scatter-add."""
    return x[order // (order.shape[0] // x.shape[0])]


def _spread_rows_fwd(x, order, inv):
    return _spread_rows(x, order, inv), (inv, x.shape[0])


def _spread_rows_bwd(res, g):
    inv, tokens = res
    return g[inv].reshape(tokens, -1, g.shape[-1]).sum(axis=1), None, None


_spread_rows.defvjp(_spread_rows_fwd, _spread_rows_bwd)

# every expert matrix is stored [out, in]: contract the rows' features
# with the matrix's LAST axis, groups along its first
_OUT_IN = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([1], [2]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])


def _gmm_tiles(x, w):
    """The megablox tile triple (rows, in, out) for ``x`` [M, K] times
    ``w`` [G, N, K]; M is a multiple of 128."""
    tm = next(t for t in (512, 256, 128) if x.shape[0] % t == 0)
    return tm, min(x.shape[1], 1024), min(w.shape[1], 1024)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_out_in(x, w, group_sizes, interpret):
    """``x`` [M, K] with M a multiple of 128, ``w`` [G, N, K] -> [M, N]
    through jax's megablox kernels, with the backward ``grouped_matmul``
    describes."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(x, w, group_sizes, x.dtype, _gmm_tiles(x, w),
               transpose_rhs=True, interpret=interpret)


def _gmm_out_in_fwd(x, w, group_sizes, interpret):
    return _gmm_out_in(x, w, group_sizes, interpret), (x, w, group_sizes)


def _gmm_out_in_bwd(interpret, res, dy):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    x, w, group_sizes = res
    tm, tk, tn = _gmm_tiles(x, w)
    tiles = (tm, tn, tk)  # both calls contract what the forward wrote
    dx = gmm(dy, w, group_sizes, x.dtype, tiles, interpret=interpret)
    # [G, N, K] as ``w`` is stored: dy is the kernel's LEFT operand
    dw = tgmm(dy.swapaxes(0, 1), x, group_sizes, w.dtype, tiles,
              num_actual_groups=w.shape[0], interpret=interpret)
    return dx, dw, None


_gmm_out_in.defvjp(_gmm_out_in_fwd, _gmm_out_in_bwd)


def _megablox_matmul(x, w, group_sizes, interpret=False):
    """``grouped_matmul``'s TPU branch.  ``interpret`` is for the tests,
    which run the kernels on the CPU."""
    m = x.shape[0]
    pad = -m % 128  # the kernel tiles the rows; rows past the groups' sum are dead
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = _gmm_out_in(x, w, group_sizes, interpret)
    return out[:m] if pad else out


def grouped_matmul(x, w, group_sizes):
    """``x`` [M, K] rows sorted by group, ``w`` [G, N, K], ``group_sizes``
    [G] int32 summing to at most M -> [M, N]: rows of group g times
    ``w[g]ᵀ``.

    On a TPU, at lane-aligned widths: jax's own megablox kernels, ``gmm``
    forward and for ``dx``, ``tgmm`` for ``dw``.  The kernels' time, timed
    once on the v5e at the OLMoE expert block's shapes (131,072 rows, 64
    groups, 2048x1024, forward + backward, PERF.md section 6): 39.2 ms
    against 51.7 ms for XLA's ``ragged_dot`` with the weights transposed
    first and 78.4 ms with them as stored.  Elsewhere (the CPU, odd
    widths) XLA's ``ragged_dot_general``, which the kernel cannot replace
    there.

    The backward is ours, not ``megablox.ops.gmm``'s.  With
    ``transpose_rhs`` that one computes ``tgmm(xᵀ, dy)`` -> [G, K, N] and
    then ``swapaxes(1, 2)``.  The kernel's output layout is fixed, so XLA
    folded the transpose into the layout of the AdamW fusion that takes
    the gradient, and converted the f32 matrix and both its moments into
    that layout and back in every step: 18 copies of 537 MB in the OLMoE
    step, 1.71 ms each (ledger, PR 30), to save one of 268 MB.
    ``tgmm(dyᵀ, x)`` IS [G, N, K]: the same products, the same f32
    accumulation, nothing transposed between the kernel and the update
    (``tools/expert_copies.py`` counts such copies in a compiled step)."""
    if jax.default_backend() != "tpu" or x.shape[1] % 128 or w.shape[1] % 128:
        return jax.lax.ragged_dot_general(
            x, w, group_sizes, _OUT_IN, preferred_element_type=x.dtype)
    return _megablox_matmul(x, w, group_sizes)


# ---------------------------------------------------------------------------
# a layer that holds a SHARE of its experts: the live rows only
# ---------------------------------------------------------------------------

# A chip that holds n of E experts is sent, under a level router, n / E
# of the (token, slot) pairs.  The pairs are sorted with the held groups
# first, so the LIVE rows are the first ``sum(group_sizes)`` sorted rows,
# and every per-row operation of the share-holding layer walks them in
# tiles of this many rows (whole megablox row tiles), ``live_tiles`` of
# them: a trip count read on the device from the ``group_sizes`` the layer
# already has, and by ``Solver._fence_stats`` from the same ``load``.
CAPACITY_TILE = 512


def live_tiles(held_pairs):
    """Tiles of ``CAPACITY_TILE`` sorted rows that hold ``held_pairs``
    live rows: the ONE count behind the device's loops (``held_pairs``
    traced) and the host's ``moe_rows_moved`` (an int of the fence)."""
    return (held_pairs + CAPACITY_TILE - 1) // CAPACITY_TILE


def _buffer(shape, dtype):
    """An array the live walk writes tile by tile.  Uninitialised where
    the backend allows (a TPU: no pass over its bytes; the CPU gives
    zeros): whatever lies past the last live tile is never read into a
    sum, whatever it holds (the grouped matmuls skip those tiles, every
    mover selects by its own ``live`` mask and never multiplies by one)."""
    return jax.lax.empty(shape, dtype)


def _tile(i, n_live):
    """(start, [TILE] bool: the rows of tile ``i`` that are live)."""
    start = i * CAPACITY_TILE
    return start, start + jnp.arange(CAPACITY_TILE, dtype=jnp.int32) < n_live


def _rows_of(a, start):
    return jax.lax.dynamic_slice_in_dim(a, start, CAPACITY_TILE)


def _put_rows(a, tile, start):
    return jax.lax.dynamic_update_slice_in_dim(a, tile, start, 0)


def _wide(mask, like):
    """[TILE] bool against a tile [TILE, ...]."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


def _take_live(x, token, n_live):
    """``gather_live``'s walk (no gradient of its own: a loop with a
    traced trip count has no reverse mode)."""
    def body(i, rows):
        start, live = _tile(i, n_live)
        tile = x[_rows_of(token, start)]
        return _put_rows(rows, jnp.where(_wide(live, tile), tile, 0), start)

    return jax.lax.fori_loop(
        0, live_tiles(n_live), body,
        _buffer(token.shape + x.shape[1:], x.dtype))


@jax.custom_vjp
def gather_live(x, token, n_live):
    """Rows [R, ...] whose first ``n_live`` are ``x[token]`` (indices may
    repeat; R a multiple of ``CAPACITY_TILE``), moved a tile at a time
    over the ``live_tiles`` alone: the last one is zero past ``n_live``,
    rows past it are never written.  The cotangent adds the live tiles'
    rows to their indices in f32 before it is rounded to ``x``'s type."""
    return _take_live(x, token, n_live)


def _gather_live_fwd(x, token, n_live):
    return _take_live(x, token, n_live), (token, n_live, x.shape[0])


def _gather_live_bwd(res, g):
    token, n_live, rows = res

    def body(i, dx):
        start, live = _tile(i, n_live)
        tile = _rows_of(g, start).astype(jnp.float32)
        return dx.at[_rows_of(token, start)].add(
            jnp.where(_wide(live, tile), tile, 0))

    with jax.named_scope(DISPATCH_SCOPE):
        dx = jax.lax.fori_loop(
            0, live_tiles(n_live), body,
            jnp.zeros((rows,) + g.shape[1:], jnp.float32))
        return dx.astype(g.dtype), None, None


gather_live.defvjp(_gather_live_fwd, _gather_live_bwd)


def _add_live(out, weights, order, n_live):
    tokens, top_k = weights.shape
    w_pair = weights.reshape(-1)

    def body(i, y):
        start, live = _tile(i, n_live)
        pair = _rows_of(order, start)
        tile = _rows_of(out, start).astype(jnp.float32) * w_pair[pair][:, None]
        return y.at[pair // top_k].add(jnp.where(live[:, None], tile, 0))

    y = jax.lax.fori_loop(
        0, live_tiles(n_live), body,
        jnp.zeros((tokens, out.shape[1]), jnp.float32))
    return y.astype(out.dtype)


@jax.custom_vjp
def combine_live(out, weights, order, n_live):
    """y [T, D]: sorted row r < ``n_live`` of ``out`` [R, D], the row of
    pair ``order[r]`` = (token, slot), times ``weights`` [T, k] of that
    pair, added to its token in f32, a tile at a time over the
    ``live_tiles`` alone (the [R, D] f32 product is never a whole array).
    The cotangents, tile by tile too: ``dy[token] · w`` to ``out`` and
    the row sums of ``dy[token] · out`` to the pairs' weights."""
    return _add_live(out, weights, order, n_live)


def _combine_live_fwd(out, weights, order, n_live):
    return _add_live(out, weights, order, n_live), (out, weights, order,
                                                    n_live)


def _combine_live_bwd(res, dy):
    out, weights, order, n_live = res
    top_k = weights.shape[1]
    w_pair = weights.reshape(-1)

    def body(i, carry):
        d_out, d_w = carry
        start, live = _tile(i, n_live)
        pair = _rows_of(order, start)
        g = dy[pair // top_k].astype(jnp.float32)
        to_out = jnp.where(live[:, None], g * w_pair[pair][:, None], 0)
        to_w = jnp.where(live, jnp.sum(
            g * _rows_of(out, start).astype(jnp.float32), axis=1), 0)
        return (_put_rows(d_out, to_out.astype(out.dtype), start),
                d_w.at[pair].add(to_w))

    with jax.named_scope(COMBINE_SCOPE):
        d_out, d_w = jax.lax.fori_loop(
            0, live_tiles(n_live), body,
            (_buffer(out.shape, out.dtype), jnp.zeros_like(w_pair)))
    return d_out, d_w.reshape(weights.shape), None, None


combine_live.defvjp(_combine_live_fwd, _combine_live_bwd)


def _map_tiles(fn, n_live, arrays):
    """``fn`` of the live tiles of ``arrays`` [R, ·], row by row the same
    function, into a buffer of whatever ``fn`` gives a tile."""
    like = jax.eval_shape(fn, *(a[:CAPACITY_TILE] for a in arrays))

    def body(i, out):
        start = i * CAPACITY_TILE
        return _put_rows(out, fn(*(_rows_of(a, start) for a in arrays)),
                         start)

    return jax.lax.fori_loop(
        0, live_tiles(n_live), body,
        _buffer(arrays[0].shape[:1] + like.shape[1:], like.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def map_live(fn, n_live, *arrays):
    """``fn(*arrays)`` for an ELEMENTWISE ``fn`` (a row of the result
    from the same row of every operand), over the live tiles alone; the
    cotangents, ``fn``'s own a tile, over the same tiles."""
    return _map_tiles(fn, n_live, arrays)


def _map_live_fwd(fn, n_live, *arrays):
    return _map_tiles(fn, n_live, arrays), (n_live, arrays)


def _map_live_bwd(fn, res, g):
    n_live, arrays = res

    def body(i, grads):
        start = i * CAPACITY_TILE
        pull = jax.vjp(fn, *(_rows_of(a, start) for a in arrays))[1]
        return tuple(_put_rows(d, tile, start)
                     for d, tile in zip(grads, pull(_rows_of(g, start))))

    with jax.named_scope(EXPERTS_SCOPE):
        grads = jax.lax.fori_loop(
            0, live_tiles(n_live), body,
            tuple(_buffer(a.shape, a.dtype) for a in arrays))
    return (None, *grads)


map_live.defvjp(_map_live_fwd, _map_live_bwd)


@jax.custom_vjp
def _twice_live(rows, n_live):
    """``rows`` for two readers, whose cotangents are added over the live
    tiles alone (autodiff's own sum passes over all R rows)."""
    return rows, rows


_twice_live.defvjp(
    lambda rows, n_live: ((rows, rows), n_live),
    lambda n_live, gs: (_map_tiles(jnp.add, n_live, gs), None))


class _EveryRow:
    """How ``_experts`` treats its rows where every one is live (a layer
    that holds every expert): whole-array operations."""

    def __init__(self, flat, order):
        self.flat, self.order = flat, order

    def twice(self, rows):
        return rows, rows

    def each(self, fn, *arrays):
        return fn(*arrays)

    def biases(self, *biases):
        of_row = self.flat[self.order]  # each sorted row's expert
        return [b[of_row] for b in biases]


class _LiveRows(_EveryRow):
    """The same where only the first ``n_live`` sorted rows are live (a
    share): every operation walks the live tiles alone."""

    def __init__(self, flat, order, n_live):
        super().__init__(flat, order)
        self.n_live = n_live

    def twice(self, rows):
        return _twice_live(rows, self.n_live)

    def each(self, fn, *arrays):
        return map_live(fn, self.n_live, *arrays)

    def biases(self, *biases):
        of_row = _take_live(self.flat, self.order, self.n_live)
        return [gather_live(b, of_row, self.n_live) for b in biases]


def _experts(rows, rest, group_sizes, how, expert_act: str):
    """The held experts on their sorted ``rows`` -> [M, D].  ``how``
    (``_EveryRow`` / ``_LiveRows``) does what is not a grouped matmul:
    the activation, the biases' rows, the sum of two cotangents."""
    with jax.named_scope(EXPERTS_SCOPE):
        if expert_act == "swiglu":
            w_gate, w_up, w_down = rest
            to_gate, to_up = how.twice(rows)
            h = how.each(lambda g, u: jax.nn.silu(g) * u,
                         grouped_matmul(to_gate, w_gate, group_sizes),
                         grouped_matmul(to_up, w_up, group_sizes))
            return grouped_matmul(h, w_down, group_sizes)
        if len(rest) == 2:
            w1, w2 = rest
            h = how.each(jax.nn.relu, grouped_matmul(rows, w1, group_sizes))
            return grouped_matmul(h, w2, group_sizes)
        w1, b1, w2, b2 = rest
        of_b1, of_b2 = how.biases(b1, b2)
        h = how.each(lambda a, b: jax.nn.relu(a + b),
                     grouped_matmul(rows, w1, group_sizes), of_b1)
        return how.each(jnp.add, grouped_matmul(h, w2, group_sizes), of_b2)


def _all_rows(x, weights, rest, flat, order, group_sizes, *, expert_act: str):
    """Dispatch, experts and combine over ALL T·k sorted rows -> y [T, D]:
    a layer that holds every expert, so every row is live."""
    tokens, top_k = weights.shape
    with jax.named_scope(DISPATCH_SCOPE):
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        rows = _spread_rows(x, order, inv)  # [T·k, D], expert-major
    out = _experts(rows, rest, group_sizes, _EveryRow(flat, order),
                   expert_act)
    with jax.named_scope(COMBINE_SCOPE):
        per_pair = _take_rows(out, inv, order).reshape(tokens, top_k, -1)
        return jnp.sum(per_pair.astype(jnp.float32) * weights[..., None],
                       axis=1).astype(x.dtype)


def _live_rows(x, weights, rest, flat, order, group_sizes, *, expert_act: str):
    """The same sum for a SHARE of the experts, whose held pairs are the
    first ``sum(group_sizes)`` sorted rows: ``gather_live`` brings those
    rows of ``x``, the grouped matmuls visit no other, what lies between
    them walks the same tiles (``_LiveRows``), ``combine_live`` adds them
    to their tokens.  The arrays are [R, ·] for R = T·k in whole tiles,
    and nothing passes over more of one than its live tiles."""
    top_k = weights.shape[1]
    n_live = jnp.sum(group_sizes)
    with jax.named_scope(DISPATCH_SCOPE):
        order = jnp.pad(order, (0, -order.shape[0] % CAPACITY_TILE))
        rows = gather_live(x, order // top_k, n_live)
    out = _experts(rows, rest, group_sizes, _LiveRows(flat, order, n_live),
                   expert_act)
    with jax.named_scope(COMBINE_SCOPE):
        return combine_live(out, weights, order, n_live)


# jitted: a net's share-holding layers of one shape are traced once, the
# path and its vjp, not once a layer (set-up time: PERF.md, PR 35)
@functools.partial(jax.jit, static_argnames=("expert_act",))
def _held_forward(x, weights, rest, flat, order, group_sizes, *, expert_act):
    return _live_rows(x, weights, rest, flat, order, group_sizes,
                      expert_act=expert_act)


@functools.partial(jax.jit, static_argnames=("expert_act",))
def _held_backward(x, weights, rest, flat, order, group_sizes, dy, *,
                   expert_act):
    return jax.vjp(
        lambda *diff: _live_rows(*diff, flat, order, group_sizes,
                                 expert_act=expert_act),
        x, weights, rest)[1](dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _held_rows(x, weights, rest, flat, order, group_sizes, expert_act):
    """y [T, D] of a layer that holds a share of its experts.  The forward
    keeps only its operands and the backward runs the path again: its
    [R, ·] arrays live inside one pass of one layer."""
    return _held_forward(x, weights, rest, flat, order, group_sizes,
                         expert_act=expert_act)


def _held_rows_fwd(x, weights, rest, flat, order, group_sizes, expert_act):
    ops = (x, weights, rest, flat, order, group_sizes)
    return _held_forward(*ops, expert_act=expert_act), ops


def _held_rows_bwd(expert_act, ops, dy):
    grads = _held_backward(*ops, dy, expert_act=expert_act)
    return (*grads, None, None, None)


_held_rows.defvjp(_held_rows_fwd, _held_rows_bwd)


def sort_pairs(experts, num_experts: int, held_n: int, first_expert: int):
    """The T·k (token, slot) pairs of ``experts`` [T, k] sorted by expert
    -> (flat [T·k]: pair t·k + s -> its held expert's position, ``held_n``
    where it is not held; order [T·k] int32: the stable sort, held groups
    first; group_sizes [held_n]: rows of each held expert; load [E] int32:
    pairs of every router output)."""
    with jax.named_scope(DISPATCH_SCOPE):
        flat = experts.reshape(-1)  # pair t*k + s -> its expert
        load = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
        if held_n == num_experts:
            group_sizes = load
        else:
            local = flat - first_expert
            held = (local >= 0) & (local < held_n)
            flat = jnp.where(held, local, held_n)  # not held: sorted last
            group_sizes = jax.lax.dynamic_slice_in_dim(
                load, first_expert, held_n)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    return flat, order, group_sizes, load


def moe_dropless(params, x, *, top_k: int, expert_act: str,
                 norm_topk_prob: bool = False, scoring: str = "softmax",
                 select_bias=None, scale: float = 1.0, first_expert: int = 0):
    """The layer's compute on [T, D] tokens -> (y [T, D], logits, scores,
    experts [T, k], load [E] f32).  ``params`` as the layer holds them:
    the router over all E experts, then the matrices of the experts HELD
    here, ``[first_expert, first_expert + n)`` with n their leading axis
    (all E by default).  A pair routed to an expert that is not held adds
    nothing: its rows sort behind the last held group and its weight
    reaches no sum.  A layer that holds a share moves, computes and
    combines its live rows alone, in tiles read off ``group_sizes`` on the
    device (``_live_rows``); one that holds every expert has no dead row
    (``_all_rows``).  Either way no pair is dropped.  ``load`` counts the
    pairs of every router output, held or not."""
    w_router, rest = params[0], tuple(params[1:])
    num_experts, held_n = w_router.shape[0], rest[0].shape[0]
    with jax.named_scope(ROUTE_SCOPE):
        logits, probs, weights, experts = route(
            w_router, x, top_k, norm_topk_prob, scoring=scoring,
            select_bias=select_bias, scale=scale)
    flat, order, group_sizes, load = sort_pairs(
        experts, num_experts, held_n, first_expert)
    if held_n < num_experts:
        y = _held_rows(x, weights, rest, flat, order, group_sizes, expert_act)
    else:  # every expert lives here: no pair is dead
        y = _all_rows(x, weights, rest, flat, order, group_sizes,
                      expert_act=expert_act)
    return y, logits, probs, experts, load.astype(jnp.float32)


@register
class MoELayer(Layer):
    """The expert layer (module docstring).  ``shared_gate: true`` (with
    ``shared_hidden_dim``) appends one more blob, w_g (1, D): the shared
    expert's output is multiplied by sigmoid(x w_g^T) a token before it
    joins the routed experts' (the Qwen MoE families' gated shared
    expert), under the same ``M.shared`` scope."""

    TYPE = "MoE"

    def __init__(self, lp, phase):
        super().__init__(lp, phase)
        p = lp.get_msg("moe_param")
        self.num_experts = p.get_int("num_experts", 1)
        self.hidden_dim = p.get_int("hidden_dim", 0)
        self.top_k = p.get_int("top_k", 1)
        self.expert_act = p.get_str("expert_act", "relu")
        self.norm_topk_prob = p.get_bool("norm_topk_prob", False)
        self.scoring = p.get_str("scoring_func", "softmax")
        self.scale = p.get_float("routed_scaling_factor", 1.0)
        # the balancing bias of the selection: state, moved by its own rule
        self.bias_rate = p.get_float("bias_update_rate", 0.0)
        self.select_bias = self.bias_rate > 0
        self.shared_dim = p.get_int("shared_hidden_dim", 0)
        self.shared_gate = p.get_bool("shared_gate", False)
        if self.shared_gate and not self.shared_dim:
            raise ValueError(
                f"{self.name}: shared_gate gates a shared expert; give "
                "shared_hidden_dim")
        # this chip's share: experts [first_expert, first_expert + held)
        self.first_expert = p.get_int("first_expert", 0)
        self.experts_held = p.get_int("experts_held", self.num_experts)
        if self.expert_act not in ("relu", "swiglu"):
            raise ValueError(
                f"{self.name}: unknown expert_act {self.expert_act!r} "
                "(relu|swiglu)")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"{self.name}: unknown scoring_func {self.scoring!r} "
                "(softmax|sigmoid)")
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"{self.name}: top_k {self.top_k} outside 1.."
                f"{self.num_experts} experts")
        if not (self.experts_held >= 1 and 0 <= self.first_expert
                <= self.num_experts - self.experts_held):
            raise ValueError(
                f"{self.name}: experts [{self.first_expert}, "
                f"{self.first_expert + self.experts_held}) are not among "
                f"the router's {self.num_experts}")
        # swiglu experts carry no biases (none of the published ones do)
        self.bias_term = (self.expert_act == "relu"
                          and p.get_bool("bias_term", True))
        if len(self.tops) > 4:
            raise ValueError(
                f"{self.name}: at most 4 tops (y, lb_loss, z_loss, load)")
        self.weight_filler = (
            p.get_msg("weight_filler")
            if p.has("weight_filler")
            else Message().set("type", "xavier")
        )

    def init(self, key, in_shapes):
        D = in_shapes[0][-1]
        H = self.hidden_dim or 4 * D
        E, N = self.num_experts, self.experts_held
        kg, k1, k2, k3 = jax.random.split(key, 4)
        w_router = fill(self.weight_filler, kg, (E, D))
        state = {"load": jnp.zeros((E,), jnp.float32)}
        if self.select_bias:
            state["bias"] = jnp.zeros((E,), jnp.float32)
        if self.expert_act == "swiglu":
            params = [w_router,
                      fill(self.weight_filler, k1, (N, H, D)),
                      fill(self.weight_filler, k3, (N, H, D)),
                      fill(self.weight_filler, k2, (N, D, H))]
        else:
            w1 = fill(self.weight_filler, k1, (N, H, D))
            w2 = fill(self.weight_filler, k2, (N, D, H))
            params = ([w_router, w1, jnp.zeros((N, H), jnp.float32),
                       w2, jnp.zeros((N, D), jnp.float32)]
                      if self.bias_term else [w_router, w1, w2])
        if self.shared_dim:
            ks = jax.random.split(jax.random.fold_in(key, 1), 3)
            params += [fill(self.weight_filler, ks[0], (self.shared_dim, D)),
                       fill(self.weight_filler, ks[1], (self.shared_dim, D)),
                       fill(self.weight_filler, ks[2], (D, self.shared_dim))]
        if self.shared_gate:
            params.append(fill(self.weight_filler,
                               jax.random.fold_in(key, 2), (1, D)))
        return params, state

    def apply(self, params, state, inputs, *, train, rng=None) -> LayerOutput:
        x = inputs[0]
        flat = x.reshape(-1, x.shape[-1])
        if self.shared_gate:
            *params, w_g = params
        routed = params[:-3] if self.shared_dim else params
        bias = state["bias"] if self.select_bias else None
        y, logits, probs, experts, load = moe_dropless(
            routed, flat, top_k=self.top_k, expert_act=self.expert_act,
            norm_topk_prob=self.norm_topk_prob, scoring=self.scoring,
            select_bias=bias, scale=self.scale,
            first_expert=self.first_expert)
        if self.shared_dim:
            with jax.named_scope(SHARED_SCOPE):
                shared = gated_mlp(flat, *params[-3:])
                if self.shared_gate:
                    shared = shared * jax.nn.sigmoid(flat @ w_g.T)
                y = y + shared
        outputs = [y.reshape(x.shape)]
        if len(self.tops) > 1:
            with jax.named_scope(ROUTE_SCOPE):
                outputs += [
                    load_balancing_loss(probs, experts, self.num_experts),
                    router_z_loss(logits), load][:len(self.tops) - 1]
        new_state = {"load": load}
        if self.select_bias:
            # after the step, no gradient: an expert that saw fewer pairs
            # than the mean is raised, a fuller one lowered (DeepSeek-V3,
            # arXiv:2412.19437 section 2.1.2; the mean is T·k / E)
            new_state["bias"] = (
                bias + self.bias_rate * jnp.sign(jnp.mean(load) - load)
                if train else bias)
        return LayerOutput(outputs=outputs, state=new_state)
