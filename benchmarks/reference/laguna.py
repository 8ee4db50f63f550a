"""Plain reference: Laguna-XS.2, a decoder whose grouped softmax attention
differs by layer KIND (three layers in four see a window of 512 keys,
every fourth sees every key; each kind with a query head count, a rotary
span and a frequency table of its own), with a head-wise sigmoid gate on
the attention output, one leading dense SwiGLU and then sigmoid-routed
experts beside one shared expert (sizes: poolside/Laguna-XS.2
``config.json``, ``model_type: laguna``).

The equations.  A line marked + is NOT fixed by the catalogued
``config.json``: the configuration's file lists it under ``assumed`` with
its origin.

* Block l (from 0) on h [B, S, D], pre-norm +, RMSNorm eps 1e-6 with a
  plain weight (y = w x / rms(x), w from one):
      a = h + Attn_l(N1_l(h));   h' = a + FFN_l(N2_l(a))
* Attention, H_l query heads (``heads[l]``: 48 full, 64 sliding) over Hk
  = 8 key/value heads of D = 128; no QK-norm +, no biases:
      q = W_q x in R^{H_l x D};  k = W_k x, v = W_v x in R^{Hk x D};
      g = sigmoid(W_g x) in R^{H_l}      one gate a head and token + (the
                                         row's ``gating: true`` and its
                                         33.4 B parameters fix the width;
                                         the sigmoid is the assumption)
  positions, rotate-half + over the first r features of a head, the rest
  pass.  Sliding layer: r = D, inv_freq_i = 10,000^(-2i / r).  Full layer:
  r = D / 2, YaRN as the public ``_compute_yarn_parameters``: with b =
  500,000, f = 64, L = 4,096: pos_i = b^(2i / r), i in [0, r / 2);
  dim(n) = r ln(L / (2 pi n)) / (2 ln b); low = max(floor(dim(beta_fast)),
  0), high = min(ceil(dim(beta_slow)), r - 1); ramp_i = clip((i - low) /
  (high - low), 0, 1); inv_freq_i = ramp_i / (f pos_i) + (1 - ramp_i) /
  pos_i; cos and sin times ``attention_factor`` (so a score's turned part
  carries its square).
      o_h = softmax(q_h k_{h // (H_l / Hk)}^T / sqrt(D) + mask) v_{...}
  mask: query t sees keys t - W + 1 .. t in a sliding layer (W = 512),
  every key up to t in a full one;
      y = W_o concat_h(g_h o_h)
* FFN: layer 0 (``mlp_layer_types``) a dense SwiGLU of 8,192; after it
  s = sigmoid(W_r x) in R^E +, the k largest, w = scale s_sel / sum s_sel +
  (scale 2.5, ``moe_routed_scaling_factor``; on the experts' OUTPUT,
  ``moe_apply_router_weight_on_input: false``), SwiGLU experts and one
  shared SwiGLU expert, no selection bias +.  Auxiliary loss + (the HF
  ``load_balancing_loss_func`` on the sigmoid scores, per layer):
  E sum_e (pairs_e / T) mean_t s_te, summed over the layers at 0.001.
* loss = mean next-token cross-entropy + coef * sum_layers aux.

THE SHARE: the experts' matrices in ``params`` are those of experts
[``first_expert``, ``first_expert`` + n) of the E the router scores, n
their leading axis; a (token, slot) pair routed elsewhere adds nothing.
The vocabulary is whatever rows ``embed`` and ``lm_head`` hold.

Straightforward ``jax.numpy``: float32, callers run it under
``jax.default_matmul_precision("highest")``; the scores of a head are
materialised [S, S] under an explicit boolean mask; no kernel, no cache.
Nothing is imported from the program; ``params`` is ``{layer: [blobs]}``
by the prototxt's layer names, read from the solver:

  embed [W (V, D)]; per block i: norm<i>a [w (D)]; attn<i> [W_q (H_i D, D);
  W_k (Hk D, D); W_v; W_o (D, H_i D); W_g (H_i, D)]; norm<i>b [w (D)];
  mlp<i> [W_gate (F, D); W_up (F, D); W_down (D, F)] or moe<i> [W_r (E, D);
  W_gate (n, F, D); W_up (n, F, D); W_down (n, D, F); Ws_gate (Fs, D);
  Ws_up; Ws_down (D, Fs)]; norm_f [w (D)]; lm_head [W (V, D)].

``cfg``: ``kinds`` (a tuple a block: "full_attention" /
"sliding_attention"), ``heads`` (a tuple a block), ``dense`` (a tuple of
bools a block), ``kv_heads``, ``head_dim``, ``window``, ``ropes`` (the
published ``rope_parameters`` as a hashable: a tuple of (kind, the group's
items)), ``eps``, ``top_k``, ``scale``, ``first_expert``, ``layers``,
``aux_coef``.

Departures from the published description, each deliberate:
* memory is not mathematics: the heads of a core are walked one at a time
  (``lax.map``), each rematerialised in the backward, so that one head's
  [S, S] scores exist at a time (268 MB at 8,192 in f32, where all 64
  would be 17 GB); ``loss_and_grads_by_block`` is
  ``jax.value_and_grad(loss)`` with the chain rule walked on the host one
  block at a time (``jax.vjp`` of ``block`` and of the tail; no derivative
  is written by hand), so that XLA compiles one block of each kind and the
  chip holds one block's residuals (harness/window_check.py runs this
  form);
* the YaRN table is computed in float64 on the host and rounded to
  float32 once (the public code computes it in float32 throughout);
* ``dtype`` is float32 for the reference proper.  ``bfloat16`` computes
  EVERYTHING in bf16 (the rotary tables' product, the scores and their
  softmax, the gates, the norm statistics, the router's sigmoid and the
  cross-entropy too): the nearest precision below the configuration's,
  the reading the benchmark's limits are set against
  (harness/window_check.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def yarn_bounds(r: int, rope: dict) -> tuple[int, int]:
    """(low, high) of the ramp over the r / 2 feature pairs."""
    b, big_l = rope["rope_theta"], rope["original_max_position_embeddings"]
    dim = lambda n: r * math.log(big_l / (2 * math.pi * n)) / (2 * math.log(b))
    return (max(math.floor(dim(rope["beta_fast"])), 0),
            min(math.ceil(dim(rope["beta_slow"])), r - 1))


def inv_freq(r: int, rope: dict) -> np.ndarray:
    """The r / 2 inverse frequencies of a layer kind's published group:
    ``rope_type`` default or yarn (module docstring), float32."""
    i = np.arange(r // 2, dtype=np.float64)
    pos = float(rope["rope_theta"]) ** (2.0 * i / r)
    if rope.get("rope_type", "default") == "default":
        return (1.0 / pos).astype(np.float32)
    low, high = yarn_bounds(r, rope)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return (ramp / (rope["factor"] * pos) + (1.0 - ramp) / pos).astype(
        np.float32)


def rotary(x, r: int, rope: dict):
    """Rotate-half rotary embedding on the first ``r`` features of every
    head of [S, H, D] at positions 0..S-1; cos and sin carry the group's
    ``attention_factor`` (1 without one)."""
    s = x.shape[0]
    ang = (jnp.arange(s, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq(r, rope))[None, :])
    factor = rope.get("attention_factor", 1.0)
    cos = (jnp.cos(ang) * factor)[:, None, :].astype(x.dtype)
    sin = (jnp.sin(ang) * factor)[:, None, :].astype(x.dtype)
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def masked_attention(q, k, v, window: int):
    """q [S, H, D], k, v [S, H, D] -> [S, H, D]: a head at a time, its
    [S, S] scores under the explicit mask (query t sees keys t - window +
    1 .. t, or every key up to t where ``window`` is 0)."""
    s, _, d = q.shape
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]  # query - key
    seen = ahead >= 0
    if window:
        seen = seen & (ahead < window)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))

    @jax.checkpoint
    def head(args):  # memory, not mathematics (module docstring)
        qh, kh, vh = args
        scores = jnp.where(seen, (qh @ kh.T) * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    out = jax.lax.map(head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return out.transpose(1, 0, 2)


def attention(p, x, heads: int, kind: str, cfg):
    """One sequence [S, D] -> [S, D]."""
    w_q, w_k, w_v, w_o, w_g = p
    s = x.shape[0]
    hk, d = cfg["kv_heads"], cfg["head_dim"]
    rope = dict(dict(cfg["ropes"])[kind])
    r = int(d * rope.get("partial_rotary_factor", 1))
    q = rotary((x @ w_q.T).reshape(s, heads, d), r, rope)
    k = rotary((x @ w_k.T).reshape(s, hk, d), r, rope)
    v = (x @ w_v.T).reshape(s, hk, d)
    gate = jax.nn.sigmoid(x @ w_g.T)  # [S, H]
    # query head j reads key / value head j // (H / Hk)
    k, v = (jnp.repeat(t, heads // hk, axis=1) for t in (k, v))
    window = cfg["window"] if kind == "sliding_attention" else 0
    o = masked_attention(q, k, v, window) * gate[:, :, None]
    return o.reshape(s, heads * d) @ w_o.T


def gated_mlp(p, x):
    w_g, w_u, w_d = p
    return (jax.nn.silu(x @ w_g.T) * (x @ w_u.T)) @ w_d.T


def router(w_r, x, cfg):
    """Tokens [T, D] -> (scores [T, E], chosen [T, k], weights [T, k]):
    a sigmoid per output, the k largest, renormalised to sum 1, times the
    routed scaling factor."""
    scores = jax.nn.sigmoid(x @ w_r.T)
    picked, chosen = jax.lax.top_k(scores, cfg["top_k"])
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return scores, chosen, weights * jnp.asarray(cfg["scale"], x.dtype)


def aux_loss(scores, chosen):
    """E sum_e (pairs_e / T) mean_t s_te (module docstring)."""
    e = scores.shape[-1]
    pairs = jnp.sum(jax.nn.one_hot(chosen, e, dtype=scores.dtype), axis=(0, 1))
    return e * jnp.sum(pairs / scores.shape[0] * jnp.mean(scores, axis=0))


def moe(p, x, cfg):
    """Tokens [T, D] -> (y [T, D], aux, scores [T, E], chosen [T, k]): the
    shared expert plus the held experts' part of the routed sum."""
    w_r, w_gate, w_up, w_down = p[:4]
    scores, chosen, weights = router(w_r, x, cfg)

    def one(y, held):
        e, w_g, w_u, w_d = held
        mine = chosen == e  # [T, k]
        w_e = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)  # 0: not routed
        return y + w_e[:, None] * gated_mlp((w_g, w_u, w_d), x), None

    ids = cfg["first_expert"] + jnp.arange(w_gate.shape[0])
    y, _ = jax.lax.scan(one, gated_mlp(p[4:], x), (ids, w_gate, w_up, w_down))
    return y, aux_loss(scores, chosen), scores, chosen


def block_names(i: int, cfg) -> tuple[str, str, str, str]:
    ffn = f"mlp{i}" if cfg["dense"][i] else f"moe{i}"
    return f"norm{i}a", f"attn{i}", f"norm{i}b", ffn


def block(bp, heads: int, kind: str, dense: bool, x, cfg):
    """One block on [B, S, D]; ``bp`` = (norm_a, attention, norm_b, ffn),
    each a list of blobs.  -> (x, aux, {"mixed": the attention's output
    [B, S, D] before the residual, "routing": (scores [T, E], chosen
    [T, k]) of an expert layer}); a dense block has no routing and aux
    0."""
    norm_a, mixer, norm_b, ffn = bp
    b, s, d = x.shape
    h = rms_norm(x, norm_a[0], cfg["eps"])
    mixed = jnp.stack([attention(mixer, h[n], heads, kind, cfg)
                       for n in range(b)])
    x = x + mixed
    h = rms_norm(x, norm_b[0], cfg["eps"]).reshape(b * s, d)
    if dense:
        y, aux, seen = gated_mlp(ffn, h), jnp.zeros((), x.dtype), {}
    else:
        y, aux, scores, chosen = moe(ffn, h, cfg)
        seen = {"routing": (scores, chosen)}
    return x + y.reshape(x.shape), aux, {"mixed": mixed, **seen}


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def tail(x, norm_w, head, labels, cfg):
    """The final norm, the head and the cross-entropy -> (main, logits)."""
    logits = rms_norm(x, norm_w, cfg["eps"]) @ head.T
    return cross_entropy(logits, labels), logits


def loss(params, ids, labels, cfg, dtype=jnp.float32):
    """-> (total f32, ((main, aux sum), (logits [B, S, V], {experts'
    layer: (scores, chosen)}, {attention layer: its output [B, S, D]})))."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = p["embed"][0][ids]
    aux, routing, mixed = jnp.zeros((), dtype), {}, {}
    for i in range(cfg["layers"]):
        names = block_names(i, cfg)
        run = jax.checkpoint(lambda bp, x, i=i: block(
            bp, cfg["heads"][i], cfg["kinds"][i], cfg["dense"][i], x, cfg))
        x, a, seen = run(tuple(p[n] for n in names), x)
        aux = aux + a
        mixed[names[1]] = seen["mixed"]
        if "routing" in seen:
            routing[names[3]] = seen["routing"]
    main, logits = tail(x, p["norm_f"][0], p["lm_head"][0], labels, cfg)
    total = main + cfg["aux_coef"] * aux
    return total.astype(jnp.float32), ((main, aux), (logits, routing, mixed))


_SIZES = ("kv_heads", "head_dim", "window", "ropes", "eps", "top_k", "scale",
          "first_expert")
_KIND = ("heads", "kind", "dense")


@functools.partial(jax.jit, static_argnames=_KIND + _SIZES)
def _block_fwd(bp, x, heads, kind, dense, **sizes):
    return block(bp, heads, kind, dense, x, sizes)


@functools.partial(jax.jit, static_argnames=_KIND + _SIZES)
def _block_bwd(bp, x, ct, ct_aux, heads, kind, dense, **sizes):
    _, pull, _ = jax.vjp(
        lambda bp, x: (lambda y, a, r: ((y, a), r))(
            *block(bp, heads, kind, dense, x, sizes)), bp, x, has_aux=True)
    return pull((ct, ct_aux))


@functools.partial(jax.jit, static_argnames=("eps",))
def _tail_grads(x, norm_w, head, labels, eps):
    return jax.value_and_grad(tail, argnums=(0, 1, 2), has_aux=True)(
        x, norm_w, head, labels, {"eps": eps})


def block_input(params, ids, cfg, i: int):
    """The residual stream [B, S, D] that enters block ``i``, by the
    by-block walk's own forward programs (float32)."""
    sizes = {k: cfg[k] for k in _SIZES}
    x = params["embed"][0][ids]
    for j in range(i):
        x = _block_fwd(tuple(params[n] for n in block_names(j, cfg)), x,
                       heads=cfg["heads"][j], kind=cfg["kinds"][j],
                       dense=cfg["dense"][j], **sizes)[0]
    return x


def loss_and_grads_by_block(params, ids, labels, cfg, dtype=jnp.float32):
    """``jax.value_and_grad(loss, has_aux=True)``, walked on the host one
    block at a time: every block forward with its input kept, the tail
    and its gradients, then the blocks backwards (module docstring)."""
    sizes = {k: cfg[k] for k in _SIZES}
    of = lambda i: dict(heads=cfg["heads"][i], kind=cfg["kinds"][i],
                        dense=cfg["dense"][i])
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = p["embed"][0][ids]
    xs, aux, routing, mixed = [], jnp.zeros((), dtype), {}, {}
    for i in range(cfg["layers"]):
        names = block_names(i, cfg)
        xs.append(x)
        x, a, seen = _block_fwd(tuple(p[n] for n in names), x, **of(i),
                                **sizes)
        aux = aux + a
        mixed[names[1]] = seen["mixed"]
        if "routing" in seen:
            routing[names[3]] = seen["routing"]
    (main, logits), (ct, d_norm, d_head) = _tail_grads(
        x, p["norm_f"][0], p["lm_head"][0], labels, cfg["eps"])
    grads = jax.tree_util.tree_map(jnp.zeros_like, p)
    grads["norm_f"], grads["lm_head"] = [d_norm], [d_head]
    ct_aux = jnp.asarray(cfg["aux_coef"], dtype)
    for i in reversed(range(cfg["layers"])):
        names = block_names(i, cfg)
        d_bp, ct = _block_bwd(tuple(p[n] for n in names), xs[i], ct, ct_aux,
                              **of(i), **sizes)
        for n, d in zip(names, d_bp):
            grads[n] = list(d)
    grads["embed"][0] = grads["embed"][0].at[ids].add(ct)
    grads = jax.tree_util.tree_map(
        lambda g, w: g.astype(w.dtype), grads, params)
    total = main + cfg["aux_coef"] * aux
    return (total.astype(jnp.float32),
            ((main, aux), (logits, routing, mixed))), grads


def clip_scale(grads, max_norm):
    """Global-norm clipping: the factor every gradient is multiplied by."""
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    return jnp.where(norm > max_norm, max_norm / norm, 1.0)


def adamw_step(w, g, m, v, t, *, lr, beta1, beta2, eps, weight_decay):
    """AdamW (Loshchilov & Hutter 2019) step ``t`` (1-based): decoupled
    decay, both moments bias-corrected.  -> (w, m, v)."""
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * g * g
    m_hat = m / (1 - beta1 ** t)
    v_hat = v / (1 - beta2 ** t)
    w = w - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * w)
    return w, m, v
