"""Plain reference: Qwen3-Next-80B-A3B, a decoder whose token mixer is
gated-DeltaNet linear attention three layers in four and output-gated
grouped softmax attention every fourth, with softmax-routed experts beside
a sigmoid-gated shared expert in every layer (sizes:
Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``, ``model_type:
qwen3_next``; the mixer: Gated Delta Networks, Yang, Kautz & Hatamizadeh
2024, arXiv:2412.06464, on the delta rule of arXiv:2406.06484).

The equations.  A line marked + is NOT in the catalogued ``config.json``:
it is the family's published modeling code and the paper as remembered
(no network here), and the configuration's file lists it under
``assumed``.

* Block i (from 0) on x [B, S, D], pre-norm, RMSNorm eps 1e-6 with the
  zero-centred weight + (y = (1 + w) x / rms(x), w from zero):
      a = x + Mix_i(N1_i(x));   y = a + Experts_i(N2_i(a))
  Mix_i is attention where (i + 1) % ``full_attention_interval`` == 0,
  else the gated DeltaNet.
* Gated DeltaNet +, H_k key heads and H_v value heads of d_k, d_v; key
  head h // (H_v / H_k) serves value head h:
      [q, k, v, z] = W_qkvz u;  [b, a] = W_ba u            (no biases)
      [q, k, v] <- silu(conv([q, k, v]))    depthwise, causal, 4 taps, no bias
      beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias)
      q, k <- q / |q|, k / |k| per head;  q <- q d_k^-1/2
      S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T;
      o_t = S^T q_t                       S in R^{d_k x d_v} a head, from 0
      out = W_out (RMSNorm_{d_v}(o) * silu(z))      one weight of d_v, ones
* Gated attention +, H query heads over H_kv key/value heads of D:
      [q | gate] = W_q u (a head's query and gate side by side);
      k, v = W_k u, W_v u;  q, k <- RMSNorm_D (zero-centred) per head;
      RoPE (theta 1e7, rotate-half) on the first ``rotary`` features of a
      head, the rest pass;  causal softmax(q k^T / sqrt(D)) v;
      out = W_o (o * sigmoid(gate))
* Experts: p = softmax(W_r u) over ALL the router's outputs, the k largest
  renormalised to sum 1 (``norm_topk_prob``); SwiGLU experts; the shared
  expert's output times sigmoid(w_g . u) +.  Auxiliary loss + (the HF
  ``load_balancing_loss_func``, per layer): E sum_e (pairs_e / T) mean_t p_te,
  summed over the layers at ``router_aux_loss_coef``.
* loss = mean next-token cross-entropy + coef * sum_layers aux.

THE SHARE: the experts' matrices in ``params`` are those of experts
[``first_expert``, ``first_expert`` + n) of the E the router scores, n
their leading axis; a (token, slot) pair routed elsewhere adds nothing.
The vocabulary is whatever rows ``embed`` and ``lm_head`` hold.

Straightforward ``jax.numpy``: float32, callers run it under
``jax.default_matmul_precision("highest")``; the delta rule is a
``lax.scan`` over time, one token a step, exactly the four assignments
above; no chunked form, no kernel, no cache.  Nothing is imported from the
program; ``params`` is ``{layer: [blobs]}`` by the prototxt's layer
names, read from the solver:

  embed [W (V, D)]; per block i: norm<i>a [w (D)]; gdn<i> [W_qkvz
  (2K + 2V', D) rows q, k, v, z; W_ba (2 H_v, D) rows b, a; conv_w
  (2K + V', 4); dt_bias (H_v); A_log (H_v); norm (d_v); W_out (D, V')] or
  attn<i> [W_q (2 H D, D); W_k (H_kv D, D); W_v; W_o (D, H D); q_norm (D);
  k_norm (D)]; norm<i>b [w (D)]; moe<i> [W_r (E, D); W_gate (n, F, D); W_up
  (n, F, D); W_down (n, D, F); Ws_gate (Fs, D); Ws_up; Ws_down (D, Fs);
  w_g (1, D)]; norm_f [w (D)]; lm_head [W (V, D)].

Departures from the published modeling code, each deliberate:
* W_qkvz and W_ba hold their parts in whole row blocks (q, then k, then
  v, then z; b, then a) where the published code interleaves them per
  key head: the same matmuls, the program's blob layout;
* the multi-token-prediction module is not built (the catalogued
  ``config.json`` has no key for it);
* memory is not mathematics: the scan over time sits under
  ``jax.checkpoint`` per ``SEGMENT`` tokens (its backward would otherwise
  hold a state a token: 4,096 x 2 MB a layer); attention runs over blocks
  of ``QUERY_BLOCK`` queries against all keys, each rematerialised;
  ``loss_and_grads_by_block`` is ``jax.value_and_grad(loss)`` with the
  chain rule walked on the host one block at a time (``jax.vjp`` of
  ``block`` and of the tail; no derivative is written by hand), so that
  XLA compiles one block of each kind and the chip holds one block's
  residuals (harness/linear_check.py runs this form);
* ``dtype`` is float32 for the reference proper.  ``bfloat16`` computes
  EVERYTHING in bf16 (the delta state and its decay, the gates, the norm
  statistics, the router's softmax and the cross-entropy too): the
  nearest precision below the configuration's, the reading the
  benchmark's limits are set against (harness/linear_check.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SEGMENT = 64


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def rope(x, theta):
    """Rotate-half rotary embedding on [S, H, r] at positions 0..S-1."""
    s, _, r = x.shape
    half = r // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def partial_rope(x, theta, rotary):
    """RoPE on the first ``rotary`` features of every head of [S, H, D]."""
    return jnp.concatenate([rope(x[..., :rotary], theta), x[..., rotary:]],
                           axis=-1)


def causal_attention(q, k, v):
    """q [S, H, D], k, v [S, H, D] -> [S, H, D]; blocks of queries, each
    against every key under the causal mask."""
    s, _, d = q.shape
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))

    @jax.checkpoint
    def block(args):
        start, qb = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(bq)
        scores = jnp.where(rows[:, None] >= cols[None, :], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(0, s, bq),
                              q.reshape((s // bq, bq) + q.shape[1:])))
    return out.reshape(q.shape)


def gated_attention(p, x, cfg):
    """One sequence [S, D] -> [S, D]."""
    w_q, w_k, w_v, w_o, q_norm, k_norm = p
    s = x.shape[0]
    h, hk, d = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    qg = (x @ w_q.T).reshape(s, h, 2, d)
    q, gate = qg[:, :, 0], qg[:, :, 1]
    k, v = (x @ w_k.T).reshape(s, hk, d), (x @ w_v.T).reshape(s, hk, d)
    q = partial_rope(rms_norm(q, 1.0 + q_norm, cfg["eps"]), cfg["theta"],
                     cfg["rotary"])
    k = partial_rope(rms_norm(k, 1.0 + k_norm, cfg["eps"]), cfg["theta"],
                     cfg["rotary"])
    # query head j reads key / value head j // (H / Hk)
    k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
    o = causal_attention(q, k, v) * jax.nn.sigmoid(gate)
    return o.reshape(s, h * d) @ w_o.T


def causal_conv(x, w):
    """Depthwise causal convolution over time: x [S, C], w [C, K]; tap j
    multiplies x[t - (K - 1) + j]."""
    taps = w.shape[1]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return sum(padded[j:j + x.shape[0]] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule on one sequence, one token a step: q, k
    [S, H, d_k], v [S, H, d_v], g, beta [S, H] -> o [S, H, d_v].  The
    state [H, d_k, d_v] is in the inputs' dtype."""
    def step(s, x):
        q1, k1, v1, g1, b1 = x
        s = jnp.exp(g1)[:, None, None] * s
        u = b1[:, None] * (v1 - jnp.einsum("hkv,hk->hv", s, k1))
        s = s + k1[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q1)

    @jax.checkpoint
    def segment(s, xs):  # memory, not mathematics (module docstring)
        return jax.lax.scan(step, s, xs)

    n = q.shape[0]
    seg = SEGMENT if n % SEGMENT == 0 else n
    cut = lambda x: x.reshape((n // seg, seg) + x.shape[1:])
    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype)
    _, o = jax.lax.scan(segment, s0, tuple(cut(x) for x in (q, k, v, g, beta)))
    return o.reshape(v.shape)


def gated_delta_net(p, x, cfg):
    """One sequence [S, D] -> [S, D]."""
    w_qkvz, w_ba, conv_w, dt_bias, a_log, norm_w, w_out = p
    s = x.shape[0]
    hk, hv, dk, dv = (cfg["lk_heads"], cfg["lv_heads"], cfg["lk_dim"],
                      cfg["lv_dim"])
    kw, vw = hk * dk, hv * dv
    qkvz, ba = x @ w_qkvz.T, x @ w_ba.T
    qkv = jax.nn.silu(causal_conv(qkvz[:, :2 * kw + vw], conv_w))
    q = l2_norm(qkv[:, :kw].reshape(s, hk, dk)) * dk ** -0.5
    k = l2_norm(qkv[:, kw:2 * kw].reshape(s, hk, dk))
    v = qkv[:, 2 * kw:].reshape(s, hv, dv)
    z = qkvz[:, 2 * kw + vw:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, hv:] + dt_bias)
    # key head h // (H_v / H_k) serves value head h
    q, k = (jnp.repeat(t, hv // hk, axis=1) for t in (q, k))
    o = delta_rule(q, k, v, g.astype(x.dtype), beta)
    y = rms_norm(o, norm_w, cfg["eps"]) * jax.nn.silu(z)
    return y.reshape(s, vw) @ w_out.T


def gated_mlp(p, x):
    w_g, w_u, w_d = p
    return (jax.nn.silu(x @ w_g.T) * (x @ w_u.T)) @ w_d.T


def router(w_r, x, cfg):
    """Tokens [T, D] -> (scores [T, E], chosen [T, k], weights [T, k]):
    softmax over all E outputs, the k largest, renormalised to sum 1."""
    scores = jax.nn.softmax(x @ w_r.T, axis=-1)
    picked, chosen = jax.lax.top_k(scores, cfg["top_k"])
    return scores, chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


def aux_loss(scores, chosen):
    """E sum_e (pairs_e / T) mean_t p_te (module docstring)."""
    e = scores.shape[-1]
    pairs = jnp.sum(jax.nn.one_hot(chosen, e, dtype=scores.dtype), axis=(0, 1))
    return e * jnp.sum(pairs / scores.shape[0] * jnp.mean(scores, axis=0))


def shared_expert(p, x):
    """The shared expert's output times sigmoid(w_g . x): p = [Ws_gate,
    Ws_up, Ws_down, w_g]."""
    return gated_mlp(p[:3], x) * jax.nn.sigmoid(x @ p[3].T)


def moe(p, x, cfg):
    """Tokens [T, D] -> (y [T, D], aux, scores [T, E], chosen [T, k]): the
    gated shared expert plus the held experts' part of the routed sum."""
    w_r, w_gate, w_up, w_down = p[:4]
    scores, chosen, weights = router(w_r, x, cfg)

    def one(y, held):
        e, w_g, w_u, w_d = held
        mine = chosen == e  # [T, k]
        w_e = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)  # 0: not routed
        return y + w_e[:, None] * gated_mlp((w_g, w_u, w_d), x), None

    ids = cfg["first_expert"] + jnp.arange(w_gate.shape[0])
    y, _ = jax.lax.scan(one, shared_expert(p[4:], x),
                        (ids, w_gate, w_up, w_down))
    return y, aux_loss(scores, chosen), scores, chosen


def is_attention(i: int, cfg) -> bool:
    return (i + 1) % cfg["interval"] == 0


def block_names(i: int, cfg) -> tuple[str, str, str, str]:
    mixer = f"attn{i}" if is_attention(i, cfg) else f"gdn{i}"
    return f"norm{i}a", mixer, f"norm{i}b", f"moe{i}"


def block(bp, attention: bool, x, cfg):
    """One block on [B, S, D]; ``bp`` = (norm_a, mixer, norm_b, experts),
    each a list of blobs.  -> (x, aux, (scores [T, E], chosen [T, k]))."""
    norm_a, mixer, norm_b, experts = bp
    b, s, d = x.shape
    mix = gated_attention if attention else gated_delta_net
    h = rms_norm(x, 1.0 + norm_a[0], cfg["eps"])
    x = x + jnp.stack([mix(mixer, h[n], cfg) for n in range(b)])
    h = rms_norm(x, 1.0 + norm_b[0], cfg["eps"]).reshape(b * s, d)
    y, aux, scores, chosen = moe(experts, h, cfg)
    return x + y.reshape(x.shape), aux, (scores, chosen)


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def tail(x, norm_w, head, labels, cfg):
    """The final norm, the head and the cross-entropy -> (main, logits)."""
    logits = rms_norm(x, 1.0 + norm_w, cfg["eps"]) @ head.T
    return cross_entropy(logits, labels), logits


def loss(params, ids, labels, cfg, dtype=jnp.float32):
    """-> (total f32, ((main, aux sum), (logits [B, S, V], {experts'
    layer: (scores, chosen)})))."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    run = jax.checkpoint(lambda bp, att, x: block(bp, att, x, cfg),
                         static_argnums=(1,))
    x = p["embed"][0][ids]
    aux, routing = jnp.zeros((), dtype), {}
    for i in range(cfg["layers"]):
        names = block_names(i, cfg)
        x, a, routing[names[3]] = run(tuple(p[n] for n in names),
                                      is_attention(i, cfg), x)
        aux = aux + a
    main, logits = tail(x, p["norm_f"][0], p["lm_head"][0], labels, cfg)
    total = main + cfg["aux_coef"] * aux
    return total.astype(jnp.float32), ((main, aux), (logits, routing))


_SIZES = ("heads", "kv_heads", "head_dim", "rotary", "theta", "eps",
          "lk_heads", "lv_heads", "lk_dim", "lv_dim", "top_k",
          "first_expert")


@functools.partial(jax.jit, static_argnames=("attention",) + _SIZES)
def _block_fwd(bp, x, attention, **sizes):
    return block(bp, attention, x, sizes)


@functools.partial(jax.jit, static_argnames=("attention",) + _SIZES)
def _block_bwd(bp, x, ct, ct_aux, attention, **sizes):
    _, pull, _ = jax.vjp(
        lambda bp, x: (lambda y, a, r: ((y, a), r))(
            *block(bp, attention, x, sizes)), bp, x, has_aux=True)
    return pull((ct, ct_aux))


@functools.partial(jax.jit, static_argnames=("eps",))
def _tail_grads(x, norm_w, head, labels, eps):
    return jax.value_and_grad(tail, argnums=(0, 1, 2), has_aux=True)(
        x, norm_w, head, labels, {"eps": eps})


def loss_and_grads_by_block(params, ids, labels, cfg, dtype=jnp.float32):
    """``jax.value_and_grad(loss, has_aux=True)``, walked on the host one
    block at a time: every block forward with its input kept, the tail
    and its gradients, then the blocks backwards (module docstring)."""
    sizes = {k: cfg[k] for k in _SIZES}
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    x = p["embed"][0][ids]
    xs, aux, routing = [], jnp.zeros((), dtype), {}
    for i in range(cfg["layers"]):
        names = block_names(i, cfg)
        xs.append(x)
        x, a, routing[names[3]] = _block_fwd(
            tuple(p[n] for n in names), x, is_attention(i, cfg), **sizes)
        aux = aux + a
    (main, logits), (ct, d_norm, d_head) = _tail_grads(
        x, p["norm_f"][0], p["lm_head"][0], labels, cfg["eps"])
    grads = jax.tree_util.tree_map(jnp.zeros_like, p)
    grads["norm_f"], grads["lm_head"] = [d_norm], [d_head]
    ct_aux = jnp.asarray(cfg["aux_coef"], dtype)
    for i in reversed(range(cfg["layers"])):
        names = block_names(i, cfg)
        d_bp, ct = _block_bwd(tuple(p[n] for n in names), xs[i], ct, ct_aux,
                              is_attention(i, cfg), **sizes)
        for n, d in zip(names, d_bp):
            grads[n] = list(d)
    grads["embed"][0] = grads["embed"][0].at[ids].add(ct)
    grads = jax.tree_util.tree_map(
        lambda g, w: g.astype(w.dtype), grads, params)
    total = main + cfg["aux_coef"] * aux
    return (total.astype(jnp.float32), ((main, aux), (logits, routing))), grads


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
