"""Plain reference: JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash
config.json, ``model_type: joyai_llm_flash``), whose block is DeepSeek-V3's
(arXiv:2412.19437; latent attention from DeepSeek-V2, arXiv:2405.04434
section 2.1; layer semantics as in Hugging Face ``modeling_deepseek_v3.py``).

Forward, both training losses and (by ``jax.grad``) gradients, the clip,
the AdamW step and the balancing-bias rule in straightforward
``jax.numpy``: float32, callers run it under
``jax.default_matmul_precision("highest")``.  No sort, no grouped matmul,
no kernel, no cache.  Nothing is imported from the program; ``params`` is
``{layer: [blobs]}`` by the prototxt's layer names, read from the solver
(every matrix ``[out, in]``):

  embed [W (V, D)]; per block i: norm<i>a [w (D)], attn<i> [W_dq (rq, D),
  q_norm (rq), W_uq (H*(dn+dr), rq), W_dkv (rkv+dr, D), kv_norm (rkv),
  W_ukv (H*(dn+dv), rkv), W_o (D, H*dv)], norm<i>b [w (D)], then
  mlp<i> [W_g (F, D), W_u (F, D), W_d (D, F)] in the leading dense blocks
  or moe<i> [W_r (E, D), W_gate (n, H, D), W_up (n, H, D), W_down
  (n, D, H), Ws_gate (Hs, D), Ws_up (Hs, D), Ws_down (D, Hs)] after them;
  norm_f [w (D)]; lm_head [W (V, D)]; the multi-token-prediction module:
  mtp_norm_h, mtp_norm_e [w (D)], mtp_proj [W_eh (D, 2D)], one more block
  (mtp_norm_a, mtp_attn, mtp_norm_b, mtp_moe), mtp_norm_f [w (D)].  The
  module's embedding and head are the main model's (``embed``, ``lm_head``).
``bias`` is ``{moe layer: b (E)}``, the selection bias of each router.

THE SHARE.  A ``moe`` layer's expert blobs hold n of the router's E
experts, [first_expert, first_expert + n): the router scores all E, the
token's k experts are chosen among all E, and an expert that is not held
adds nothing.  With n = E this is the whole layer (the CPU test sums the
shares against it).

Departures from ``modeling_deepseek_v3.py``, each deliberate:
* the rotary pair of angle i is features (2i, 2i + 1), each left where it
  is; HF moves them to (i, i + dr/2) first, in q and k alike, which gives
  the same scores;
* k_nope and v come from ONE matrix per head ([k_nope ; v], HF's
  ``kv_b_proj``) and the latent and the rotary key from one (HF's
  ``kv_a_proj_with_mqa``): the program's blob layout;
* every HELD expert computes every token in a ``lax.scan`` and the tokens
  it was not routed get weight exactly 0 (HF selects rows, a
  data-dependent shape): the same sum, term for term;
* attention runs over blocks of queries against all keys (the whole
  [S, S] masked softmax of a block at once), each block rematerialized in
  the backward pass, and each decoder block is rematerialized too, so that
  4,096 positions fit beside the program: the same arithmetic;
* ``n_group`` = ``topk_group`` = 1 (as published): no group-limited
  selection;
* the multi-token-prediction module is DeepSeek-V3's section 2.2 (HF ships
  none): h'_i = W_eh [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))], one block,
  the shared head; h_i is the residual stream before the final norm;
* ``dtype`` is float32 for the reference proper.  ``bfloat16`` computes
  EVERYTHING in bf16 (router scores, norm statistics and both
  cross-entropies too): the nearest precision below the configuration's,
  the reading the benchmark's limits are set against
  (harness/decoder_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def rope_interleaved(x, theta):
    """Rotary embedding on [S, ..., dr] at positions 0..S-1: angle i turns
    the pair of features (2i, 2i + 1)."""
    s, dr = x.shape[0], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (dr // 2,))
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    pairs = x.reshape(x.shape[:-1] + (dr // 2, 2))
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v, scale):
    """q, k [S, H, dqk], v [S, H, dv] -> [S, H, dv]; blocks of queries,
    each against every key under the causal mask."""
    s = q.shape[0]
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        start, qb = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = start + jnp.arange(bq)
        scores = jnp.where(rows[:, None] >= cols[None, :], scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(0, s, bq),
                              q.reshape((s // bq, bq) + q.shape[1:])))
    return out.reshape((s,) + out.shape[2:])


def latent_attention(p, x, cfg):
    """One sequence [S, D] -> [S, D]."""
    w_dq, q_norm, w_uq, w_dkv, kv_norm, w_ukv, w_o = p
    s = x.shape[0]
    h, dn, dr, dv = cfg["heads"], cfg["nope"], cfg["rope"], cfg["v"]
    rkv = kv_norm.shape[0]
    c_q = rms_norm(x @ w_dq.T, q_norm, cfg["eps"])
    q = (c_q @ w_uq.T).reshape(s, h, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], rope_interleaved(q[..., dn:], cfg["theta"])], axis=-1)
    dkv = x @ w_dkv.T
    c_kv = rms_norm(dkv[:, :rkv], kv_norm, cfg["eps"])
    k_rope = rope_interleaved(dkv[:, rkv:], cfg["theta"])  # [S, dr]: ONE key
    kv = (c_kv @ w_ukv.T).reshape(s, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope[:, None, :], (s, h, dr))],
        axis=-1)
    o = causal_attention(q, k, kv[..., dn:],
                         1.0 / jnp.sqrt(jnp.asarray(dn + dr, x.dtype)))
    return o.reshape(s, h * dv) @ w_o.T


def gated_mlp(p, x):
    w_g, w_u, w_d = p
    return (jax.nn.silu(x @ w_g.T) * (x @ w_u.T)) @ w_d.T


def router(w_r, x, bias, cfg):
    """Tokens [T, D] -> (scores [T, E], chosen [T, k], weights [T, k]):
    sigmoid scores, the k largest of score + bias, the unbiased scores of
    the chosen renormalised to sum 1 and times the scaling factor."""
    scores = jax.nn.sigmoid(x @ w_r.T)
    _, chosen = jax.lax.top_k(scores + bias.astype(scores.dtype), cfg["top_k"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg["scale"]
    return scores, chosen, weights


def moe(p, x, bias, cfg):
    """Tokens [T, D] -> (y [T, D], scores [T, E], chosen [T, k]): the
    shared expert plus the held experts' part of the routed sum."""
    w_r, w_gate, w_up, w_down = p[:4]
    scores, chosen, weights = router(w_r, x, bias, cfg)

    def one(y, held):
        e, w_g, w_u, w_d = held
        mine = chosen == e  # [T, k]
        w_e = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)  # 0: not routed
        return y + w_e[:, None] * gated_mlp((w_g, w_u, w_d), x), None

    ids = cfg["first_expert"] + jnp.arange(w_gate.shape[0])
    y, _ = jax.lax.scan(one, gated_mlp(p[4:], x), (ids, w_gate, w_up, w_down))
    return y, scores, chosen


def block(p, names, x, bias, cfg):
    """One decoder block on [B, S, D]; ``names`` = (norm_a, attn, norm_b,
    ffn).  -> (x, scores or None, chosen or None)."""
    norm_a, attn, norm_b, ffn = names
    b, s, d = x.shape
    h = rms_norm(x, p[norm_a][0], cfg["eps"])
    x = x + jnp.stack([latent_attention(p[attn], h[n], cfg) for n in range(b)])
    h = rms_norm(x, p[norm_b][0], cfg["eps"]).reshape(b * s, d)
    if len(p[ffn]) == 3:
        return x + gated_mlp(p[ffn], h).reshape(x.shape), None, None
    y, scores, chosen = moe(p[ffn], h, bias[ffn], cfg)
    return x + y.reshape(x.shape), scores, chosen


def block_names(cfg):
    main = [(f"norm{i}a", f"attn{i}", f"norm{i}b",
             f"mlp{i}" if i <= cfg["dense_layers"] else f"moe{i}")
            for i in range(1, cfg["layers"] + 1)]
    return main, ("mtp_norm_a", "mtp_attn", "mtp_norm_b", "mtp_moe")


def forward(params, bias, ids, labels, cfg, dtype=jnp.float32):
    """Token ids and next tokens [B, S] -> (main logits [B, S, V], MTP
    logits [B, S-1, V], {moe layer: (scores + bias [T, E], chosen [T, k])})."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    routing = {}
    run = jax.checkpoint(lambda p, names, x: block(p, names, x, bias, cfg),
                         static_argnums=(1,))
    main, mtp = block_names(cfg)

    def through(names, x):
        x, scores, chosen = run(p, names, x)
        if scores is not None:
            routing[names[3]] = (scores + bias[names[3]].astype(dtype), chosen)
        return x

    x = p["embed"][0][ids]  # [B, S, D]
    for names in main:
        x = through(names, x)
    logits = rms_norm(x, p["norm_f"][0], cfg["eps"]) @ p["lm_head"][0].T
    # multi-token prediction: h_i and the embedding of t_{i+1} = label_i
    both = jnp.concatenate(
        [rms_norm(x, p["mtp_norm_h"][0], cfg["eps"]),
         rms_norm(p["embed"][0][labels], p["mtp_norm_e"][0], cfg["eps"])],
        axis=-1)
    x = through(mtp, both @ p["mtp_proj"][0].T)
    x = rms_norm(x, p["mtp_norm_f"][0], cfg["eps"])
    return logits, x[:, :-1] @ p["lm_head"][0].T, routing


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def loss(params, bias, ids, labels, cfg, dtype=jnp.float32):
    """main cross-entropy + mtp_weight * MTP cross-entropy (position i of
    the module against t_{i+2} = labels[i + 1]).  -> (total, ((main, mtp),
    (logits, MTP logits, routing)))."""
    logits, mtp_logits, routing = forward(params, bias, ids, labels, cfg, dtype)
    main = cross_entropy(logits, labels)
    mtp = cross_entropy(mtp_logits, labels[:, 1:])
    total = main + cfg["mtp_weight"] * mtp
    return total.astype(jnp.float32), ((main, mtp),
                                       (logits, mtp_logits, routing))


def load_of(chosen, num_experts):
    """(token, slot) pairs per router output, over ALL of them."""
    return jnp.sum(jax.nn.one_hot(chosen.reshape(-1), num_experts,
                                  dtype=jnp.float32), axis=0)


def bias_step(bias, load, rate):
    """After the step, no gradient (DeepSeek-V3 section 2.1.2): an expert
    with fewer pairs than the mean is raised by ``rate``, a fuller one
    lowered."""
    return bias + rate * jnp.sign(jnp.mean(load) - load)


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
