"""Plain reference: OLMoE (Muennighoff et al. 2024, arXiv:2409.02060;
allenai/OLMoE-1B-7B-0125-Instruct config.json; layer semantics as in
Hugging Face ``modeling_olmoe.py``).

Forward, the three-term training loss and (by ``jax.grad``) gradients in
straightforward ``jax.numpy``: float32, callers run it under
``jax.default_matmul_precision("highest")``, the whole [S, S] masked
softmax, a Python loop over the experts with a boolean mask.  No sort, no
grouped matmul, no kernel, no cache.  Nothing is imported from the
program; ``params`` is ``{layer: [blobs]}`` by the prototxt's layer
names, read from the solver:

  embed [W (V, D)]; per layer i: norm<i>a [w (D)], attn<i> [W_qkv (3D, D)
  rows q, k, v; W_o (D, D); q_norm (D); k_norm (D)], norm<i>b [w (D)],
  moe<i> [W_router (E, D); W_gate (E, H, D); W_up (E, H, D); W_down
  (E, D, H)]; norm_f [w (D)]; lm_head [W (V, D)].

Departures from ``modeling_olmoe.py``, each deliberate:
* q/k/v come from one fused matrix (rows q, then k, then v): the same
  three matmuls, the program's blob layout;
* every expert computes every token and the tokens it was not routed get
  weight exactly 0 (HF selects rows with ``torch.where``, a data-dependent
  shape): the same sum, term for term;
* the auxiliary losses follow the TRAINING code the model was made with
  (OLMo + megablocks): load-balancing and router z-loss per layer,
  averaged over the layers.  HF's ``load_balancing_loss_func``
  concatenates the layers before taking its two means (a product of means
  over layers, not a mean of products) and has no z-loss at all; with one
  layer the load-balancing terms coincide;
* ``dtype`` is float32 for the reference proper.  ``bfloat16`` computes
  EVERYTHING in bf16 (router softmax, RMSNorm statistics and the
  cross-entropy too): the nearest precision below the configuration's, the
  reading the benchmark's limits are set against (harness/lm_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def rope(x, theta):
    """Rotate-half rotary embedding on [S, H, Dh]: the first half of a
    head's features pairs with the second half."""
    s, _, dh = x.shape
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(p, x, cfg):
    """One sequence [S, D] -> [S, D]."""
    w_qkv, w_o, q_norm, k_norm = p
    s, d = x.shape
    h = cfg["heads"]
    q, k, v = jnp.split(x @ w_qkv.T, 3, axis=-1)
    q = rms_norm(q, q_norm, cfg["eps"])  # over all D features, pre-split
    k = rms_norm(k, k_norm, cfg["eps"])
    q, k, v = (t.reshape(s, h, d // h) for t in (q, k, v))
    q, k = rope(q, cfg["theta"]), rope(k, cfg["theta"])
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(
        jnp.asarray(d // h, x.dtype))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(s, d) @ w_o.T


def moe(p, x, cfg):
    """Tokens [T, D] -> (y [T, D], router logits [T, E], experts [T, k])."""
    w_router, w_gate, w_up, w_down = p
    logits = x @ w_router.T
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg["top_k"])
    # norm_topk_prob is false: the k weights are NOT renormalised
    y = jnp.zeros_like(x)
    for e in range(w_router.shape[0]):
        mine = experts == e  # [T, k]
        w_e = jnp.sum(jnp.where(mine, weights, 0.0), axis=-1)  # 0 if not routed
        h = jax.nn.silu(x @ w_gate[e].T) * (x @ w_up[e].T)
        y = y + w_e[:, None] * (h @ w_down[e].T)
    return y, logits, experts


def forward(params, ids, cfg, dtype=jnp.float32):
    """Token ids [B, S] -> (logits [B, S, V], [router logits [B·S, E] per
    layer], [experts [B·S, k] per layer])."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    b, s = ids.shape
    x = p["embed"][0][ids]  # [B, S, D]
    router_logits, chosen = [], []
    for i in range(1, cfg["layers"] + 1):
        h = rms_norm(x, p[f"norm{i}a"][0], cfg["eps"])
        x = x + jnp.stack([attention(p[f"attn{i}"], h[n], cfg)
                           for n in range(b)])
        h = rms_norm(x, p[f"norm{i}b"][0], cfg["eps"])
        y, logits, experts = moe(p[f"moe{i}"], h.reshape(b * s, -1), cfg)
        x = x + y.reshape(x.shape)
        router_logits.append(logits)
        chosen.append(experts)
    x = rms_norm(x, p["norm_f"][0], cfg["eps"])
    return x @ p["lm_head"][0].T, router_logits, chosen


def loss_terms(params, ids, labels, cfg, dtype=jnp.float32):
    """(cross-entropy, load-balancing, router z-loss), unweighted, plus
    (logits, router logits per layer, experts per layer)."""
    logits, router_logits, chosen = forward(params, ids, cfg, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    lb = z = 0.0
    for rl, ex in zip(router_logits, chosen):
        n_exp = rl.shape[-1]
        probs = jax.nn.softmax(rl, axis=-1)
        # share of tokens whose slot s chose expert e: [k, E]
        share = jnp.mean(jax.nn.one_hot(ex, n_exp, dtype=rl.dtype), axis=0)
        lb = lb + n_exp * jnp.sum(share * jnp.mean(probs, axis=0)[None, :])
        z = z + jnp.mean(jax.nn.logsumexp(rl, axis=-1) ** 2)
    return ((ce, lb / cfg["layers"], z / cfg["layers"]),
            (logits, router_logits, chosen))


def loss(params, ids, labels, cfg, dtype=jnp.float32):
    """cross-entropy + lb_weight · load-balancing + z_weight · z-loss."""
    (ce, lb, z), aux = loss_terms(params, ids, labels, cfg, dtype)
    total = ce + cfg["lb_weight"] * lb + cfg["z_weight"] * z
    return total.astype(jnp.float32), ((ce, lb, z), aux)


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
