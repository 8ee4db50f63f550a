"""Plain reference: Ouro, a looped language model (ByteDance, "Scaling
Latent Reasoning via Looped Language Models", the Ouro / LoopLM report,
2025; sizes: ByteDance/Ouro-2.6B ``config.json``, ``model_type: ouro``).

The equations.  A line marked + is NOT in the catalogued ``config.json``:
it is the family's published modeling code and the report's section 3 as
remembered (no network here), and the configuration's file lists it
under ``assumed``.

* Block l on x [B, S, D], four RMSNorms (eps 1e-6) a block, "sandwich"
  normalisation +:
      a = x + N2_l(Attn_l(N1_l(x)))
      y = a + N4_l(MLP_l(N3_l(a)))
  Attn: q, k, v = W_q u, W_k u, W_v u (no biases +, no QK-norm +), H heads
  of D / H, RoPE (theta 1e6) in the rotate-half layout + on q and k,
  causal softmax(q k^T / sqrt(D / H)) v, then W_o.
  MLP(u) = W_down(silu(W_gate u) * W_up u).
* The loop +: h^0 = Embed(tokens); for t = 1 .. T (``total_ut_steps``):
      h^t = N_f(Stack(h^(t-1)))
  where Stack is ALL the blocks in order with the SAME weights at every t
  and N_f the final RMSNorm, applied inside the loop so that step t + 1
  reads the normed state.  Positions (RoPE) are the same at every t.
* Exit gate +: lambda_t = sigmoid(w_g . h^t + b_g) per token (one Linear
  D -> 1, shared over t).  p_1 = lambda_1,
  p_t = lambda_t prod_{j<t} (1 - lambda_j) for 1 < t < T,
  p_T = prod_{j<T} (1 - lambda_j): a distribution over exit steps.
* Training loss + (the report's Stage-I objective): z_t = W_head h^t (the
  head at EVERY t), L_t the next-token cross-entropy of z_t per token,
      loss = mean_tokens[ sum_t p_t L_t - beta H(p) ],
      H(p) = -sum_t p_t log p_t,   beta = 0.1 +.
  At inference ``early_exit_threshold: 1`` means all T steps run and z_T
  is the output: ``forward`` returns z_T.

Straightforward ``jax.numpy``: float32, callers run it under
``jax.default_matmul_precision("highest")``; a Python loop over t and over
the blocks with the same parameter tree reused; no scan over the passes,
no kernel, no cache.  Nothing is imported from the program; ``params`` is
``{layer: [blobs]}`` by the prototxt's layer names, read from the solver:

  embed [W (V, D)]; per block i (from 0): norm<i>a [w (D)], attn<i>
  [W_qkv (3D, D) rows q, k, v; W_o (D, D)], norm<i>b [w (D)], norm<i>c
  [w (D)], mlp<i> [W_gate (F, D), W_up (F, D), W_down (D, F)], norm<i>d
  [w (D)]; norm_f [w (D)]; exit_gate [w_g (1, D), b_g (1)]; lm_head
  [W (V, D)].

Departures from the published modeling code, each deliberate:
* q/k/v come from one fused matrix (rows q, then k, then v): the same
  three matmuls, the program's blob layout;
* attention runs over blocks of ``QUERY_BLOCK`` queries against all keys
  (the whole masked softmax of a block at once), each block
  rematerialised in the backward pass, and each block-pass is
  rematerialised too: the same arithmetic in less memory (sixteen
  [16, 4096, 4096] score arrays would not fit the chip beside the model);
* ``loss_and_grads_by_block`` is ``loss_and_grads`` with the chain rule
  walked on the host, one block-pass at a time (``jax.vjp`` of ``block``,
  of the final norm and of ``exit_loss``; no derivative is written by
  hand): XLA then compiles ONE block where the whole program holds
  passes x blocks of them, forward, recomputed and backward (at the
  published widths 567 MB of generated code and 117 s of compiling for
  16 block-passes against 40 MB and 57 s for the five pieces, both
  compiled for a described v5e; harness/looped_check.py runs this form);
* ``dtype`` is float32 for the reference proper.  ``bfloat16`` computes
  EVERYTHING in bf16 (RMSNorm statistics, the softmaxes, the gate's
  log-sigmoids and the cross-entropy too): the nearest precision below
  the configuration's, the reading the benchmark's limits are set against
  (harness/looped_check.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
# one block's layers, by the prototxt's names
BLOCK_LAYERS = ("norm{}a", "attn{}", "norm{}b", "norm{}c", "mlp{}", "norm{}d")


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def rope(x, theta):
    """Rotate-half rotary embedding on [S, H, Dh] at positions 0..S-1: the
    first half of a head's features pairs with the second half."""
    s, _, dh = x.shape
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v):
    """q, k, v [S, H, Dh] -> [S, H, Dh]; blocks of queries, each against
    every key under the causal mask."""
    s, _, dh = q.shape
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, q.dtype))

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


def attention(p, x, cfg):
    """One sequence [S, D] -> [S, D]."""
    w_qkv, w_o = p
    s, d = x.shape
    h = cfg["heads"]
    q, k, v = (t.reshape(s, h, d // h)
               for t in jnp.split(x @ w_qkv.T, 3, axis=-1))
    q, k = rope(q, cfg["theta"]), rope(k, cfg["theta"])
    return causal_attention(q, k, v).reshape(s, d) @ w_o.T


def gated_mlp(p, x):
    w_g, w_u, w_d = p
    return (jax.nn.silu(x @ w_g.T) * (x @ w_u.T)) @ w_d.T


def block(p, i, x, cfg):
    """Block i on [B, S, D], sandwich-normed."""
    eps = cfg["eps"]
    u = rms_norm(x, p[f"norm{i}a"][0], eps)
    att = jnp.stack([attention(p[f"attn{i}"], u[n], cfg)
                     for n in range(x.shape[0])])
    a = x + rms_norm(att, p[f"norm{i}b"][0], eps)
    u = rms_norm(a, p[f"norm{i}c"][0], eps)
    return a + rms_norm(gated_mlp(p[f"mlp{i}"], u), p[f"norm{i}d"][0], eps)


def states(params, ids, cfg, dtype=jnp.float32):
    """Token ids [B, S] -> ([h^1 .. h^T], the parameters as computed
    with): the normed state after every pass."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    run = jax.checkpoint(lambda p, i, x: block(p, i, x, cfg),
                         static_argnums=(1,))
    h = p["embed"][0][ids]  # [B, S, D]
    out = []
    for _ in range(cfg["ut_steps"]):  # the SAME weights at every pass
        for i in range(cfg["layers"]):
            h = run(p, i, h)
        h = rms_norm(h, p["norm_f"][0], cfg["eps"])
        out.append(h)
    return out, p


def exit_distribution(gate_logits):
    """Gate logits [T, ...] -> log p [T, ...] over the exit steps, from
    log lambda = log sigmoid(g) and log (1 - lambda) = log sigmoid(-g)."""
    t = gate_logits.shape[0]
    log_p, stayed = [], jnp.zeros_like(gate_logits[0])
    for j in range(t - 1):
        log_p.append(stayed + jax.nn.log_sigmoid(gate_logits[j]))
        stayed = stayed + jax.nn.log_sigmoid(-gate_logits[j])
    return jnp.stack(log_p + [stayed])  # step T takes what is left


def forward(params, ids, cfg, dtype=jnp.float32, every_step=False):
    """Token ids [B, S] -> z_T [B, S, V]; with ``every_step`` ->
    (z [T, B, S, V], p [T, B, S])."""
    hs, p = states(params, ids, cfg, dtype)
    if not every_step:
        return hs[-1] @ p["lm_head"][0].T
    w_g, b_g = p["exit_gate"]
    z = jnp.stack([h @ p["lm_head"][0].T for h in hs])
    gate = jnp.stack([(h @ w_g.T)[..., 0] + b_g[0] for h in hs])
    return z, jnp.exp(exit_distribution(gate))


def exit_loss(hs, head, gate_w, gate_b, labels, beta):
    """The normed states of the T passes [T, B, S, D], the head's rows,
    the gate and the labels [B, S] -> (loss, (L [T] mean per-step
    cross-entropies, mean exit step, z_T [B, S, V], p [T, B, S]))."""
    z = hs @ head.T
    gate = (hs @ gate_w.T)[..., 0] + gate_b[0]
    logp = jax.nn.log_softmax(z, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.broadcast_to(labels, z.shape[:-1])[..., None], axis=-1)[..., 0]
    log_p = exit_distribution(gate)
    prob = jnp.exp(log_p)
    entropy = -jnp.sum(prob * log_p, axis=0)
    total = jnp.mean(jnp.sum(prob * nll, axis=0) - beta * entropy)
    steps = jnp.arange(1, hs.shape[0] + 1, dtype=prob.dtype)
    exit_mean = jnp.mean(jnp.tensordot(steps, prob, axes=1))
    return total, (jnp.mean(nll, axis=(1, 2)), exit_mean, z[-1], prob)


def loss(params, ids, labels, cfg, dtype=jnp.float32):
    """The exit-weighted loss -> (loss f32, (z_T [B, S, V], p [T, B, S],
    L [T], mean exit step))."""
    hs, p = states(params, ids, cfg, dtype)
    total, (step_loss, exit_mean, z_last, prob) = exit_loss(
        jnp.stack(hs), p["lm_head"][0], *p["exit_gate"], labels,
        cfg["entropy_weight"])
    return total.astype(jnp.float32), (z_last, prob, step_loss, exit_mean)


def loss_and_grads(params, ids, labels, cfg, dtype=jnp.float32):
    """((loss, aux as ``loss`` gives it), the gradients of every blob)."""
    return jax.value_and_grad(loss, has_aux=True)(
        params, ids, labels, cfg, dtype)


def block_params(p, i):
    """Block i's blobs under block 0's names, so that one traced block
    serves every block."""
    return {n.format(0): p[n.format(i)] for n in BLOCK_LAYERS}


_SIZES = ("heads", "eps", "theta")


@functools.partial(jax.jit, static_argnames=_SIZES)
def _block_fwd(bp, x, **sizes):
    return block(bp, 0, x, sizes)


@functools.partial(jax.jit, static_argnames=_SIZES)
def _block_bwd(bp, x, ct, **sizes):
    return jax.vjp(lambda bp, x: block(bp, 0, x, sizes), bp, x)[1](ct)


_norm_fwd = jax.jit(rms_norm, static_argnums=2)
_norm_bwd = jax.jit(
    lambda x, w, ct, eps: jax.vjp(
        lambda x, w: rms_norm(x, w, eps), x, w)[1](ct),
    static_argnums=3)
_exit_loss_grads = jax.jit(
    jax.value_and_grad(exit_loss, argnums=(0, 1, 2, 3), has_aux=True),
    static_argnums=5)


def loss_and_grads_by_block(params, ids, labels, cfg, dtype=jnp.float32):
    """``loss_and_grads``, walked on the host: every block-pass forward
    with its input kept, the exit-weighted loss and its gradients, then
    the passes and the blocks backwards, a looped blob's gradient summed
    over the passes as it goes (in ``dtype``, as the cotangents are)."""
    sizes = {k: cfg[k] for k in _SIZES}
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    n_blocks, w_f = cfg["layers"], p["norm_f"][0]
    h = p["embed"][0][ids]
    xs, pre, hs = [], [], []
    for _ in range(cfg["ut_steps"]):  # the SAME weights at every pass
        for i in range(n_blocks):
            xs.append(h)
            h = _block_fwd(block_params(p, i), h, **sizes)
        pre.append(h)
        h = _norm_fwd(h, w_f, cfg["eps"])
        hs.append(h)
    (total, (step_loss, exit_mean, z_last, prob)), tail = _exit_loss_grads(
        jnp.stack(hs), p["lm_head"][0], *p["exit_gate"], labels,
        cfg["entropy_weight"])
    grads = jax.tree_util.tree_map(jnp.zeros_like, p)
    grads["lm_head"], grads["exit_gate"] = [tail[1]], [tail[2], tail[3]]
    ct = jnp.zeros_like(h)
    for t in reversed(range(cfg["ut_steps"])):
        ct, d_w = _norm_bwd(pre[t], w_f, ct + tail[0][t], cfg["eps"])
        grads["norm_f"][0] = grads["norm_f"][0] + d_w
        for i in reversed(range(n_blocks)):
            d_bp, ct = _block_bwd(block_params(p, i), xs[t * n_blocks + i],
                                  ct, **sizes)
            for n in BLOCK_LAYERS:
                grads[n.format(i)] = [a + b for a, b in zip(
                    grads[n.format(i)], d_bp[n.format(0)])]
    grads["embed"][0] = grads["embed"][0].at[ids].add(ct)
    grads = jax.tree_util.tree_map(
        lambda g, w: g.astype(w.dtype), grads, params)
    return (total.astype(jnp.float32),
            (z_last, prob, step_loss, exit_mean)), grads


def clip_scale(grads, max_norm):
    """Global-norm clipping: the factor every gradient is multiplied by.
    A looped blob is one leaf: its gradient, the sum over the passes,
    counts once."""
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
