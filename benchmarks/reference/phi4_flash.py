"""Plain reference: Phi-4-mini-flash-reasoning
(microsoft/Phi-4-mini-flash-reasoning config.json, ``model_type:
phi4flash``), the SambaY decoder-hybrid-decoder (Ren et al. 2025,
arXiv:2507.06607) with differential attention (Ye et al. 2024,
arXiv:2410.05258) and Mamba layers (Gu & Dao 2023, arXiv:2312.00752).

Forward, the training loss and (by ``jax.grad``) gradients, the clip and
the AdamW step in straightforward ``jax.numpy``: float32, callers run it
under ``jax.default_matmul_precision("highest")``.  The scan is one
``lax.scan`` over time, attention materialises its masked scores a block
of queries at a time; no kernel, no chunking, no cache.  Nothing is
imported from the program; ``params`` is ``{layer: [blobs]}`` by the
prototxt's layer names, read from the solver (every matrix ``[out, in]``).

THE MODEL.  L published layers (32), L / 2 even, over hidden width E.
With v = LayerNorm(x) (weight and bias, eps 1e-5), every layer i is

    u  = x + Mix_i(LayerNorm_a(x))
    x' = u + W_down (silu(W_gate LayerNorm_b(u)) ⊙ W_up LayerNorm_b(u))

``role(i, L, mb_per_layer)`` gives Mix_i by the PUBLISHED index, as the
published modeling code assigns it: i % mb_per_layer == 0 is a Mamba-side
layer, else an attention-side one; i < L/2 the self-decoder ("mamba" /
"window"), i = L/2 the Mamba layer whose scan output is the MEMORY
("memory"), i = L/2 + 1 the one full attention layer, whose keys and
values are kept ("full"), i >= L/2 + 2 the cross-decoder ("gmu" /
"cross").  A cut keeps some layers (``cfg["kept"]``); each keeps the role
and the lambda_init of its published index.

Mamba (``mamba<i>`` [W_in (2d, E), conv_w (d, K), conv_b (d), W_x
(R + 2N, d), W_dt (d, R), b_dt (d), A_log (d, N), D (d), W_out (E, d)];
d = 2 E, N = 16, K = 4, R = ceil(E / 16)):
    [x~, z] = W_in v
    c_t = silu(sum_k conv_w[:, k] x~_{t - (K-1) + k} + conv_b)    x~_{<0} = 0
    [delta_t, B_t, C_t] = W_x c_t                                 (R, N, N)
    Δ_t = softplus(W_dt delta_t + b_dt);  A = -exp(A_log)
    h_t = exp(Δ_t A) ⊙ h_{t-1} + (Δ_t ⊙ c_t) B_tᵀ,  h_{-1} = 0,  h ∈ R^{d x N}
    y_t = h_t C_t + D ⊙ c_t
    Mix = W_out (y ⊙ silu(z));  the "memory" layer also hands on m = y.

Differential attention (``attn<i>`` [W_qkv ((H + 2 Hk) D, E), W_o
(E, H D), lambda_q1, lambda_k1, lambda_q2, lambda_k2 (D), subln (2D)];
H = 40 query and Hk = 20 key/value heads of D = 64; no positional
encoding, no biases):
    [q ; k ; v] = W_qkv v, split into heads of D
    query heads (2j, 2j+1) = (q1_j, q2_j), j < H/2; key heads (2g, 2g+1) =
    (k1_g, k2_g); v_g = [value head 2g ; value head 2g+1] ∈ R^{2D}, g < Hk/2;
    query pair j reads pair g = j // (H / Hk)
    a^r_j = softmax(q^r_j k^r_gᵀ / sqrt(D) + mask) v_g,   r = 1, 2
    lambda = exp(lambda_q1 · lambda_k1) - exp(lambda_q2 · lambda_k2) + lambda_init_i
    lambda_init_i = 0.8 - 0.6 exp(-0.3 i),  i the published index
    o_j = (1 - lambda_init_i) · subln ⊙ RMSNorm_{2D}(a^1_j - lambda a^2_j)   (eps 1e-5)
    Mix = W_o [o_0 .. o_{H/2 - 1}]
  mask: "window" lets query t see keys t - W + 1 .. t (W = 512), "full"
  every key up to t; the "full" layer hands on its k and v.
Cross-attention (``xattn<i>`` [W_q (H D, E), W_o, the four lambdas,
subln]): q = W_q v; k, v are the "full" layer's; the rest as above with
its own lambda vectors, sub-norm and lambda_init_i, full causal mask.
Gated memory unit (``gmu<i>`` [W_1 (d, E), W_2 (E, d)]):
    Mix = W_2 (silu(W_1 v) ⊙ m),  m the "memory" layer's.
Head: logits = LayerNorm_f(x) Eᵀ with E the embedding (``embed``; tied);
loss = mean cross-entropy over every position.

THE SHARE.  ``embed`` holds the rows of the vocabulary this chip holds;
ids and labels are drawn from them and the loss is over them.

Departures from the paper and from the published modeling code, each
deliberate:
* W_qkv and W_o carry no bias (the published modeling code gives both
  one, as I recall it; config.json names no attention bias, and the
  issue's parameter count has none): 10,240 parameters a layer left out;
* which heads pair is the Diff Transformer's own layout (its
  ``multihead_flashdiff_2.py``: consecutive heads); the Phi-4-mini-flash
  code's could not be checked here;
* the window's edge: query t sees W keys, itself included (flash
  attention's ``window_size=(W - 1, 0)``);
* attention runs over blocks of ``QUERY_BLOCK`` queries against all keys,
  each block rematerialised in the backward pass, and each layer is
  rematerialised too: the same arithmetic in less memory;
* both softmax maps of a layer go through ONE call of the attention (the
  halves side by side on the head axis): the same arithmetic in a smaller
  compiled program (the machine's compile cache holds 192 MiB for the
  cell's three executables together; PERF.md section 7).  A ``lax.scan``
  over the LAYERS with their weights stacked would share more (85 MB of
  generated code against 216 unrolled) but its stacked copies of the
  weights and of their gradients need 16 GB beside the solver's 8.4;
* ``dtype`` is float32 for the reference proper.  ``bfloat16`` computes
  EVERYTHING in bf16 (the scan's state, Δ and the exponential, every
  norm's statistics, lambda, the cross-entropy): the nearest precision
  below the configuration's, the reading the benchmark's limits are set
  against (harness/hybrid_check.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def role(i: int, layers: int, mb_per_layer: int = 2) -> str:
    half = layers // 2
    mamba_side = i % mb_per_layer == 0
    if i < half:
        return "mamba" if mamba_side else "window"
    if i == half:
        return "memory"
    if i == half + 1:
        return "full"
    return "gmu" if mamba_side else "cross"


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return w * ((x - mean) * jax.lax.rsqrt(var + eps)) + b


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return w * (x * jax.lax.rsqrt(var + eps))


def mamba(p, x):
    """One sequence [S, E] -> (Mix [S, E], y [S, d])."""
    w_in, conv_w, conv_b, w_x, w_dt, b_dt, a_log, d_skip, w_out = p
    s = x.shape[0]
    d, n = a_log.shape
    taps, rank = conv_w.shape[1], w_dt.shape[1]
    xz = x @ w_in.T
    xt, z = xz[:, :d], xz[:, d:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), x.dtype), xt])
    c = jax.nn.silu(sum(conv_w[:, k] * padded[k:k + s] for k in range(taps))
                    + conv_b)
    dbc = c @ w_x.T
    delta = jax.nn.softplus(dbc[:, :rank] @ w_dt.T + b_dt)
    b_mat, c_mat = dbc[:, rank:rank + n], dbc[:, rank + n:]
    a = -jnp.exp(a_log)

    def step(h, t):
        delta_t, c_t, b_t, cm_t = t
        h = (jnp.exp(delta_t[:, None] * a) * h
             + (delta_t * c_t)[:, None] * b_t[None, :])
        return h, h @ cm_t + d_skip * c_t

    _, y = jax.lax.scan(step, jnp.zeros((d, n), x.dtype),
                        (delta, c, b_mat, c_mat))
    return (y * jax.nn.silu(z)) @ w_out.T, y


def masked_attention(q, k, v, window):
    """q [S, H, D], k [S, H, D], v [S, H, Dv] -> [S, H, Dv]: blocks of
    queries, each against every key under the mask; ``window`` 0 is no
    window."""
    s, d = q.shape[0], q.shape[-1]
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)
    scale = jnp.asarray(1.0 / math.sqrt(d), q.dtype)
    width = window or s

    @jax.checkpoint
    def block(args):
        start, qb = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        ahead = (start + jnp.arange(bq))[:, None] - cols[None, :]
        scores = jnp.where((ahead >= 0) & (ahead < width), scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(0, s, bq),
                              q.reshape((s // bq, bq) + q.shape[1:])))
    return out.reshape((s,) + out.shape[2:])


def diff_attention(p, x, kv, lam0, window, cfg):
    """One sequence [S, E] -> (Mix [S, E], (k [S, Hk, D], v [S, Hk, D])).
    ``kv``: the keys and values to read (cross-attention) or None;
    ``lam0`` the layer's lambda_init, ``window`` its window or 0."""
    h, hk, eps = cfg["heads"], cfg["kv_heads"], cfg["eps"]
    s, e = x.shape
    d = e // h
    w_in, w_o, lq1, lk1, lq2, lk2, subln = p
    proj = x @ w_in.T
    q = proj[:, :h * d].reshape(s, h // 2, 2, d)  # [S, pair j, half r, D]
    if kv is None:
        k = proj[:, h * d:(h + hk) * d].reshape(s, hk, d)
        v = proj[:, (h + hk) * d:].reshape(s, hk, d)
    else:
        k, v = kv
    rep = h // hk
    k_pairs = jnp.repeat(k.reshape(s, hk // 2, 2, d), rep, axis=1)
    v_pairs = jnp.repeat(v.reshape(s, hk // 2, 1, 2 * d), rep, axis=1)
    # both maps of every pair in one call: [S, (pair, half), .]
    a = masked_attention(
        q.reshape(s, h, d), k_pairs.reshape(s, h, d),
        jnp.broadcast_to(v_pairs, (s, h // 2, 2, 2 * d)).reshape(s, h, 2 * d),
        window).reshape(s, h // 2, 2, 2 * d)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    o = (1 - lam0) * rms_norm(a[:, :, 0] - lam.astype(x.dtype) * a[:, :, 1],
                              subln, eps)
    return o.astype(x.dtype).reshape(s, h * d) @ w_o.T, (k, v)


def gated_mlp(p, x):
    w_g, w_u, w_d = p
    return (jax.nn.silu(x @ w_g.T) * (x @ w_u.T)) @ w_d.T


def mixer_name(i: int, kind: str) -> str:
    return {"mamba": "mamba", "memory": "mamba", "window": "attn",
            "full": "attn", "gmu": "gmu", "cross": "xattn"}[kind] + str(i)


def layer(p, i, kind, x, memory, kv, cfg):
    """One layer on one sequence [S, E].  -> (x', memory, kv)."""
    v = layer_norm(x, *p[f"norm{i}a"], cfg["eps"])
    blobs = p[mixer_name(i, kind)]
    if kind in ("mamba", "memory"):
        mix, y = mamba(blobs, v)
        memory = y if kind == "memory" else memory
    elif kind == "gmu":
        mix = (jax.nn.silu(v @ blobs[0].T) * memory) @ blobs[1].T
    else:
        mix, own = diff_attention(
            blobs, v, kv if kind == "cross" else None, lambda_init(i),
            cfg["window"] if kind == "window" else 0, cfg)
        kv = own if kind == "full" else kv
    u = x + mix
    return (u + gated_mlp(p[f"mlp{i}"], layer_norm(u, *p[f"norm{i}b"],
                                                   cfg["eps"])),
            memory, kv)


def forward(params, ids, cfg, dtype=jnp.float32):
    """Token ids [B, S] -> logits [B, S, V] over the rows held."""
    p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    kinds = [(i, role(i, cfg["layers"], cfg["mb_per_layer"]))
             for i in cfg["kept"]]

    def one(seq):
        x = p["embed"][0][seq]
        memory = kv = None  # written by the "memory" / "full" layer
        for i, kind in kinds:
            run = jax.checkpoint(
                lambda p, x, memory, kv, i=i, kind=kind:
                layer(p, i, kind, x, memory, kv, cfg))
            x, memory, kv = run(p, x, memory, kv)
        return layer_norm(x, *p["norm_f"], cfg["eps"]) @ p["embed"][0].T

    return jax.vmap(one)(ids)


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def loss(params, ids, labels, cfg, dtype=jnp.float32):
    """-> (mean cross-entropy over every position, logits)."""
    logits = forward(params, ids, cfg, dtype)
    return cross_entropy(logits, labels).astype(jnp.float32), logits


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
