"""Operations and bytes a training step of a window-and-full attention
decoder (grouped attention whose layers differ by kind in head count,
window and rotary table, a head-wise output gate, one leading dense MLP,
sigmoid-routed experts beside a shared expert) REQUIRES, from the
configuration's sizes, in ``harness/flops.py``'s row format.

The rules are ``harness/lm_flops.py``'s: a multiply-add is 2 operations;
every matmul counts three passes (forward, weight gradient, data
gradient; ``from_data`` is False on every row); norms, RoPE, softmax,
SiLU, the gates' sigmoid and product, the dispatch's gathers, the
cross-entropy and the optimizer count zero; recomputed operations (the
attention backward's second QK^T) never count.

Per token, forward, at Laguna-XS.2's published widths (hidden 2048; 8
key/value heads of 128 under 48 query heads in a full layer and 64 in a
sliding one, window 512; a dense SwiGLU of 8192; 256 experts of width 512,
8 a token, one shared expert of 512), S = 8192, the leading dense layer
and one period (full, sliding x 3, full), on one chip of 16 that holds 16
of the experts and 12,544 rows, in MFLOP:

  attention proj.   full: W_q 2048x6144 + W_k, W_v 2048x1024
                    + W_o 6144x2048                       58.72   x 2
                    sliding: W_q 2048x8192 + W_k, W_v
                    + W_o 8192x2048                       75.50   x 3
  gates             W_g 2048x48 / 2048x64                  0.20 / 0.26
  full core         48 heads x (128 + 128) x 4096.5 keys 100.67   x 2
  window core       64 heads x (128 + 128) x 496.03 keys  16.25   x 3
  dense MLP         3 x 2048x8192                        100.66
  router            2048x256                               1.05   x 4
  shared expert     3 x 2048x512                           6.29   x 4
  held experts      8 pairs a token, 16/256 of them here
                    when the router is balanced:
                    0.5 x 3 x 2048x512                     3.15   x 4
  head              2048 x 12,544 rows                    51.38
  total                                                  789.21

``core_row`` counts what the MASK asks, whatever kernel computes it and
whatever blocks that kernel visits: query t of a window layer sees
min(t + 1, window) keys (496.03 a query at 8,192 under 512; the 512-wide
blocks a ``LocalMask`` leaves visit 31 x 512 x 512 pairs a head where the
mask has 4,063,488: the half-masked blocks are time, not work), query t
of a full layer t + 1; each (query, key) pair ``head_dim`` multiply-adds
in QK^T and as many in PV; q, k, v read and o written once a pass.  At
the v5e's peaks both kinds are compute-bound: 2.03 ms a window layer's
three passes, 12.56 ms a full layer's.

The held experts' row is the EXPECTED work under a balanced router: what
the step really needs follows the routing, which the program counts
(``moe_pairs_held`` on the fence; ``swa.held_pair_share``).

``layer_rows`` (``decoder_flops.py``'s: the parts of a layer summed)
holds one row per prototxt layer that multiplies, named as the layer's
``L.<name>`` scope is (``attn<i>``, ``mlp<i>``, ``moe<i>``, ``lm_head``),
so the readers written for the CNN cells find them; ``parts`` holds the
finer rows this configuration's own readers take.

Bytes are the least a part must move through HBM in the compute dtype
(``flops.layer_floor_s``: inputs, outputs and weights once per pass).
"""

from __future__ import annotations

import math

from benchmarks.harness.decoder_flops import _row, layer_rows  # noqa: F401

KINDS = {"sliding_attention": "window_core", "full_attention": "full_core"}


def seen_pairs(seq_len: int, window: int) -> int:
    """(query, key) pairs of one sequence and head under the causal mask,
    and a window where there is one: sum over t of min(t + 1, window)."""
    if not window or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def core_row(name: str, kind: str, sequences: int, seq_len: int, heads: int,
             kv_heads: int, head_dim: int, window: int = 0) -> dict:
    """The causal core of one layer (module docstring); ``kind`` is
    ``window_core`` or ``full_core``."""
    t = sequences * seq_len
    return _row(name, kind,
                sequences * seen_pairs(seq_len, window) * heads * 2 * head_dim,
                t * (heads + 2 * kv_heads) * head_dim, t * heads * head_dim, 0)


def _experts(name: str, c: dict, t: int) -> list[dict]:
    d, k = c["hidden_size"], c["num_experts_per_tok"]
    e, held = c["num_experts_published"], c["num_experts"]
    h, hs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    pairs = t * k * held // e  # balanced: the share's part of the T*k pairs
    return [
        _row(name + ".router", "ip", t * d * e, t * d, t * e, e * d),
        _row(name + ".shared", "ip", t * 3 * d * hs, t * (2 * d + hs),
             t * (2 * hs + d), 3 * d * hs),
        _row(name + ".experts", "grouped", pairs * 3 * d * h,
             pairs * (2 * d + h), pairs * (2 * h + d), 3 * held * h * d),
    ]


def parts(config: dict, sequences: int, seq_len: int) -> list[dict]:
    """The finest rows, per block i: ``attn<i>.proj``, ``attn<i>.gate`` and
    ``attn<i>.core``; ``mlp<i>`` or ``moe<i>.router`` / ``.shared`` /
    ``.experts``; then ``lm_head``."""
    c = config
    e, t = c["hidden_size"], sequences * seq_len
    hk, d, f = c["num_key_value_heads"], c["head_dim"], c["intermediate_size"]
    rows = []
    for i in range(c["num_hidden_layers"]):
        kind = c["layer_types"][i]
        h = c["num_attention_heads_per_layer"][i]
        mats = [(e, h * d), (e, hk * d), (e, hk * d), (h * d, e)]
        w = sum(a * b for a, b in mats)
        rows += [
            _row(f"attn{i}.proj", "ip", t * w, t * sum(a for a, _ in mats),
                 t * sum(b for _, b in mats), w),
            _row(f"attn{i}.gate", "ip", t * e * h, t * e, t * h, e * h),
            core_row(f"attn{i}.core", KINDS[kind], sequences, seq_len, h, hk,
                     d, c["sliding_window"] * (kind == "sliding_attention")),
        ]
        if c["mlp_layer_types"][i] == "dense":
            rows.append(_row(f"mlp{i}", "ip", t * 3 * e * f, t * (2 * e + f),
                             t * (2 * f + e), 3 * e * f))
        else:
            rows += _experts(f"moe{i}", c, t)
    v = c["vocab_rows"]
    rows.append(_row("lm_head", "ip", t * e * v, t * e, t * v, v * e))
    return rows


# (layer stem, part) or a core's kind -> the name of the part in the table
_TABLE = {
    ("attn", "proj"): "attention_projections", ("attn", "gate"): "gates",
    "full_core": "full_cores", "window_core": "window_cores",
    ("mlp", ""): "dense_mlp", ("moe", "router"): "routers",
    ("moe", "shared"): "shared_experts",
    ("moe", "experts"): "held_experts_balanced", ("lm_head", ""): "head",
}


def forward_mflop_per_token(config: dict, seq_len: int) -> dict[str, float]:
    """The docstring's table, computed: forward MFLOP per token by part,
    summed over the blocks, and their ``total``."""
    out: dict[str, float] = {}
    for r in parts(config, 1, seq_len):
        layer, _, part = r["name"].partition(".")
        key = _TABLE.get(r["kind"]) or _TABLE[layer.rstrip("0123456789"), part]
        out[key] = out.get(key, 0.0) + 2 * r["macs"] / seq_len / 1e6
    out["total"] = math.fsum(out.values())
    return out
