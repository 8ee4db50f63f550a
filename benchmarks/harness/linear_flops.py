"""Operations and bytes a training step of a linear-attention decoder
(gated-DeltaNet layers, output-gated grouped attention every fourth
layer, softmax-routed experts beside a gated shared expert) REQUIRES,
from the configuration's sizes, in ``harness/flops.py``'s row format.

The rules are ``harness/lm_flops.py``'s: a multiply-add is 2 operations;
every matmul counts three passes (forward, weight gradient, data
gradient; ``from_data`` is False on every row); norms, the depthwise
convolution (4 taps), RoPE, softmax, softplus, SiLU, the gates, the
dispatch's gathers, the cross-entropy and the optimizer count zero;
recomputed operations (the attention backward's second QK^T, the delta
rule's backward forming a chunk's quantities again) never count.

Per token, forward, at Qwen3-Next-80B-A3B's published widths (hidden
2048; DeltaNet 16 key and 32 value heads of 128; attention 16 query heads
over 2 key/value heads of 256; 512 experts of width 512, 10 a token, one
shared expert of 512), S = 4096, one period of four layers, on one chip
of 16 that holds 32 of the experts and 18,992 rows, in MFLOP:

  DeltaNet projections  W_qkvz 2048x12288 + W_ba 2048x64
                        + W_out 4096x2048                   67.37   x 3
  delta rule            7 d_k d_v a value head: decay, S^T k,
                        the rank-one update, S^T q           3.67   x 3
  attention proj.       W_q 2048x8192 + W_k, W_v 2048x512
                        + W_o 4096x2048                     54.53
  attention core        16 heads x (256 + 256) x 2048.5 keys 33.56
  router                2048x512                             2.10   x 4
  shared expert         3 x 2048x512 + the gate 2048         6.30   x 4
  held experts          10 pairs a token, 32/512 of them
                        here when the router is balanced:
                        0.625 x 3 x 2048x512                 3.93   x 4
  head                  2048 x 18,992 rows                  77.79
  total                                                    428.35

``delta_core_row`` counts the RECURRENCE's work, not an algorithm's: 7
operations a state element a token (3.5 multiply-adds in this file's
unit) and the bytes of q, k, v, g, beta in and o out once a pass (g and
beta are f32: two elements each), the state never through HBM, whatever
the chunk and whether XLA or Pallas computes it: a chunked form does more
arithmetic (its T, W, U_0 and masked Q K^T) and that is time, not work.
It IS in its layer's row (the chunked form runs on the MXU), so
``model_step.mfu_busy`` and ``kernels.matmul_roofline`` count it.

The held experts' row is the EXPECTED work under a balanced router: what
the step really needs follows the routing, which the program counts
(``moe_pairs_held`` on the fence; ``moe.wide_held_pair_share``).

``layer_rows`` (``decoder_flops.py``'s: the parts of a layer summed)
holds one row per prototxt layer that multiplies, named as
the layer's ``L.<name>`` scope is (``gdn<i>``, ``attn<i>``, ``moe<i>``,
``lm_head``), so the readers written for the CNN cells find them;
``parts`` holds the finer rows this configuration's own readers take.

Bytes are the least a part must move through HBM in the compute dtype
(``flops.layer_floor_s``: inputs, outputs and weights once per pass).  The
attention core reads q at 256 a query head, k and v at 256 a key/value
head, and writes o at 256 a query head.
"""

from __future__ import annotations

import math

from benchmarks.harness.decoder_flops import _row, layer_rows  # noqa: F401


def is_attention(i: int, config: dict) -> bool:
    """The published rule: every ``full_attention_interval``-th layer."""
    return (i + 1) % config["full_attention_interval"] == 0


def delta_core_row(name: str, sequences: int, seq_len: int, k_heads: int,
                   v_heads: int, d_k: int, d_v: int) -> dict:
    """The gated delta rule of one layer (module docstring)."""
    t = sequences * seq_len
    return _row(name, "delta_core", t * v_heads * d_k * d_v * 7 // 2,
                t * (2 * k_heads * d_k + v_heads * d_v + 4 * v_heads),
                t * v_heads * d_v, 0)


def gated_core_row(name: str, sequences: int, seq_len: int, heads: int,
                   kv_heads: int, head_dim: int) -> dict:
    """Causal softmax attention of ``heads`` query heads over ``kv_heads``
    key/value heads of ``head_dim``: query t sees t + 1 keys, each pair
    ``head_dim`` multiply-adds in QK^T and as many in PV."""
    t = sequences * seq_len
    pairs = seq_len * (seq_len + 1) // 2
    return _row(name, "gated_core", sequences * pairs * heads * 2 * head_dim,
                t * (heads + 2 * kv_heads) * head_dim, t * heads * head_dim, 0)


def _experts(name: str, c: dict, t: int) -> list[dict]:
    d, k = c["hidden_size"], c["num_experts_per_tok"]
    e, held = c["num_experts_published"], c["num_experts"]
    h, hs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    pairs = t * k * held // e  # balanced: the share's part of the T*k pairs
    return [
        _row(name + ".router", "ip", t * d * e, t * d, t * e, e * d),
        _row(name + ".shared", "ip", t * (3 * d * hs + d), t * (2 * d + hs),
             t * (2 * hs + d + 1), 3 * d * hs + d),
        _row(name + ".experts", "grouped", pairs * 3 * d * h,
             pairs * (2 * d + h), pairs * (2 * h + d), 3 * held * h * d),
    ]


def parts(config: dict, sequences: int, seq_len: int) -> list[dict]:
    """The finest rows, per block i: ``gdn<i>.proj`` and ``gdn<i>.core``, or
    ``attn<i>.proj`` and ``attn<i>.core``; ``moe<i>.router`` / ``.shared`` /
    ``.experts``; then ``lm_head``."""
    c = config
    e, t = c["hidden_size"], sequences * seq_len
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    h, hkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    kw, vw = hk * dk, hv * dv
    rows = []
    for i in range(c["num_hidden_layers"]):
        if is_attention(i, c):
            mats = [(e, 2 * h * d), (e, hkv * d), (e, hkv * d), (h * d, e)]
            w = sum(a * b for a, b in mats)
            rows += [
                _row(f"attn{i}.proj", "ip", t * w, t * sum(a for a, _ in mats),
                     t * sum(b for _, b in mats), w),
                gated_core_row(f"attn{i}.core", sequences, seq_len, h, hkv, d),
            ]
        else:
            mats = [(e, 2 * kw + 2 * vw), (e, 2 * hv), (vw, e)]
            w = sum(a * b for a, b in mats)
            rows += [
                _row(f"gdn{i}.proj", "ip", t * w, t * sum(a for a, _ in mats),
                     t * sum(b for _, b in mats), w),
                delta_core_row(f"gdn{i}.core", sequences, seq_len, hk, hv,
                               dk, dv),
            ]
        rows += _experts(f"moe{i}", c, t)
    v = c["vocab_rows"]
    rows.append(_row("lm_head", "ip", t * e * v, t * e, t * v, v * e))
    return rows


# (layer stem, part) -> the name of the part in the table above
_TABLE = {
    ("gdn", "proj"): "deltanet_projections", ("gdn", "core"): "delta_rule",
    ("attn", "proj"): "attention_projections",
    ("attn", "core"): "attention_core", ("moe", "router"): "routers",
    ("moe", "shared"): "shared_experts",
    ("moe", "experts"): "held_experts_balanced", ("lm_head", ""): "head",
}


def forward_mflop_per_token(config: dict, seq_len: int) -> dict[str, float]:
    """The docstring's table, computed: forward MFLOP per token by part,
    summed over the blocks, and their ``total``."""
    out: dict[str, float] = {}
    for r in parts(config, 1, seq_len):
        layer, _, part = r["name"].partition(".")
        key = _TABLE[layer.rstrip("0123456789"), part]
        out[key] = out.get(key, 0.0) + 2 * r["macs"] / seq_len / 1e6
    out["total"] = math.fsum(out.values())
    return out
