"""Operations and bytes a training step of a SambaY-class hybrid decoder
(Mamba layers, window and full differential attention with grouped heads,
gated memory units, cross-attention on kept keys and values) REQUIRES,
from the configuration's sizes, in ``harness/flops.py``'s row format.

The rules are ``harness/lm_flops.py``'s: a multiply-add is 2 operations;
every matmul counts three passes (forward, weight gradient, data
gradient; ``from_data`` is False on every row); norms, the depthwise
convolution (4 taps), softmax, softplus, SiLU, the gates, lambda, the
cross-entropy and the optimizer count zero; recomputed operations (the
attention backward's second QK^T, the scan backward's second walk over a
chunk's states) never count.

Per token, forward, at Phi-4-mini-flash-reasoning's published widths
(hidden 2560, 40 query and 20 key/value heads of 64, MLP 10240, Mamba
d_inner 5120 x state 16, dt_rank 160, window 512), S = 2048, the six kept
layers {0, 1, 16, 17, 18, 19} and 25,008 rows, in multiply-adds:

  each MLP          3 matrices 2560x10240                     78,643,200   x 6
  Mamba projections 2560x10240 + 5120x192 + 160x5120
                    + 5120x2560                               41,123,840   x 2
  attention proj.   W_qkv 2560x5120 + W_o 2560x2560           19,660,800   x 2
  cross-attn proj.  W_q + W_o, 2560x2560 each                 13,107,200
  GMU               W_1 2560x5120 + W_2 5120x2560             26,214,400
  head              2560 x 25,008 rows                        64,020,480
  window core       40 heads x (64 + 128) x mean keys 448.125  3,441,600
  each full core    40 heads x (64 + 128) x mean keys 1024.5   7,868,160   x 2
  total                                                      715,948,480

(mean keys: a query t of the window sees min(t + 1, 512) keys, (512*513/2
+ 1536*512) / 2048; a full one t + 1, 2049 / 2.)  The scan's own
arithmetic (3 multiply-adds a state element a step: 5120 x 16 x 3 =
245,760 a token a layer) runs on the vector unit, not the MXU: it is in
``scan_row`` for the scan's own roofline and in no layer row, so
``model_step.mfu_busy`` and ``kernels.matmul_roofline`` do not count it.

``layer_rows`` holds one row per prototxt layer that multiplies, named as
the layer's ``L.<name>`` scope is (``mamba<i>``, ``attn<i>``, ``gmu<i>``,
``xattn<i>``, ``mlp<i>``, ``lm_head``), so the readers written for the CNN
cells find them; ``parts`` holds the finer rows this configuration's own
readers take.

Bytes are the least a part must move through HBM in the compute dtype
(``flops.layer_floor_s``: inputs, outputs and weights once per pass).  The
scan reads c, Δ, B and C and writes y (per token 2 x 5120 + 2 x 16 in,
5120 out; A and D once; the state never through HBM.  The gate's z is
NOT counted: the program's scan does not read it, the gate is outside
``R.scan``).  A core reads q at 64 a query head, k at 64 a key head and v
at 64 a value head, and writes both maps' 128-wide results: per token
(40 + 20 + 20) x 64 in, 40 x 128 out.
"""

from __future__ import annotations

import math

from benchmarks.reference.phi4_flash import role  # the published rule


def _row(name, kind, macs, in_elems, out_elems, weight_elems) -> dict:
    return {"name": name, "kind": kind, "macs": int(macs),
            "in_elems": int(in_elems), "out_elems": int(out_elems),
            "weight_elems": int(weight_elems), "from_data": False}


def sizes(c: dict) -> dict:
    e, h = c["hidden_size"], c["num_attention_heads"]
    return {"e": e, "h": h, "hk": c["num_key_value_heads"], "d": e // h,
            "f": c["intermediate_size"], "inner": c["expand"] * e,
            "n": c["d_state"], "rank": c["dt_rank"],
            "window": c["sliding_window"]}


def scan_row(name: str, sequences: int, seq_len: int, d_inner: int,
             d_state: int) -> dict:
    """The selective scan of one layer: 3 multiply-adds a state element a
    step (Δ A, decay x h + (Δ c) B, h C); c, Δ, B, C in and y out once,
    A and D once, the state never."""
    t = sequences * seq_len
    return _row(name, "scan", t * d_inner * d_state * 3,
                t * (2 * d_inner + 2 * d_state), t * d_inner,
                d_inner * d_state + d_inner)


def _core_row(name, kind, pairs, sequences, seq_len, s) -> dict:
    t = sequences * seq_len
    return _row(name, kind, sequences * pairs * s["h"] * 3 * s["d"],
                t * (s["h"] + 2 * s["hk"]) * s["d"], t * s["h"] * 2 * s["d"],
                0)


def window_core_row(name: str, sequences: int, seq_len: int, s: dict) -> dict:
    """Both softmax maps of every pair under the window: query t sees
    min(t + 1, W) keys; a (query head, key) pair is D multiply-adds in
    QK^T and 2 D in PV over the doubled values."""
    w = min(s["window"], seq_len)
    pairs = w * (w + 1) // 2 + (seq_len - w) * w
    return _core_row(name, "window_core", pairs, sequences, seq_len, s)


def full_core_row(name: str, sequences: int, seq_len: int, s: dict) -> dict:
    """The same under the full causal mask: query t sees t + 1 keys."""
    return _core_row(name, "full_core", seq_len * (seq_len + 1) // 2,
                     sequences, seq_len, s)


def parts(config: dict, sequences: int, seq_len: int) -> list[dict]:
    """The finest rows, per kept layer i by its role: ``mamba<i>.proj`` and
    ``mamba<i>.scan``; ``attn<i>.proj`` and ``attn<i>.core``; ``gmu<i>``;
    ``xattn<i>.proj`` and ``xattn<i>.core``; each with its ``mlp<i>``; then
    ``lm_head``."""
    c, s = config, sizes(config)
    e, d_in, n, rank = s["e"], s["inner"], s["n"], s["rank"]
    qd, kd = s["h"] * s["d"], s["hk"] * s["d"]
    t = sequences * seq_len
    rows = []
    for i in c["kept_layers"]:
        kind = role(i, c["num_hidden_layers_published"], c["mb_per_layer"])
        if kind in ("mamba", "memory"):
            w = e * 2 * d_in + d_in * (rank + 2 * n) + rank * d_in + d_in * e
            rows += [
                _row(f"mamba{i}.proj", "ip", t * w,
                     t * (e + d_in + rank + d_in),
                     t * (2 * d_in + rank + 2 * n + d_in + e), w),
                scan_row(f"mamba{i}.scan", sequences, seq_len, d_in, n),
            ]
        elif kind == "gmu":
            rows.append(_row(f"gmu{i}", "ip", t * 2 * e * d_in,
                             t * (e + d_in), t * (d_in + e), 2 * e * d_in))
        elif kind == "cross":
            rows += [
                _row(f"xattn{i}.proj", "ip", t * 2 * e * qd, t * (e + qd),
                     t * (qd + e), 2 * e * qd),
                full_core_row(f"xattn{i}.core", sequences, seq_len, s),
            ]
        else:
            w = e * (qd + 2 * kd) + qd * e
            core = window_core_row if kind == "window" else full_core_row
            rows += [
                _row(f"attn{i}.proj", "ip", t * w, t * (e + qd),
                     t * (qd + 2 * kd + e), w),
                core(f"attn{i}.core", sequences, seq_len, s),
            ]
        rows.append(_row(f"mlp{i}", "ip", t * 3 * e * s["f"],
                         t * (2 * e + s["f"]), t * (2 * s["f"] + e),
                         3 * e * s["f"]))
    v = c["vocab_rows"]
    rows.append(_row("lm_head", "ip", t * e * v, t * e, t * v, v * e))
    return rows


def layer_rows(part_rows: list[dict]) -> list[dict]:
    """One row per prototxt layer: the parts of a layer summed, the scan
    left out (module docstring)."""
    merged: dict[str, dict] = {}
    for r in part_rows:
        if r["kind"] == "scan":
            continue
        layer = r["name"].split(".")[0]
        m = merged.setdefault(layer, _row(layer, "decoder", 0, 0, 0, 0))
        for key in ("macs", "in_elems", "out_elems", "weight_elems"):
            m[key] += r[key]
    return list(merged.values())


# (layer stem, part or kind) -> the name of the part in the table above
_TABLE = {
    ("mlp", "ip"): "mlps", ("lm_head", "ip"): "head", ("gmu", "ip"): "gmu",
    ("mamba", "proj"): "mamba_projections",
    ("mamba", "scan"): "mamba_scan_vector_unit",
    ("attn", "proj"): "attention_projections",
    ("attn", "window_core"): "attention_window_core",
    ("attn", "full_core"): "attention_full_core",
    ("xattn", "proj"): "cross_attention_projections",
    ("xattn", "full_core"): "cross_attention_full_core",
}


def forward_mflop_per_token(config: dict, seq_len: int) -> dict[str, float]:
    """The docstring's table, computed: forward MFLOP per token by part,
    and their ``total`` without the scan, which no layer row holds."""
    out: dict[str, float] = {}
    for r in parts(config, 1, seq_len):
        layer, _, part = r["name"].partition(".")
        key = _TABLE[layer.rstrip("0123456789"),
                     part if part in ("proj", "scan") else r["kind"]]
        out[key] = out.get(key, 0.0) + 2 * r["macs"] / seq_len / 1e6
    out["total"] = math.fsum(v for k, v in out.items()
                             if k != "mamba_scan_vector_unit")
    return out
