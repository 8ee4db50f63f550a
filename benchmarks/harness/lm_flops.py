"""Operations and bytes a decoder-block training step REQUIRES, from the
configuration's sizes, in ``harness/flops.py``'s row format.

A multiply-add is 2 operations.  Every matmul is counted for three
passes (forward, weight gradient, data gradient: the embedding below the
first projection is trained, so no pass is dropped; ``from_data`` is
False on every row).  Norms, RoPE, softmax, SiLU, the gathers of the
dispatch, the cross-entropy and the optimizer count zero operations.
Recomputed operations (the flash backward's second QK^T) never count.

Per token, forward, at the published OLMoE-1B-7B widths (hidden 2048, 16
heads of 128, 64 experts of width 1024, 8 per token, S = 4096):

  projections  4 matrices 2048x2048            2*4*2048^2      = 33.55 MFLOP
  causal core  QK^T and AV over S/2 keys       2*2*2048*4096/2 = 16.78
  router       2048x64                         2*2048*64       =  0.26
  experts      8 x 3 matrices 2048x1024        2*8*3*2048*1024 = 100.66
  head         2048 x 12576 rows               2*2048*12576    = 51.51

``layer_rows`` holds ONE row per prototxt layer that multiplies
(``attn<i>``, ``moe<i>``, ``lm_head``), named as the layer's ``L.<name>``
scope is, so the readers written for the CNN cells
(``kernels.matmul_roofline``, ``model_step.mfu_busy``) find them; ``parts``
holds the finer rows this configuration's own readers take (the grouped
matmuls alone, the attention core alone).

Bytes are the least a part must move through HBM in the compute dtype
(``flops.layer_floor_s``: inputs, outputs and weights once per pass):
  core     q, k, v in; o out                      4 * T * D
  experts  gate, up: T*k rows of D in, H out; down: H in, D out;
           the three expert matrices              T*k*(2D+H) in, T*k*(2H+D)
                                                  out, 3*E*H*D weights
"""

from __future__ import annotations


def _row(name, kind, macs, in_elems, out_elems, weight_elems) -> dict:
    return {"name": name, "kind": kind, "macs": int(macs),
            "in_elems": int(in_elems), "out_elems": int(out_elems),
            "weight_elems": int(weight_elems), "from_data": False}


def parts(config: dict, sequences: int, seq_len: int) -> list[dict]:
    """The finest rows: per layer ``attn<i>.proj``, ``attn<i>.core``,
    ``moe<i>.router``, ``moe<i>.experts``; then ``lm_head``."""
    d = config["hidden_size"]
    e, k, h = (config["num_experts"], config["num_experts_per_tok"],
               config["intermediate_size"])
    t = sequences * seq_len
    rows = []
    for i in range(1, config["num_hidden_layers"] + 1):
        rows += [
            _row(f"attn{i}.proj", "ip", t * 4 * d * d, 2 * t * d, 4 * t * d,
                 4 * d * d),
            # causal: query t sees t+1 keys, S(S+1)/2 ~ S^2/2 pairs a
            # sequence, each pair d MACs in QK^T and d in AV (all heads)
            _row(f"attn{i}.core", "attn_core",
                 sequences * (seq_len * seq_len // 2) * 2 * d,
                 3 * t * d, t * d, 0),
            _row(f"moe{i}.router", "ip", t * d * e, t * d, t * e, e * d),
            _row(f"moe{i}.experts", "grouped", t * k * 3 * d * h,
                 t * k * (2 * d + h), t * k * (2 * h + d), 3 * e * h * d),
        ]
    v = config["vocab_rows"]
    rows.append(_row("lm_head", "ip", t * d * v, t * d, t * v, v * d))
    return rows


def layer_rows(part_rows: list[dict]) -> list[dict]:
    """One row per prototxt layer: the parts of a layer summed."""
    merged: dict[str, dict] = {}
    for r in part_rows:
        layer = r["name"].split(".")[0]
        m = merged.setdefault(layer, _row(layer, "decoder", 0, 0, 0, 0))
        for key in ("macs", "in_elems", "out_elems", "weight_elems"):
            m[key] += r[key]
    return list(merged.values())


def forward_mflop_per_token(config: dict, seq_len: int) -> dict[str, float]:
    """The docstring's table, computed: forward MFLOP per token by part."""
    rows = parts(config, 1, seq_len)
    out: dict[str, float] = {}
    for r in rows:
        key = r["name"].split(".")[-1]
        out[key] = out.get(key, 0.0) + 2 * r["macs"] / seq_len / 1e6
    return out
