"""Operations and bytes a training step of a LOOPED dense decoder (one
stack of blocks run ``total_ut_steps`` times on the same weights, an exit
gate and the head read at every pass) REQUIRES, from the configuration's
sizes, in ``harness/flops.py``'s row format.

The rules are ``harness/lm_flops.py``'s: a multiply-add is 2 operations;
every matmul counts three passes (forward, weight gradient, data
gradient; ``from_data`` is False on every row); norms, RoPE, softmax,
SiLU, the gate's sigmoids, the cross-entropies and the optimizer count
zero; recomputed operations (the attention backward's second QK^T) never
count.  What is new is the loop: EVERY multiplying layer of the region is
counted ``total_ut_steps`` times, and so are the head and the gate, which
read every pass's state.  A weight is counted as read once a PASS (a
block's 103 MB of bf16 weights do not stay on the chip from one pass to
the next), an activation once a pass too.

Per token, forward, at Ouro-2.6B's published widths (hidden 2048, 16
heads of 128, MLP 5632), S = 4096, 4 of the 48 blocks, 4 passes and 6,144
rows, in multiply-adds:

  attention proj.  W_qkv 2048x6144 + W_o 2048x2048   16,777,216  x 4 x 4
  attention core   16 heads x (128 + 128) x mean keys
                   2048.5                              8,390,656  x 4 x 4
  each MLP         3 matrices 2048x5632               34,603,008  x 4 x 4
  head             2048 x 6,144 rows                  12,582,912  x 4
  exit gate        2048 x 1                                2,048  x 4
  total                                            1,006,673,920

(mean keys: a query t sees t + 1 keys, 4097 / 2.)  ``layer_rows`` holds
one row per prototxt layer that multiplies, named as the layer's
``L.<name>`` scope is (``attn<i>``, ``mlp<i>``, ``lm_head``,
``exit_gate``: one scope a layer, all passes under it), so the readers
written for the CNN cells find them; ``parts`` holds the finer rows this
configuration's own readers take (``attn<i>.proj`` / ``attn<i>.core``).

Bytes are the least a part must move through HBM in the compute dtype
(``flops.layer_floor_s``: inputs, outputs and weights once per pass of
the three).  A core reads q, k and v at 128 a head and writes o: per
token and pass 16 x 3 x 128 in, 16 x 128 out.
"""

from __future__ import annotations


def _row(name, kind, macs, in_elems, out_elems, weight_elems) -> dict:
    return {"name": name, "kind": kind, "macs": int(macs),
            "in_elems": int(in_elems), "out_elems": int(out_elems),
            "weight_elems": int(weight_elems), "from_data": False}


def core_row(name: str, sequences: int, seq_len: int, heads: int,
             head_dim: int, passes: int) -> dict:
    """Causal softmax attention, ``passes`` times over: query t sees t + 1
    keys, S (S + 1) / 2 pairs a sequence and head, each ``head_dim``
    multiply-adds in QK^T and ``head_dim`` in PV."""
    t = sequences * seq_len
    pairs = seq_len * (seq_len + 1) // 2
    return _row(name, "loop_core",
                passes * sequences * pairs * heads * 2 * head_dim,
                passes * t * heads * 3 * head_dim,
                passes * t * heads * head_dim, 0)


def parts(config: dict, sequences: int, seq_len: int) -> list[dict]:
    """The finest rows: per block ``attn<i>.proj``, ``attn<i>.core`` and
    ``mlp<i>``, then ``lm_head`` and ``exit_gate``; every one over all
    ``total_ut_steps`` passes."""
    c = config
    d, h = c["hidden_size"], c["num_attention_heads"]
    f, v = c["intermediate_size"], c["vocab_rows"]
    n = c["total_ut_steps"]
    t = sequences * seq_len
    rows = []
    for i in range(c["num_hidden_layers"]):
        rows += [
            _row(f"attn{i}.proj", "ip", n * t * 4 * d * d, n * t * 2 * d,
                 n * t * 4 * d, n * 4 * d * d),
            core_row(f"attn{i}.core", sequences, seq_len, h, d // h, n),
            _row(f"mlp{i}", "ip", n * t * 3 * d * f, n * t * (2 * d + f),
                 n * t * (2 * f + d), n * 3 * d * f),
        ]
    rows += [
        _row("lm_head", "ip", n * t * d * v, n * t * d, n * t * v, n * v * d),
        _row("exit_gate", "ip", n * t * d, n * t * d, n * t, n * d),
    ]
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
    """The docstring's table, computed: forward MFLOP per token by part,
    summed over the blocks and the passes, and their ``total``."""
    names = {"proj": "attention_projections", "core": "attention_core",
             "lm_head": "head", "exit_gate": "exit_gate"}
    out: dict[str, float] = {}
    for r in parts(config, 1, seq_len):
        last = r["name"].split(".")[-1]
        key = "mlps" if last.startswith("mlp") else names[last]
        out[key] = out.get(key, 0.0) + 2 * r["macs"] / seq_len / 1e6
    out["total"] = sum(out.values())
    return out
