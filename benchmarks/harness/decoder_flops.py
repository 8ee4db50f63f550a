"""Operations and bytes a training step of a latent-attention decoder with
sigmoid-routed experts and a multi-token-prediction module REQUIRES, from
the configuration's sizes, in ``harness/flops.py``'s row format.

The rules are ``harness/lm_flops.py``'s: a multiply-add is 2 operations;
every matmul counts three passes (forward, weight gradient, data
gradient; ``from_data`` is False on every row); norms, RoPE, softmax,
SiLU, the dispatch's gathers, both cross-entropies and the optimizer
count zero; recomputed operations (the attention backward's second QK^T)
never count.

Per token, forward, at JoyAI-LLM-Flash's published widths (hidden 2048, 32
heads with keys of 128 + 64 and values of 128, latent ranks 1536 / 512,
dense width 7168, experts of width 768, 8 per token of 256, one shared),
S = 4096, on one chip of 32 that holds 8 of the experts and 16,160 rows:

  MLA projections  2048x1536, 1536x6144, 2048x576,
                   512x8192, 4096x2048              2*26,345,472  = 52.69 MFLOP
  MLA core         QK^T over 192 and PV over 128,
                   S/2 keys, 32 heads               2*320*32*2048 = 41.94
  dense MLP        3 matrices 2048x7168             2*3*2048*7168 = 88.08
  shared expert    3 matrices 2048x768              2*3*2048*768  =  9.44
  router           2048x256                         2*2048*256    =  1.05
  held experts     8 pairs a token, 8/256 of them
                   land here when the router is
                   balanced: 0.25 x 3 x 2048x768    2*0.25*3*2048*768 = 2.36
  MTP projection   4096x2048                        2*4096*2048   = 16.78
  each head        2048 x 16,160 rows               2*2048*16160  = 66.19

The held experts' row is the EXPECTED work under a balanced router: what
the step really needs follows the routing, which the program counts
(``moe_pairs_held`` on the fence; ``moe.held_pair_share``).

``layer_rows`` holds one row per prototxt layer that multiplies, named as
the layer's ``L.<name>`` scope is (``attn<i>``, ``mlp<i>``, ``moe<i>``,
``lm_head``, ``mtp_proj``, ``mtp_attn``, ``mtp_moe``, ``mtp_head``), so the
readers written for the CNN cells find them; ``parts`` holds the finer
rows this configuration's own readers take.

Bytes are the least a part must move through HBM in the compute dtype
(``flops.layer_floor_s``: inputs, outputs and weights once per pass).  The
attention core reads q and k at 192 a head and v at 128, and writes o at
128: per token 32 * (192 + 192 + 128) in, 32 * 128 out.
"""

from __future__ import annotations


def _row(name, kind, macs, in_elems, out_elems, weight_elems) -> dict:
    return {"name": name, "kind": kind, "macs": int(macs),
            "in_elems": int(in_elems), "out_elems": int(out_elems),
            "weight_elems": int(weight_elems), "from_data": False}


def mla_core_row(name: str, sequences: int, seq_len: int, heads: int,
                 qk_dim: int, v_dim: int) -> dict:
    """Causal softmax attention with keys of ``qk_dim`` and values of
    ``v_dim``: query t sees t + 1 keys, S(S+1)/2 ~ S^2/2 pairs a sequence
    and head, each ``qk_dim`` MACs in QK^T and ``v_dim`` in PV."""
    t = sequences * seq_len
    return _row(name, "mla_core",
                sequences * (seq_len * seq_len // 2) * heads * (qk_dim + v_dim),
                t * heads * (2 * qk_dim + v_dim), t * heads * v_dim, 0)


def _attention(name: str, c: dict, sequences: int, seq_len: int) -> list[dict]:
    d, h = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    t = sequences * seq_len
    # (in, out) of the five projections, in the order of the forward
    mats = [(d, rq), (rq, h * (dn + dr)), (d, rkv + dr),
            (rkv, h * (dn + dv)), (h * dv, d)]
    weights = sum(i * o for i, o in mats)
    return [
        _row(name + ".proj", "ip", t * weights, t * sum(i for i, _ in mats),
             t * sum(o for _, o in mats), weights),
        mla_core_row(name + ".core", sequences, seq_len, h, dn + dr, dv),
    ]


def _experts(name: str, c: dict, t: int) -> list[dict]:
    d, k = c["hidden_size"], c["num_experts_per_tok"]
    e, held = c["n_routed_experts_published"], c["n_routed_experts"]
    h, hs = c["moe_intermediate_size"], (c["n_shared_experts"]
                                         * c["moe_intermediate_size"])
    pairs = t * k * held // e  # balanced: the share's part of the T*k pairs
    return [
        _row(name + ".router", "ip", t * d * e, t * d, t * e, e * d),
        _row(name + ".shared", "ip", t * 3 * d * hs, t * (2 * d + hs),
             t * (2 * hs + d), 3 * d * hs),
        _row(name + ".experts", "grouped", pairs * 3 * d * h,
             pairs * (2 * d + h), pairs * (2 * h + d), 3 * held * h * d),
    ]


def parts(config: dict, sequences: int, seq_len: int) -> list[dict]:
    """The finest rows: per block ``attn<i>.proj``, ``attn<i>.core`` and
    ``mlp<i>`` or ``moe<i>.router`` / ``.shared`` / ``.experts``; ``lm_head``;
    then the multi-token-prediction module's ``mtp_proj``, ``mtp_attn.*``,
    ``mtp_moe.*`` and ``mtp_head`` (one position fewer a sequence)."""
    c = config
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_rows"]
    t = sequences * seq_len
    rows = []
    for i in range(1, c["num_hidden_layers"] + 1):
        rows += _attention(f"attn{i}", c, sequences, seq_len)
        if i <= c["first_k_dense_replace"]:
            rows.append(_row(f"mlp{i}", "ip", t * 3 * d * f, t * (2 * d + f),
                             t * (2 * f + d), 3 * d * f))
        else:
            rows += _experts(f"moe{i}", c, t)
    rows.append(_row("lm_head", "ip", t * d * v, t * d, t * v, v * d))
    for _ in range(c["num_nextn_predict_layers"]):
        rows.append(_row("mtp_proj", "ip", t * 2 * d * d, t * 2 * d, t * d,
                         2 * d * d))
        rows += _attention("mtp_attn", c, sequences, seq_len)
        rows += _experts("mtp_moe", c, t)
        t_mtp = sequences * (seq_len - 1)
        rows.append(_row("mtp_head", "ip", t_mtp * d * v, t_mtp * d,
                         t_mtp * v, v * d))
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
    """The docstring's table, computed: forward MFLOP per token by the
    part's last name, summed over the blocks."""
    out: dict[str, float] = {}
    for r in parts(config, 1, seq_len):
        key = r["name"].split(".")[-1]
        key = {"lm_head": "head", "mtp_head": "head"}.get(key, key)
        key = "mlp" if key.startswith("mlp") else key
        out[key] = out.get(key, 0.0) + 2 * r["macs"] / seq_len / 1e6
    return out
