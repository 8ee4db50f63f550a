"""Operations and bytes a training step REQUIRES, from layer shapes.

Not what the compiler emitted (``cost_analysis()`` counts that, and the
program's old roofline took 911 GB/s from it on a chip that peaks at 819):
what the mathematics of the net needs.  A multiply-add is 2 operations.

Per Convolution / InnerProduct layer, per step:
  forward            2 * MACs
  backward (weights) 2 * MACs
  backward (data)    2 * MACs, except for a layer fed by the data blob
                     (nobody needs the gradient of the images)
Everything else (ReLU, LRN, pooling, BatchNorm, softmax, the update) is
counted as zero operations: they are bandwidth, and ``mfu`` is about the
MXU.  Recomputed operations never count.

Bytes are the least a layer must move through HBM in the compute dtype:
input, output and weights once per pass (three passes, the data-gradient
pass dropped as above).

``walk`` follows a parsed train prototxt with Caffe's shape rules
(conv: floor, pooling: ceil).  Layer types it does not know keep their
first bottom's shape, so a new configuration needs no edit here unless it
brings a new shape-changing layer.
"""

from __future__ import annotations

import math


def conv_macs(n, cin, cout, kh, kw, hout, wout, group=1) -> int:
    return n * cout * hout * wout * (cin // group) * kh * kw


def ip_macs(n, cin, cout) -> int:
    return n * cin * cout


def conv_out(size, k, s, pad) -> int:
    return (size + 2 * pad - k) // s + 1


def pool_out(size, k, s, pad=0) -> int:
    """Caffe's ceil-mode pooled extent (pooling_layer.cpp)."""
    out = int(math.ceil((size + 2 * pad - k) / s)) + 1
    if pad and (out - 1) * s >= size + pad:
        out -= 1
    return out


def _hw(p, base, default):
    if p.has(base + "_h") or p.has(base + "_w"):
        return p.get_int(base + "_h", default), p.get_int(base + "_w", default)
    if base == "kernel" and p.has("kernel_size"):
        return (p.get_int("kernel_size"),) * 2
    if p.has(base):
        return (p.get_int(base),) * 2
    return default, default


def walk(net_msg, batch: int, chw: tuple[int, int, int]) -> list[dict]:
    """One row per Convolution / InnerProduct layer of the TRAIN net:
    {name, kind, macs, in_elems, out_elems, weight_elems, from_data}."""
    shapes: dict[str, tuple] = {}
    data_blobs: set[str] = set()
    rows: list[dict] = []
    for layer in net_msg.get_all("layer"):
        kind = layer.get_str("type")
        tops, bottoms = layer.get_all("top"), layer.get_all("bottom")
        if any(inc.get_str("phase") == "TEST" for inc in layer.get_all("include")):
            continue
        if kind in ("Data", "JavaData", "Input", "MemoryData"):
            shapes[tops[0]] = (batch, *chw)
            data_blobs.add(tops[0])
            for t in tops[1:]:
                shapes[t] = (batch,)
            continue
        src = shapes.get(bottoms[0]) if bottoms else None
        if kind == "Convolution":
            p = layer.get_msg("convolution_param")
            kh, kw = _hw(p, "kernel", 1)
            sh, sw = _hw(p, "stride", 1)
            ph, pw = _hw(p, "pad", 0)
            cout, group = p.get_int("num_output"), p.get_int("group", 1)
            n, cin, h, w = src
            ho, wo = conv_out(h, kh, sh, ph), conv_out(w, kw, sw, pw)
            out = (n, cout, ho, wo)
            rows.append({
                "name": layer.get_str("name"), "kind": "conv",
                "macs": conv_macs(n, cin, cout, kh, kw, ho, wo, group),
                "in_elems": n * cin * h * w, "out_elems": n * cout * ho * wo,
                "weight_elems": cout * (cin // group) * kh * kw,
                "from_data": bottoms[0] in data_blobs})
        elif kind == "InnerProduct":
            cout = layer.get_msg("inner_product_param").get_int("num_output")
            n, cin = src[0], math.prod(src[1:])
            out = (n, cout)
            rows.append({
                "name": layer.get_str("name"), "kind": "ip",
                "macs": ip_macs(n, cin, cout), "in_elems": n * cin,
                "out_elems": n * cout, "weight_elems": cin * cout,
                "from_data": bottoms[0] in data_blobs})
        elif kind == "Pooling":
            p = layer.get_msg("pooling_param")
            n, c, h, w = src
            if p.get_bool("global_pooling", False):
                out = (n, c, 1, 1)
            else:
                kh, kw = _hw(p, "kernel", 1)
                sh, sw = _hw(p, "stride", 1)
                ph, pw = _hw(p, "pad", 0)
                out = (n, c, pool_out(h, kh, sh, ph), pool_out(w, kw, sw, pw))
        elif kind == "Concat":
            axis = layer.get_msg("concat_param").get_int("axis", 1)
            out = list(src)
            out[axis] = sum(shapes[b][axis] for b in bottoms)
            out = tuple(out)
        elif kind in ("SoftmaxWithLoss", "Accuracy", "EuclideanLoss",
                      "SigmoidCrossEntropyLoss", "HingeLoss"):
            out = ()
        else:
            out = src
        for t in tops:
            shapes[t] = out
    return rows


def step_flops(rows) -> int:
    """Operations one training step requires (forward + backward)."""
    return sum(2 * r["macs"] * (2 if r["from_data"] else 3) for r in rows)


def layer_floor_s(row, peak_flops: float, peak_bytes_s: float,
                  bytes_per_elem: int = 2) -> tuple[float, str]:
    """The least time a chip could take for this layer's three passes and
    which peak bounds it: max(ops/peak, bytes/peak)."""
    passes = 2 if row["from_data"] else 3
    ops = 2 * row["macs"] * passes
    byts = passes * bytes_per_elem * (
        row["in_elems"] + row["out_elems"] + row["weight_elems"])
    t_ops, t_bytes = ops / peak_flops, byts / peak_bytes_s
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
