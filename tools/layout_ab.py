"""NCHW vs NHWC conv orientation at the MXU — isolated and framework A/Bs.

One layout experiment on the zoo's pure-MFU member (VGG-16), and the
FRAMEWORK-level cost of an NHWC-native blob orientation — the
isolated-vs-framework delta is the verdict: how much of the raw-jax
layout win the real graph-compiler path keeps.  The headline shape
(AlexNet) gets its own arm.

Two modes:

* isolated (default): the SAME conv stack (``--model vgg16``: 13 convs,
  5 pools, 3 fc; ``--model alexnet``: the Caffe geometry — 11/4 entry
  conv, grouped 5x5 and 3x3 convs, 3x3/2 pools; LRN excluded — it is
  layout-invariant pointwise+window math, and the framework mode prices
  it) built with NCHW/OIHW vs NHWC/HWIO dimension numbers, identical
  math, raw jax — no framework surgery, so the verdict is about
  XLA:TPU's preference, not our graph compiler.
* ``--framework``: both arms through the REAL zoo/solver path — the
  exact ``bench._build_step`` construction the headline number uses,
  with ``Config.layout`` flipping the internal orientation
  (ops/layout.py) and the synthetic feed shipped in each arm's natural
  layout.  Full train step: LRN, dropout, SGD update, donation.

Timing protocol (both modes): all iters fused in ONE dispatch (scan),
warm-vs-timed dispatches carry different args, fence on the scalar
VALUE of the producing program's own output (common.value_fence).

Run:                   python tools/layout_ab.py [--batch 128]
                       python tools/layout_ab.py --framework --model alexnet
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# VGG-16 config D conv plan: (out_channels, convs_in_block)
PLAN = [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]


def _layers(model: str) -> list[tuple]:
    """Conv-stack plan: ("conv", cout, k, stride, pad, groups) and
    ("pool", k, stride) entries (max pool, VALID — Caffe's ceil shapes
    coincide with floor at these geometries)."""
    if model == "vgg16":
        layers: list[tuple] = []
        for cout, reps in PLAN:
            layers += [("conv", cout, 3, 1, 1, 1)] * reps
            layers.append(("pool", 2, 2))
        return layers
    if model == "alexnet":
        # ref: caffe/models/bvlc_alexnet/train_val.prototxt geometry
        return [
            ("conv", 96, 11, 4, 0, 1), ("pool", 3, 2),
            ("conv", 256, 5, 1, 2, 2), ("pool", 3, 2),
            ("conv", 384, 3, 1, 1, 1),
            ("conv", 384, 3, 1, 1, 2),
            ("conv", 256, 3, 1, 1, 2), ("pool", 3, 2),
        ]
    raise SystemExit(f"layout_ab: unknown --model {model!r}")


def build(layout: str, model: str, batch: int, crop: int, nclass: int,
          dtype):
    """Returns (params, step_fn(params, x, y) -> loss) for one layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    nchw = layout == "NCHW"
    dn = ("NCHW", "OIHW", "NCHW") if nchw else ("NHWC", "HWIO", "NHWC")
    layers = _layers(model)
    rs = np.random.RandomState(0)
    conv_params = []
    cin = 3
    for spec in layers:
        if spec[0] != "conv":
            continue
        _, cout, k, _, _, g = spec
        # msra scale: variance-preserving for the deep stack
        w = rs.randn(cout, cin // g, k, k) * np.sqrt(2.0 / (cin // g * k * k))
        if not nchw:
            w = w.transpose(2, 3, 1, 0)  # OIHW -> HWIO
        conv_params.append(jnp.asarray(w, dtype))
        cin = cout

    def conv_stack(h, weights):
        import jax.lax as lax

        i = 0
        for spec in layers:
            if spec[0] == "conv":
                _, _, _, s, p, g = spec
                h = lax.conv_general_dilated(
                    h, weights[i], window_strides=(s, s),
                    padding=[(p, p), (p, p)], dimension_numbers=dn,
                    feature_group_count=g)
                h = jax.nn.relu(h)
                i += 1
            else:
                _, k, s = spec
                wdims = (2, 3) if nchw else (1, 2)
                h = lax.reduce_window(
                    h, -jnp.inf, lax.max,
                    window_dimensions=tuple(
                        k if d in wdims else 1 for d in range(4)),
                    window_strides=tuple(
                        s if d in wdims else 1 for d in range(4)),
                    padding="VALID")
        return h

    xshape = (batch, 3, crop, crop) if nchw else (batch, crop, crop, 3)
    out = jax.eval_shape(lambda h: conv_stack(h, conv_params),
                         jax.ShapeDtypeStruct(xshape, dtype))
    fc_in = int(np.prod(out.shape[1:]))
    params = list(conv_params)
    for m, n in [(fc_in, 4096), (4096, 4096), (4096, nclass)]:
        params.append(jnp.asarray(rs.randn(m, n) * np.sqrt(2.0 / m), dtype))
    n_conv = len(conv_params)

    def fwd(params, x, y):
        # the conv weights ride the traced params so grads flow
        h = conv_stack(x, params[:n_conv])
        h = h.reshape(h.shape[0], -1)
        for w in params[n_conv:]:
            h = h @ w
        logp = jax.nn.log_softmax(h.astype(jnp.float32))
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    def step(params, x, y):
        loss, grads = jax.value_and_grad(fwd)(params, x, y)
        # SGD-less: fold the grads into the loss scalar so the backward
        # pass is live without threading an optimizer through the A/B.
        # 1e-30, not 0.0 — mul-by-zero is foldable and would let XLA
        # delete the whole backward pass
        gsum = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in grads)
        return loss + 1e-30 * gsum

    return params, step


def measure(layout: str, model: str, batch: int, crop: int, iters: int,
            dtype_name: str):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from sparknet_tpu.common import value_fence as fence

    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    params, step = build(layout, model, batch, crop, 1000, dtype)
    rs = np.random.RandomState(1)
    shape = ((batch, 3, crop, crop) if layout == "NCHW"
             else (batch, crop, crop, 3))
    x = jax.device_put(jnp.asarray(rs.randn(*shape), dtype))
    y = jax.device_put(jnp.asarray(rs.randint(0, 1000, batch), jnp.int32))
    params = jax.device_put(params)

    def chained(params, x, y, salt):
        def body(carry, _):
            x2 = x + (carry * 1e-24).astype(x.dtype)
            return step(params, x2, y).astype(jnp.float32), None

        s, _ = lax.scan(body, jnp.float32(salt), None, length=iters)
        return s

    cfn = jax.jit(chained)
    fence(cfn(params, x, y, 0.0))  # warm: compiles + runs the chain once
    t0 = time.perf_counter()
    out = cfn(params, x, y, 1.0)
    fence(out)
    dt = time.perf_counter() - t0
    from sparknet_tpu.common import device_stamp

    stamp = device_stamp()
    platform = stamp["platform"]
    return {
        "metric": f"{model}_shape_fwd_bwd_img_s", "arm": layout,
        "value": round(batch * iters / dt, 1), "batch": batch,
        "iters": iters, "dtype": dtype_name,
        # CPU plumbing checks must never read as chip evidence
        "measured": platform != "cpu", **stamp,
    }


def measure_framework(layout: str, model: str, batch: int, crop: int,
                      iters: int, dtype_name: str):
    """One arm through the REAL zoo/solver path — bench._build_step, the
    exact construction the headline number rides (full train step: LRN,
    dropout, SGD update, donated carry), with ``Config.layout`` flipping
    the internal orientation (ops/layout.py)."""
    import jax

    import bench
    from sparknet_tpu.common import get_config, set_config
    from sparknet_tpu.common import value_fence as fence

    prior = get_config().layout
    set_config(layout=layout.lower())
    try:
        step, variables, slots, key, feeds = bench._build_step(
            batch, model, crop, dtype_name, scan=max(iters, 2))
        # warm dispatch compiles + runs the fused chain once; threading
        # variables/slots through gives the timed dispatch fresh args
        variables, slots, loss = step(variables, slots, 0, feeds, key)
        fence(loss)
        t0 = time.perf_counter()
        variables, slots, loss = step(variables, slots, iters, feeds, key)
        fence(loss)
        dt = time.perf_counter() - t0
    finally:
        set_config(layout=prior)
    from sparknet_tpu.common import device_stamp

    stamp = device_stamp()
    return {
        "metric": f"{model}_framework_train_img_s", "arm": layout,
        "value": round(batch * max(iters, 2) / dt, 1), "batch": batch,
        "iters": max(iters, 2), "dtype": dtype_name,
        "measured": stamp["platform"] != "cpu", **stamp,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="vgg16",
                    choices=["vgg16", "alexnet"],
                    help="shape under test (alexnet = the headline "
                    "shape, where the 2.0 ms formatting tax was "
                    "measured)")
    ap.add_argument("--framework", action="store_true",
                    help="build both arms through the real zoo/solver "
                    "path (bench._build_step + Config.layout) instead "
                    "of raw jax — the isolated-vs-framework delta is "
                    "the verdict")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--crop", type=int, default=None)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dtype", default="bf16")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from sparknet_tpu.common import require_chip

    on_accel = require_chip("layout_ab")["platform"] != "cpu"

    if args.framework:
        # the net is built at the zoo's bench crop; --crop is ignored
        from sparknet_tpu.models import BENCH_CROPS

        args.crop = BENCH_CROPS.get(args.model, 224)
        if not on_accel:  # offline plumbing check
            args.batch, args.iters, args.dtype = 2, 2, "f32"
        arms = ("nchw", "nhwc")
        run = lambda lay: measure_framework(  # noqa: E731
            lay, args.model, args.batch, args.crop, args.iters, args.dtype)
    else:
        if args.crop is None:
            args.crop = 224 if args.model == "vgg16" else 227
        if not on_accel:  # offline plumbing check
            args.batch, args.iters, args.dtype = 2, 2, "f32"
            # smallest crops the stacks survive (vgg: one 1x1 cell out;
            # alexnet: 67 -> 15 -> 7 -> 3 -> 1 through its pools)
            args.crop = 32 if args.model == "vgg16" else 67
        arms = ("NCHW", "NHWC")
        run = lambda lay: measure(  # noqa: E731
            lay, args.model, args.batch, args.crop, args.iters, args.dtype)

    results = [run(lay) for lay in arms]
    for r in results:
        print(json.dumps(r), flush=True)

    if not on_accel:
        # plumbing check only — never bank as chip evidence
        print("layout_ab: cpu run, not banking", file=sys.stderr)
        return 0

    out_path = args.out
    if out_path is None:
        # the historical vgg16 isolated A/B keeps its banked filename
        stem = ("layout_ab_last" if args.model == "vgg16"
                and not args.framework else
                f"layout_ab_{args.model}{'_fw' if args.framework else ''}"
                "_last")
        out_path = f"docs/{stem}.json"
    if not os.path.isabs(out_path):
        out_path = os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), out_path)
    # common.bank_guard: the one blessed evidence sink (bank-guard lint
    # rule) — atomic write; unmeasured payloads divert to /tmp
    from sparknet_tpu.common import bank_guard

    if bank_guard(out_path,
                  {"mode": "framework" if args.framework else "isolated",
                   "model": args.model, "arms": results,
                   "utc": time.strftime(
                       "%Y-%m-%d %H:%M:%SZ", time.gmtime())},
                  measured=on_accel) is None:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
