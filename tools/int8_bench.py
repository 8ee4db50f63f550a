"""Forward (deploy) throughput A/B: float vs post-training int8.

The int8 MXU mode is where a v5e doubles its matmul peak (394 int8 TOPS
vs 197 bf16 TFLOP/s — `sparknet_tpu.common.TPU_PEAK_FLOPS`); this
measures what that buys the zoo's deploy forward at batch ``--batch``
(classification is forward-only — ref: the cpp_classification example,
caffe/examples/cpp_classification/classification.cpp).  Prints one JSON
line per arm and banks both to ``--out``.

Run:  python tools/int8_bench.py [--model alexnet]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="alexnet")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (cpu for offline checks)")
    ap.add_argument("--fold-bn", action="store_true",
                    help="fold BatchNorm/Scale chains before measuring "
                    "(required for BN nets like resnet50; the float arm "
                    "then measures the folded forward)")
    ap.add_argument("--out", default="docs/int8_bench_last.json")
    args = ap.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu import models, quant
    from sparknet_tpu.common import Phase, require_chip, set_config
    from sparknet_tpu.compiler.graph import Network

    stamp = require_chip("int8_bench")  # no chip, no pin: exit 2
    on_accel = stamp["platform"] != "cpu"
    if on_accel:
        set_config(compute_dtype=jnp.bfloat16)
    from sparknet_tpu.models import BENCH_CROPS

    crop = BENCH_CROPS[args.model]
    B = args.batch if on_accel else 8
    iters = args.iters if on_accel else 2

    net = Network(getattr(models, args.model)(B), Phase.TEST)
    variables = net.init(jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    feeds = jax.device_put({
        "data": jnp.asarray(rs.randn(B, 3, crop, crop) * 50, jnp.float32),
        "label": jnp.asarray(rs.randint(0, 1000, B), jnp.int32),
    })

    def fwd(v, f):
        blobs, _, _ = net.apply(v, f, rng=None, train=False)
        return blobs[net.output_blobs()[0]]

    def measure(label, ctx):
        import contextlib

        from jax import lax

        from sparknet_tpu.common import value_fence as fence

        def run(apply_fn):
            # All ``iters`` forwards fused into ONE lax.scan dispatch,
            # chained through a numerically-negligible carry (logit[0]
            # * 1e-24 added to the input — absorbed exactly by f32 at
            # data magnitude ~50, but XLA cannot elide the dependence),
            # and salted so the warm and timed dispatches never carry
            # identical args.
            def chained(v, f, salt):
                def body(carry, _):
                    f2 = dict(f)
                    f2["data"] = f["data"] + (carry * 1e-24).astype(
                        f["data"].dtype)
                    logits = apply_fn(v, f2)
                    return logits.astype(jnp.float32).ravel()[0], None

                s, _ = lax.scan(body, jnp.float32(salt), None,
                                length=iters)
                return s

            cfn = jax.jit(chained)
            fence(cfn(variables, feeds, 0.0))  # warm: full chain once
            t0 = time.perf_counter()
            out = cfn(variables, feeds, 1.0)
            fence(out)
            return B * iters / (time.perf_counter() - t0)

        with ctx or contextlib.nullcontext():
            img_s = run(lambda v, f: fwd(v, f))
        rec = {"metric": f"{args.model}_deploy_forward_img_s", "arm": label,
               "value": round(img_s, 1), "batch": B, "iters": iters,
               # CPU plumbing checks must never read as chip evidence
               "measured": on_accel, **stamp}
        print(json.dumps(rec), flush=True)
        return rec

    results = [measure("float", None)]
    if args.fold_bn:
        # merge_bn (models/fold_bn.py): the folded-float arm measures
        # what deleting the BN/Scale passes buys on its own, and BN
        # nets must be in folded (pure Conv/IP) form before int8
        # calibration anyway
        from sparknet_tpu.compiler.graph import NetVars
        from sparknet_tpu.models.fold_bn import fold_batchnorm

        net_p2, params2, state2, folded = fold_batchnorm(
            net.net_param, variables.params, variables.state)
        print(json.dumps({"fold_bn": len(folded)}), flush=True)
        if folded:
            net = Network(net_p2, Phase.TEST)
            variables = NetVars(params=params2, state=state2)
            results.append(measure("float_folded", None))
    qstate = quant.calibrate(net, variables, [feeds])
    results.append(measure("int8", quant.quantized_inference(qstate)))

    if not on_accel:
        # plumbing check only — never bank as chip evidence
        print("int8_bench: cpu run, not banking", file=sys.stderr)
        return 0

    out_path = args.out
    if not os.path.isabs(out_path):
        out_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            out_path)
    # common.bank_guard is the one blessed evidence sink (bank-guard
    # lint rule): atomic write, and — although the CPU branch above
    # already returned — an unmeasured payload would divert to /tmp
    # rather than overwrite banked chip evidence
    from sparknet_tpu.common import bank_guard

    if bank_guard(out_path,
                  {"arms": results,
                   "utc": time.strftime("%Y-%m-%d %H:%M:%SZ",
                                        time.gmtime())},
                  measured=on_accel) is None:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
