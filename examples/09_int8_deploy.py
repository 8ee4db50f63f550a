"""int8 deploy walkthrough: classify with a quantized net.

The reference's classification example runs a float deploy net
(ref: caffe/examples/cpp_classification/classification.cpp,
00-classification.ipynb); this adds the TPU-native deploy twist — the
MXU's int8 mode doubles the v5e's matmul peak, and post-training
quantization (sparknet_tpu.quant) gets a prototxt net onto it without
retraining:

1. train LeNet on real digit pixels (the unmodified zoo recipe),
2. calibrate int8 scales on a few training batches,
3. compare float vs int8 predictions + wall time.

Run:  python examples/09_int8_deploy.py [--platform cpu]
"""

import argparse
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="cpu")
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()
    import jax

    jax.config.update("jax_platforms", args.platform)
    import numpy as np

    from sparknet_tpu import models, quant
    from sparknet_tpu.data.digits import load_digits_dataset
    from sparknet_tpu.solvers.solver import Solver

    xtr, ytr, xte, yte = load_digits_dataset()
    xtr, xte = xtr / 16.0, xte / 16.0
    B = 64
    solver = Solver(models.lenet_solver(), models.lenet(B))
    rs = np.random.RandomState(0)
    solver.step(args.iters, lambda it: (
        lambda idx: {"data": xtr[idx], "label": ytr[idx]}
    )(rs.randint(0, len(ytr), B)))

    net, variables = solver.test_net, solver.variables
    calib = [{"data": xtr[i * B:(i + 1) * B],
              "label": ytr[i * B:(i + 1) * B]} for i in range(4)]
    qstate = quant.calibrate(net, variables, calib)

    feeds = {"data": xte[:128], "label": yte[:128]}

    def top1(fn_label, ctx):
        import contextlib

        def fwd(v, f):
            return net.apply(v, f, rng=None, train=False)[0]["ip2"]

        with ctx or contextlib.nullcontext():
            jf = jax.jit(fwd)
            # np.asarray IS the fence: it copies the VALUE of the
            # program's own output buffer (common.value_fence)
            out = np.asarray(jf(variables, feeds))
            t0 = time.perf_counter()
            out = np.asarray(jf(variables, feeds))
            ms = (time.perf_counter() - t0) * 1e3
        pred = np.argmax(out, -1)
        acc = float((pred == yte[:128]).mean())
        print(json.dumps({"arm": fn_label, "accuracy": round(acc, 4),
                          "ms_per_batch": round(ms, 2)}))
        return pred

    f_pred = top1("float", None)
    q_pred = top1("int8", quant.quantized_inference(qstate))
    agree = float((f_pred == q_pred).mean())
    print(json.dumps({"top1_agreement": round(agree, 4),
                      "quantized_layers": sorted(qstate)}))


if __name__ == "__main__":
    main()
