"""Pallas-vs-XLA kernel shootout on the real chip.

The repo ships opt-in pallas kernels (`ops/pallas_kernels.py`); this
tool times two of them — cross-channel LRN and flash attention, both
with custom VJPs and interpret-mode tests — against their XLA lowering
on TPU, in one command and one process.

    python tools/pallas_bench.py            # both kernels, fwd+bwd
    python tools/pallas_bench.py --op lrn   # one kernel

Prints one JSON record per (op, direction, impl) with amortized ms/iter
(chained-iteration mean — see _time_fn; NOT a per-call median), and a
final verdict line per op: promote pallas, keep XLA, or unmeasured.
Decision rule: the winner at the bench shapes becomes the default; a
kernel that loses stays opt-in or gets deleted.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# AlexNet's LRN shape at bench batch (b256 conv1 output) and a
# transformer-ish attention shape; SPARKNET_PALLAS_BENCH_SMALL=1 shrinks
# both for plumbing checks on small boxes
if os.environ.get("SPARKNET_PALLAS_BENCH_SMALL"):
    LRN_SHAPE = (4, 16, 16, 16)
    ATTN_SHAPE = (2, 2, 256, 64)
else:
    LRN_SHAPE = (256, 96, 55, 55)
    ATTN_SHAPE = (8, 8, 1024, 64)  # (batch, heads, seq, head_dim)
# Long-context override, e.g. "2,8,8192,64": at multi-k sequence the
# O(seq^2) materialized-scores XLA path is where flash tiling earns its
# keep
if os.environ.get("SPARKNET_PALLAS_ATTN_SHAPE"):
    ATTN_SHAPE = tuple(
        int(x) for x in
        os.environ["SPARKNET_PALLAS_ATTN_SHAPE"].split(","))
    assert len(ATTN_SHAPE) == 4, ATTN_SHAPE


def _probed(fn):
    """Wrap a jitted ``fn`` so every dispatch ALSO returns a tiny f32
    probe scalar summing one element of each output leaf, computed
    INSIDE the producing program.

    This is how a big-output kernel satisfies ``common.value_fence``'s
    caller contract: the probe is an output buffer of the producing
    program itself — fetching its VALUE is the fence — without copying
    the multi-MB outputs to the host.  The chained iterations make the
    LAST probe transitively depend on every timed call; per-element cost
    is one gather per leaf, noise against the kernels under test and
    identical across impls."""
    import jax
    import jax.numpy as jnp

    def wrapped(*a):
        out = fn(*a)
        leaves = jax.tree_util.tree_leaves(out)
        probe = sum(x.ravel()[0].astype(jnp.float32) for x in leaves)
        return out, probe

    return jax.jit(wrapped)


def _time_fn(fn, args, chain, iters=20, warmup=3):
    """ms/iter over `iters` invocations chained through `chain(args, out)
    -> next_args` so each call consumes the previous call's output: the
    device can't overlap or elide iterations, no two dispatches carry
    identical args, and one value_fence on the final probe times real
    execution with dispatch overhead amortized."""
    from sparknet_tpu.common import value_fence

    pfn = _probed(fn)
    a = args
    for _ in range(warmup):
        out, probe = pfn(*a)
        a = chain(a, out)
    value_fence(probe)
    t0 = time.perf_counter()
    for _ in range(iters):
        out, probe = pfn(*a)
        a = chain(a, out)
    value_fence(probe)
    return (time.perf_counter() - t0) * 1e3 / iters


def bench_lrn(records, dtype="float32"):
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.ops import pallas_kernels as pk

    dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    x = jax.random.normal(jax.random.key(0), LRN_SHAPE, dt)
    grads = jax.random.normal(jax.random.key(1), LRN_SHAPE, dt)
    results = {}
    for impl in ("xla", "fused", "pallas"):
        fwd = jax.jit(functools.partial(
            pk.lrn_across_channels, size=5, alpha=1e-4, beta=0.75, k=1.0,
            force=impl))
        vjp = jax.jit(lambda x, g, f=fwd: jax.vjp(f, x)[1](g)[0])
        try:
            results[impl] = {
                # fwd: feed the (shape-preserving) output back in; bwd:
                # feed dx back as x, keeping the cotangent fixed
                "fwd_ms": round(_time_fn(fwd, (x,),
                                         lambda a, out: (out,)), 3),
                "bwd_ms": round(_time_fn(vjp, (x, grads),
                                         lambda a, out: (out, a[1])), 3),
            }
        except Exception as e:
            results[impl] = {"error": repr(e)[:300]}
        records.append({"op": "lrn", "impl": impl, "shape": list(LRN_SHAPE),
                        "dtype": dtype, **results[impl]})
    return results


def bench_flash(records, dtype="float32", fwd_only=False):
    """``fwd_only``: skip the backward arm.  REQUIRED at long sequence:
    the pallas custom-VJP backward is currently ``jax.vjp`` of the XLA
    path (pallas_kernels._flash_diff_bwd), so at multi-k seq BOTH arms'
    backward re-materializes the O(seq^2) score matrix — the fwd+bwd
    total would compare XLA against XLA-plus-overhead (and can OOM the
    chip) instead of measuring the flash forward tiling."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.ops import pallas_kernels as pk

    dt = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    q, k, v = (jax.random.normal(jax.random.key(i), ATTN_SHAPE, dt)
               for i in range(3))
    g = jax.random.normal(jax.random.key(3), ATTN_SHAPE, dt)
    results = {}
    for impl in ("xla", "pallas"):
        fwd = jax.jit(functools.partial(pk.flash_attention, causal=True,
                                        force=impl))
        # time the FULL backward (dq, dk, dv): returning only dq would let
        # XLA dead-code-eliminate 2/3 of its backward while the pallas
        # custom-VJP kernel computes all three — an asymmetric comparison
        vjp = jax.jit(lambda q, k, v, g, f=fwd: jax.vjp(f, q, k, v)[1](g))
        try:
            results[impl] = {
                # fwd output has q's shape -> chain it into q; bwd
                # (dq, dk, dv) chain into (q, k, v), cotangent fixed
                "fwd_ms": round(_time_fn(
                    fwd, (q, k, v),
                    lambda a, out: (out, a[1], a[2])), 3),
            }
            if not fwd_only:
                results[impl]["bwd_ms"] = round(_time_fn(
                    vjp, (q, k, v, g),
                    lambda a, out: (out[0], out[1], out[2], a[3])), 3)
        except Exception as e:
            results[impl] = {"error": repr(e)[:300]}
        records.append({"op": "flash_attention", "impl": impl,
                        "shape": list(ATTN_SHAPE), "dtype": dtype,
                        **({"fwd_only": True} if fwd_only else {}),
                        **results[impl]})
    return results


def verdict(op, results):
    """Promote the fastest non-default impl iff it beats the XLA default
    by >5% fwd+bwd; an impl that errors on chip can never promote."""
    x = results.get("xla", {})
    if "error" in x or "fwd_ms" not in x:
        return {"op": op, "verdict": "xla lowering failed (unexpected)",
                "xla_error": x.get("error")}
    totals = {}
    errors = {}
    for impl, r in results.items():
        if "fwd_ms" in r:
            totals[impl] = round(r["fwd_ms"] + r.get("bwd_ms", 0.0), 3)
        else:
            errors[impl] = r.get("error")
    best = min(totals, key=totals.get)
    challengers = {i: t for i, t in totals.items() if i != "xla"}
    if not challengers:
        # every alternative errored: that is NOT a measured tie — keep the
        # round-2 fix-or-delete signal loud in the headline line
        v = (f"every challenger failed on chip ({', '.join(errors)}) — "
             "keep XLA default, fix or delete the kernels")
    elif best != "xla" and totals[best] < 0.95 * totals["xla"]:
        v = (f"PROMOTE {best} ({totals[best]:.2f} ms vs "
             f"{totals['xla']:.2f} ms XLA fwd+bwd)")
    else:
        v = (f"keep XLA default ({totals['xla']:.2f} ms; best challenger "
             f"{min(challengers.values()):.2f} ms)")
    out = {"op": op, "verdict": v, "totals_ms": totals}
    if errors:
        out["errors"] = errors
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--op", choices=["lrn", "flash", "all"], default="all")
    ap.add_argument("--dtype", choices=["float32", "bf16"], default="float32",
                    help="arm dtype (the r3 shootout was f32; the training "
                    "step runs bf16 — the promote decision should too)")
    ap.add_argument("--fwd-only", action="store_true",
                    help="skip the backward arms (required at long "
                    "sequence: the pallas VJP is the XLA path, see "
                    "bench_flash docstring)")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run on CPU/interpret anyway (numbers meaningless "
                    "for the promote decision; for plumbing checks only)")
    args = ap.parse_args()

    from sparknet_tpu.common import require_chip

    stamp = require_chip("pallas_bench")  # no chip, no pin: exit 2
    on_accel = stamp["platform"] != "cpu"
    if not on_accel and not args.allow_cpu:
        print("pallas_bench: CPU backend; pass --allow-cpu for a "
              "plumbing-only run", file=sys.stderr)
        return 2

    records: list[dict] = []
    verdicts = []
    if args.op in ("lrn", "all"):
        verdicts.append(verdict("lrn", bench_lrn(records, args.dtype)))
    if args.op in ("flash", "all"):
        verdicts.append(verdict("flash_attention",
                                bench_flash(records, args.dtype,
                                            fwd_only=args.fwd_only)))
    if not on_accel:
        # CPU numbers can't drive the promote decision (and pallas only
        # runs in interpret mode here) — mark every line
        for r in records + verdicts:
            r["plumbing_only_cpu"] = True
    for r in records + verdicts:
        r.update(stamp)
        print(json.dumps(r))
    # the blessed evidence sink: CPU/interpret plumbing runs divert to
    # /tmp with a rehearsal stamp instead of overwriting a banked
    # on-chip shootout
    from sparknet_tpu.common import bank_guard

    bank_guard(os.path.join(REPO, "docs", "pallas_bench_last.json"),
               {"records": records, "verdicts": verdicts},
               measured=on_accel)
    return 0


if __name__ == "__main__":
    sys.exit(main())
