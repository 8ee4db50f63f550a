"""Time the hybrid decoder's two new device paths alone, on the chip, at
the cell's sizes.  Run by hand through the chip tool; never imported,
never part of a run:

    python benchmarks/scratch/hybrid_kernels.py [--what scan,core]

* the selective scan (``ops/ssm.py selective_scan``) forward + backward at
  1 x 2048 x 5120, state 16, for a few values of CHUNK: the source of the
  constant (PR 32 also timed the inner loop unrolled 8 and 16 times: no
  faster, PERF.md section 6, so the program does not unroll it);
* differential attention's core at 1 x 40 x 2048, keys 64 on 20 heads,
  values 128 on 10 heads, forward + backward: the splash kernels
  (``_splash_causal``) against the XLA formulation (``_attention_xla``),
  full and under the window of 512, at 512- and 1024-wide blocks: the
  timing ``attention_core``'s docstring cites.

Each line of ``chiprun_out/hybrid_kernels.jsonl`` names its device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def timed(fn, args, reps=10):
    import jax

    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / reps * 1e3, first


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--what", default="scan,core")
    ap.add_argument("--seq", type=int, default=2048)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sparknet_tpu.ops import attention, ssm

    dev = jax.devices()[0]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "hybrid_kernels.jsonl"), "a")

    def emit(**row):
        row.update(platform=dev.platform, device_kind=dev.device_kind)
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    S, key = a.seq, jax.random.key(0)
    ks = jax.random.split(key, 8)
    if "scan" in a.what:
        d, n = 5120, 16
        c = jax.random.normal(ks[0], (1, S, d), jnp.bfloat16)
        dt = jax.random.normal(ks[1], (1, S, d), jnp.float32) - 4.0
        bm = jax.random.normal(ks[2], (1, S, n), jnp.bfloat16)
        cm = jax.random.normal(ks[3], (1, S, n), jnp.bfloat16)
        a_log = jnp.broadcast_to(jnp.log(jnp.arange(1.0, n + 1)), (d, n))
        skip = jnp.ones((d,), jnp.float32)
        for chunk in (64, 32, 128, 256):
            ssm.CHUNK = chunk

            def loss(c, dt, bm, cm, a_log, skip):
                y = ssm.selective_scan(c, dt, bm, cm, a_log, skip)
                return jnp.sum(y.astype(jnp.float32) ** 2)

            fwd = jax.jit(lambda *x: ssm.selective_scan(*x))
            both = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5)))
            args = (c, dt, bm, cm, a_log, skip)
            ms_f, first_f = timed(fwd, args)
            ms_b, first_b = timed(both, args)
            emit(what="scan", seq=S, chunk=chunk,
                 fwd_ms=round(ms_f, 3), fwd_bwd_ms=round(ms_b, 3),
                 first_call_s=round(first_f + first_b, 1))
    if "core" in a.what:
        q = jax.random.normal(ks[4], (1, 40, S, 64), jnp.bfloat16)
        k = jax.random.normal(ks[5], (1, 20, S, 64), jnp.bfloat16)
        v = jax.random.normal(ks[6], (1, 10, S, 128), jnp.bfloat16)
        cases = [("splash", w, b) for w in (0, 512) for b in (512, 1024)]
        cases += [("xla", 0, 0), ("xla", 512, 0)]
        for impl, window, block in cases:
            if impl == "splash":
                core = lambda q, k, v: attention._splash_causal(
                    q, k, v, block, window)
            else:
                core = lambda q, k, v: attention._attention_xla(
                    q, k, v, True, window)
            loss = lambda q, k, v: jnp.sum(core(q, k, v).astype(jnp.float32) ** 2)
            ms_f, first_f = timed(jax.jit(core), (q, k, v))
            ms_b, first_b = timed(jax.jit(jax.grad(loss, (0, 1, 2))), (q, k, v))
            emit(what="core", seq=S, impl=impl, window=window, block=block,
                 fwd_ms=round(ms_f, 3), fwd_bwd_ms=round(ms_b, 3),
                 first_call_s=round(first_f + first_b, 1))


if __name__ == "__main__":
    main()
