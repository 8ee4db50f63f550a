"""Time the linear-attention decoder's two device paths alone, on the
chip, at the cell's sizes.  Run by hand through the chip tool; never
imported, never part of a run:

    python benchmarks/scratch/linear_kernels.py [--what delta,core]

* the gated delta rule (``ops/linear_attention.py gated_delta_rule``)
  forward and forward + backward at 1 x 4096 tokens, 16 key and 32 value
  heads of 128, for a few values of CHUNK (the source of the constant),
  and the token-at-a-time definition (``gated_delta_rule_steps``) at the
  same size: the form that did not ship;
* the gated attention layer's core at 1 x 16 x 4096 over 2 key/value heads
  of 256, forward + backward: the splash kernels' grouped form
  (``_splash_causal``) against the XLA formulation (``_attention_xla``).

Each line of ``chiprun_out/linear_kernels.jsonl`` names its device.
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
    ap.add_argument("--what", default="delta,core")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--chunks", default="32,64,128")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from sparknet_tpu.ops import attention, linear_attention as la

    dev = jax.devices()[0]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "linear_kernels.jsonl"), "a")

    def emit(**rec):
        rec.update(device=dev.device_kind, platform=dev.platform, seq=a.seq)
        text = json.dumps(rec)
        print(text, flush=True)
        sink.write(text + "\n")
        sink.flush()

    keys = jax.random.split(jax.random.key(0), 8)
    if "delta" in a.what:
        hk, hv, d = 16, 32, 128
        bf = lambda k, *s: jax.random.normal(k, s, jnp.float32).astype(
            jnp.bfloat16)
        q, k = bf(keys[0], 1, a.seq, hk, d), bf(keys[1], 1, a.seq, hk, d)
        v = bf(keys[2], 1, a.seq, hv, d)
        aa = jax.random.normal(keys[3], (1, a.seq, hv), jnp.float32)
        bb = jax.random.normal(keys[4], (1, a.seq, hv), jnp.float32)
        a_log = jnp.log(jax.random.uniform(keys[5], (hv,), jnp.float32,
                                           0.01, 16.0))
        dt_bias = jnp.full((hv,), -3.0, jnp.float32)
        args = (q, k, v, aa, bb, a_log, dt_bias)

        def both(rule):
            loss = lambda *xs: jnp.sum(rule(*xs).astype(jnp.float32) ** 2)
            return jax.jit(rule), jax.jit(jax.grad(loss, argnums=range(7)))

        for chunk in (int(c) for c in a.chunks.split(",")):
            fwd, grad = both(lambda *xs: la.gated_delta_rule(*xs, chunk=chunk))
            f_ms, f_first = timed(fwd, args)
            g_ms, g_first = timed(grad, args)
            emit(what="delta", form="chunked", chunk=chunk, fwd_ms=f_ms,
                 fwd_bwd_ms=g_ms, compile_s=f_first + g_first)
        fwd, grad = both(la.gated_delta_rule_steps)
        f_ms, f_first = timed(fwd, args, reps=3)
        emit(what="delta", form="steps", fwd_ms=f_ms, compile_s=f_first)
        want = fwd(*args).astype(jnp.float32)
        got = jax.jit(la.gated_delta_rule)(*args).astype(jnp.float32)
        emit(what="delta", form="chunked against steps, forward, bf16 in",
             rel=float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)))
    if "core" in a.what:
        h, hk, d = 16, 2, 256
        bf = lambda k, n: (jax.random.normal(k, (1, n, a.seq, d), jnp.float32)
                           ).astype(jnp.bfloat16)
        q, k, v = bf(keys[0], h), bf(keys[1], hk), bf(keys[2], hk)
        forms = {"splash_1024": lambda q, k, v: attention._splash_causal(
                     q, k, v, 1024),
                 "splash_512": lambda q, k, v: attention._splash_causal(
                     q, k, v, 512),
                 "xla": lambda q, k, v: attention._attention_xla(
                     q, k, v, True, 0)}
        for name, core in forms.items():
            loss = lambda q, k, v: jnp.sum(core(q, k, v).astype(jnp.float32) ** 2)
            grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                ms, first = timed(grad, (q, k, v))
                emit(what="core", form=name, fwd_bwd_ms=ms, compile_s=first)
            except Exception as e:  # the XLA form's scores may not fit
                emit(what="core", form=name, error=repr(e)[:200])


if __name__ == "__main__":
    main()
