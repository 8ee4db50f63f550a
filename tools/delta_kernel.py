"""The gated delta rule's kernels alone (``ops/linear_attention.py``), at
the linear-attention cell's size: 1 x 4,096 tokens, 16 key and 32 value
heads of 128, q / k / v bf16, the gates' inputs f32.

    python tools/delta_kernel.py [--seq 4096] [--heads 4,8] [--chunks 1,2]
    JAX_PLATFORMS=cpu python tools/delta_kernel.py --aot

On the chip (run through the chip tool): the XLA chunked form and the
kernels on the same inputs, forward against the definition
(``gated_delta_rule_steps``: one ``lax.scan`` over time) and all seven
gradients against the chunked form's (the definition's backward would
keep a state a token, 8.6 GB), relative distances; then forward and
forward + backward milliseconds of the chunked form and of the kernels at
each block choice (``--heads``: key heads a grid step, ``--chunks``:
chunks a grid step).  ``ops/linear_attention.py``'s ``HEAD_BLOCK`` and
``CHUNK_BLOCK`` were set from this table.  One JSON line a row, each
naming its device, also appended to ``chiprun_out/delta_kernel.jsonl``;
exit code 1 if a kernel was refused or its forward lies further from the
definition than 1.5 x the chunked form's does; without a chip it exits 2
and prints no number.

``--aot`` compiles both kernels for a described v5e at that size, no
chip needed (the on-chip-measurement guide, section 2): Mosaic refuses
here what it would refuse there.  A compile that passes is not a run.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.scan_kernel import rel, timed  # noqa: E402  (no jax at import)

K_HEADS, V_HEADS, HEAD_DIM = 16, 32, 128


def shapes(seq):
    import jax.numpy as jnp
    bf16, f32 = jnp.bfloat16, jnp.float32
    return [((1, seq, K_HEADS, HEAD_DIM), bf16),
            ((1, seq, K_HEADS, HEAD_DIM), bf16),
            ((1, seq, V_HEADS, HEAD_DIM), bf16),
            ((1, seq, V_HEADS), f32), ((1, seq, V_HEADS), f32),
            ((V_HEADS,), f32), ((V_HEADS,), f32)]


def inputs(seq):
    """As ``benchmarks/scratch/linear_kernels.py`` draws them (PR 47's
    timings are of these)."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.key(0), 8)
    draw = [jax.random.normal(k, s, jnp.float32)
            for k, (s, _) in zip(ks, shapes(seq))]
    draw[5] = jnp.log(jax.random.uniform(ks[5], (V_HEADS,), jnp.float32,
                                         0.01, 16.0))
    draw[6] = jnp.full((V_HEADS,), -3.0, jnp.float32)
    return tuple(x.astype(dt) for x, (_, dt) in zip(draw, shapes(seq)))


def both(rule):
    """forward + backward of ``rule``: all seven gradients of a loss that
    weighs every output."""
    import jax
    import jax.numpy as jnp

    def loss(*a):
        return jnp.sum(rule(*a).astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=tuple(range(7))))


def set_blocks(heads, chunks):
    import jax

    from sparknet_tpu.ops import linear_attention as la
    la.HEAD_BLOCK, la.CHUNK_BLOCK = heads, chunks
    jax.clear_caches()  # the kernels' jitted entries read them


def aot(seq, choices) -> int:
    import jax
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.ops import linear_attention as la
    from tools.expert_copies import v5e_chip  # pins the CPU when imported

    device = v5e_chip()
    chip = SingleDeviceSharding(device)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip)
            for s, dt in shapes(seq)]
    wrong = 0
    for heads, chunks in choices:
        set_blocks(heads, chunks)
        for name, fn in (("forward", jax.jit(la.gated_delta_rule_kernel)),
                         ("forward+backward",
                          both(la.gated_delta_rule_kernel))):
            t = time.perf_counter()
            row = {"compiled": name, "for": device.device_kind,
                   "heads": heads, "chunks": chunks}
            try:
                stats = fn.lower(*args).compile().memory_analysis()
                row.update(temp_bytes=stats.temp_size_in_bytes,
                           output_bytes=stats.output_size_in_bytes)
            except Exception as e:  # Mosaic's refusal is the row
                row.update(refused=str(e)[:600])
                wrong += 1
            row["seconds"] = round(time.perf_counter() - t, 1)
            print(json.dumps(row), flush=True)
    return 1 if wrong else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", default="")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--aot", action="store_true")
    a = ap.parse_args()

    from sparknet_tpu.ops import linear_attention as la

    ints = lambda text, default: ([int(x) for x in text.split(",")]
                                  if text else [default])
    choices = list(itertools.product(ints(a.heads, la.HEAD_BLOCK),
                                     ints(a.chunks, la.CHUNK_BLOCK)))
    if a.aot:
        return aot(a.seq, choices)

    import jax

    from sparknet_tpu.common import require_chip

    stamp = require_chip("delta_kernel")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "delta_kernel.jsonl"), "a")

    def emit(**row):
        row.update(seq=a.seq, platform=stamp["platform"],
                   device_kind=stamp["device_kind"])
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    args, wrong = inputs(a.seq), 0
    interpret = stamp["platform"] != "tpu"  # a CPU rehearsal of this script

    def chunked(*x):
        with jax.named_scope(la.DELTA_SCOPE):
            return la._rule(*x, la.CHUNK)

    # the definition: one lax.scan over time (forward only: its backward
    # keeps a state a token)
    true_o = jax.jit(la.gated_delta_rule_steps)(*args)
    want_o, want_g = jax.jit(chunked)(*args), both(chunked)(*args)
    near = lambda x: float(f"{x:.3g}")
    xla_far = rel(want_o, true_o)
    emit(what="chunked", chunk=la.CHUNK, fwd_ms=timed(jax.jit(chunked), args),
         fwd_bwd_ms=timed(both(chunked), args), fwd_against_steps=near(xla_far))
    for heads, chunks in choices:
        set_blocks(heads, chunks)

        def kernel(*x):
            return la.gated_delta_rule_kernel(*x, interpret=interpret)

        fwd, grad = jax.jit(kernel), both(kernel)
        t = time.perf_counter()
        try:
            o, g = jax.block_until_ready((fwd(*args), grad(*args)))
        except Exception as e:  # Mosaic's refusal is the row
            emit(what="kernel", heads=heads, chunks=chunks,
                 refused=str(e)[:600])
            wrong += 1
            continue
        first = round(time.perf_counter() - t, 1)
        far = rel(o, true_o)
        emit(what="kernel", heads=heads, chunks=chunks, compile_s=first,
             fwd_ms=timed(fwd, args), fwd_bwd_ms=timed(grad, args),
             fwd_against_steps=near(far),
             fwd_against_chunked=near(rel(o, want_o)),
             grads_against_chunked=[near(rel(x, y))
                                    for x, y in zip(g, want_g)])
        wrong += not far <= 1.5 * xla_far
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
