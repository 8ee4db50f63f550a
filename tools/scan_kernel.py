"""The selective scan's kernels alone (``ops/ssm.py``), at the hybrid
cell's size: 1 x 2,048 x 5120, state 16, c / B / C bf16, Δ f32.

    python tools/scan_kernel.py [--seq 2048] [--blocks 64,128] [--widths 512]
    JAX_PLATFORMS=cpu python tools/scan_kernel.py --aot

On the chip (run through the chip tool): the kernels against the loop
form (``lax.scan`` over chunks around ``lax.scan`` over steps) on the
same inputs and both against the oracle (``selective_scan_steps``: one
``lax.scan`` over time under plain autodiff): forward and all six
gradients, relative distances; then forward and forward + backward milliseconds of the loop form and of
the kernels at each time block (``--blocks``: steps between kept states)
and d-block width (``--widths``).  ``ops/ssm.py``'s ``TIME_BLOCK`` and
``D_BLOCKS`` were set from this table.  One JSON line a row, each naming
its device, also appended to ``chiprun_out/scan_kernel.jsonl``; exit
code 1 if a kernel was refused or lies further than 1e-3 from the
oracle (on the v5e the kernels read 0 to 2e-6, the loop form 7e-5 to
2.6e-3: PERF.md section 6, PR 33); without a chip it exits 2 and prints
no number.

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

D_INNER, D_STATE = 5120, 16


def shapes(seq):
    import jax.numpy as jnp
    bf16, f32 = jnp.bfloat16, jnp.float32
    return [((1, seq, D_INNER), bf16), ((1, seq, D_INNER), f32),
            ((1, seq, D_STATE), bf16), ((1, seq, D_STATE), bf16),
            ((D_INNER, D_STATE), f32), ((D_INNER,), f32)]


def inputs(seq):
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.key(0), 6)
    draw = [jax.random.normal(k, s, jnp.float32)
            for k, (s, _) in zip(ks, shapes(seq))]
    draw[1] = draw[1] - 4.0  # Δ about 0.02, as the initialisation has it
    draw[4] = jnp.broadcast_to(jnp.log(jnp.arange(1.0, D_STATE + 1)),
                               (D_INNER, D_STATE))
    return tuple(x.astype(dt) for x, (_, dt) in zip(draw, shapes(seq)))


def both(scan):
    """forward + backward of ``scan``: all six gradients of a loss that
    weighs every output."""
    import jax
    import jax.numpy as jnp

    def loss(*a):
        return jnp.sum(scan(*a).astype(jnp.float32) ** 2)

    return jax.jit(jax.grad(loss, argnums=tuple(range(6))))


def timed(fn, args, reps=20):
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t) / reps * 1e3, 3)


def rel(got, want):
    import numpy as np
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def aot(seq) -> int:
    import jax
    from jax.sharding import SingleDeviceSharding

    from sparknet_tpu.ops import ssm
    from tools.expert_copies import v5e_chip  # pins the CPU when imported

    device = v5e_chip()
    chip = SingleDeviceSharding(device)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip)
            for s, dt in shapes(seq)]
    for name, fn in (("forward", jax.jit(ssm.selective_scan_kernel)),
                     ("forward+backward", both(ssm.selective_scan_kernel))):
        t = time.perf_counter()
        stats = fn.lower(*args).compile().memory_analysis()
        print(json.dumps({
            "compiled": name, "for": device.device_kind,
            "seconds": round(time.perf_counter() - t, 1),
            "temp_bytes": stats.temp_size_in_bytes,
            "output_bytes": stats.output_size_in_bytes}))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--blocks", default="64,128")
    ap.add_argument("--widths", default="512")
    ap.add_argument("--aot", action="store_true")
    a = ap.parse_args()
    if a.aot:
        return aot(a.seq)

    import jax

    from sparknet_tpu.common import require_chip
    from sparknet_tpu.ops import ssm

    stamp = require_chip("scan_kernel")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "scan_kernel.jsonl"), "a")

    def emit(**row):
        row.update(seq=a.seq, platform=stamp["platform"],
                   device_kind=stamp["device_kind"])
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    args, wrong = inputs(a.seq), 0
    interpret = stamp["platform"] != "tpu"  # a CPU rehearsal of this script

    def loop(*x):
        with jax.named_scope(ssm.SCAN_SCOPE):
            return ssm._selective_scan(*x, None)

    want_y, want_g = jax.jit(loop)(*args), both(loop)(*args)
    # the oracle: one lax.scan over time under plain autodiff
    true_y = jax.jit(ssm.selective_scan_steps)(*args)
    true_g = both(ssm.selective_scan_steps)(*args)

    def far(y, g, want_y=true_y, want_g=true_g):
        return [float(f"{rel(a, b):.3g}")
                for a, b in zip((y,) + tuple(g), (want_y,) + tuple(want_g))]

    emit(what="loop", chunk=ssm.CHUNK, fwd_ms=timed(jax.jit(loop), args),
         fwd_bwd_ms=timed(both(loop), args), against_oracle=far(want_y, want_g))
    ints = lambda text: [int(x) for x in text.split(",")]
    for width, block in itertools.product(ints(a.widths), ints(a.blocks)):
        ssm.D_BLOCKS = (width,)
        jax.clear_caches()  # the kernels' jitted entries read that

        def kernel(*x):
            return ssm.selective_scan_kernel(*x, block=block,
                                             interpret=interpret)

        fwd, grad = jax.jit(kernel), both(kernel)
        try:
            y, g = fwd(*args), grad(*args)
        except Exception as e:  # Mosaic's refusal is the row
            emit(what="kernel", block=block, width=width,
                 refused=str(e)[:400])
            wrong += 1
            continue
        emit(what="kernel", block=block, width=width,
             fwd_ms=timed(fwd, args), fwd_bwd_ms=timed(grad, args),
             against_loop=far(y, g, want_y, want_g),
             against_oracle=far(y, g))
        wrong += not max(far(y, g)) <= 1e-3
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
