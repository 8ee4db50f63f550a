"""The long causal attention core alone, at the shape two cells run
(OLMoE's and Ouro's: 16 heads of 128 at 4,096 tokens, bf16), forward +
backward, every candidate kernel form in one process.

    python tools/attn_core_kernel.py [--batches 1,4] [--seq 4096]
    JAX_PLATFORMS=cpu python tools/attn_core_kernel.py --aot

On the chip (run through the chip tool), per batch size: jax's
three-kernel pallas ``flash_attention`` at 1,024-wide blocks (what
``ops/attention.py attention_core`` ran at this shape until PR 43; the
call lives on here, as the row the others are read against), jax's
splash kernels at each ``FORMS`` row (query block, key block, key
compute block, fused or separate backward kernels), and ``attention_core``
itself (the row ``shipped``: whatever the program takes at this shape).
Each row: forward and forward + backward milliseconds, the compiled
forward + backward's temporaries, and at the first batch size the
relative distance of the output and the three gradients from an f32
(``highest``) XLA formulation on the same bf16 inputs.  The blocks
``attention_core`` reads off S were set from this table (PERF.md section
6, PR 43).  One JSON line a row, each naming its device, also appended to
``chiprun_out/attn_core_kernel.jsonl``; exit code 1 if a form was
refused or lies further than 2e-2 from the reference; without a chip it
exits 2 and prints no number.

``--aot`` compiles every form's forward + backward for a described v5e,
no chip needed (the on-chip-measurement guide, section 2): Mosaic
refuses here what it would refuse there.  A compile that passes is not
a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HEADS, WIDTH = 16, 128
# splash forms: (query block, key block, key compute block, fused backward)
FORMS = [(1024, 1024, 1024, True), (512, 512, 512, True),
         (1024, 512, 512, True), (512, 1024, 512, True),
         (512, 1024, 1024, True), (1024, 2048, 512, True),
         (1024, 1024, 1024, False), (512, 512, 512, False)]
# refused by Mosaic under vmap over 4 sequences (its scratch takes the
# batch's 4 too: out of VMEM), so not candidates: (1024, 2048, 1024) and
# (2048, 1024, 1024); `--aot` found that without a chip


def flash(q, k, v):
    """jax's pallas flash kernels (forward, dkv, dq) as ``attention_core``
    called them until PR 43."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    b = 1024
    sizes = fa.BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
        block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)
    return fa.flash_attention(q, k, v, causal=True,
                              sm_scale=q.shape[-1] ** -0.5, block_sizes=sizes)


def splash(bq, bkv, bkvc, fused):
    """jax's splash kernels over equal head counts at these blocks, q
    scaled before the kernel as ``_splash_causal`` scales it."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    def core(q, k, v):
        H, S, D = q.shape[1:]
        sizes = sk.BlockSizes(
            block_q=bq, block_kv=bkv, block_kv_compute=bkvc,
            block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkvc,
            block_q_dq=None if fused else bq,
            block_kv_dq=None if fused else bkv, use_fused_bwd_kernel=fused)
        kernel = sk.make_splash_mha_single_device(
            sm.MultiHeadMask([sm.CausalMask((S, S))] * H), block_sizes=sizes)
        return jax.vmap(kernel)((q * D ** -0.5).astype(q.dtype), k, v)

    return core


def shipped(q, k, v):
    from sparknet_tpu.ops.attention import attention_core
    return attention_core(q, k, v, True)


def reference(q, k, v):
    """The scores materialized in f32 at the highest matmul precision."""
    import jax
    import jax.numpy as jnp
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") * q.shape[-1] ** -0.5
    S = s.shape[-1]
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def both(core):
    """forward + backward of ``core``: the output's cotangent is a fixed
    draw, all three gradients come back."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v, do):
        return jnp.sum(core(q, k, v).astype(jnp.float32) * do)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def rows():
    """(name, blocks, core) of every form, the flash row first."""
    out = [("flash", [1024, 1024, 1024], flash)]
    out += [("splash" + ("" if fused else "_unfused"), [bq, bkv, bkvc],
             splash(bq, bkv, bkvc, fused)) for bq, bkv, bkvc, fused in FORMS]
    return out + [("shipped", None, shipped)]


def aot(seq, batches) -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tools.expert_copies import lowering_for_tpu, v5e_chip

    device, refused = v5e_chip(), 0
    chip = SingleDeviceSharding(device)
    for batch in batches:
        arg = lambda dt: jax.ShapeDtypeStruct(
            (batch, HEADS, seq, WIDTH), dt, sharding=chip)
        args = [arg(jnp.bfloat16)] * 3 + [arg(jnp.float32)]
        for name, blocks, core in rows():
            t = time.perf_counter()
            row = {"compiled": name, "blocks": blocks, "batch": batch,
                   "for": device.device_kind}
            try:
                with lowering_for_tpu():
                    stats = both(core).lower(*args).compile().memory_analysis()
                row.update(temp_bytes=stats.temp_size_in_bytes)
            except Exception as e:  # Mosaic's refusal is the row
                row.update(refused=str(e)[:300])
                refused += 1
            row.update(seconds=round(time.perf_counter() - t, 1))
            print(json.dumps(row), flush=True)
    return 1 if refused else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batches", default="1,4")
    ap.add_argument("--aot", action="store_true")
    a = ap.parse_args()
    batches = [int(x) for x in a.batches.split(",")]
    if a.aot:
        return aot(a.seq, batches)

    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import require_chip
    from tools.scan_kernel import rel, timed

    stamp = require_chip("attn_core_kernel")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "attn_core_kernel.jsonl"), "a")

    def emit(**row):
        row.update(seq=a.seq, heads=HEADS, width=WIDTH,
                   platform=stamp["platform"],
                   device_kind=stamp["device_kind"])
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    wrong = 0
    for batch in batches:
        ks = jax.random.split(jax.random.key(batch), 4)
        shape = (batch, HEADS, a.seq, WIDTH)
        args = [jax.random.normal(key, shape, jnp.bfloat16) for key in ks[:3]]
        do = jax.random.normal(ks[3], shape, jnp.float32)
        want = None
        if batch == batches[0]:
            want = (jax.jit(reference)(*args),) + both(reference)(*args, do)
            want = jax.block_until_ready(want)
        for name, blocks, core in rows():
            fwd, grad = jax.jit(core), both(core)
            try:
                got = (fwd(*args),) + grad(*args, do)
                temp = grad.lower(*args, do).compile(
                ).memory_analysis().temp_size_in_bytes
            except Exception as e:  # Mosaic's refusal is the row
                emit(what=name, blocks=blocks, batch=batch,
                     refused=str(e)[:400])
                wrong += 1
                continue
            row = dict(what=name, blocks=blocks, batch=batch,
                       fwd_ms=timed(fwd, args),
                       fwd_bwd_ms=timed(grad, (*args, do)), temp_bytes=temp)
            if want is not None:
                row["against_f32"] = [float(f"{rel(g, w):.3g}")
                                      for g, w in zip(got, want)]
                wrong += not max(row["against_f32"]) <= 2e-2
            emit(**row)
            del got
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
