"""The long causal attention core alone, forward + backward, bf16, every
candidate kernel form in one process: by default at the shape two cells
run (OLMoE's and Ouro's: 16 heads of 128 at 4,096 tokens), and at any
other by its options: grouped heads, keys and values of widths of their
own, a window.

    python tools/attn_core_kernel.py [--batches 1,4] [--seq 4096]
    python tools/attn_core_kernel.py --batches 1 --seq 8192 --heads 64 \
        --kv-heads 8 --window 512          # Laguna's sliding layers
    python tools/attn_core_kernel.py --batches 1 --seq 2048 --heads 40 \
        --kv-heads 20 --v-heads 10 --width 64 --v-width 128 --window 512
                                           # the hybrid's windowed layer
    JAX_PLATFORMS=cpu python tools/attn_core_kernel.py --aot [the same]

On the chip (run through the chip tool), per batch size: jax's
three-kernel pallas ``flash_attention`` at 1,024-wide blocks (what
``ops/attention.py attention_core`` ran at the default shape until PR 43;
the call lives on here, as the row the others are read against; it takes
equal heads and widths and no window, so only there), jax's splash
kernels at each row of ``FORMS`` (without a window) or ``WINDOW_FORMS``
(under one): the forward's (query block, key block, key compute block),
the dkv kernel's, and the dq kernel's (query block, key block), or None
where ONE fused backward kernel gives dq, dk and dv; and
``attention_core`` itself (the row ``shipped``: whatever the program
takes at this shape).  Each row: forward and forward + backward
milliseconds, the compiled forward + backward's temporaries, and at the
first batch size the relative distance of the output and the three
gradients from an f32 (``highest``) XLA formulation on the same bf16
inputs, a head at a time.  The blocks and the backward's form
``attention_core`` reads off S and the window were set from these tables
(PERF.md section 6, PRs 43 and 51).  One JSON line a row, each naming its
device, also appended to ``chiprun_out/attn_core_kernel.jsonl``; exit
code 1 if a form was refused or lies further than 2e-2 from the
reference; without a chip it exits 2 and prints no number.

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

# splash forms without a window: the forward's (query block, key block, key
# compute block), the dkv kernel's, and the dq kernel's (query block, key
# block), None where one fused kernel gives all three gradients
FORMS = [((1024, 1024, 1024), (1024, 1024, 1024), None),
         ((512, 512, 512), (512, 512, 512), None),
         ((1024, 512, 512), (1024, 512, 512), None),
         ((512, 1024, 512), (512, 1024, 512), None),
         ((512, 1024, 1024), (512, 1024, 1024), None),
         ((1024, 2048, 512), (1024, 2048, 512), None),
         ((1024, 1024, 1024), (1024, 1024, 1024), (1024, 1024)),
         ((512, 512, 512), (512, 512, 512), (512, 512))]
# refused by Mosaic under vmap over 4 sequences (its scratch takes the
# batch's 4 too: out of VMEM), so not candidates: (1024, 2048, 1024) and
# (2048, 1024, 1024); `--aot` found that without a chip

# under a window: the fused backward's grid is not shrunk to the mask and
# its dq is one q-sized partial a key block; the separate kernels' grids
# are.  A block of width b visits (window / b + 1) b keys a query: 2 x the
# mask's pairs at 512 under a window of 512, 1.5 x at 256, 1.25 x at 128
WINDOW_FORMS = [((512, 512, 512), (512, 512, 512), None),
                ((1024, 1024, 1024), (1024, 1024, 1024), None),
                ((512, 512, 512), (512, 512, 512), (512, 512)),
                ((512, 512, 512), (512, 512, 512), (256, 256)),
                ((512, 512, 512), (256, 256, 256), (512, 512)),
                ((256, 256, 256), (256, 256, 256), (256, 256)),
                ((256, 256, 256), (256, 256, 256), (256, 512)),
                ((128, 128, 128), (128, 128, 128), (128, 128)),
                ((256, 256, 256), (128, 128, 128), (128, 128))]


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


def splash(fwd, dkv, dq, window):
    """jax's splash kernels at these blocks through the program's own
    call (``ops/attention.py _splash``: q scaled before the kernel, the
    grouped form where key heads are fewer)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk)

    from sparknet_tpu.ops.attention import _splash

    sizes = sk.BlockSizes(
        block_q=fwd[0], block_kv=fwd[1], block_kv_compute=fwd[2],
        block_q_dkv=dkv[0], block_kv_dkv=dkv[1], block_kv_dkv_compute=dkv[2],
        block_q_dq=dq and dq[0], block_kv_dq=dq and dq[1],
        use_fused_bwd_kernel=dq is None)
    return lambda q, k, v: _splash(q, k, v, sizes, window)


# the repo's own banded kernels, forward and backward
# (``ops/band_attention.py``): (query and key block, rows of a query
# sub-block)
BAND_FORMS = [(512, 128), (512, 256), (512, 512), (256, 128), (256, 256),
              (1024, 128), (1024, 256)]


def band(block, sub, window):
    import jax.numpy as jnp

    from sparknet_tpu.ops.band_attention import band_core

    def core(q, k, v):
        B, H, S, D = q.shape
        Hk = k.shape[1]
        q = (q * D ** -0.5).astype(q.dtype).reshape(B, Hk, H // Hk, S, D)
        v = jnp.repeat(v, Hk // v.shape[1], axis=1)
        o = band_core(q, k, v, block, window, False, sub)
        return o.reshape(B, H, S, -1)

    return core


def shipped(window):
    from sparknet_tpu.ops.attention import attention_core
    return lambda q, k, v: attention_core(q, k, v, True, window)


def reference(window):
    """The scores materialized in f32 at the highest matmul precision, one
    query head's [S, S] at a time (64 heads at 8,192 tokens would be
    17 GB at once), recomputed in the backward."""
    import jax
    import jax.numpy as jnp

    def core(q, k, v):
        B, H, S, D = q.shape
        q, k, v = (x.astype(jnp.float32).reshape((-1,) + x.shape[2:])
                   for x in (q, k, v))
        per_k, per_v = H // (k.shape[0] // B), H // (v.shape[0] // B)
        ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        seen = (ahead >= 0) & (ahead < window) if window else ahead >= 0

        @jax.checkpoint
        def head(i):
            s = jnp.einsum("qd,kd->qk", q[i], k[i // per_k],
                           precision="highest") * D ** -0.5
            s = jnp.where(seen, s, -1e30)
            return jnp.einsum("qk,kd->qd", jax.nn.softmax(s, axis=-1),
                              v[i // per_v], precision="highest")

        o = jax.lax.map(head, jnp.arange(B * H))
        return o.reshape(B, H, S, -1)

    return core


def both(core):
    """forward + backward of ``core``: the output's cotangent is a fixed
    draw, all three gradients come back."""
    import jax
    import jax.numpy as jnp

    def loss(q, k, v, do):
        return jnp.sum(core(q, k, v).astype(jnp.float32) * do)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def rows(a):
    """(name, blocks, core) of every form, the flash row first where jax's
    flash kernels take the shape."""
    plain = (a.kv_heads == a.v_heads == a.heads and a.width == a.v_width
             and not a.window)
    out = [("flash", [[1024, 1024, 1024]], flash)] if plain else []
    out += [("splash" + ("" if dq is None else "_unfused"),
             [list(fwd), list(dkv), dq and list(dq)],
             splash(fwd, dkv, dq, a.window))
            for fwd, dkv, dq in (WINDOW_FORMS if a.window else FORMS)]
    if a.window:
        out += [("band", [[b, b, b], [b, sub]], band(b, sub, a.window))
                for b, sub in BAND_FORMS if a.seq % b == 0]
    return out + [("shipped", None, shipped(a.window))]


def shapes(a, batch):
    """q, k, v and the output's cotangent: [B, heads, S, width] each."""
    return [(batch, a.heads, a.seq, a.width),
            (batch, a.kv_heads, a.seq, a.width),
            (batch, a.v_heads, a.seq, a.v_width),
            (batch, a.heads, a.seq, a.v_width)]


def aot(a, batches) -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from tools.expert_copies import lowering_for_tpu, v5e_chip

    device, refused = v5e_chip(), 0
    chip = SingleDeviceSharding(device)
    for batch in batches:
        args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in
                zip(shapes(a, batch), [jnp.bfloat16] * 3 + [jnp.float32])]
        for name, blocks, core in rows(a):
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
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, help="default: --heads")
    ap.add_argument("--v-heads", type=int, help="default: --kv-heads")
    ap.add_argument("--width", type=int, default=128, help="of q and k")
    ap.add_argument("--v-width", type=int, help="default: --width")
    ap.add_argument("--window", type=int, default=0)
    ap.add_argument("--aot", action="store_true")
    a = ap.parse_args()
    a.kv_heads = a.kv_heads or a.heads
    a.v_heads = a.v_heads or a.kv_heads
    a.v_width = a.v_width or a.width
    batches = [int(x) for x in a.batches.split(",")]
    if a.aot:
        return aot(a, batches)

    import jax
    import jax.numpy as jnp

    from sparknet_tpu.common import require_chip
    from tools.scan_kernel import rel, timed

    stamp = require_chip("attn_core_kernel")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "attn_core_kernel.jsonl"), "a")

    def emit(**row):
        row.update(seq=a.seq, heads=[a.heads, a.kv_heads, a.v_heads],
                   width=[a.width, a.v_width], window=a.window,
                   platform=stamp["platform"],
                   device_kind=stamp["device_kind"])
        print(json.dumps(row), flush=True)
        sink.write(json.dumps(row) + "\n")
        sink.flush()

    wrong = 0
    for batch in batches:
        ks = jax.random.split(jax.random.key(batch), 4)
        *args, do = (jax.random.normal(key, s, dt) for key, s, dt in zip(
            ks, shapes(a, batch), [jnp.bfloat16] * 3 + [jnp.float32]))
        want = None
        if batch == batches[0]:
            ref = reference(a.window)
            want = (jax.jit(ref)(*args),) + both(ref)(*args, do)
            want = jax.block_until_ready(want)
        for name, blocks, core in rows(a):
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
