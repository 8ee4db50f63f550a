"""One share-holding MoE layer alone (``ops/moe.py``), at the shapes of the
two cells that run one: ``--cell joyai`` (4,096 tokens x 8 slots, D 2,048,
experts of 768, 8 of 256 held) or ``--cell qwen3next`` (4,096 x 10 slots,
D 2,048, experts of 512, 32 of 512 held).

    python tools/moe_dispatch.py [--cell joyai|qwen3next] [--reps 20]
    JAX_PLATFORMS=cpu python tools/moe_dispatch.py --rehearse
    JAX_PLATFORMS=cpu python tools/moe_dispatch.py --aot [--cell ...]

On the chip (run through the chip tool): forward + backward milliseconds
(gradients to ``x``, the combine weights and the expert matrices) of the
layer's row movement under routings that put 0, the LEVEL share (each
token k of E at random), 4 x the level share, 16,384 and all T·k of the
(token, slot) pairs on the held experts: the layer from its sorted pairs
on, with its grouped matmuls (router, sort and shared expert, which do
not depend on the rows, are not in it).  The paths:

``all_rows``    the path over ALL T·k sorted rows with its masks: what a
                share-holding layer did before PR 35 and, over its
                capacity, until PR 49 (kept HERE, as the yardstick and
                the reference the others are held to);
``live/loop``   what ships (``ops/moe.py _held_rows``): ``gather_live``,
                ``combine_live`` and what lies between them, each one
                ``lax.fori_loop`` over the live tiles of 512 rows, the
                trip count read on the device, the [R, ·] arrays over
                all R = T·k rows and never initialised; operands are the
                only residuals, so the backward runs the forward again
                (every ``live/*`` row shares that rule);
``live/cap``    the same walk in arrays of the capacity PR 35 gave the
                layer (6 x the level share in whole tiles), where the
                held pairs fit it: what keeping the ``lax.cond`` and the
                constant would have bought;
``live/zeros``  ``live/loop`` with its buffers written as zeros first
                (what ``lax.empty`` saves);
``live/kernel`` the forward's two movers as ONE Pallas kernel each (the
                indices and the live count scalar-prefetched, ``x`` / y
                whole in VMEM as f32, a row a dynamic load / add, dead
                tiles skipped by ``pl.when``), the cotangents as
                ``live/loop``.

Every row also gives its distance from ``all_rows`` (y and the
gradients, relative L2; bf16 inputs).  One JSON line a row, each naming
its device, also appended to ``chiprun_out/moe_dispatch.jsonl``; exit
code 1 if a path lies further than 2e-2 from ``all_rows``; without a chip
it exits 2 and prints no number.  ``--rehearse`` walks the same code at a
tiny size on the CPU (the kernel in Pallas's interpreter) and prints no
time; ``--aot`` compiles every path at the cell's shapes for a described
v5e here and prints what the compiler says of each (no time either).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELLS = {
    "joyai": dict(tokens=4096, top_k=8, hidden=2048, expert_dim=768,
                  experts=256, held=8),
    "qwen3next": dict(tokens=4096, top_k=10, hidden=2048, expert_dim=512,
                      experts=512, held=32),
}
TINY = dict(tokens=512, top_k=4, hidden=128, expert_dim=128, experts=128,
            held=4)
FAR = 2e-2
PR35_FACTOR = 6  # the capacity PR 35 read off the JoyAI cell's fences


def routing(held_pairs, size: dict, rng):
    """experts [T, k] int32 with exactly ``held_pairs`` (token, slot)
    pairs on the held experts [0, held), spread over them and over the
    tokens; None: the level routing, each token k of E at random."""
    import numpy as np
    t, k, e, n = (size[key] for key in ("tokens", "top_k", "experts", "held"))
    if held_pairs is None:
        return np.argsort(rng.random((t, e)), axis=1)[:, :k].astype(np.int32)
    per_token = held_pairs // t + (np.arange(t) < held_pairs % t)  # [T]
    slot = np.arange(k)[None, :]
    held = (np.arange(t)[:, None] + slot) % n  # distinct: k <= n or few
    other = n + (np.arange(t)[:, None] * 7 + slot) % (e - n)
    return np.where(slot < per_token[:, None], held, other).astype(np.int32)


def all_rows(moe, x, weights, rest, flat, order, group_sizes):
    """The share's path over ALL T·k sorted rows (``ops/moe.py`` before
    PR 49: ``_all_rows(live=True)``): spread, mask, compute, un-sort."""
    import jax.numpy as jnp
    tokens, top_k = weights.shape
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    live = (jnp.arange(order.shape[0], dtype=jnp.int32)
            < jnp.sum(group_sizes))[:, None]
    rows = jnp.where(live, moe._spread_rows(x, order, inv), 0)
    out = jnp.where(live, moe._experts(
        rows, rest, group_sizes, moe._EveryRow(flat, order), "swiglu"), 0)
    per_pair = moe._take_rows(out, inv, order).reshape(tokens, top_k, -1)
    return jnp.sum(per_pair.astype(jnp.float32) * weights[..., None],
                   axis=1).astype(x.dtype)


def kernel_gather(x, token, n_live, *, interpret=False):
    """``moe.gather_live``'s forward as one Pallas kernel: grid over the
    tiles of 512 sorted rows, ``n_live`` and ``token`` scalar-prefetched,
    ``x`` whole in VMEM as f32 (Mosaic slices single rows of 32-bit
    arrays only, and refuses a one-row DMA outright: "Slice shape along
    dimension 0 must be aligned to tiling (8), but is 1", compiled for a
    described v5e, PR 49), every row of a live tile one dynamic row load
    and store, the last live tile masked; a dead tile does nothing and
    its block index is the last live tile's, so nothing is written for
    it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = 512
    rows, width = token.shape[0], x.shape[1]

    def kernel(n_ref, token_ref, x_ref, o_ref, tile_ref):
        i, n = pl.program_id(0), n_ref[0]

        @pl.when(i * tile < n)
        def _():
            def move(r, c):
                src = token_ref[i * tile + r]
                tile_ref[pl.ds(r, 1), :] = x_ref[pl.ds(src, 1), :]
                return c

            jax.lax.fori_loop(0, tile, move, 0)
            row = i * tile + jax.lax.broadcasted_iota(
                jnp.int32, o_ref.shape, 0)
            o_ref[...] = jnp.where(row < n, tile_ref[...], 0).astype(
                o_ref.dtype)

    last = lambda n: jnp.maximum((n[0] + tile - 1) // tile - 1, 0)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows // tile,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(
                (tile, width), lambda i, n, tok: (jnp.minimum(i, last(n)), 0)),
            scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret, name="moe_gather_live",
    )(jnp.reshape(n_live, (1,)).astype(jnp.int32), token,
      x.astype(jnp.float32))


def kernel_combine(out, weights, order, n_live, *, interpret=False):
    """``moe.combine_live``'s forward as one Pallas kernel: y [T, D] f32
    whole in VMEM, the tiles of ``out`` streamed in (a dead tile's block
    index is the last live tile's: nothing is fetched for it), the pairs
    and their weights scalar-prefetched, every live row one dynamic
    read-modify-write of its token's row."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile = 512
    (tokens, top_k), (rows, width) = weights.shape, out.shape

    def kernel(n_ref, token_ref, w_ref, out_ref, y_ref, tile_ref):
        i, n = pl.program_id(0), n_ref[0]

        @pl.when(i == 0)
        def _():
            y_ref[...] = jnp.zeros_like(y_ref)

        @pl.when(i * tile < n)
        def _():
            tile_ref[...] = out_ref[...].astype(jnp.float32)

            def add(r, c):
                dst = token_ref[i * tile + r]
                y_ref[pl.ds(dst, 1), :] += (
                    tile_ref[pl.ds(r, 1), :] * w_ref[i * tile + r])
                return c

            jax.lax.fori_loop(0, jnp.minimum(tile, n - i * tile), add, 0)

    last = lambda n: jnp.maximum((n[0] + tile - 1) // tile - 1, 0)
    y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows // tile,),
            in_specs=[pl.BlockSpec(
                (tile, width),
                lambda i, n, tok, w: (jnp.minimum(i, last(n)), 0))],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret, name="moe_combine_live",
    )(jnp.reshape(n_live, (1,)).astype(jnp.int32), order // top_k,
      weights.reshape(-1)[order], out)
    return y.astype(out.dtype)


def timed(fn, args, reps):
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", choices=sorted(CELLS), default="joyai")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--paths", default="",
                    help="the paths to run beside all_rows (default: all)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--aot", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.common import require_chip
    from sparknet_tpu.ops import moe

    if a.rehearse:
        size, stamp = TINY, {"platform": jax.default_backend(),
                             "device_kind": "rehearsal"}
    elif a.aot:
        size, stamp = CELLS[a.cell], {"platform": "described",
                                      "device_kind": "v5e, compiled only"}
    else:
        size, stamp = CELLS[a.cell], require_chip("moe_dispatch")
    measured = not (a.rehearse or a.aot)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "moe_dispatch.jsonl"), "a")

    def emit(**row):
        row.update(cell="tiny" if a.rehearse else a.cell,
                   platform=stamp["platform"],
                   device_kind=stamp["device_kind"])
        print(json.dumps(row), flush=True)
        if measured:
            sink.write(json.dumps(row) + "\n")
            sink.flush()

    t, k, d, h, e, n = (size[key] for key in (
        "tokens", "top_k", "hidden", "expert_dim", "experts", "held"))
    rng = np.random.default_rng(0)
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.bfloat16)
    x, dy = bf16(t, d), bf16(t, d)
    weights = jnp.asarray(rng.random((t, k)), jnp.float32)
    rest = tuple(0.03 * bf16(*s) for s in ((n, h, d), (n, h, d), (n, d, h)))
    sort = jax.jit(functools.partial(
        moe.sort_pairs, num_experts=e, held_n=n, first_expert=0))
    level = t * k * n // e
    cap = -(-PR35_FACTOR * level // moe.CAPACITY_TILE) * moe.CAPACITY_TILE
    targets = list(dict.fromkeys(
        [0, None, 4 * level, min(16384, t * k), t * k]))
    real = dict(buffer=moe._buffer, take=moe._take_live, add=moe._add_live)
    wrong = 0

    def recomputed(path):
        """``path`` under ``_held_rows``'s rule: operands are the only
        residuals, the backward runs the forward again."""
        @jax.custom_vjp
        def f(x, weights, rest):
            return path(x, weights, rest)

        f.defvjp(lambda *ops: (path(*ops), ops),
                 lambda ops, dy: jax.vjp(path, *ops)[1](dy))
        return f

    def compiled_for_v5e(fn, args):
        """What the chip's compiler says of ``fn`` (nothing runs)."""
        from jax.sharding import SingleDeviceSharding
        from tools.expert_copies import lowering_for_tpu, v5e_chip
        one = SingleDeviceSharding(v5e_chip())
        shapes = jax.tree_util.tree_map(lambda v: jax.ShapeDtypeStruct(
            v.shape, v.dtype, sharding=one), args)
        with lowering_for_tpu():
            m = jax.jit(fn).lower(*shapes).compile().memory_analysis()
        return {"temp_mb": round(m.temp_size_in_bytes / 1e6, 1)}

    def both(path):
        def loss(x, weights, rest):
            y = path(x, weights, rest)
            return jnp.sum(y.astype(jnp.float32) * dy), y
        return jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))

    args = (x, weights, rest)
    for target in targets:
        flat, order, group_sizes, _ = sort(
            jnp.asarray(routing(target, size, rng)))
        held_pairs = int(jnp.sum(group_sizes))
        walk = lambda order: recomputed(lambda x, w, rest: moe._live_rows(
            x, w, rest, flat, order, group_sizes, expert_act="swiglu"))
        paths = {
            "live/loop": (lambda x, w, rest: moe._held_rows(
                x, w, rest, flat, order, group_sizes, "swiglu"), {}),
            "live/zeros": (walk(order), {"_buffer": jnp.zeros}),
            "live/kernel": (walk(order), {
                "_take_live": lambda x, token, n_live: (
                    kernel_gather(x, token, n_live, interpret=a.rehearse)
                    if x.ndim == 2 else real["take"](x, token, n_live)),
                "_add_live": functools.partial(
                    kernel_combine, interpret=a.rehearse)}),
        }
        if held_pairs <= cap < t * k:
            paths["live/cap"] = (walk(order[:cap]), {})
        if a.paths:
            paths = {p: paths[p] for p in a.paths.split(",") if p in paths}
        row = dict(held_pairs=held_pairs,
                   routing="level" if target is None else "exact",
                   rows_moved=moe.CAPACITY_TILE * int(
                       moe.live_tiles(held_pairs)))
        full = both(lambda x, w, rest: all_rows(
            moe, x, w, rest, flat, order, group_sizes))
        if a.aot:
            emit(path="all_rows", **row, **compiled_for_v5e(full, args))
        else:
            want = full(*args)
            emit(path="all_rows", **row, **(
                {"fwd_bwd_ms": timed(full, args, a.reps)}
                if measured else {}))
        for name, (path, patches) in paths.items():
            for attr, value in patches.items():
                setattr(moe, attr, value)
            jax.clear_caches()  # the paths read moe's names as traced
            fn = both(path)
            try:
                if a.aot:
                    emit(path=name, **row, **compiled_for_v5e(fn, args))
                    continue
                got = fn(*args)
                far = max(rel(g, w) for g, w in zip(
                    jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)))
                wrong += not far <= FAR
                emit(path=name, **row, from_all_rows=float(f"{far:.3g}"), **(
                    {"fwd_bwd_ms": timed(fn, args, a.reps)}
                    if measured else {}))
            except Exception as err:  # a form the compiler refuses
                if name != "live/kernel":
                    raise
                emit(path=name, **row, refused=str(err)[:400])
            finally:
                moe._buffer, moe._take_live, moe._add_live = (
                    real["buffer"], real["take"], real["add"])
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
