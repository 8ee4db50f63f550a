"""One share-holding MoE layer alone (``ops/moe.py``), at the JoyAI cell's
shapes: 4,096 tokens x 8 slots, D 2,048, experts of 768, 8 of 256 held.

    python tools/moe_dispatch.py [--reps 20] [--factors 4,8]
    JAX_PLATFORMS=cpu python tools/moe_dispatch.py --rehearse

On the chip (run through the chip tool): forward + backward milliseconds
(gradients to ``x``, the combine weights and the expert matrices) of the
path over ALL T·k sorted rows (``_all_rows``: what the layer did before
PR 35, and still does above its capacity) against the path at the
capacity (``_capacity_rows``), under routings that put 0, 1, 2, 3 or all
8 LUMPS of 4,096 pairs on the held experts (near initialisation a layer
sends every token of one sequence to the same 8 outputs) and under a
level one (each token 8 of 256 at random: 1,024 held pairs).  Two sizes
of row: ``ends`` times the dispatch and the combine with an elementwise
stand-in for the experts, ``layer`` the layer with its grouped matmuls.
At the capacity the rows of one token are added in one of three ways, so
the choice is on record with its times: ``scatter`` (an XLA scatter-add
of the capacity's rows in f32: what ships), ``sorted`` (the rows sorted by
token first, the scatter told so), ``onehot`` (a [T, capacity] one-hot
product on the MXU, the f32 rows in three bf16 parts so that no product
is rounded where it was not).  ``shipped`` is ``_held_rows``: the
``lax.cond`` between the two paths under its ``custom_vjp``, whose
backward runs the taken path again.  Every row also gives its distance
from ``_all_rows`` (y and the gradients, relative L2; bf16 inputs).  One
JSON line a row, each naming its device, also appended to
``chiprun_out/moe_dispatch.jsonl``; exit code 1 if a path lies further
than 2e-2 from ``_all_rows``; without a chip it exits 2 and prints no
number.  ``--rehearse`` walks the same code at a tiny size on the CPU and
prints no time.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = dict(tokens=4096, top_k=8, hidden=2048, expert_dim=768, experts=256,
            held=8)
TINY = dict(tokens=512, top_k=4, hidden=128, expert_dim=128, experts=128,
            held=4)
FAR = 2e-2


def routing(kind: str, size: dict, rng):
    """experts [T, k] int32.  ``level``: k of E at random per token;
    ``lumps<m>``: every token the same k outputs, m of them held."""
    import numpy as np
    t, k, e = size["tokens"], size["top_k"], size["experts"]
    if kind == "level":
        return np.argsort(rng.random((t, e)), axis=1)[:, :k].astype(np.int32)
    m = int(kind.removeprefix("lumps"))
    chosen = list(range(m)) + list(range(e // 2, e // 2 + k - m))
    return np.tile(np.asarray(chosen, np.int32), (t, 1))


def onehot_add(v, idx, rows):
    """``moe._add_rows`` as a one-hot product: [rows, C] x [C, D] on the
    MXU, ``v`` (f32) in three bf16 parts, each product summed in f32."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def add(v):
        hot = (idx[None, :] == jnp.arange(rows, dtype=idx.dtype)[:, None]
               ).astype(jnp.bfloat16)
        total, rest = 0.0, v
        for _ in range(3):
            part = rest.astype(jnp.bfloat16)
            total = total + jnp.dot(hot, part,
                                    preferred_element_type=jnp.float32)
            rest = rest - part.astype(jnp.float32)
        return total

    add.defvjp(lambda v: (add(v), None), lambda _, g: (g[idx],))
    return add(v)


def sorted_add(v, idx, rows):
    """``moe._add_rows`` with the rows sorted by token first."""
    import jax.numpy as jnp
    by_token = jnp.argsort(idx)
    return jnp.zeros((rows, v.shape[1]), jnp.float32).at[idx[by_token]].add(
        v[by_token], indices_are_sorted=True)


def stand_in(rows, rest, group_sizes, flat, order, live, expert_act):
    """An elementwise "expert": the ends of the layer without its middle."""
    import jax.numpy as jnp
    return jnp.where(live, rows * rest[0], 0)


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
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--factors", default="",
                    help="capacities to time, as multiples of the level "
                    "share (default: ops/moe.py's CAPACITY_FACTOR alone)")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparknet_tpu.common import require_chip
    from sparknet_tpu.ops import moe

    if a.rehearse:
        size, stamp = TINY, {"platform": jax.default_backend(),
                             "device_kind": "rehearsal"}
    else:
        size, stamp = CELL, require_chip("moe_dispatch")
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "moe_dispatch.jsonl"), "a")

    def emit(**row):
        row.update(platform=stamp["platform"],
                   device_kind=stamp["device_kind"])
        print(json.dumps(row), flush=True)
        if not a.rehearse:
            sink.write(json.dumps(row) + "\n")
            sink.flush()

    t, k, d, h, e, n = (size[key] for key in (
        "tokens", "top_k", "hidden", "expert_dim", "experts", "held"))
    rng = np.random.default_rng(0)
    bf16 = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                      jnp.bfloat16)
    x, dy = bf16(t, d), bf16(t, d)
    weights = jnp.asarray(rng.random((t, k)), jnp.float32)
    middles = {
        "ends": (stand_in, (bf16(1, d),)),
        "layer": (moe._experts, tuple(
            0.03 * bf16(*s) for s in ((n, h, d), (n, h, d), (n, d, h)))),
    }
    adds = {"scatter": moe._add_rows, "sorted": sorted_add,
            "onehot": onehot_add}
    sort = jax.jit(functools.partial(
        moe.sort_pairs, num_experts=e, held_n=n, first_expert=0))
    wrong = 0
    factors = [int(f) for f in a.factors.split(",") if f] or [
        moe.CAPACITY_FACTOR]
    for factor, kind in itertools.product(factors, (
            "level", "lumps0", "lumps1", "lumps2", "lumps3",
            f"lumps{min(k, n)}")):
        moe.CAPACITY_FACTOR = factor
        cap = moe.capacity(t * k, n, e)
        flat, order, group_sizes, _ = sort(jnp.asarray(routing(kind, size, rng)))
        held_pairs = int(jnp.sum(group_sizes))
        fits = bool(moe.takes_compact(held_pairs, cap))
        for what, (middle, rest) in middles.items():
            moe._experts = middle

            def both(path):
                def loss(x, weights, rest):
                    y = path(x, weights, rest, flat, order, group_sizes)
                    return jnp.sum(y.astype(jnp.float32) * dy), y
                return jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True))

            full = both(functools.partial(
                moe._all_rows, live=True, expert_act="swiglu"))
            want = full(x, weights, rest)
            row = dict(what=what, routing=kind, held_pairs=held_pairs,
                       capacity=cap, fits=fits)
            emit(path="all_rows", **row, **(
                {} if a.rehearse else
                {"fwd_bwd_ms": timed(full, (x, weights, rest), a.reps)}))
            paths = {"shipped": functools.partial(
                moe._held_rows, cap=cap, expert_act="swiglu")}
            if fits:
                paths.update({f"capacity/{name}": functools.partial(
                    moe._capacity_rows, cap=cap, expert_act="swiglu")
                    for name in adds})
            for name, path in paths.items():
                moe._add_rows = adds.get(name.partition("/")[2],
                                         adds["scatter"])
                jax.clear_caches()  # the paths read moe._add_rows as traced
                fn = both(path)
                got = fn(x, weights, rest)
                far = max(rel(g, w) for g, w in zip(
                    jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)))
                wrong += not far <= FAR
                emit(path=name, **row, from_all_rows=float(f"{far:.3g}"), **(
                    {} if a.rehearse else
                    {"fwd_bwd_ms": timed(fn, (x, weights, rest), a.reps)}))
            moe._add_rows = adds["scatter"]
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
