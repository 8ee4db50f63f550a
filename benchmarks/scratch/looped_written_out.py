"""A looped net WRITTEN OUT: the same layers ``count`` times over, with
the parameters shared by ``param { name }``: the form a net with a looped
region (``compiler/graph.py LoopRegion``) had to take before there was
one.  It is the ORACLE of ``tests/test_ouro.py`` and the other side of
the builder's timing on the chip (step time, compile time, set-up); the
program has one lowering and this file is not part of it.

    written_out(net)   the flat NetParameter of a net with ``loop``s

Pass 1 keeps the layers' own names, so it owns every blob and the
parameters of the two forms have the same names and shapes; pass t > 1 is
``<layer>@<t>`` and aliases them.  A collected top becomes a ``Concat``
along axis 0 of the blob's copies.

As a script, on the chip: one configuration's step built both ways and
timed through ``Solver.step`` on a fixed batch (no feed), with the
compile seconds of each:

    python benchmarks/scratch/looped_written_out.py \
        --config ouro-2.6b-l4-ut4-v8-bf16 [--steps 24] [--rehearse]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _blob_counts(net) -> dict[str, int]:
    """Learnable blobs per layer of ``net``, without building them."""
    import jax

    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network

    n = Network(net, Phase.TRAIN)
    shapes = jax.eval_shape(lambda k: n.init(k, None, None).params,
                            jax.random.key(0))
    return {name: len(blobs) for name, blobs in shapes.items()}


def written_out(net):
    """``net`` (a NetParameter Message with ``loop`` regions) as a flat
    net: every region's layers copied ``count`` times, parameters shared
    by name, collected tops concatenated."""
    from sparknet_tpu.proto.text_format import Message

    counts = _blob_counts(net)
    layers = net.get_all("layer")
    names = [l.get_str("name") for l in layers]
    out = Message().set("name", net.get_str("name") + "-written-out")
    at = 0
    for loop in net.get_all("loop"):
        first, last = names.index(loop.get_str("first")), names.index(
            loop.get_str("last"))
        for l in layers[at:first]:
            out.add("layer", l.copy())
        region = layers[first:last + 1]
        inner = {t for l in region for t in l.get_all("top")}
        cin, cout = loop.get_str("carry_in"), loop.get_str("carry_out")
        count = loop.get_int("count", 1)
        tag = lambda blob, t: blob if t == 1 else f"{blob}@{t}"
        for t in range(1, count + 1):
            def blob_at(b):
                if b in inner:
                    return tag(b, t)
                return tag(cout, t - 1) if b == cin and t > 1 else b

            for l in region:
                c = l.copy()
                name = l.get_str("name")
                c.set("name", tag(name, t))
                c.fields["bottom"] = [blob_at(b) for b in l.get_all("bottom")]
                c.fields["top"] = [tag(b, t) for b in l.get_all("top")]
                c.fields["param"] = [
                    Message().set("name", f"{name}.{i}")
                    for i in range(counts.get(name, 0))]
                out.add("layer", c)
        for col in loop.get_all("collect"):
            cat = Message().set("name", col.get_str("top")).set(
                "type", "Concat")
            for t in range(1, count + 1):
                cat.add("bottom", tag(col.get_str("blob"), t))
            cat.add("top", col.get_str("top"))
            cat.set("concat_param", Message().set("axis", 0))
            out.add("layer", cat)
        if count > 1:  # the name the layers behind the region read
            last_pass = Message().set("name", cout + "@last").set(
                "type", "Split")
            last_pass.add("bottom", tag(cout, count)).add("top", cout)
            out.add("layer", last_pass)
        at = last + 1
    for l in layers[at:]:
        out.add("layer", l.copy())
    return out


def _time_steps(solver, feeds, steps: int) -> dict:
    import jax

    from sparknet_tpu.obs.sentinel import get_sentinel

    sentinel = get_sentinel().install()
    before = dict(sentinel.thread_seconds())
    t0 = time.perf_counter()
    solver.step(1, lambda it: feeds)
    first_s = time.perf_counter() - t0
    solver.step(3, lambda it: feeds)
    t0 = time.perf_counter()
    solver.step(steps, lambda it: feeds)
    wall = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}
    compile_s = {k: round(v - before.get(k, 0.0), 2)
                 for k, v in sentinel.thread_seconds().items()}
    return {"first_step_s": round(first_s, 2),
            "step_ms": round(1000 * wall / steps, 3),
            "compile_s_by_event": compile_s,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="ouro-2.6b-l4-ut4-v8-bf16")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--forms", default="written,region",
                    help="written: this file's flat net; region: the "
                         "program's own lowering of the loop")
    ap.add_argument("--seq-len", type=int, default=0,
                    help="another length than the configuration's")
    ap.add_argument("--rehearse", action="store_true",
                    help="the configuration's tiny preset, on the CPU")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from benchmarks.jobs.lm_decoder_solo import zoo_net
    from sparknet_tpu import models
    from sparknet_tpu.common import set_config
    from sparknet_tpu.solvers.solver import Solver

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    if args.rehearse:
        config = {**config, **config["rehearse_preset"]}
    else:
        set_config(compute_dtype=jnp.bfloat16)  # the recipe's --dtype bf16
    if args.seq_len:
        config = {**config, "seq_len": args.seq_len}
    solver_cfg = getattr(models, config["zoo"] + "_solver")()
    solver_cfg = dataclasses.replace(solver_cfg, display=0)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config["vocab_rows"],
                       (config["sequences_per_step"], config["seq_len"] + 1))
    feeds = {"data": jnp.asarray(ids[:, :-1], jnp.int32),
             "label": jnp.asarray(ids[:, 1:], jnp.int32)}
    net = zoo_net(config)
    for form in args.forms.split(","):
        t0 = time.perf_counter()
        solver = Solver(solver_cfg, written_out(net) if form == "written"
                        else net)
        build_s = time.perf_counter() - t0
        row = {"form": form, "seq_len": config["seq_len"],
               "build_s": round(build_s, 2),
               **_time_steps(solver, feeds, args.steps)}
        print(json.dumps(row), flush=True)
        del solver
    return 0


if __name__ == "__main__":
    sys.exit(main())
