"""What a WRONG program would read in the window-and-full attention
decoder cell's check (``harness/window_check.py``), on the chip at the
published widths.

Run by hand when the configuration's check is ADDED or its limits are
revisited (never by run.py), beside ``hybrid_readings.py --workload
laguna-solo-s8192 --seeds ...``, which reads the limits' two sides (the
program, and the reference computed entirely in bf16):

    python benchmarks/scratch/window_readings.py --seeds 5000000101,...

Per seed, through the cell's own front door (the job's flags, the
solver's own initial parameters, the first window of the seeded token
file): the f32 reference with ONE setting changed (a window of 511 and of
513, plain RoPE where YaRN belongs, ``attention_factor`` 1) against the
f32 reference proper, forward only: the check's readings (e) and (f) of
the two attention layers it reads.  One JSON line per seed on stdout and in
``chiprun_out/window_readings.jsonl``.

``--programs``: the wrong PROGRAM instead, through the whole check: the
cell's own train prototxt with the one setting edited (``wrong_prototxts``),
a solver built from it through the same front door and the same seed (the
same blobs and initial parameters: no edit changes a shape), its forward
and ONE step of its own compiled train step, held to the f32 reference
proper by ``check_step``: EVERY reading of the check, the timed step's
``update_rel.*`` leaves among them, under each fault.  One solver at a
time: two do not fit the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
WORKLOAD = "laguna-solo-s8192"


def wrong_configs(rcfg: dict) -> dict:
    """name -> the reference's sizes with one setting changed."""
    ropes = {kind: dict(group) for kind, group in rcfg["ropes"]}
    frozen = lambda r: tuple((k, tuple(sorted(g.items())))
                             for k, g in sorted(r.items()))
    full = ropes["full_attention"]
    plain = {**ropes, "full_attention": {
        "rope_type": "default", "rope_theta": full["rope_theta"],
        "partial_rotary_factor": full["partial_rotary_factor"]}}
    factor_1 = {**ropes, "full_attention": {**full, "attention_factor": 1.0}}
    return {"window_minus_1": {**rcfg, "window": rcfg["window"] - 1},
            "window_plus_1": {**rcfg, "window": rcfg["window"] + 1},
            "plain_rope_for_yarn": {**rcfg, "ropes": frozen(plain)},
            "attention_factor_1": {**rcfg, "ropes": frozen(factor_1)}}


def wrong_prototxts(text: str, window: int) -> dict:
    """name -> the train prototxt ``text`` with one setting edited: the
    same four faults as ``wrong_configs``, planted in the program."""
    import re

    edits = {
        "window_minus_1": lambda t: t.replace(f"window: {window}\n",
                                              f"window: {window - 1}\n"),
        "window_plus_1": lambda t: t.replace(f"window: {window}\n",
                                             f"window: {window + 1}\n"),
        "plain_rope_for_yarn":
            lambda t: re.sub(r"\s*rope_scaling \{[^}]*\}", "", t),
        "attention_factor_1":
            lambda t: re.sub(r"attention_factor: [0-9.]+",
                             "attention_factor: 1.0", t)}
    out = {name: edit(text) for name, edit in edits.items()}
    same = [name for name, edited in out.items() if edited == text]
    if same:
        raise SystemExit(f"the prototxt has nothing to edit for {same}")
    return out


def wrong_programs(args, checker, ref, sized, line, log) -> None:
    """``line[name]`` <- the check's readings of each wrong program
    against the reference proper (``--programs``)."""
    import copy
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import front_door

    solver = front_door.build_solver(args)
    first = front_door.open_feed(args, solver)(0)
    ids, labels = np.array(first["data"][:1]), np.array(first["label"][:1])
    want = jax.tree_util.tree_map(np.asarray, checker.run_reference(
        ref, solver.variables.params, jnp.asarray(ids), jnp.asarray(labels),
        checker.reference_config(sized), solver.config,
        checker.leaves(sized)))
    log("the reference proper is computed")
    start = np.array(solver.variables.params["norm_f"][0]), np.array(
        solver.variables.params["attn1"][-1])
    with open(args.solver) as f:
        solver_text = f.read()
    net_name = next(l.split('"')[1] for l in solver_text.splitlines()
                    if l.startswith("net:"))
    here = os.path.dirname(os.path.abspath(args.solver))
    with open(os.path.join(here, net_name)) as f:
        texts = wrong_prototxts(f.read(), sized["sliding_window"])
    del solver
    for name, text in texts.items():
        gc.collect()
        out = os.path.join(ROOT, "chiprun_out", "wrong_programs", name)
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, net_name), "w") as f:
            f.write(text)
        with open(os.path.join(out, os.path.basename(args.solver)), "w") as f:
            f.write(solver_text)
        wrong_args = copy.copy(args)
        wrong_args.solver = os.path.join(out, os.path.basename(args.solver))
        solver = front_door.build_solver(wrong_args)
        now = solver.variables.params
        assert np.array_equal(start[0], now["norm_f"][0]) and np.array_equal(
            start[1], now["attn1"][-1]), "another initialisation"
        forward = checker.forward_program(solver)
        facts, problems = checker.check_step(
            solver, ref, sized, ids, labels, checker.tolerances(),
            forward, want)
        line[name] = {k: v for k, v in facts.items() if k in checker.TOL
                      or k in ("logits_rel_all", "logits_positions",
                               "topk_sets_differ")
                      or k.startswith("update_flipped")}
        line[name]["failed"] = [p.split()[0] for p in problems]
        log(f"{name}: failed {line[name]['failed']}")
        del solver, forward, now


def mixed_of(ref, params, ids, rcfg) -> dict:
    """reading -> the reference's output of that attention layer, f32,
    from a forward walk of the blocks up to the last one read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = {k: rcfg[k] for k in ref._SIZES}
    wanted = dict(rcfg["mixed_readings"])
    last = max(int(layer[4:]) for layer in wanted.values())
    out = {}
    with jax.default_matmul_precision("highest"):
        x = params["embed"][0][jnp.asarray(ids)]
        for i in range(last + 1):
            names = ref.block_names(i, rcfg)
            x, _, seen = ref._block_fwd(
                tuple(params[n] for n in names), x, heads=rcfg["heads"][i],
                kind=rcfg["kinds"][i], dense=rcfg["dense"][i], **sizes)
            for reading, layer in wanted.items():
                if layer == names[1]:
                    out[reading] = np.asarray(seen["mixed"])
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--programs", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args()
    if a.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import numpy as np

    from benchmarks.harness import front_door, load_by_name

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == WORKLOAD)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    job = load_by_name("jobs", traffic["job"])
    checker = load_by_name("harness", config["check"])
    ref = load_by_name("reference", config["reference"])
    t_start = time.perf_counter()
    log = lambda m: print(f"[{time.perf_counter() - t_start:7.1f}s] {m}",
                          file=sys.stderr, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", "window_readings.jsonl")

    for seed in (int(s) for s in a.seeds.split(",")):
        ctx = types.SimpleNamespace(seed=seed, root=ROOT, log=log,
                                    rehearse=a.rehearse_cpu, cell=cell,
                                    config=config)
        sized = job.sized(ctx)
        line: dict = {"seed": seed, "device": jax.devices()[0].device_kind}

        def body(args) -> int:
            if a.programs:
                line["programs"] = True
                wrong_programs(args, checker, ref, sized, line, log)
                return 0
            solver = front_door.build_solver(args)
            ids = np.array(front_door.open_feed(args, solver)(0)["data"][:1])
            rcfg = checker.reference_config(sized)
            params = solver.variables.params
            right = mixed_of(ref, params, ids, rcfg)
            edges = jax.tree_util.tree_map(
                np.asarray, checker.window_edges(ref, params, ids, rcfg))
            for name, wcfg in wrong_configs(rcfg).items():
                line[name] = checker.mixed_facts(
                    mixed_of(ref, params, ids, wcfg), right, edges)
                log(f"seed {seed} {name}: {line[name]}")
            return 0

        line["rc"] = front_door.run_as_train(job.train_flags(ctx, sized), body)
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
