"""The two readings the hybrid decoder cell's limits are set from, on the
chip.

Run by hand when the configuration's check is ADDED or its limits are
revisited (never by run.py):

    python benchmarks/scratch/hybrid_readings.py \
        --workload phi4flash-solo-s2048 --seeds 3200000101,3200000102,...

``decoder_readings.py`` for ``harness/hybrid_check.py``: per seed, through
the cell's own front door (the job's flags, the solver's own initial
parameters, the first window of the seeded token file), the facts of the
check for the PROGRAM against the f32 reference, and the same facts for
the reference computed ENTIRELY in bf16 (the scan's state included)
against the f32 reference: the nearest precision below the
configuration's, which has to come out not correct.  One JSON line per
seed on stdout and in ``chiprun_out/hybrid_readings.jsonl``.
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


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="phi4flash-solo-s2048")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--skip-bf16", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    a = ap.parse_args()
    if a.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import front_door, load_by_name

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
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
    out_path = os.path.join(ROOT, "chiprun_out", "hybrid_readings.jsonl")

    for seed in (int(s) for s in a.seeds.split(",")):
        ctx = types.SimpleNamespace(seed=seed, root=ROOT, log=log,
                                    rehearse=a.rehearse_cpu, cell=cell,
                                    config=config)
        sized = job.sized(ctx)
        tol = checker.tolerances(a.rehearse_cpu)
        line: dict = {"seed": seed, "device": jax.devices()[0].device_kind}

        def body(args) -> int:
            solver = front_door.build_solver(args)
            train_fn = front_door.open_feed(args, solver)
            first = train_fn(0)
            ids, labels = np.array(first["data"][:1]), np.array(first["label"][:1])
            forward = checker.forward_program(solver)
            # both references BEFORE the check: it steps the solver
            which = checker.leaves(sized)
            runs = {}
            for name, dtype in (("f32", None), ("bf16", jnp.bfloat16)):
                if name == "bf16" and a.skip_bf16:
                    continue
                t = time.perf_counter()
                runs[name] = jax.tree_util.tree_map(
                    np.asarray, checker.run_reference(
                        ref, solver.variables.params, jnp.asarray(ids),
                        jnp.asarray(labels), checker.reference_config(sized),
                        solver.config, which, dtype))
                log(f"seed {seed} reference in {name}: "
                    f"{time.perf_counter() - t:.1f}s")
            facts, problems = checker.check_step(
                solver, ref, sized, ids, labels, tol, forward, runs["f32"])
            line["program"], line["program_problems"] = facts, problems
            log(f"seed {seed} program: {problems or 'correct'}")
            if a.skip_bf16:
                return 0
            facts = checker.compare(runs["bf16"], runs["f32"])
            line["all_bf16"] = facts
            line["all_bf16_problems"] = [
                f"{k} {facts[k]:.3g} > {v:g}" for k, v in tol.items()
                if not facts[k] <= v]
            log(f"seed {seed} all-bf16 reference: "
                f"{line['all_bf16_problems'] or 'CORRECT (must not be)'}")
            return 0

        rc = front_door.run_as_train(job.train_flags(ctx, sized), body)
        line["rc"] = rc
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
