"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the cell's chips, makes the data set and the weights
from the seed, checks the program against the plain reference, warms up
the cell's own programs (all of that is ``setup_s``), measures for
``--seconds`` through the program's front door, and prints ONE JSON
object as the last line of stdout.  Without a TPU, or with fewer chips
than the cell asks for, it exits 2 and prints no result: there is no CPU
fallback under a metric's name.  ``--rehearse-cpu`` is the sandbox's
tiny-size walk through the same code; it prints no metric.

Everything about a cell is data, found by the names in BENCHMARK.json:
``configs/<configuration>.json`` (the ``tpunet train`` flags of the
documented recipe), ``traffic/<traffic>.json`` (the job kind and its
parameters), ``jobs/<job>.py``, ``reference/<configuration>.py`` and
``metrics/<metric>.py``.
"""

import time

T_START = time.perf_counter()  # process start, as near as Python allows

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny-size walk on the CPU; prints NO metric")
    args = ap.parse_args()

    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; BENCHMARK.json has "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(cfg_entry["file"])
    traffic = load_json("benchmarks", "traffic", cell["traffic"] + ".json")

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    import sparknet_tpu  # noqa: F401 — a bare benchmarks/ dir fails here

    import jax

    devices = jax.devices()
    log(f"jax sees {len(devices)} x {devices[0].device_kind}")
    if not args.rehearse_cpu and (
            devices[0].platform != "tpu" or len(devices) < cell["chips"]):
        print(f"{args.workload} needs {cell['chips']} TPU chip(s); jax sees "
              f"{len(devices)} x {devices[0].platform}. No CPU fallback "
              "(--rehearse-cpu walks the code at a tiny size).",
              file=sys.stderr)
        return 2

    from benchmarks.harness import cell as cell_mod

    ctx = cell_mod.Context(
        bench=bench, cell=cell, config=config, traffic=traffic,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        rehearse=args.rehearse_cpu, t_start=T_START, root=ROOT, log=log)
    line = cell_mod.run_cell(ctx)
    if args.rehearse_cpu:
        log("REHEARSAL on the CPU at a tiny size: no metric is reported. "
            + json.dumps({k: line[k] for k in ("correct", "attempted", "failed")}))
        return 0 if line["correct"] else 1
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
