"""One run of one cell: data set, flags, the job, and the result line."""

from __future__ import annotations

import dataclasses
import math
import os
import re
import shutil
from typing import Any, Callable

from benchmarks.harness import dataset, load_by_name


@dataclasses.dataclass
class Context:
    bench: dict
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    t_start: float
    root: str
    log: Callable[[str], None]

    @property
    def batch(self) -> int:
        """Images per worker per step (the prototxt's batch_size)."""
        if self.rehearse:
            return int(self.traffic.get("rehearse_batch", 4))
        return int(self.config["batch_per_worker"])

    def knob(self, name: str, default=None):
        """A traffic parameter; ``rehearse_<name>`` replaces it on the CPU."""
        if self.rehearse and f"rehearse_{name}" in self.traffic:
            return self.traffic[f"rehearse_{name}"]
        return self.traffic.get(name, default)

    def trace_dir(self) -> str:
        d = os.path.join(dataset.CACHE_DIR, "trace", self.cell["name"])
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
        return d

    def train_flags(self) -> list[str]:
        """The job a user types: the configuration's recipe flags, the
        traffic's own (``--tau 10``), and the run's seed.  ``{db}`` is the
        seeded RecordDB, ``{configs}`` the directory of the prototxts."""
        ds = self.config["dataset"]
        records = 64 if self.rehearse else ds["records"]
        db = dataset.ensure_db(self.seed, records, tuple(ds["chw"]),
                               ds["classes"])
        self.log(f"data set ready: {db}")
        configs = os.path.join(self.root, "benchmarks", "configs")
        if self.rehearse:
            configs = self._rehearsal_configs(configs)
        flags = [*self.config["train_flags"], *self.knob("train_flags", [])]
        flags = [f.replace("{db}", db).replace("{configs}", configs)
                 for f in flags]
        return [*flags, "--seed", str(self.seed)]

    def _rehearsal_configs(self, configs: str) -> str:
        """Copies of the prototxts with a tiny batch, for the CPU walk."""
        out = os.path.join(dataset.CACHE_DIR, "rehearse", self.cell["name"])
        shutil.rmtree(out, ignore_errors=True)
        os.makedirs(out)
        name = self.config["name"]
        for suffix in (".solver.prototxt", ".train.prototxt"):
            with open(os.path.join(configs, name + suffix)) as f:
                text = f.read()
            text = re.sub(r"batch_size: \d+", f"batch_size: {self.batch}", text)
            with open(os.path.join(out, name + suffix), "w") as f:
                f.write(text)
        return out


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def device_record(summary: dict | None, log=None) -> dict:
    import jax

    devs = jax.devices()
    # libtpu reports live buffers (peak_bytes_in_use) and the scratch the
    # compiled programs reserve (peak_bytes_reserved) apart; a step holds
    # both at once, so the chip's peak is their sum (PERF.md, section 7)
    stats = [d.memory_stats() or {} for d in devs]
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats]
    if log is not None:
        log(f"memory_stats chip 0: {devs[0].memory_stats()}")
    rec: dict[str, Any] = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs), "memory_peak_bytes": int(max(peaks)),
    }
    if summary is not None and summary["chips"]:
        busy = [c["busy_s"] for c in summary["chips"].values()]
        rec["busy_s"] = sum(busy) / len(busy)
        rec["window_s"] = summary["window_s"]
    return rec


def run_cell(ctx: Context) -> dict:
    from benchmarks.harness import trace as trace_mod

    job = load_by_name("jobs", ctx.traffic["job"])
    res = job.run(ctx)
    summary = res.get("summary")
    device = device_record(summary, ctx.log)
    name = ctx.cell["name"]
    metrics: dict[str, dict] = {}
    if not ctx.trace:
        for m in ctx.bench["end_to_end"]:
            if applies(m, name):
                metrics[m["name"]] = {
                    "value": res["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        run = dict(res["run"], memory_peak_bytes=device["memory_peak_bytes"],
                   device_kind=device["kind"])
        for m in ctx.bench["per_layer"]:
            if not applies(m, name):
                continue
            value = load_by_name("metrics", m["name"]).read(summary, run)
            if value is not None and math.isfinite(value):
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for p in res["problems"]:
        ctx.log(f"NOT CORRECT: {p}")
    line = {
        "correct": not res["problems"], "attempted": res["attempted"],
        "failed": res["failed"], "metrics": metrics, "device": device,
    }
    if ctx.trace and summary is not None:
        line["breakdown"] = trace_mod.breakdown(summary)
    return line
