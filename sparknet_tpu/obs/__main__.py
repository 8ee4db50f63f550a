"""obs CLI: ``python -m sparknet_tpu.obs
{report|validate|slo|top|dryrun} ...``.

* ``report <journal> [--out f.md] [--lineage]`` — render a journal to
  markdown (refuses unstamped walls; never prints a throughput above
  its stated roofline bound).  ``--lineage`` appends the causal-span
  audit and the parent/child waterfalls for the last round and the
  last request (obs/lineage.py).
* ``validate [journals...]`` — schema-check journal files; with no
  arguments, every ``docs/evidence_r*/*.jsonl`` in the repo.  Exit 1
  on any violation.
* ``slo [journals...] [--manifest f.json]`` — evaluate the declarative
  SLO manifest (``docs/slo_manifest.json``) against journal(s); same
  default discovery as ``validate``.  Gates with no subject events
  pass vacuously (and say so); exit 1 on any burn.
* ``top <journal> [--interval s] [--once]`` — live-tail a GROWING
  journal: each poll folds only the newly appended complete lines into
  streaming metrics (obs/metrics.py) and repaints one summary frame —
  bounded memory however long the run.
* ``dryrun [--out p] [--rounds N] [--elastic]`` — the zero-chip-time
  proof: run dp (tau=1 sync SGD) and tau (SparkNet averaging) rounds on
  the virtual 8-device CPU mesh with the Recorder armed, producing a
  journal whose per-round records carry fenced walls, img/s, loss EMA,
  and the comm_model-predicted collective budget.  ``--elastic`` adds a
  fault-injected elastic leg (kill/join/straggle between rounds) whose
  membership events land on the same schema.  ``--serve`` swaps the
  training legs for the serving load run (sparknet_tpu/serve): >= 500
  synthetic requests through every AOT bucket, a journaled over-HBM
  load refusal, and exit 1 unless the recompile sentinel saw 0
  post-warmup compiles.  ``--loop`` drives the full train-to-serve
  production loop (sparknet_tpu/loop): elastic rounds -> atomic
  checkpoint -> hot swap into the live engine -> over-HBM refusal ->
  bitwise rollback, with traffic in flight; exit 1 unless every gate
  holds (zero serving-path compiles, zero dropped tickets, scores
  change then restore).  ``--replica`` drives the pod-serving fault
  plan (dryrun mode 20): a K-replica pool under open-loop Poisson load
  takes a deterministic kill with a known backlog (stolen tickets
  re-routed, zero dropped), holds queue p99 inside max_wait + one pump
  tick on a steady no-fault leg, survives live join/kill/swap with
  zero serving-path compiles, and pins continuous-batching exactness;
  exit 1 unless every gate holds.  ``--ctl`` replays the four
  control-plane scenarios (tools/ctl_scenarios.py) through the
  SLOController on virtual time: action traces diffed against the
  banked ``docs/ctl_contracts/`` manifests, controller-vs-bare A/B
  (the bare arm must burn ≥ 1 gate per scenario, the controlled arm
  must hold every gate with zero drops); exit 1 on any divergence —
  zero chip time, and no jax import at all.  Render with ``report``.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def report_main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.obs report",
        description="render an obs journal to markdown")
    ap.add_argument("journal")
    ap.add_argument("--out", help="write here instead of stdout")
    ap.add_argument("--lineage", action="store_true",
                    help="append the causal-span audit + waterfalls")
    args = ap.parse_args(argv)
    if not os.path.exists(args.journal):
        print(f"no such journal: {args.journal}", file=sys.stderr)
        return 2
    from sparknet_tpu.obs.report import render_path

    text = render_path(args.journal, lineage=args.lineage)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text)
        print(f"wrote {args.out}")
    else:
        print(text)
    return 0


def validate_main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.obs validate",
        description="schema-check journal files (default: every "
        "docs/evidence_r*/*.jsonl)")
    ap.add_argument("journals", nargs="*")
    args = ap.parse_args(argv)
    from sparknet_tpu.obs import schema

    paths = args.journals or _discover_journals()
    if not paths:
        print("no journals found", file=sys.stderr)
        return 2
    rc = 0
    for path in paths:
        try:
            n, errors = schema.validate_journal(path)
        except OSError as e:
            print(f"{path}: unreadable ({e})", file=sys.stderr)
            rc = 1
            continue
        status = "OK" if not errors else "FAIL"
        print(f"{status} {path}: {n} line(s)")
        for err in errors:
            print(f"  {err}")
        if errors:
            rc = 1
    return rc


def _discover_journals() -> list[str]:
    """Every banked journal in the repo (``docs/evidence_r*/*.jsonl``
    — the dryrun journals)."""
    return sorted(glob.glob(
        os.path.join(_REPO, "docs", "evidence_r*", "*.jsonl")))


def slo_main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.obs slo",
        description="evaluate the declarative SLO manifest "
        "(docs/slo_manifest.json) against journal(s); default: every "
        "docs/evidence_r*/*.jsonl.  Exit 1 on any burn.")
    ap.add_argument("journals", nargs="*")
    ap.add_argument("--manifest", help="alternate manifest path")
    ap.add_argument("--quiet", action="store_true",
                    help="verdict lines only, no per-gate detail")
    args = ap.parse_args(argv)
    from sparknet_tpu.obs import slo

    manifest_path = args.manifest or slo.default_manifest_path()
    manifest = slo.load_manifest(manifest_path)
    paths = args.journals or _discover_journals()
    if not paths:
        print("no journals found", file=sys.stderr)
        return 2
    rc = 0
    for path in paths:
        try:
            results = slo.evaluate_journal(path, manifest)
        except OSError as e:
            print(f"{path}: unreadable ({e})", file=sys.stderr)
            rc = 1
            continue
        burned = [r["id"] for r in results if not r["ok"]]
        applicable = sum(1 for r in results if r["applicable"])
        status = "OK" if not burned else "BURN"
        print(f"{status} {path}: {applicable}/{len(results)} gate(s) "
              "applicable")
        if not args.quiet:
            for r in results:
                mark = "pass" if r["ok"] else "BURN"
                scope = "" if r["applicable"] else " (vacuous)"
                print(f"  [{mark}] {r['id']}{scope}: {r['detail']}")
        if burned:
            rc = 1
    return rc


def top_main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.obs top",
        description="live-tail a growing journal into streaming "
        "metrics: each poll folds only newly appended complete lines "
        "(obs/metrics.py JournalTail) — bounded memory at any run "
        "length")
    ap.add_argument("journal")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="seconds between polls (default 2)")
    ap.add_argument("--once", action="store_true",
                    help="one poll, one frame, exit (tests/CI)")
    ap.add_argument("--frames", type=int, default=0,
                    help="exit after N frames (0 = until Ctrl-C)")
    args = ap.parse_args(argv)
    import time

    from sparknet_tpu.obs import metrics as obs_metrics

    from collections import deque

    tail = obs_metrics.JournalTail(args.journal)
    # fold-only hub: the flush clock never fires (top reads state
    # directly; it must not mint metrics events for someone's journal)
    hub = obs_metrics.MetricsHub(flush_every=1 << 62)
    # the live ctl decision stream: last few decide/act/cooldown lines
    # verbatim (the counters say how many; these say WHAT)
    ctl_recent: deque = deque(maxlen=5)
    folded = 0
    frames = 0
    try:
        while True:
            for ev in tail.poll():
                kind = ev.get("event")
                if isinstance(kind, str):
                    hub.observe_event(kind, ev)
                    folded += 1
                    if kind == "ctl" and ev.get("kind") in (
                            "decide", "act", "cooldown"):
                        ctl_recent.append(ev)
            frames += 1
            print(_top_frame(args.journal, folded, hub, ctl_recent),
                  flush=True)
            if args.once or (args.frames and frames >= args.frames):
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


def _top_frame(path: str, folded: int, hub, ctl_recent=()) -> str:
    from sparknet_tpu.obs import metrics as obs_metrics

    lines = [f"== obs top {path} — {folded} event(s) folded =="]
    for name in sorted(hub.counters):
        value = hub.counters[name]
        lines.append(f"  {name} = {value:g}")
    for name in sorted(hub.gauges):
        lines.append(f"  {name} ~ {hub.gauges[name]:g} (gauge)")
    for name in sorted(hub.hists):
        snap = hub.hists[name].snapshot()
        p50 = obs_metrics.percentile(snap, 50)
        p99 = obs_metrics.percentile(snap, 99)
        lines.append(
            f"  {name}: n={snap['count']} p50={p50:.3f} "
            f"p99={p99:.3f} max={snap['max']:.3f}")
    if ctl_recent:
        lines.append("  -- ctl decisions (most recent last) --")
        for ev in ctl_recent:
            t = ev.get("t")
            bits = [f"t={t:g}" if isinstance(t, (int, float)) else None,
                    ev.get("action"), ev.get("gate"),
                    ev.get("reason") or ev.get("note")]
            lines.append(f"  ctl/{ev.get('kind', '?')}: "
                         + " ".join(b for b in bits if b))
    if len(lines) == 1:
        lines.append("  (no metric-bearing events yet)")
    return "\n".join(lines)


def _dryrun_gates(path: str) -> int:
    """The post-dryrun machine gates (dryrun modes 17-20 acceptance):
    zero schema findings AND a clean lineage audit — every parent ref
    in the journal resolves to a defined span or a declared root."""
    from sparknet_tpu.obs import lineage, schema

    rc = 0
    n, errors = schema.validate_journal(path)
    if errors:
        print(f"obs dryrun: SCHEMA FAIL — {len(errors)} finding(s) "
              f"over {n} line(s):", file=sys.stderr)
        for err in errors[:20]:
            print(f"  {err}", file=sys.stderr)
        rc = 1
    else:
        print(f"obs dryrun: schema clean over {n} line(s)",
              file=sys.stderr)
    verdict = lineage.audit(schema.stream_journal(path))
    if verdict["dangling"]:
        print(f"obs dryrun: LINEAGE FAIL — "
              f"{len(verdict['dangling'])} dangling ref(s):",
              file=sys.stderr)
        for ref in verdict["dangling"][:20]:
            print(f"  {ref}", file=sys.stderr)
        rc = 1
    else:
        print(f"obs dryrun: lineage complete — {verdict['spans']} "
              f"span(s), {verdict['edges']} edge(s), "
              f"{verdict['requests_linked']} request(s) linked",
              file=sys.stderr)
    if _chaos_gate():
        rc = 1
    return rc


def _chaos_gate() -> int:
    """conccheck leg (c): when ``SPARKNET_CHAOS_SCHED`` is armed, the
    instrumented locks have been recording actual acquisition edges all
    run — diff them against the banked static graph.  Any observed edge
    absent from ``docs/conc_contracts/lock_graph.json`` means the
    static model missed a real interleaving: fail the dryrun.  A no-op
    (rc 0) when chaos mode is off."""
    from sparknet_tpu._chaoslock import (
        chaos_armed, chaos_seed, observed_edges)

    if not chaos_armed():
        return 0
    import json

    from sparknet_tpu.analysis.conccheck import MANIFEST_DIR

    path = os.path.join(MANIFEST_DIR, "lock_graph.json")
    try:
        with open(path, encoding="utf-8") as f:
            static = {tuple(e)
                      for e in json.load(f)["contract"]["edges"]}
    except (OSError, KeyError, ValueError):
        print("obs dryrun: CHAOS FAIL — no banked lock_graph manifest "
              "(run `python -m sparknet_tpu.analysis conc --update`)",
              file=sys.stderr)
        return 1
    observed = observed_edges()
    novel = sorted(observed - static)
    if novel:
        print(f"obs dryrun: CHAOS FAIL — {len(novel)} observed "
              f"acquisition edge(s) absent from the static graph "
              f"(seed {chaos_seed()}):", file=sys.stderr)
        for a, b in novel[:20]:
            print(f"  {a} -> {b}", file=sys.stderr)
        return 1
    print(f"obs dryrun: chaos schedule clean — {len(observed)} "
          f"observed edge(s) within the {len(static)}-edge static "
          f"graph (seed {chaos_seed()})", file=sys.stderr)
    return 0


def _ctl_dryrun(out: str) -> int:
    """Dryrun mode 21's CLI surface: full scenario replay + banked
    trace diff, then the four CONTROLLED journals concatenated into
    ``out`` — the bankable specimen.  Bare-arm journals burn their
    gates BY DESIGN and stay in the tmp dir: they must never land next
    to banked evidence, where every journal is required to pass the
    SLO manifest."""
    import importlib.util
    import tempfile

    path = os.path.join(_REPO, "tools", "ctl_scenarios.py")
    spec = importlib.util.spec_from_file_location("ctl_scenarios", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    tmp = tempfile.mkdtemp(prefix="ctl_dryrun_")
    summary = mod.replay(
        update=False, journal_dir=tmp,
        log=lambda m: print(f"obs dryrun [ctl]: {m}", file=sys.stderr))
    out_dir = os.path.dirname(os.path.abspath(out))
    os.makedirs(out_dir, exist_ok=True)
    with open(out, "w", encoding="utf-8") as dst:
        for record in summary["scenarios"]:
            with open(record["controlled"]["journal"],
                      encoding="utf-8") as src:
                dst.write(src.read())
    acted = sum(len(r["controlled"]["actions"])
                for r in summary["scenarios"])
    print(f"obs dryrun [ctl]: {len(summary['scenarios'])} scenario(s), "
          f"{acted} controller action(s), traces "
          f"{'MATCH' if summary['ok'] else 'DIVERGED'} vs "
          "docs/ctl_contracts/ (bare arms burned, controlled arms "
          "held, zero drops)")
    print(f"obs dryrun: journal at {out} — render with "
          f"`python -m sparknet_tpu.obs report {out}`")
    gates = _dryrun_gates(out)
    return 0 if summary["ok"] and gates == 0 else 1


def dryrun_main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.obs dryrun",
        description="dp+tau rounds on the virtual CPU mesh with the "
        "Recorder armed — zero chip time")
    ap.add_argument("--out", default=os.path.join(
        os.path.sep + "tmp", "obs_dryrun.jsonl"))
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--tau", type=int, default=3)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--family", default="cifar10_quick")
    ap.add_argument(
        "--elastic", action="store_true",
        help="add an elastic fault-injection leg (parallel/elastic.py): "
        "kill/join/straggle across rounds on the virtual mesh, so the "
        "journal carries worker_lost/worker_joined/mesh_resize events "
        "— still zero chip time")
    ap.add_argument(
        "--serve", action="store_true",
        help="run the serving load run INSTEAD of the training legs "
        "(sparknet_tpu/serve): >= --requests synthetic requests through "
        "every AOT bucket on two resident models, one journaled "
        "over-HBM load refusal, and the recompile sentinel pinned at 0 "
        "post-warmup compiles — still zero chip time")
    ap.add_argument("--requests", type=int, default=504,
                    help="request count for --serve (default 504)")
    ap.add_argument(
        "--loop", action="store_true",
        help="run the train-to-serve production loop INSTEAD of the "
        "training legs (sparknet_tpu/loop): elastic rounds -> atomic "
        "checkpoint -> candidate -> hot swap -> refusal -> bitwise "
        "rollback with requests in flight; exit 1 unless all gates "
        "pass — still zero chip time")
    ap.add_argument("--iterations", type=int, default=1,
                    help="train->rollout cycles for --loop (default 1)")
    ap.add_argument(
        "--replica", action="store_true",
        help="run the pod-serving fault plan INSTEAD of the training "
        "legs (serve/router.py): K replicas under open-loop Poisson "
        "load with a kill/join/swap plan firing mid-stream, zero-drop "
        "ticket re-route, deadline-aware shedding, and the "
        "continuous-batching exactness gate; exit 1 unless all gates "
        "pass — still zero chip time")
    ap.add_argument("--replicas", type=int, default=4,
                    help="pool width for --replica (default 4)")
    ap.add_argument(
        "--ctl", action="store_true",
        help="replay the four control-plane scenarios "
        "(tools/ctl_scenarios.py) INSTEAD of the training legs: "
        "deterministic virtual-time traffic through the SLOController, "
        "action traces diffed against docs/ctl_contracts/, and the "
        "controller-vs-bare A/B (bare must burn, controlled must hold "
        "with zero drops); exit 1 on any divergence — zero chip time, "
        "no jax import")
    args = ap.parse_args(argv)

    if args.ctl:
        # pure host-side sim: no backend, no mesh, no Recorder here —
        # the harness arms one Recorder per scenario arm itself
        return _ctl_dryrun(args.out)

    # pin the CPU platform and force the virtual device count —
    # graphcheck's helper does both, before any backend initializes
    from sparknet_tpu.analysis.graphcheck import _pin_cpu_mesh

    _pin_cpu_mesh(args.devices)

    # a fresh journal per dryrun: appending over a previous run would
    # interleave run ids in the rendered report
    out_dir = os.path.dirname(os.path.abspath(args.out))
    os.makedirs(out_dir, exist_ok=True)
    if os.path.exists(args.out):
        os.remove(args.out)
    from sparknet_tpu.obs.recorder import Recorder, set_recorder

    rec = set_recorder(Recorder(args.out))

    if args.replica:
        from sparknet_tpu.serve.dryrun import replica_run

        summary = replica_run(
            replicas=args.replicas,
            log=lambda m: print(f"obs dryrun [replica]: {m}",
                                file=sys.stderr))
        rec.close()
        set_recorder(None)
        print(
            f"obs dryrun [replica]: {summary['replicas_start']} -> "
            f"{summary['replicas_end']} replica(s) through faults "
            f"{summary['faults_fired']}, {summary['requests']} "
            f"request(s) ({summary['dropped']} dropped, "
            f"{summary['shed']} shed, {summary['rerouted']} "
            f"re-routed), queue p99 {summary['queue_p99_ms']:.1f} ms "
            f"(bound {summary['queue_bound_ms']:.0f} ms), "
            f"{summary['serve_path_compiles']} serving-path "
            f"compile(s), continuous exact: "
            f"{summary['continuous_exact']}")
        print(f"obs dryrun: journal at {args.out} — render with "
              f"`python -m sparknet_tpu.obs report {args.out}`")
        return 0 if summary["ok"] and _dryrun_gates(args.out) == 0 else 1

    if args.loop:
        from sparknet_tpu.loop.dryrun import loop_run

        summary = loop_run(
            iterations=args.iterations, rounds_per_rollout=args.rounds,
            family=args.family, tau=args.tau,
            log=lambda m: print(f"obs dryrun [loop]: {m}",
                                file=sys.stderr))
        rec.close()
        set_recorder(None)
        print(
            f"obs dryrun [loop]: {summary['rounds']} elastic round(s) "
            f"-> {summary['rollouts']} rollout(s) / "
            f"{summary['rollbacks']} rollback(s), "
            f"{summary['requests']} request(s) "
            f"({summary['dropped']} dropped), "
            f"{summary['serve_path_compiles']} serving-path compile(s), "
            f"scores changed: {summary['scores_changed']}, restored "
            f"bitwise: {summary['scores_restored']}, refusal "
            f"journaled: {summary['refused']}")
        print(f"obs dryrun: journal at {args.out} — render with "
              f"`python -m sparknet_tpu.obs report {args.out}`")
        return 0 if summary["ok"] and _dryrun_gates(args.out) == 0 else 1

    if args.serve:
        from sparknet_tpu.serve.loadgen import load_run

        summary = load_run(
            requests=args.requests, family=args.family,
            log=lambda m: print(f"obs dryrun [serve]: {m}",
                                file=sys.stderr))
        rec.close()
        set_recorder(None)
        print(
            f"obs dryrun [serve]: {summary['requests']} request(s), "
            f"buckets {summary['buckets_exercised']}, "
            f"{summary['compiles_post_warmup']} post-warmup compile(s), "
            f"p50 {summary['p50_ms']:.2f} ms / "
            f"p99 {summary['p99_ms']:.2f} ms, refusal journaled: "
            f"{summary['refused']}")
        print(f"obs dryrun: journal at {args.out} — render with "
              f"`python -m sparknet_tpu.obs report {args.out}`")
        return 0 if summary["compiles_post_warmup"] == 0 \
            and _dryrun_gates(args.out) == 0 else 1

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES
    from sparknet_tpu.parallel.modes import _feeds_for
    from sparknet_tpu.parallel.trainer import ParallelTrainer
    from sparknet_tpu.solvers.solver import Solver

    family = GRAPH_SWEEP_FAMILIES[args.family]
    devices = jax.devices()[:args.devices]
    mesh = Mesh(np.array(devices), ("data",))
    per_device = 2
    batch = per_device * len(devices)
    rs = np.random.RandomState(0)

    print(f"obs dryrun: dp mode, {args.rounds} round(s) ...",
          file=sys.stderr)
    trainer = ParallelTrainer(
        Solver(family.solver(), family.net(batch)), mesh=mesh, tau=1)
    for _ in range(args.rounds):
        trainer.train_round(lambda it: _feeds_for(family, batch, rs))

    print(f"obs dryrun: tau={args.tau} mode, {args.rounds} round(s) ...",
          file=sys.stderr)
    trainer = ParallelTrainer(
        Solver(family.solver(), family.net(per_device)), mesh=mesh,
        tau=args.tau)
    # one data fn for every round: the trainer places a round ahead
    def tau_feeds(it):
        return _feeds_for(family, batch, rs, tau=args.tau)

    for _ in range(args.rounds):
        trainer.train_round(tau_feeds)
    trainer.close()

    if args.elastic:
        from sparknet_tpu.parallel.elastic import (
            ElasticTrainer, FaultPlan, delay, join, kill,
        )

        W = len(devices)
        rounds = max(args.rounds, 4)  # enough rounds for every fault
        print(f"obs dryrun: elastic mode, {rounds} round(s) with "
              "kill/join/straggle ...", file=sys.stderr)
        plan = FaultPlan([
            kill(W - 1, at_round=1),
            join(at_round=2),
            delay(0, at_round=2, steps=args.tau),
        ])
        el = ElasticTrainer(
            Solver(family.solver(), family.net(per_device)),
            width=W, tau=args.tau, plan=plan, devices=devices)
        el.train(
            rounds,
            lambda g: _feeds_for(family, per_device,
                                 np.random.RandomState(g % 997)))

    rec.close()
    set_recorder(None)
    print(f"obs dryrun: journal at {args.out} — render with "
          f"`python -m sparknet_tpu.obs report {args.out}`")
    return _dryrun_gates(args.out)


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    commands = {"report": report_main, "validate": validate_main,
                "slo": slo_main, "top": top_main,
                "dryrun": dryrun_main}
    if not argv or argv[0] not in commands:
        print(__doc__)
        return 2
    return commands[argv[0]](argv[1:])


if __name__ == "__main__":
    sys.exit(main())
