"""Analysis CLI: ``python -m sparknet_tpu.analysis [lint|graph|mem] ...``.

Three engines share one front door and one findings schema:

* ``lint``  — graftlint, the AST source-contract linter (the default:
  a bare invocation or one starting with paths/flags lints, so every
  pre-existing call site keeps working).
* ``graph`` — graphcheck, the jaxpr/StableHLO/HLO graph-contract
  analysis (lowers each parallel mode on the virtual CPU mesh and
  audits comm budget, sharding, dtype, donation against the banked
  manifests in docs/graph_contracts/).
* ``mem``   — memcheck, the static HBM/VMEM footprint analysis (same
  CPU-mesh lowerings, cross-checking an analytic jaxpr-liveness model
  against XLA's ``memory_analysis()``, banking docs/mem_contracts/;
  ``--fit`` runs the batch-fit solver whose table the serving
  admission gate consults).
* ``conc``  — conccheck, the static concurrency-contract analysis
  (lock-discipline inference, lock-order + blocking-call audit, and
  the thread/process roles over the serving/feed/loop plane,
  banking docs/conc_contracts/; the chaos scheduler
  ``SPARKNET_CHAOS_SCHED`` cross-validates the banked graph at
  dryrun time).  Pure AST — no jax, no lowering, zero chip time.
* ``bytes`` — bytecheck, the static per-step HBM traffic census
  (gross eqn census + per-op-class floor over the same CPU-mesh
  tracings, reconciled against the measured headline step bytes,
  banking docs/byte_contracts/; ``--remat`` runs the chip-free
  remat/donation schedule search that banks the ``Config.remat``
  policy table).
* ``num``   — numcheck, the static numerics-contract census (dtype
  flow of every traced mode: matmul/conv accumulation, sum-reduction
  operands, the cast census with round-trip detection, the f32 loss
  pin — banking docs/num_contracts/; ``--mixed`` runs the chip-free
  mixed-precision policy search that banks the
  ``Config.activation_dtype`` table).
* ``all``   — every engine above in sequence (lint, conc, graph, mem,
  bytes, num), merged findings, one exit code — the single
  pre-commit/CI front door.

Exit codes (all subcommands): 0 clean (or suppressed-only), 1
unsuppressed findings, 2 usage error.  ``--json`` (or the legacy
``--format json``) emits the shared schema: ``{"findings": [{rule,
path, line, message, suppressed}...], "unsuppressed": N,
"suppressed": N}``.
"""

from __future__ import annotations

import argparse
import os
import re
import sys

from sparknet_tpu.analysis import (
    RULES,
    lint_paths,
    render_json,
    render_text,
)

# repo root = parent of the sparknet_tpu package directory
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_SCOPE = ("sparknet_tpu", "tools", "bench.py")


def default_paths() -> list[str]:
    """The standard lint scope, resolved against the repo root so the
    command works from any cwd.  tests/ and examples/ are deliberately
    out of scope: test fixtures contain intentional violations, and the
    examples are narrated walkthroughs linted by review, not CI."""
    out = []
    for rel in DEFAULT_SCOPE:
        p = os.path.join(_REPO, rel)
        if os.path.exists(p):
            out.append(p)
    return out


def lint_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.analysis lint",
        description="graftlint: machine-check the repo's TPU timing, "
        "platform, and evidence-banking contracts",
    )
    ap.add_argument("paths", nargs="*",
                    help="files or directories (default: repo scope "
                    f"{'/'.join(DEFAULT_SCOPE)})")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--rule", action="append", default=[],
                    help="run only this rule id (repeatable)")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="also print suppressed findings (text format)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    args = ap.parse_args(argv)

    if args.list_rules:
        for info in RULES.values():
            print(f"{info.id}: {info.summary}")
        return 0

    unknown = set(args.rule) - set(RULES)
    if unknown:
        print(f"unknown rule id(s): {', '.join(sorted(unknown))} "
              f"(--list-rules for the catalog)", file=sys.stderr)
        return 2

    paths = args.paths or default_paths()
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"no such path: {', '.join(missing)}", file=sys.stderr)
        return 2

    findings = lint_paths(paths, only=set(args.rule) or None)
    if args.json or args.format == "json":
        print(render_json(findings))
    else:
        print(render_text(findings, show_suppressed=args.show_suppressed))
    return 1 if any(not f.suppressed for f in findings) else 0


def graph_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.analysis graph",
        description="graphcheck: lower each parallel mode's train step "
        "on the virtual CPU mesh and machine-check comm-budget, "
        "sharding, dtype, and donation contracts against the banked "
        "manifests (docs/graph_contracts/) — zero chip time",
    )
    ap.add_argument("--mode", action="append", default=[],
                    help="check only this mode (repeatable; default all)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the banked manifests (and the "
                    "SOURCES.json freshness fingerprint on a full run) "
                    "instead of diffing against them")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument("--list-modes", action="store_true",
                    help="print the mode registry and exit")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the graph-rule catalog and exit")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU mesh width (default 8, the test "
                    "harness mesh)")
    args = ap.parse_args(argv)

    from sparknet_tpu.analysis import graphcheck

    if args.list_rules:
        for rule_id, summary in graphcheck.iter_rules():
            print(f"{rule_id}: {summary}")
        return 0
    if args.list_modes:
        # mode names live in parallel/modes.py, which imports jax —
        # safe here: listing never initializes a backend
        from sparknet_tpu.parallel.modes import list_modes

        for name in list_modes():
            print(name)
        return 0

    as_json = args.json or args.format == "json"
    progress = None if as_json else (
        lambda m: print(f"graphcheck: lowering {m} ...", file=sys.stderr))
    try:
        findings, _ = graphcheck.run_graphcheck(
            args.mode or None, update=args.update, n_devices=args.devices,
            progress=progress)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 2
    if as_json:
        print(render_json(findings))
    else:
        print(render_text(findings, show_suppressed=args.show_suppressed,
                          label="graphcheck"))
        if args.update:
            print(f"graphcheck: manifests updated in "
                  f"{os.path.relpath(graphcheck.MANIFEST_DIR)}")
    return 1 if any(not f.suppressed for f in findings) else 0


def _parse_bytes(text: str) -> int:
    """'16GiB' / '8g' / '123456789' -> bytes (usage errors raise
    ValueError for the caller's rc-2 path)."""
    m = re.fullmatch(
        r"\s*(\d+(?:\.\d+)?)\s*([kmgt]i?b?)?\s*", text, re.IGNORECASE)
    if not m:
        raise ValueError(f"cannot parse byte size {text!r} "
                         "(want e.g. 16GiB, 8g, or a plain byte count)")
    scale = {"": 1, "k": 2**10, "m": 2**20, "g": 2**30, "t": 2**40}
    unit = (m.group(2) or "").lower().rstrip("b").rstrip("i")
    return int(float(m.group(1)) * scale[unit])


def mem_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.analysis mem",
        description="memcheck: statically predict each parallel mode's "
        "per-device HBM footprint on the virtual CPU mesh (analytic "
        "jaxpr-liveness model cross-checked against XLA's "
        "memory_analysis()), audit pallas-kernel VMEM bounds, and diff "
        "against the banked manifests (docs/mem_contracts/) — zero chip "
        "time.  --fit solves max safe batch per zoo family x dtype x "
        "mode (the table the serving admission gate consults)",
    )
    ap.add_argument("--mode", action="append", default=[],
                    help="check only this mode (repeatable; default all "
                    "modes + the 'kernels' VMEM audit)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the banked manifests (and SOURCES.json "
                    "on a full run) instead of diffing against them")
    ap.add_argument("--fit", action="store_true",
                    help="run the batch-fit solver instead of the "
                    "per-mode audit (banks docs/mem_contracts/"
                    "batch_fit.json with --update)")
    ap.add_argument("--hbm", default=None, metavar="SIZE",
                    help="accelerator HBM to fit against (e.g. 16GiB; "
                    "default: the v5e's 16 GiB)")
    ap.add_argument("--family", action="append", default=[],
                    help="--fit: solve only this zoo family (repeatable)")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument("--list-modes", action="store_true",
                    help="print the mode registry (+ 'kernels') and exit")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the memory-rule catalog and exit")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU mesh width (default 8, the test "
                    "harness mesh)")
    args = ap.parse_args(argv)

    from sparknet_tpu.analysis import mem_model, memcheck

    if args.list_rules:
        for rule_id, summary in memcheck.iter_rules():
            print(f"{rule_id}: {summary}")
        return 0
    if args.list_modes:
        from sparknet_tpu.parallel.modes import list_modes

        for name in list_modes() + ["kernels"]:
            print(name)
        return 0

    hbm = mem_model.V5E_HBM_BYTES
    if args.hbm:
        try:
            hbm = _parse_bytes(args.hbm)
        except ValueError as e:
            print(e, file=sys.stderr)
            return 2

    as_json = args.json or args.format == "json"
    try:
        if args.fit:
            progress = None if as_json else (
                lambda f: print(f"memcheck: fitting {f} ...",
                                file=sys.stderr))
            findings, _ = memcheck.run_batch_fit(
                hbm_bytes=hbm, update=args.update,
                families=args.family or None, n_devices=args.devices,
                progress=progress)
        else:
            progress = None if as_json else (
                lambda m: print(f"memcheck: tracing {m} ...",
                                file=sys.stderr))
            findings, _ = memcheck.run_memcheck(
                args.mode or None, update=args.update,
                n_devices=args.devices, progress=progress)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 2
    if as_json:
        print(render_json(findings))
    else:
        print(render_text(findings, show_suppressed=args.show_suppressed,
                          label="memcheck"))
        if args.update:
            print(f"memcheck: manifests updated in "
                  f"{os.path.relpath(memcheck.MANIFEST_DIR)}")
    return 1 if any(not f.suppressed for f in findings) else 0


def conc_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.analysis conc",
        description="conccheck: infer lock discipline and the static "
        "lock-acquisition graph over the serving/feed/loop plane "
        "(serve/, loop/, obs/, the process feed), "
        "fail on lock-order cycles, blocking calls under a lock, and "
        "jax reachable from ring workers, and diff against the banked "
        "manifests (docs/conc_contracts/) — pure AST, zero chip time",
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the banked manifests (and the "
                    "SOURCES.json freshness fingerprint) instead of "
                    "diffing against them")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the concurrency-rule catalog and exit")
    args = ap.parse_args(argv)

    from sparknet_tpu.analysis import conccheck

    if args.list_rules:
        for rule_id, summary in conccheck.iter_rules():
            print(f"{rule_id}: {summary}")
        return 0

    findings, _ = conccheck.run_conccheck(update=args.update)
    if args.json or args.format == "json":
        print(render_json(findings))
    else:
        print(render_text(findings, show_suppressed=args.show_suppressed,
                          label="conccheck"))
        if args.update:
            print(f"conccheck: manifests updated in "
                  f"{os.path.relpath(conccheck.MANIFEST_DIR)}")
    return 1 if any(not f.suppressed for f in findings) else 0


def bytes_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.analysis bytes",
        description="bytecheck: statically census each parallel mode's "
        "per-step HBM traffic on the virtual CPU mesh (gross eqn census "
        "+ per-op-class floor), reconcile the headline config against "
        "the measured step bytes, and diff against the banked manifests "
        "(docs/byte_contracts/) — zero chip time.  --remat runs the "
        "chip-free remat/donation schedule search instead and banks the "
        "bytes-minimal Config.remat policy per zoo family x dtype "
        "(docs/byte_contracts/remat_policy.json)",
    )
    ap.add_argument("--mode", action="append", default=[],
                    help="census only this mode (repeatable; default all)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the banked manifests (and SOURCES.json "
                    "on a full run) instead of diffing against them")
    ap.add_argument("--remat", action="store_true",
                    help="run the remat/donation schedule search instead "
                    "of the per-mode census (banks docs/byte_contracts/"
                    "remat_policy.json with --update)")
    ap.add_argument("--family", action="append", default=[],
                    help="--remat: search only this zoo family "
                    "(repeatable)")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument("--list-modes", action="store_true",
                    help="print the mode registry and exit")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the byte-rule catalog and exit")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU mesh width (default 8, the test "
                    "harness mesh)")
    args = ap.parse_args(argv)

    from sparknet_tpu.analysis import bytecheck

    if args.list_rules:
        for rule_id, summary in bytecheck.iter_rules():
            print(f"{rule_id}: {summary}")
        return 0
    if args.list_modes:
        from sparknet_tpu.parallel.modes import list_modes

        for name in list_modes():
            print(name)
        return 0

    as_json = args.json or args.format == "json"
    try:
        if args.remat:
            progress = None if as_json else (
                lambda f: print(f"bytecheck: scoring {f} ...",
                                file=sys.stderr))
            findings, _ = bytecheck.run_remat_search(
                update=args.update, families=args.family or None,
                n_devices=args.devices, progress=progress)
        else:
            progress = None if as_json else (
                lambda m: print(f"bytecheck: censusing {m} ...",
                                file=sys.stderr))
            findings, _ = bytecheck.run_bytecheck(
                args.mode or None, update=args.update,
                n_devices=args.devices, progress=progress)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 2
    if as_json:
        print(render_json(findings))
    else:
        print(render_text(findings, show_suppressed=args.show_suppressed,
                          label="bytecheck"))
        if args.update:
            print(f"bytecheck: manifests updated in "
                  f"{os.path.relpath(bytecheck.MANIFEST_DIR)}")
    return 1 if any(not f.suppressed for f in findings) else 0


def num_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.analysis num",
        description="numcheck: statically census each parallel mode's "
        "dtype flow on the virtual CPU mesh (matmul/conv accumulation "
        "dtypes, sum-reduction operands, the cast census with "
        "round-trip detection, the f32 loss pin) and diff against the "
        "banked manifests (docs/num_contracts/) — zero chip time.  "
        "--mixed runs the chip-free mixed-precision policy search "
        "instead: scores every Config.activation_dtype storage policy "
        "per zoo family on the byte model, gates each on a "
        "deterministic CPU error probe, and banks the bytes-minimal "
        "safe winner (docs/num_contracts/mixed_policy.json)",
    )
    ap.add_argument("--mode", action="append", default=[],
                    help="census only this mode (repeatable; default all)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the banked manifests (and SOURCES.json "
                    "on a full run) instead of diffing against them")
    ap.add_argument("--mixed", action="store_true",
                    help="run the mixed-precision policy search instead "
                    "of the per-mode census (banks docs/num_contracts/"
                    "mixed_policy.json with --update)")
    ap.add_argument("--family", action="append", default=[],
                    help="--mixed: search only this zoo family "
                    "(repeatable)")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument("--list-modes", action="store_true",
                    help="print the mode registry and exit")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the numerics-rule catalog and exit")
    ap.add_argument("--devices", type=int, default=8,
                    help="virtual CPU mesh width (default 8, the test "
                    "harness mesh)")
    args = ap.parse_args(argv)

    from sparknet_tpu.analysis import numcheck

    if args.list_rules:
        for rule_id, summary in numcheck.iter_rules():
            print(f"{rule_id}: {summary}")
        return 0
    if args.list_modes:
        from sparknet_tpu.parallel.modes import list_modes

        for name in list_modes():
            print(name)
        return 0

    as_json = args.json or args.format == "json"
    try:
        if args.mixed:
            progress = None if as_json else (
                lambda f: print(f"numcheck: scoring {f} ...",
                                file=sys.stderr))
            findings, _ = numcheck.run_mixed_search(
                update=args.update, families=args.family or None,
                n_devices=args.devices, progress=progress)
        else:
            progress = None if as_json else (
                lambda m: print(f"numcheck: censusing {m} ...",
                                file=sys.stderr))
            findings, _ = numcheck.run_numcheck(
                args.mode or None, update=args.update,
                n_devices=args.devices, progress=progress)
    except KeyError as e:
        print(e.args[0], file=sys.stderr)
        return 2
    if as_json:
        print(render_json(findings))
    else:
        print(render_text(findings, show_suppressed=args.show_suppressed,
                          label="numcheck"))
        if args.update:
            print(f"numcheck: manifests updated in "
                  f"{os.path.relpath(numcheck.MANIFEST_DIR)}")
    return 1 if any(not f.suppressed for f in findings) else 0


def _all_engines() -> list:
    """(label, runner) per engine, cheap-static first — module-level so
    the smoke test can swap in stubs.  Each runner takes no args and
    returns a findings list."""
    from sparknet_tpu.analysis import (
        bytecheck,
        conccheck,
        graphcheck,
        memcheck,
        numcheck,
    )

    return [
        ("graftlint", lambda: lint_paths(default_paths())),
        ("conccheck", lambda: conccheck.run_conccheck()[0]),
        ("graphcheck", lambda: graphcheck.run_graphcheck(None)[0]),
        ("memcheck", lambda: memcheck.run_memcheck(None)[0]),
        ("bytecheck", lambda: bytecheck.run_bytecheck(None)[0]),
        ("numcheck", lambda: numcheck.run_numcheck(None)[0]),
    ]


def all_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m sparknet_tpu.analysis all",
        description="run every analysis engine (graftlint, conccheck, "
        "graphcheck, memcheck, bytecheck, numcheck) in sequence — "
        "merged findings, one exit code.  The single pre-commit/CI "
        "front door; each engine stays individually invocable for "
        "focused runs",
    )
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--json", action="store_true",
                    help="shorthand for --format json")
    ap.add_argument("--show-suppressed", action="store_true")
    args = ap.parse_args(argv)

    as_json = args.json or args.format == "json"
    merged: list = []
    failed: list[str] = []
    for label, runner in _all_engines():
        if not as_json:
            print(f"analysis all: running {label} ...", file=sys.stderr)
        try:
            found = runner()
        except Exception as e:  # an engine crash must not mask the rest
            failed.append(label)
            print(f"analysis all: {label} CRASHED: {e}", file=sys.stderr)
            continue
        merged.extend(found)
    if as_json:
        print(render_json(merged))
    else:
        print(render_text(merged, show_suppressed=args.show_suppressed,
                          label="analysis all"))
        if failed:
            print(f"analysis all: engine crash(es): {', '.join(failed)}")
    if failed:
        return 1
    return 1 if any(not f.suppressed for f in merged) else 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "graph":
        return graph_main(argv[1:])
    if argv and argv[0] == "mem":
        return mem_main(argv[1:])
    if argv and argv[0] == "bytes":
        return bytes_main(argv[1:])
    if argv and argv[0] == "conc":
        return conc_main(argv[1:])
    if argv and argv[0] == "num":
        return num_main(argv[1:])
    if argv and argv[0] == "all":
        return all_main(argv[1:])
    if argv and argv[0] == "lint":
        return lint_main(argv[1:])
    # legacy invocation: bare paths/flags mean lint
    return lint_main(argv)


if __name__ == "__main__":
    sys.exit(main())
