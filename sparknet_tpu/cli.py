"""``tpunet`` — the framework CLI.

Equivalent of the ``caffe`` brew tool (ref: caffe/tools/caffe.cpp:153-380:
train/test/time/device_query subcommands wired through gflags).  argparse
subcommands; model/solver configs are prototxt paths (parsed by the
framework's own text-format parser) or zoo names (``zoo:alexnet``).

Data sources (the reference's in-net LMDB layers are host-plane inputs
here): ``--data cifar:<dir>`` reads real CIFAR-10 binaries;
``--data db:<path>[,<test_path>]`` streams a record DB or Caffe LMDB
(``{proc}`` expands to the process id — the per-worker-DB layout);
``--data tokens:<file>[,<test_file>]`` feeds a language model windows of
a flat ``uint16`` token file (the OLMo / Megatron on-disk convention;
``data/text.py token_windows``): ``data`` and next-token ``label``
``[batch, seq_len]``, through the same destination-passing feed as ``db:``;
``--data synthetic`` generates pixel-scale random batches (enough for
``time``/smoke runs, like ``caffe time``'s dummy forward/backward).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

import numpy as np

# benchmarks/harness/front_door.py calls ``cli._stack_tau``; a ``benchmark``
# issue repoints it at ``data.rounds`` (ROADMAP.md, D11)
from sparknet_tpu.data.rounds import stack_tau as _stack_tau, widen_batch


def _build_net_and_solver(args):
    from sparknet_tpu import models
    from sparknet_tpu.obs.recorder import Span
    from sparknet_tpu.proto.text_format import parse_file
    from sparknet_tpu.solvers.solver import SolverConfig, load_solver_net

    if not args.solver:
        raise SystemExit("--solver is required (prototxt path or zoo:<name>)")
    # sn.setup.net: the net and solver messages, parsed or built from the zoo
    with Span(None, "sn.setup.net", host=True, compile_stats=True):
        if args.solver.startswith("zoo:"):
            name = args.solver[4:]
            net_param = getattr(models, name)(args.batch or 100)
            solver_cfg = getattr(models, f"{name}_solver")()
            return net_param, solver_cfg
        solver_msg = parse_file(args.solver)
        net_param = load_solver_net(
            solver_msg, root=_net_root(solver_msg, args.solver))
        return net_param, SolverConfig.from_proto(solver_msg)


def _net_root(solver_msg, solver_path: str) -> str:
    """Root for the solver's relative ``net:``/``train_net:`` path.

    Caffe resolves it against the CWD (the tool is run from the caffe
    root — ref: examples/cifar10/train_full.sh invokes
    ``build/tools/caffe`` with ``examples/...`` paths).  When that
    fails, walk up from the solver file's own directory until the
    relative path resolves, so ``tpunet train --solver
    /any/tree/examples/cifar10/x_solver.prototxt`` works from any CWD.
    """
    rel = next(
        (solver_msg.get_str(f) for f in ("net", "train_net")
         if solver_msg.has(f)),
        "",
    )
    if not rel or os.path.isabs(rel) or os.path.exists(rel):
        return ""
    d = os.path.dirname(os.path.abspath(solver_path))
    while True:
        if os.path.exists(os.path.join(d, rel)):
            return d
        parent = os.path.dirname(d)
        if parent == d:
            return ""  # let load_solver_net raise the plain not-found
        d = parent


def _peeked_feed_shapes(args, net_param):
    """--data db: shapes for a throwaway TRAIN-phase probe net (shared by
    every Solver/TPUNet construction site)."""
    from sparknet_tpu.data import feed

    spec = getattr(args, "data", "")
    if feed.parse_spec(spec)[0] != "db:":
        return None  # the probe Network below would be wasted work
    import jax

    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network

    # cmd_train initializes jax.distributed before any Solver is built,
    # so the process index is correct here
    return feed.db_peek_shapes(spec, Network(net_param, Phase.TRAIN),
                               jax.process_index()) or None


def _make_solver(solver_cfg, net_param, args):
    """Solver whose train net can shape-infer even when its prototxt uses
    DB-backed ``Data`` layers: feed shapes peeked from --data db: fill in
    what the layer declarations leave open."""
    import dataclasses

    from sparknet_tpu.solvers.solver import Solver

    if getattr(args, "seed", None) is not None:
        # --seed outranks the prototxt (ref: solver.cpp random_seed
        # handling — one knob controls the run's RNG)
        solver_cfg = dataclasses.replace(solver_cfg, random_seed=args.seed)
    with _clean_shape_errors():
        return Solver(
            solver_cfg, net_param,
            feed_shapes=_peeked_feed_shapes(args, net_param),
        )


@contextlib.contextmanager
def _clean_shape_errors():
    """Turn the compiler's unknown-input-shape ValueError into an
    actionable CLI exit (every net-construction site shares it)."""
    try:
        yield
    except ValueError as e:
        if "no shape known" not in str(e):
            raise
        raise SystemExit(
            f"{e} — the net's data layers declare no geometry on this "
            "host (a Data layer's shape comes from its DB, ref: "
            "data_layer.cpp DataLayerSetUp); stream one with --data "
            "db:<path>, keep data_param.source on disk, or use "
            "Input/RDD layers"
        ) from None


def _data_fns(args, net, test_net=None):
    """(train feed, test fn) from --data: the CLI's adapter to
    ``data.feed.open_feeds``, which knows no flag names.  Resolves the
    ``auto`` sentinel IN PLACE (``args.data`` holds the concrete mode
    afterwards).  ``benchmarks/harness/front_door.py`` calls it by this
    name; a ``benchmark`` issue repoints it at ``data.feed`` (ROADMAP.md,
    D11)."""
    import jax

    from sparknet_tpu.data import feed

    spec, args.data = args.data, feed.parse_spec(args.data, net)[1]
    return feed.open_feeds(
        spec, net, test_net,
        pid=jax.process_index(), nproc=jax.process_count(),
        seed=getattr(args, "seed", None),
        augment=getattr(args, "augment", "host"),
        solver_path=getattr(args, "solver", ""),
        data_scale=getattr(args, "data_scale", 0.0),
        prefetch=getattr(args, "prefetch", 0),
        trainer=(getattr(args, "tau", 1) > 1
                 or getattr(args, "distributed", False)
                 or getattr(args, "elastic_alpha", 0.0) > 0))


def _setup_line() -> str | None:
    """Where this job's set-up went, from the program's own record
    (``obs.recorder.flight`` reduced by ``obs.recorder.stages``): the
    front door to the first fenced step or round, by stage, with what
    each built and compiled, the main thread's compile seconds by jax's
    own events, and the HBM account of the step program that first step
    compiled (``utils/profiling.step_account``, on its span).
    ``setup_s`` as a user of ``tpunet train`` feels it
    (docs/OBSERVABILITY.md, "The record")."""
    import threading

    from sparknet_tpu.obs.recorder import flight, stages
    from sparknet_tpu.obs.sentinel import EVENT_LABELS, get_sentinel

    me = threading.get_ident()
    mine = [s for s in flight()[0] if s[1] == me]
    front = next((s for s in reversed(mine) if s[0] == "sn.main"), None)
    if front is None:
        return None
    mine = [s for s in mine if s[2] >= front[2]]
    fence = next((s for s in mine
                  if s[0] in ("sn.step.fence", "sn.round.fence")), None)
    if fence is None:
        return None
    first = [s for s in mine if s[0] in ("sn.step", "sn.round")][:1]
    labels = {"sn.main": "front door", "sn.setup.net": "net",
              "sn.solver.build": "solver build",
              "sn.solver.nets": "of it nets", "sn.solver.init": "init",
              "sn.trainer.build": "trainer build",
              "sn.feed.open": "feed open", "sn.step": "first step",
              "sn.round": "first step", fence[0]: "its fence"}
    parts = []
    for row in (*stages(mine), *stages(first, ("sn.step", "sn.round")),
                *stages([fence], (fence[0],))):
        text = f"{labels[row['name']]} {row['wall_s']:.1f}s"
        stats = {k: v for k, v in row["stats"].items()
                 if not k.startswith("hbm_")}  # the account ends the line
        if stats:
            text += " (" + ", ".join(
                f"{k} {'/'.join(map(str, v))}" for k, v in stats.items()) + ")"
        if row["compiles"]:
            text += (f" [{row['compiles']} compiles {row['compile_s']:.1f}s"
                     + (f", {row['cache_hits']} from the cache"
                        if row["cache_hits"] else "") + "]")
        parts.append(text)
    seconds = get_sentinel().thread_seconds()
    split = ", ".join(f"{label} {seconds[event]:.1f}s"
                      for event, label in EVENT_LABELS.items()
                      if event in seconds)
    total = (fence[2] + fence[3] - front[2]) / 1e9
    account = first[0][4] if first else {}
    program = ""
    if "hbm_args_bytes" in account:
        held = account["hbm_args_bytes"] + account["hbm_temps_bytes"]
        limit = account.get("hbm_limit_bytes")
        program = (f"; step program: {account['hbm_args_bytes'] / 1e9:.2f} GB "
                   f"arguments + {account['hbm_temps_bytes'] / 1e9:.2f} GB "
                   "temporaries"
                   + (f" of {limit / 1e9:.2f} ({100 * held / limit:.0f} %)"
                      if limit else ""))
    return (f"set-up: {total:.1f}s from the front door to the first fenced "
            "step: " + ", ".join(parts)
            + (f"; this thread's compile seconds by event: {split}"
               if split else "") + program)


def _load_weights_into(
    solver, path: str, strict_shapes: bool, require_match: bool
) -> list[str]:
    """Copy .caffemodel/.h5 weights into a solver's params by layer name,
    with clean CLI errors; returns the loaded layer names.

    ``require_match=False`` (the permissive finetune path) tolerates zero
    loadable layers — the donor's layers are all renamed/reshaped and
    training starts fresh, Caffe's CopyTrainedLayersFrom behavior."""
    import struct

    from sparknet_tpu.compiler.graph import NetVars
    from sparknet_tpu.net import copy_caffemodel_params, copy_hdf5_params

    copy = (
        copy_hdf5_params
        if path.endswith((".h5", ".hdf5", ".caffemodel.h5"))
        else copy_caffemodel_params
    )
    try:
        params, state, loaded = copy(
            solver.variables.params, path, strict_shapes=strict_shapes,
            state=solver.variables.state,
        )
    except (OSError, ValueError, KeyError, struct.error) as e:
        # missing/corrupt/truncated file, wrong HDF5 layout, bad shapes
        raise SystemExit(f"{path}: {e}") from None
    if require_match and not loaded:
        raise SystemExit(
            f"{path}: no layers could be loaded (names or shapes do not "
            "match this net)"
        )
    solver.variables = NetVars(params=params, state=state)
    return loaded


def cmd_train(args) -> int:
    """ref: caffe.cpp:153-218 train()."""
    import jax

    from sparknet_tpu.common import get_config
    from sparknet_tpu.data.feed import process_feed
    from sparknet_tpu.parallel.trainer import ParallelTrainer
    from sparknet_tpu.utils import EventLogger, SignalHandler, SolverAction, agree_action

    if args.snapshot and getattr(args, "weights", ""):
        # ref: caffe.cpp:161-163 "Give a snapshot to resume training or
        # weights to finetune but not both." — fail before building the net
        raise SystemExit("--snapshot and --weights are mutually exclusive")
    if getattr(args, "coordinator", "") and not getattr(args, "num_processes", 0):
        # a lone --coordinator would silently skip the whole multi-host
        # block and train unsynced independent models on every host
        raise SystemExit("--coordinator requires --num-processes")
    if getattr(args, "num_processes", 0):
        # multi-host bring-up (ref: SURVEY §2.4 — the Spark driver/executor
        # topology's replacement).  Must precede the first jax backend
        # touch, i.e. before the net builds; each process then feeds only
        # its own batch shards.
        from sparknet_tpu.parallel.mesh import initialize_distributed

        if not args.coordinator:
            raise SystemExit("--num-processes requires --coordinator host:port")
        if not (args.distributed or args.tau > 1 or args.elastic_alpha > 0):
            # without the mesh trainer each process would train a full
            # independent model with no gradient sync — never intended
            raise SystemExit(
                "--num-processes requires --distributed, --tau > 1, or "
                "--elastic-alpha > 0"
            )
        initialize_distributed(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
    net_param, solver_cfg = _build_net_and_solver(args)
    solver = _make_solver(solver_cfg, net_param, args)
    if args.snapshot:
        solver.restore(args.snapshot)
    elif getattr(args, "weights", ""):
        # finetuning: copy params by layer name from a zoo model, fresh
        # optimizer state (ref: caffe.cpp:184-189 CopyLayers / the
        # finetune_flickr_style recipe); permissive shapes so changed
        # heads are skipped
        loaded = _load_weights_into(
            solver, args.weights, strict_shapes=False, require_match=False
        )
        print(json.dumps({"finetune_from": args.weights, "layers_loaded": loaded}))
    # The reference logs where you run, but ad-hoc runs from the repo
    # root kept littering checkouts with tpunet_train_<ts>.txt (eight
    # deleted across three PRs) — default under the system tempdir;
    # SPARKNET_TRAIN_LOG_DIR reroutes explicitly.
    import tempfile

    default_log_dir = os.path.join(tempfile.gettempdir(), "tpunet_logs")
    log = EventLogger(os.environ.get("SPARKNET_TRAIN_LOG_DIR",
                                     default_log_dir),
                      prefix="tpunet_train")
    train_fn, test_fn = _data_fns(args, solver.train_net,
                                  test_net=solver.test_net)
    profile_ctx = contextlib.nullcontext()
    if args.profile:
        from sparknet_tpu.utils import profiling

        profile_ctx = profiling.trace(args.profile)
        log(f"profiling -> {args.profile}")

    iters = args.iterations or solver_cfg.max_iter
    setup_pending = [True]

    def log_setup():
        """One line, once the first step or round has fenced."""
        if setup_pending:
            setup_pending.clear()
            line = _setup_line()
            if line:
                log(line)

    with profile_ctx:
        elastic = args.elastic_alpha > 0
        if args.tau > 1 or args.distributed or elastic:
            if getattr(args, "num_processes", 0):
                log(f"distributed: process {args.process_id}/{args.num_processes}")
            trainer = ParallelTrainer(
                solver, tau=args.tau, elastic_alpha=args.elastic_alpha
            )
            # --augment device on the trainer path: the wire stays uint8
            # all the way through _put_feeds; the augment runs post-
            # placement, outside the jitted round program.
            if train_fn.trainer_device_fn is not None:
                trainer.feed_device_fn = train_fn.trainer_device_fn
                log("augment: device (post-placement, tau wire uint8)")
            outer = -(-iters // max(args.tau, 1))  # ceil: run >= requested
            feed_ctx = contextlib.nullcontext()
            if get_config().feed == "process":
                # one host-side pipeline feeds the whole tau round; the
                # trainer keeps packing + device_put (its feeds carry
                # the [tau, B*workers] contract, not per-batch puts)
                feed_ctx, train_fn = process_feed(
                    train_fn,
                    outer * max(args.tau, 1) * trainer.num_local_workers,
                    0, log, workers=getattr(args, "feed_workers", 0),
                    device_stage=False)
            tau_fn = _stack_tau(train_fn, args.tau, trainer.num_local_workers)
            scan_n = max(getattr(args, "scan", 1), 1)
            wide_fn = widen_batch(train_fn, trainer.num_local_workers,
                                  keep=scan_n)
            # tau_fn's feed thread is joined when the loop ends or a
            # signal stops it, before the process feed it reads is closed;
            # the round the trainer placed ahead is let go before that
            with feed_ctx, SignalHandler() as sig, \
                    contextlib.closing(tau_fn), contextlib.closing(trainer):
                o = 0
                while o < outer:
                    if args.tau > 1 or elastic:
                        # elastic rounds always take the [tau, B, ...]
                        # feed contract, tau may be 1 (dispatch already
                        # amortized over the tau local steps)
                        loss = trainer.train_round(tau_fn)
                        o += 1
                    else:
                        # tau=1 sync-SGD: --scan fuses rounds per dispatch
                        # (signal checks land between chunks).  A short
                        # TAIL runs per-round: compiling a one-off n-step
                        # program costs more than the dispatches it saves.
                        if scan_n > 1 and outer - o >= scan_n:
                            loss = trainer.train_rounds(scan_n, wide_fn)
                            o += scan_n
                        else:
                            loss = trainer.train_round(wide_fn)
                            o += 1
                    log(f"loss: {loss:.5f}", i=trainer.iter)
                    log_setup()
                    action = agree_action(sig.check())
                    if action is SolverAction.SNAPSHOT:
                        trainer.sync_to_solver()
                        # process 0 owns snapshots (replicated params are
                        # identical; concurrent same-path writes from
                        # every host would corrupt the file)
                        if jax.process_index() == 0:
                            solver.save(f"tpunet_iter_{trainer.iter}")
                    elif action is SolverAction.STOP:
                        break
            trainer.sync_to_solver()
        else:
            pf_ctx = contextlib.nullcontext()
            if get_config().feed == "process":
                # multi-process shared-memory feed + double-buffered
                # device stage (data/pipeline.py); streams from
                # solver.iter so snapshot resume continues the sequence
                pf_ctx, train_fn = process_feed(
                    train_fn, iters, solver.iter, log,
                    workers=getattr(args, "feed_workers", 0),
                    prefetch=getattr(args, "prefetch", 0))
            elif getattr(args, "prefetch", 0) > 0:
                # async host->HBM feed (the BasePrefetchingDataLayer role):
                # the worker thread transforms + device_puts ahead of the
                # step.  Streams from solver.iter so snapshot resume
                # continues the data sequence; the context closes the
                # worker on STOP so queued device batches release.
                from sparknet_tpu.data.prefetch import DevicePrefetcher

                pf_ctx = DevicePrefetcher(
                    train_fn, iters, depth=args.prefetch,
                    start_iter=solver.iter,
                    device_fn=train_fn.device_fn,
                )
                pf_iter = iter(pf_ctx)

                def train_fn(it):  # noqa: F811
                    return next(pf_iter)

                log(f"prefetch: depth {args.prefetch}")
            display = solver_cfg.display
            with pf_ctx, SignalHandler() as sig:
                def hook(it, loss):
                    log_setup()
                    # mirror the solver's display cadence into the event log
                    # so parse_log gets train-table rows (the reference's
                    # single glog stream carries both)
                    if display and it % display == 0:
                        log(f"loss: {loss:.5f}", i=it)
                    action = sig.check()
                    if action is SolverAction.SNAPSHOT:
                        solver.save(f"tpunet_iter_{it}")
                    elif action is SolverAction.STOP:
                        raise KeyboardInterrupt

                try:
                    solver.step(iters, train_fn, callback=hook,
                                scan_chunk=getattr(args, "scan", 1))
                except KeyboardInterrupt:
                    log("stopped by signal", i=solver.iter)
    if args.test_iters:
        scores = solver.test(args.test_iters, test_fn)
        log(f"scores: {scores}", i=solver.iter)
    if jax.process_index() == 0:
        out = solver.save(args.output or "tpunet_final")
        log(f"saved {out}")
    return 0


def cmd_test(args) -> int:
    """ref: caffe.cpp:222-287 test() — score a model from --weights
    (the reference's canonical usage: caffe test --weights m.caffemodel)
    or from a --snapshot solver state."""
    from sparknet_tpu.solvers.solver import Solver

    if args.snapshot and getattr(args, "weights", ""):
        raise SystemExit("--snapshot and --weights are mutually exclusive")
    if not args.snapshot and not getattr(args, "weights", ""):
        # ref: caffe.cpp test() CHECK_GT(FLAGS_weights.size(), 0)
        # "Need model weights to score." — scoring a random init is
        # never what the user meant
        raise SystemExit("test needs --weights or --snapshot to score")
    net_param, solver_cfg = _build_net_and_solver(args)
    solver = _make_solver(solver_cfg, net_param, args)
    if args.snapshot:
        solver.restore(args.snapshot)
    else:
        _load_weights_into(
            solver, args.weights, strict_shapes=True, require_match=True
        )
    _, test_fn = _data_fns(args, solver.test_net)
    scores = solver.test(args.iterations or 10, test_fn)
    print(json.dumps(scores))
    return 0


def cmd_time(args) -> int:
    """Per-layer forward/backward breakdown (ref: caffe.cpp:290-380).
    ``--fused`` times the whole jitted train step; ``--trace`` runs the
    fused step under jax.profiler and attributes device-op time back to
    layers via the compiler's L.<name> HLO scopes — the honest per-layer
    number on TPU, where per-layer dispatch measures launch overhead."""
    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network
    from sparknet_tpu.utils.timing import time_layers
    import jax

    net_param, solver_cfg = _build_net_and_solver(args)
    if getattr(args, "trace", False):
        return _time_trace(args, net_param, solver_cfg)
    if args.fused:
        import time as _time

        from sparknet_tpu.solvers.solver import Solver

        solver = _make_solver(solver_cfg, net_param, args)
        train_fn, _ = _data_fns(args, solver.train_net)
        feeds = jax.device_put(train_fn(0))
        step, v, s, key = solver.jitted_train_step(donate=True)
        iters = args.iterations or 10
        v, s, loss = step(v, s, 0, feeds, key)
        float(loss)  # compile + fence
        t0 = _time.perf_counter()
        for i in range(1, iters + 1):
            v, s, loss = step(v, s, i, feeds, key)
        float(loss)
        dt = (_time.perf_counter() - t0) / iters
        batch = next(iter(feeds.values())).shape[0]
        print(json.dumps({
            "fused_step_ms": round(dt * 1e3, 3),
            "batch": int(batch),
            "img_per_sec": round(batch / dt, 1),
        }))
        return 0

    if args.hlo:
        # XLA's own cost model for the compiled train step — flops and
        # HBM traffic per program (SURVEY §5: the `caffe time` analog is a
        # per-op HLO cost breakdown on TPU, where the layer loop is fused)
        from sparknet_tpu.solvers.solver import Solver

        solver = _make_solver(solver_cfg, net_param, args)
        train_fn, _ = _data_fns(args, solver.train_net)
        feeds = jax.device_put(train_fn(0))
        step, v, s, key = solver.jitted_train_step(donate=False)
        compiled = step.lower(v, s, 0, feeds, key).compile()
        cost = compiled.cost_analysis() or {}
        # "bytes accessed" extraction lives in the byte model — the same
        # arithmetic bench.py banks and the `bytes` engine reconciles,
        # so "hbm_bytes_per_step" here can never drift from the banked
        # step_gbytes definition (analysis/byte_model.py)
        from sparknet_tpu.analysis.byte_model import xla_cost_step_bytes

        bytes_ = xla_cost_step_bytes(cost)
        if isinstance(cost, list):  # older jax returns [dict]
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0))
        batch = next(iter(feeds.values())).shape[0]
        from sparknet_tpu.utils.profiling import step_account

        account = step_account(step, v, s, 0, feeds, key)
        print(json.dumps({
            "flops_per_step": flops,
            "hbm_bytes_per_step": bytes_,
            "arithmetic_intensity": round(flops / bytes_, 2) if bytes_ else None,
            "batch": int(batch),
            "gflops_per_image": round(flops / batch / 1e9, 3) if batch else None,
            "temp_bytes": account.get("hbm_temps_bytes"),
            "argument_bytes": account.get("hbm_args_bytes"),
        }))
        return 0

    net = Network(net_param, Phase.TRAIN)
    variables = net.init(jax.random.PRNGKey(0))
    train_fn, _ = _data_fns(args, net)
    feeds = train_fn(0)
    rows = time_layers(net, variables, feeds, iterations=args.iterations or 10)
    w = max(len(r["layer"]) for r in rows) + 2
    print(f"{'layer':<{w}}{'type':<18}{'forward':>10}  {'backward':>10}")
    tot_f = tot_b = 0.0
    for r in rows:
        b = f"{r['backward_ms']:.3f}" if r["backward_ms"] is not None else "-"
        print(f"{r['layer']:<{w}}{r['type']:<18}{r['forward_ms']:>9.3f}ms {b:>9}ms")
        tot_f += r["forward_ms"]
        tot_b += r["backward_ms"] or 0.0
    print(f"{'TOTAL':<{w}}{'':<18}{tot_f:>9.3f}ms {tot_b:>9.3f}ms")
    print("(layers timed in isolation; the fused jit step is faster)")
    return 0


def _time_trace(args, net_param, solver_cfg) -> int:
    """tpunet time --trace: profiler-attributed per-layer device time on
    the fused step, plus MFU and HBM bytes/step (replaces
    dispatch-dominated per-layer jit calls).

    Staged: each stage writes what it has to ``--trace-out`` before the
    next runs — compile stats first, then an untraced wall timing, then
    a 1-iter trace, then the full trace — so a failure mid-trace still
    leaves the earlier stages on disk."""
    import time as _time

    import jax

    from sparknet_tpu.utils.op_profile import table_from_trace, trace_step

    out_path = getattr(args, "trace_out", None) or "tpunet_trace.json"
    artifact: dict = {"stage": "init", "argv_solver": args.solver,
                      "utc": _time.strftime("%Y-%m-%d %H:%M:%SZ",
                                            _time.gmtime())}

    def bank(stage: str, **kv) -> None:
        artifact["stage"] = stage
        artifact.update(kv)
        with open(out_path + ".tmp", "w") as f:
            json.dump(artifact, f, indent=1, default=str)
        os.replace(out_path + ".tmp", out_path)

    solver = _make_solver(solver_cfg, net_param, args)
    train_fn, _ = _data_fns(args, solver.train_net)
    feeds = jax.device_put(train_fn(0))
    step, v, s, key = solver.jitted_train_step(donate=False)
    iters = args.iterations or 10

    # cost analysis for MFU / bytes alongside the measured time; the SAME
    # compiled executable then drives the profiled run (one XLA compile,
    # not two)
    compiled = step.lower(v, s, 0, feeds, key).compile()
    cost = compiled.cost_analysis() or {}
    # bytes through the byte model's shared extraction (the drift pin in
    # tests/test_bytecheck.py covers this path too)
    from sparknet_tpu.analysis.byte_model import xla_cost_step_bytes

    hbm_bytes = xla_cost_step_bytes(cost)
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))

    batch = next(iter(feeds.values())).shape[0]
    device = jax.devices()[0]
    platform = device.platform
    # Peak FLOP/s by TPU generation AND active compute dtype (public specs;
    # f32 matmuls emulate on the MXU at a fraction of bf16 rate).  MFU
    # against the wrong cell is off by ~4x, so the record also names which
    # peak it was computed against.
    import jax.numpy as jnp

    from sparknet_tpu.common import get_config

    dtype = get_config().compute_dtype
    dtype_name = "bf16" if dtype == jnp.bfloat16 else "f32"
    kind = device.device_kind
    # one peak table shared with bench.py; on a TPU it does not list,
    # tpu_peak_flops raises — no utilization against an assumed peak
    from sparknet_tpu.common import tpu_peak_flops

    peak = peak_label = None
    if platform == "tpu":
        peak = tpu_peak_flops(kind)[dtype_name]
        peak_label = f"{kind} {dtype_name}"

    bank("compiled", batch=int(batch), dtype=dtype_name,
         platform=platform, device_kind=kind, iters=int(iters),
         gflop_per_step=round(flops / 1e9, 2),
         hbm_gb_per_step=round(hbm_bytes / 1e9, 3))

    # Stage 2 — wall timing WITHOUT the profiler: throughput + MFU
    # evidence lands even if the profiler run below fails.
    from sparknet_tpu.common import value_fence

    run = lambda *a: compiled(*a)  # noqa: E731
    # Timing protocol (same as bench.py): thread the state through the
    # loop, as training does, and fence on the loss value
    # (common.value_fence).
    thread = lambda a, o: (o[0], o[1]) + a[2:]  # noqa: E731

    tv, ts, loss = run(v, s, 0, feeds, key)  # warm (executable cached)
    value_fence(loss)
    t0 = _time.perf_counter()
    for _ in range(3):
        tv, ts, loss = run(tv, ts, 0, feeds, key)
    value_fence(loss)
    wall_untraced_s = (_time.perf_counter() - t0) / 3
    mfu_untraced = (flops / wall_untraced_s / peak
                    if peak and wall_untraced_s else None)
    bank("wall_timed",
         wall_ms_per_step_untraced=round(wall_untraced_s * 1e3, 3),
         img_per_sec_untraced=round(batch / wall_untraced_s, 1),
         mfu_untraced=(round(mfu_untraced, 4)
                       if mfu_untraced is not None else None),
         mfu_vs_peak=peak_label,
         # consumers (tools/trace_report.py) refuse untraced walls
         # without this stamp
         fence_protocol="loss-value+threaded-args")

    layer_names = [l.name for l in solver.train_net.layers]

    # Stage 3 — SHORT trace (1 iter): its parsed table is written
    # before the longer run, continuing from stage 2's end state.
    prof1 = trace_step(run, (tv, ts, 0, feeds, key), iters=1,
                       thread_fn=thread)
    table = table_from_trace(prof1, layer_names, iters=1)
    bank("trace_short",
         rows_short=[(n, round(us, 1)) for n, us in table["rows"]],
         device_us_per_step_short=round(table["device_us_per_step"], 1),
         attributed_frac_short=round(table["attributed_frac"], 3),
         trace_dir_short=table["trace_dir"])

    # Stage 4 — full trace for stable per-layer statistics.
    if iters > 1:
        prof = trace_step(run, prof1["final_args"], iters=iters,
                          thread_fn=thread)
        table = table_from_trace(prof, layer_names, iters=iters)

    wall_s = table["wall_us_per_step"] / 1e6
    mfu = flops / wall_s / peak if peak and wall_s else None

    if table["rows"]:
        # the reference's `caffe time` table: per-layer Forward and
        # Backward walls plus the total (ref: caffe/tools/caffe.cpp:
        # 290-380); here attributed from the fused step's device trace
        fb = {name: (f, b) for name, f, b in table.get("rows_fwd_bwd", [])}
        w = max(len(r) for r, _ in table["rows"]) + 2
        print(f"{'layer':<{w}}{'fwd ms':>10}{'bwd ms':>10}{'total ms':>11}")
        for name, us in table["rows"]:
            f_us, b_us = fb.get(name, (0.0, 0.0))
            print(f"{name:<{w}}{f_us / 1e3:>10.3f}{b_us / 1e3:>10.3f}"
                  f"{us / 1e3:>11.3f}")
        print(
            f"{'DEVICE TOTAL':<{w}}{'':>10}{'':>10}"
            f"{table['device_us_per_step'] / 1e3:>11.3f}"
            f"  (attributed {table['attributed_frac'] * 100:.0f}%)"
        )
    else:
        print(
            "(no device-op lanes in the trace — per-layer attribution "
            "needs an accelerator backend; wall/MFU numbers below are "
            "still measured)"
        )
    summary = {
        "wall_ms_per_step": round(wall_s * 1e3, 3),
        "img_per_sec": round(batch / wall_s, 1),
        "batch": int(batch),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_vs_peak": peak_label,
        "gflop_per_step": round(flops / 1e9, 2),
        "hbm_gb_per_step": round(hbm_bytes / 1e9, 3),
        "platform": platform,
        "trace_dir": table["trace_dir"],
    }
    bank("final",
         rows=[(n, round(us, 1)) for n, us in table["rows"]],
         rows_fwd_bwd=[(n, round(f, 1), round(b, 1))
                       for n, f, b in table.get("rows_fwd_bwd", [])],
         device_us_per_step=round(table["device_us_per_step"], 1),
         attributed_frac=round(table["attributed_frac"], 3),
         **summary)
    print(json.dumps(summary))
    return 0


def cmd_convert_imageset(args) -> int:
    """Image list -> record DB (ref: caffe/tools/convert_imageset.cpp:
    listfile of "<relpath> <label>" lines, optional resize, LMDB out)."""
    from sparknet_tpu.data.createdb import create_db
    from sparknet_tpu.data.minibatch import decode_jpeg

    def samples():
        import os

        with open(args.listfile) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                rel, label = line.rsplit(maxsplit=1)
                try:
                    with open(os.path.join(args.root, rel), "rb") as img:
                        arr = decode_jpeg(img.read(), args.resize, args.resize)
                except OSError:
                    arr = None  # missing file == broken image: drop, continue
                if arr is None:
                    continue
                yield arr, int(label)

    n = create_db(args.db, samples(), backend=args.backend)
    if n == 0:
        raise SystemExit(
            f"no decodable images: check --root {args.root!r} and the "
            f"listfile paths (0 of the listed files produced records)"
        )
    print(json.dumps({"records": n, "db": args.db, "backend": args.backend}))
    return 0


def cmd_convert_db(args) -> int:
    """LMDB <-> RecordDB conversion — the ingest bridge for existing
    Caffe datasets (ref: caffe/src/caffe/util/db_lmdb.cpp is the
    reference's reader; tpunet reads that format directly and this
    command re-materializes it for the native data plane)."""
    from sparknet_tpu.data.createdb import convert_db

    n = convert_db(args.src, args.dst, backend=args.backend)
    print(json.dumps({"records": n, "src": args.src, "dst": args.dst,
                      "backend": args.backend}))
    return 0


def cmd_compute_image_mean(args) -> int:
    """Record DB -> mean image .npy (ref: caffe/tools/compute_image_mean.cpp)."""
    from sparknet_tpu.data.createdb import db_mean

    try:
        mean = db_mean(args.db, args.batch or 64)
    except ValueError as e:
        raise SystemExit(str(e)) from None
    if args.out.endswith(".binaryproto"):
        from sparknet_tpu.data.io_utils import save_mean_binaryproto

        save_mean_binaryproto(args.out, mean)
    else:
        np.save(args.out, mean)
    print(json.dumps({"out": args.out, "shape": list(mean.shape)}))
    return 0


def cmd_extract_features(args) -> int:
    """Forward a dataset and dump an intermediate blob per batch to .npy
    (ref: caffe/tools/extract_features.cpp + apps/FeaturizerApp.scala)."""
    from sparknet_tpu.apps.featurizer import FeaturizerApp
    from sparknet_tpu.net import TPUNet

    net_param, solver_cfg = _build_net_and_solver(args)
    with _clean_shape_errors():
        net = TPUNet(
            solver_cfg, net_param,
            feed_shapes=_peeked_feed_shapes(args, net_param),
        )
    if args.snapshot and getattr(args, "weights", ""):
        raise SystemExit("--snapshot and --weights are mutually exclusive")
    if args.snapshot:
        # --snapshot is a .solverstate.npz (what `train --output` writes);
        # restore via the solver, like cmd_train/cmd_test
        net.solver.restore(args.snapshot)
    elif getattr(args, "weights", ""):
        # the reference tool takes a .caffemodel directly
        # (extract_features.cpp: pretrained_net_param argv)
        _load_weights_into(
            net.solver, args.weights, strict_shapes=True, require_match=True
        )
    _, test_fn = _data_fns(args, net.test_net)
    app = FeaturizerApp(net, feature_blob=args.blob)
    feats = list(
        app.featurize(test_fn(b) for b in range(args.iterations or 10))
    )
    out = np.concatenate(feats)
    np.save(args.out, out)
    print(json.dumps({"out": args.out, "shape": list(out.shape)}))
    return 0


def cmd_draw(args) -> int:
    """Net prototxt -> Graphviz DOT (ref: caffe/python/draw_net.py)."""
    from sparknet_tpu import models
    from sparknet_tpu.utils.draw import draw_net_to_file

    if args.net.startswith("zoo:"):
        net_param = getattr(models, args.net[4:])(args.batch or 100)
    else:
        from sparknet_tpu.proto_loader import load_net_prototxt

        net_param = load_net_prototxt(args.net)
    draw_net_to_file(
        net_param,
        args.out,
        rankdir=args.rankdir,
        phase=args.phase or None,
    )
    print(json.dumps({"out": args.out, "rankdir": args.rankdir}))
    return 0


def cmd_classify(args) -> int:
    """Classify images with a deploy net: top-N labels per image
    (ref: examples/cpp_classification/classification.cpp — model_file
    trained_file mean_file label_file image)."""
    from sparknet_tpu.data.io_utils import load_image
    from sparknet_tpu.models.classifier import Classifier

    mean = None
    if args.mean:
        from sparknet_tpu.data.transform import load_mean_file

        m = load_mean_file(args.mean)
        if m.ndim == 2:  # (H, W) grayscale mean
            m = m[None]
        # cpp_classification collapses the mean image to per-channel values
        # (classification.cpp SetMean: channel_mean)
        mean = m.reshape(m.shape[0], -1).mean(axis=1)
    labels = None
    if args.labels:
        with open(args.labels) as f:
            labels = [line.strip() for line in f if line.strip()]

    if args.oversample and args.center_only:
        raise SystemExit("--oversample and --center-only are mutually exclusive")
    image_dims = None
    if args.images_dim:
        try:
            h, w = (int(v) for v in args.images_dim.split(","))
        except ValueError:
            raise SystemExit(
                f'--images-dim must be "H,W" (got {args.images_dim!r})'
            ) from None
        image_dims = (h, w)
    clf = Classifier(
        args.model,
        args.weights or None,
        image_dims=image_dims,
        mean=mean,
        raw_scale=args.raw_scale if args.raw_scale else None,
        channel_swap=(2, 1, 0) if args.bgr else None,
    )
    crop_h, crop_w = clf.feed_shapes[clf.inputs[0]][2:]
    if image_dims and (image_dims[0] < crop_h or image_dims[1] < crop_w):
        raise SystemExit(
            f"--images-dim {image_dims} is smaller than the net input "
            f"({crop_h}, {crop_w}); crops would be out of bounds"
        )
    # match the deploy net's channel count: 1-channel nets (LeNet-style)
    # get grayscale loads (pycaffe classify.py's --gray, auto-detected)
    channels = clf.feed_shapes[clf.inputs[0]][1]
    images = [load_image(p, color=channels != 1) for p in args.images]
    # single center pass by default like cpp_classification; --oversample
    # needs --images-dim larger than the crop to cut distinct crops;
    # preprocessing runs ONCE (calibration and prediction share blobs)
    blobs = clf.preprocess_images(images, args.oversample)
    if getattr(args, "fold_bn", False):
        folded = clf.fold_batchnorm()
        print(json.dumps({"fold_bn": folded}))
    if getattr(args, "int8", False):
        qstate = clf.calibrate_int8(blobs=blobs)
        print(json.dumps({"int8": sorted(qstate)}))
    probs = clf.predict_blobs(blobs, oversample=args.oversample)
    results = []
    for path, p in zip(args.images, probs):
        top = np.argsort(p)[::-1][: args.top]
        results.append({
            "image": path,
            "predictions": [
                {
                    "label": labels[i] if labels and i < len(labels) else int(i),
                    "prob": round(float(p[i]), 4),
                }
                for i in top
            ],
        })
    print(json.dumps(results))
    return 0


def cmd_pull_shards(args) -> int:
    """Explode a contiguous range of tar shards into a staging directory —
    per-worker dataset staging (ref: ec2/pull.py, which pulled
    files-shuf-NNN.tar from S3).  ``--store`` takes a local/NFS dir or a
    ``gs://``/``s3://`` prefix (via data.remote — remote shards are
    fetched into the staging area before exploding)."""
    import re
    import tarfile

    from sparknet_tpu.data.remote import get_store

    try:
        store = get_store(args.store)
        shards = [u for u in store.list_prefix(args.store) if u.endswith(".tar")]
    except (ValueError, RuntimeError) as e:
        raise SystemExit(f"--store {args.store}: {e}") from None
    if not shards:
        raise SystemExit(f"no .tar shards under {args.store}")
    # select by the shard NUMBER in the filename (files-shuf-007.tar is
    # shard 7 even when earlier shards are missing), like the reference's
    # explicit 'files-shuf-%03d.tar' % idx
    sel = []
    for path in shards:
        m = re.findall(r"(\d+)", os.path.basename(path))
        if m and args.start <= int(m[-1]) < args.stop:
            sel.append(path)
    if not sel:
        raise SystemExit(
            f"no shards numbered [{args.start}, {args.stop}) under {args.store}"
        )
    outdir = os.path.join(args.out, "%03d-%03d" % (args.start, args.stop))
    os.makedirs(outdir, exist_ok=True)
    written: set[str] = set()
    clobbered = 0
    # local/NFS shards open in place; remote ones fetch into a cache dir
    is_remote = "://" in args.store and not args.store.startswith("file://")
    cache = os.path.join(outdir, ".shard_cache")
    for path in sel:
        fetched = None
        if is_remote:
            try:
                path = fetched = store.fetch(path, cache)
            except RuntimeError as e:
                raise SystemExit(f"--store {args.store}: {e}") from None
        with tarfile.open(path) as tar:
            for member in tar.getmembers():
                if not member.isfile():
                    continue
                src = tar.extractfile(member)
                if src is None:
                    continue
                # preserve in-archive relative paths; refuse escapes
                rel = os.path.normpath(member.path).lstrip("/")
                if rel.startswith(".."):
                    raise SystemExit(f"shard member escapes outdir: {member.path}")
                dst = os.path.join(outdir, rel)
                os.makedirs(os.path.dirname(dst) or outdir, exist_ok=True)
                if dst in written:
                    clobbered += 1
                written.add(dst)
                with open(dst, "wb") as f:
                    f.write(src.read())
        if fetched is not None:
            # exploded successfully: drop the cached tar so staging costs
            # 1x the dataset, not 2x (the cache only guards re-fetch
            # within this run's loop, and each shard is visited once)
            try:
                os.remove(fetched)
            except OSError:
                pass
    print(json.dumps({
        "out": outdir, "shards": len(sel), "files": len(written),
        "clobbered": clobbered,
    }))
    return 0


def cmd_create_labelfile(args) -> int:
    """Write a train.txt for the files actually present in a directory,
    labels looked up (case-normalized) from a master label file
    (ref: ec2/create_labelfile.py)."""
    labelmap = {}
    with open(args.trainfile) as f:
        for line in f:
            parts = line.split()
            if len(parts) >= 2:
                labelmap[parts[0].upper()] = parts[1]
    n, missing = 0, 0
    with open(args.outfile, "w") as out:
        for root, _dirs, files in os.walk(args.directory):
            for fname in sorted(files):
                label = labelmap.get(fname.upper())
                if label is None:
                    missing += 1
                    continue
                out.write(f"{fname} {label}\n")
                n += 1
    print(json.dumps({"out": args.outfile, "entries": n, "unlabeled": missing}))
    return 0


def cmd_upgrade_net_proto_text(args) -> int:
    """Legacy V0/V1 net prototxt -> current schema (ref:
    caffe/tools/upgrade_net_proto_text.cpp)."""
    from sparknet_tpu.proto.text_format import parse_file, serialize
    from sparknet_tpu.proto.upgrade import upgrade_net

    upgraded = upgrade_net(parse_file(args.input))
    with open(args.output, "w") as f:
        f.write(serialize(upgraded) + "\n")
    print(json.dumps({"out": args.output, "layers": len(upgraded.get_all("layer"))}))
    return 0


def cmd_upgrade_net_proto_binary(args) -> int:
    """Legacy binary NetParameter (V1LayerParameter records) -> current
    schema (ref: caffe/tools/upgrade_net_proto_binary.cpp).  Wire-level
    field remapping: connectivity, include/exclude rules, typed params,
    loss weights, and blobs all pass through byte-identically; the type
    enum becomes the V2 string and blobs_lr/weight_decay fold into
    ParamSpec messages."""
    from sparknet_tpu.proto.binary import loads_caffemodel, upgrade_net_binary

    with open(args.input, "rb") as f:
        raw = f.read()
    out_bytes, upgraded = upgrade_net_binary(raw)
    model = loads_caffemodel(out_bytes)
    if not model.layers:
        raise SystemExit(f"no layers decoded from {args.input}")
    with open(args.output, "wb") as f:
        f.write(out_bytes)
    print(json.dumps({
        "out": args.output,
        "layers": len(model.layers),
        "upgraded_v1_records": upgraded,
        "blobs": sum(len(l.blobs) for l in model.layers),
    }))
    return 0


def cmd_upgrade_solver_proto_text(args) -> int:
    """Deprecated solver_type enum -> type string (ref:
    caffe/tools/upgrade_solver_proto_text.cpp)."""
    from sparknet_tpu.proto.text_format import parse_file, serialize
    from sparknet_tpu.proto.upgrade import upgrade_solver

    upgraded = upgrade_solver(parse_file(args.input))
    with open(args.output, "w") as f:
        f.write(serialize(upgraded) + "\n")
    print(json.dumps({"out": args.output, "type": upgraded.get_str("type", "SGD")}))
    return 0


def cmd_parse_log(args) -> int:
    """ref: tools/extra/parse_log.py — training log -> .train/.test CSVs."""
    from sparknet_tpu.utils.log_parse import parse_log_to_csv

    train_path, test_path = parse_log_to_csv(
        args.logfile, args.out_dir, delimiter=args.delimiter
    )
    print(json.dumps({"train": train_path, "test": test_path}))
    return 0


def cmd_plot_training_log(args) -> int:
    """ref: tools/extra/plot_training_log.py.example — chart type 0-7."""
    from sparknet_tpu.utils.plotting import plot_chart

    try:
        out = plot_chart(args.chart_type, args.logfile, args.out)
    except (ValueError, RuntimeError) as e:
        raise SystemExit(str(e)) from None
    print(json.dumps({"chart": out}))
    return 0


def cmd_resize_images(args) -> int:
    """ref: tools/extra/resize_and_crop_images.py — offline dataset prep."""
    from sparknet_tpu.data.resize_images import resize_tree

    try:
        ok, errors = resize_tree(
            args.input_folder, args.output_folder, args.side, args.workers
        )
    except ValueError as e:
        raise SystemExit(str(e)) from None
    for path, msg in errors[:20]:
        print(f"{path}: {msg}", file=sys.stderr)
    print(json.dumps({"resized": ok, "errors": len(errors)}))
    return 0 if not errors else 1


def _cmd_deprecated(replacement):
    def fn(args) -> int:
        # ref: tools/{train,test,finetune}_net.cpp, net_speed_benchmark.cpp —
        # LOG(FATAL) stubs pointing at the brew subcommand
        raise SystemExit(f"Deprecated. Use tpunet {replacement} instead.")

    return fn


def cmd_bench(args) -> int:
    """The headline throughput benchmark (bench.py) as a brew: 20 timed
    AlexNet-class training iterations, one JSON line (see
    docs/BENCHMARKS.md for measured results)."""
    import importlib.util

    from sparknet_tpu.common import get_config, set_config

    overrides = {}
    if args.model:
        overrides["SPARKNET_BENCH_MODEL"] = args.model
    if args.batch:
        overrides["SPARKNET_BENCH_BATCH"] = str(args.batch)
    if args.dtype:
        overrides["SPARKNET_BENCH_DTYPE"] = args.dtype
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench_path = os.path.join(root, "bench.py")
    if not os.path.exists(bench_path):
        raise SystemExit("bench.py not found next to the package")
    spec = importlib.util.spec_from_file_location("sparknet_bench", bench_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # scope the env-var IPC and the global compute dtype to this call —
    # the CLI process may outlive it (tests, interactive use)
    saved = {k: os.environ.get(k) for k in overrides}
    prev_dtype = get_config().compute_dtype
    os.environ.update(overrides)
    try:
        mod.main()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        set_config(compute_dtype=prev_dtype)
    return 0


def cmd_serve(args) -> int:
    """Synthetic load run through the AOT-batched serving engine
    (sparknet_tpu/serve; docs/SERVING.md): loads a primary + aux model,
    proves the priced over-HBM refusal, drives a closed-loop burst plan
    through every bucket, and prints one summary JSON line.  The
    recompile sentinel must read ZERO post-warmup compiles or the run
    exits 1.

    With ``--replicas K`` (K > 1) the run goes through the
    ``ReplicaRouter`` pod instead: K ServedModel copies, projected-wait
    routing, deadline shedding, open-loop arrivals — zero post-warmup
    compiles AND zero dropped tickets or exit 1 (docs/SERVING.md
    "Replication & elasticity").

    ref: apps/FeaturizerApp.scala:1 (the reference's batch scoring app;
    dynamic request batching is new TPU-first surface)."""
    import json as _json

    from sparknet_tpu.serve.loadgen import load_run, pod_run

    buckets = tuple(int(b) for b in args.buckets.split(","))
    if args.replicas > 1:
        summary = pod_run(
            replicas=args.replicas, family=args.family, arm=args.arm,
            buckets=buckets, max_wait_ms=args.max_wait_ms,
            rate=args.rate, seconds=args.seconds,
            controller=args.controller,
            log=lambda m: print(f"serve: {m}", file=sys.stderr))
        print(_json.dumps(
            {k: v for k, v in summary.items() if k != "per_replica"}))
        ok = (summary["compiles_post_warmup"] == 0
              and summary["dropped"] == 0)
        return 0 if ok else 1
    summary = load_run(
        requests=args.requests, family=args.family, arm=args.arm,
        buckets=buckets, max_wait_ms=args.max_wait_ms,
        log=lambda m: print(f"serve: {m}", file=sys.stderr))
    print(_json.dumps(summary))
    return 0 if summary["compiles_post_warmup"] == 0 else 1


def cmd_loop(args) -> int:
    """The train-to-serve production loop (sparknet_tpu/loop;
    docs/ARCHITECTURE.md "Production loop"): elastic training rounds ->
    atomic checkpoint -> deploy-arm candidate AOT-compiled off the
    request path -> hot swap into the live engine -> over-HBM refusal
    -> bitwise rollback, with traffic in flight throughout.  Prints one
    summary JSON line; exits 1 unless every gate holds (zero
    serving-path compiles, zero dropped tickets, scores change on
    rollout and restore on rollback).  A chip-free gate: pins the
    virtual CPU mesh — production rollouts go through ProductionLoop
    directly.

    ref: apps/FeaturizerApp.scala:1 (the reference's single driver app
    owning both training and scoring; the hot-reload protocol is new
    TPU-first surface)."""
    import json as _json

    # a chip-free verification drive, like `obs dryrun --loop`: pin the
    # virtual CPU mesh so the elastic pool exists on any host
    from sparknet_tpu.analysis.graphcheck import _pin_cpu_mesh

    _pin_cpu_mesh(max(8, args.width))

    from sparknet_tpu.loop.dryrun import loop_run

    buckets = tuple(int(b) for b in args.buckets.split(","))
    summary = loop_run(
        iterations=args.iterations, rounds_per_rollout=args.rounds,
        family=args.family, arm=args.arm, buckets=buckets,
        width=args.width, tau=args.tau, requests=args.requests,
        max_wait_ms=args.max_wait_ms, workdir=args.workdir or None,
        controller=args.controller,
        log=lambda m: print(f"loop: {m}", file=sys.stderr))
    print(_json.dumps(summary))
    return 0 if summary["ok"] else 1


def cmd_device_query(args) -> int:
    """ref: caffe.cpp:110-150 device_query().  Lists the devices of this
    process's own backend: the chip belongs to one process at a time, so
    a query from a child would collide with a parent that holds it."""
    import jax

    for d in jax.devices():
        print(json.dumps({"id": d.id, "platform": d.platform,
                          "device_kind": d.device_kind,
                          "process_index": d.process_index}))
    return 0


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    from sparknet_tpu.common import get_config, set_config
    from sparknet_tpu.obs.recorder import Span

    # sn.main: the front door, up to the hand-over to the sub-command.
    # Its start is where a job's set-up clocks in (the record:
    # obs/recorder).  No Recorder yet: --obs arms it in here
    with Span(None, "sn.main", host=True, compile_stats=True):
        args, overrides = _front_door(argv)
    if not overrides:
        return args.fn(args)
    prev = {k: getattr(get_config(), k) for k in overrides}
    set_config(**overrides)
    try:
        return args.fn(args)
    finally:
        set_config(**prev)


def _front_door(argv):
    """``(args, config overrides)``: the parser, the platform, the compile
    cache's placement and the scoped config a sub-command runs under."""
    p = argparse.ArgumentParser(prog="tpunet", description=__doc__)
    p.add_argument(
        "--platform",
        default="",
        help="force a jax platform (cpu/tpu) for this process",
    )
    p.add_argument(
        "--obs",
        default="",
        metavar="PATH.jsonl",
        help="arm the obs journal for this run (same as SPARKNET_OBS=PATH; "
        "off by default — the disabled path is bit-identical)",
    )
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--solver", help="solver prototxt path or zoo:<name>")
        sp.add_argument("--data", default="auto",
                        help="auto (default: the net's own data layers when "
                        "they declare a streamable source, else synthetic) | "
                        "cifar:<dir> | db:<path>[,<test_path>] | "
                        "tokens:<uint16 file>[,<test_file>] | proto "
                        "(stream from the net's own Data/ImageData/WindowData/"
                        "HDF5Data layers — the caffe-train-from-solver flow) "
                        "| synthetic")
        sp.add_argument("--data-scale", type=float, default=0.0,
                        help="multiply db feeds by this (transform_param."
                        "scale parity, e.g. 0.00390625 for lenet)")
        sp.add_argument("--batch", type=int, default=0, help="zoo batch override")
        sp.add_argument("--iterations", type=int, default=0)
        sp.add_argument("--snapshot", help=".solverstate.npz to restore")
        sp.add_argument("--dtype", default="",
                        choices=["", "bf16", "bfloat16", "f32"],
                        help="compute dtype for the step (bf16 = mixed "
                        "precision: bf16 activations/matmuls, f32 params "
                        "and BN statistics; default f32)")
        sp.add_argument("--layout", default="",
                        choices=["", "nchw", "nhwc"],
                        help="internal rank-4 activation layout (default "
                        "nchw — Caffe blob order; nhwc runs the step "
                        "channels-last, the MXU-preferred orientation — "
                        "weights/checkpoints stay wire-order either way; "
                        "SPARKNET_LAYOUT seeds the default)")

    sp = sub.add_parser("train", help="train a model")
    common(sp)
    sp.add_argument("--weights", default="",
                    help="finetune: copy params by layer name from a "
                    ".caffemodel/.h5 (fresh optimizer state)")
    sp.add_argument("--tau", type=int, default=1, help="model-averaging interval")
    sp.add_argument("--prefetch", type=int, default=0,
                    help="async device-feed queue depth (0 = off; the "
                    "reference's PREFETCH_COUNT is 3)")
    sp.add_argument("--feed", default="",
                    choices=["", "threaded", "process"],
                    help="host feed architecture (Config.feed): threaded "
                    "(default — daemon-thread prefetcher, bit-identical "
                    "legacy path) or process (multi-process shared-memory "
                    "ring, data/pipeline.py: decode+transform escape the "
                    "GIL; synthetic and cifar: sources; SPARKNET_FEED "
                    "seeds the default)")
    sp.add_argument("--feed-workers", type=int, default=0,
                    help="process-feed worker count (0 = auto: "
                    "SPARKNET_FEED_WORKERS or min(cpus, 4))")
    sp.add_argument("--augment", choices=["host", "device"], default="host",
                    help="where the data transform runs: host (numpy/C++ "
                    "DataTransformer) or device (ship uint8, "
                    "mean/crop/mirror in XLA via DeviceAugment; requires "
                    "--prefetch; cifar: source)")
    sp.add_argument("--distributed", action="store_true", help="use the device mesh")
    sp.add_argument("--elastic-alpha", type=float, default=0.0,
                    help="EASGD coupling strength (~0.9/num_workers); "
                    "0 = hard averaging")
    sp.add_argument("--coordinator", default="",
                    help="multi-host: coordination service host:port")
    sp.add_argument("--num-processes", type=int, default=0,
                    help="multi-host: total process count")
    sp.add_argument("--process-id", type=int, default=0,
                    help="multi-host: this process's id")
    sp.add_argument("--test-iters", type=int, default=0)
    sp.add_argument("--seed", type=int, default=None,
                    help="override the solver's random_seed; also offsets "
                    "the host/device data-augmentation streams (without "
                    "it, augmentation keys derive from process id only)")
    sp.add_argument("--scan", type=int, default=1,
                    help="iterations fused per device dispatch (lax.scan "
                    "over staged minibatches). Single-chip: auto-shrunk "
                    "to divide the display/snapshot cadences. With "
                    "--distributed at tau=1: fuses that many sync-SGD "
                    "rounds (loss then logs once per chunk). Ignored for "
                    "tau>1/elastic, which already amortize dispatch over "
                    "their tau local steps. Signal checks land between "
                    "chunks either way")
    sp.add_argument("--output", help="snapshot prefix for the final model")
    sp.add_argument("--profile", help="capture a jax.profiler trace into DIR")
    sp.set_defaults(fn=cmd_train)

    sp = sub.add_parser("test", help="score a model")
    common(sp)
    sp.add_argument("--weights", default="",
                    help="score a .caffemodel / .h5 (the caffe test usage)")
    sp.set_defaults(fn=cmd_test)

    sp = sub.add_parser("time", help="per-layer timing")
    common(sp)
    sp.add_argument("--fused", action="store_true",
                    help="time the whole jitted train step instead")
    sp.add_argument("--hlo", action="store_true",
                    help="XLA cost analysis of the compiled step (flops, "
                    "HBM bytes, arithmetic intensity)")
    sp.add_argument("--trace", action="store_true",
                    help="profiler-attributed per-layer device time on the "
                    "fused step + MFU + bytes/step (accelerator backends)")
    sp.add_argument("--trace-out", default=None, metavar="PATH",
                    help="JSON artifact for --trace, flushed incrementally "
                    "after every stage so a failure mid-trace still leaves "
                    "the earlier stages (default: ./tpunet_trace.json)")
    sp.set_defaults(fn=cmd_time)

    sp = sub.add_parser("convert_imageset", help="image list -> record DB")
    sp.add_argument("--root", required=True, help="image directory")
    sp.add_argument("--listfile", required=True, help='lines of "relpath label"')
    sp.add_argument("--db", required=True, help="output record DB path")
    sp.add_argument("--resize", type=int, default=256)
    sp.add_argument("--backend", choices=("record", "lmdb", "leveldb"),
                    default="record",
                    help="output format (lmdb/leveldb = Caffe-compatible)")
    sp.set_defaults(fn=cmd_convert_imageset)

    sp = sub.add_parser("convert_db",
                        help="convert between LMDB / LevelDB / native "
                        "record DB (source auto-detected)")
    sp.add_argument("--src", required=True, help="source DB (any format)")
    sp.add_argument("--dst", required=True, help="destination path")
    sp.add_argument("--backend", choices=("record", "lmdb", "leveldb"),
                    default="record", help="destination format")
    sp.set_defaults(fn=cmd_convert_db)

    sp = sub.add_parser("compute_image_mean", help="record DB -> mean .npy")
    sp.add_argument("--db", required=True)
    sp.add_argument("--out", required=True)
    sp.add_argument("--batch", type=int, default=0)
    sp.set_defaults(fn=cmd_compute_image_mean)

    sp = sub.add_parser("extract_features", help="dump an intermediate blob")
    common(sp)
    sp.add_argument("--blob", required=True, help="blob name, e.g. ip1")
    sp.add_argument("--out", required=True, help="output .npy")
    sp.add_argument("--weights", default="",
                    help=".caffemodel/.h5 to score with (the reference "
                    "tool's pretrained_net_param argument)")
    sp.set_defaults(fn=cmd_extract_features)

    sp = sub.add_parser("draw", help="net prototxt -> Graphviz DOT")
    sp.add_argument("--net", required=True, help="net prototxt path or zoo:<name>")
    sp.add_argument("--out", required=True, help="output .dot path")
    sp.add_argument("--rankdir", default="LR", choices=["LR", "TB", "BT", "RL"])
    sp.add_argument("--phase", default="", help="filter by TRAIN/TEST")
    sp.add_argument("--batch", type=int, default=0, help="zoo batch override")
    sp.set_defaults(fn=cmd_draw)

    sp = sub.add_parser("classify", help="top-N labels for images (deploy net)")
    sp.add_argument("--model", required=True, help="deploy prototxt")
    sp.add_argument("--weights", default="", help=".caffemodel / .h5")
    sp.add_argument("--mean", default="", help="mean .binaryproto or .npy")
    sp.add_argument("--labels", default="", help="one label per line")
    sp.add_argument("--top", type=int, default=5)
    sp.add_argument("--raw-scale", type=float, default=255.0)
    sp.add_argument("--bgr", action="store_true", help="swap channels RGB->BGR")
    sp.add_argument("--oversample", action="store_true",
                    help="average 10-crop predictions (pycaffe classify.py); "
                    "pair with --images-dim > net input for distinct crops")
    sp.add_argument("--images-dim", default="",
                    help='resize target "H,W" before cropping '
                    "(pycaffe classify.py --images_dim)")
    sp.add_argument("--center-only", action="store_true",
                    help="deprecated: single center pass is now the default")
    sp.add_argument("--int8", action="store_true",
                    help="post-training int8 inference (MXU int8 mode): "
                    "self-calibrates activation scales on the input "
                    "images, per-channel int8 weights")
    sp.add_argument("--fold-bn", action="store_true",
                    help="fold in-place BatchNorm/Scale chains into their "
                    "convolutions before inference (the merge_bn deploy "
                    "flow; combine with --int8 to quantize BN nets)")
    sp.add_argument("images", nargs="+")
    sp.set_defaults(fn=cmd_classify)

    sp = sub.add_parser("pull_shards", help="stage tar shards into a directory")
    sp.add_argument("--store", required=True, help="directory of .tar shards")
    sp.add_argument("--start", type=int, required=True)
    sp.add_argument("--stop", type=int, required=True)
    sp.add_argument("--out", required=True)
    sp.set_defaults(fn=cmd_pull_shards)

    sp = sub.add_parser("create_labelfile", help="train.txt for staged files")
    sp.add_argument("directory")
    sp.add_argument("trainfile")
    sp.add_argument("outfile")
    sp.set_defaults(fn=cmd_create_labelfile)

    for cmd, fn, help_ in (
        ("upgrade_net_proto_text", cmd_upgrade_net_proto_text,
         "migrate a legacy net prototxt (V0/V1 -> current)"),
        ("upgrade_net_proto_binary", cmd_upgrade_net_proto_binary,
         "migrate a legacy binary NetParameter/caffemodel (V1 -> current)"),
        ("upgrade_solver_proto_text", cmd_upgrade_solver_proto_text,
         "migrate a legacy solver prototxt (solver_type enum -> type)"),
    ):
        sp = sub.add_parser(cmd, help=help_)
        sp.add_argument("input")
        sp.add_argument("output")
        sp.set_defaults(fn=fn)

    sp = sub.add_parser("parse_log", help="training log -> .train/.test CSVs")
    sp.add_argument("logfile")
    sp.add_argument("out_dir", nargs="?", default=None,
                    help="output directory (default: next to the log)")
    sp.add_argument("--delimiter", default=",")
    sp.set_defaults(fn=cmd_parse_log)

    sp = sub.add_parser("plot_training_log",
                        help="training log -> chart PNG (types 0-7)")
    sp.add_argument("chart_type", type=int,
                    help="0/1 test acc, 2/3 test loss, 4/5 train lr, "
                    "6/7 train loss (vs iters/seconds)")
    sp.add_argument("out", help="output .png")
    sp.add_argument("logfile")
    sp.set_defaults(fn=cmd_plot_training_log)

    sp = sub.add_parser("resize_images",
                        help="resize-shorter-side + center-crop a tree")
    sp.add_argument("--input-folder", required=True)
    sp.add_argument("--output-folder", required=True)
    sp.add_argument("--side", type=int, default=256)
    sp.add_argument("--workers", type=int, default=0)
    sp.set_defaults(fn=cmd_resize_images)

    for cmd, repl in (
        ("train_net", "train --solver=... [--snapshot=...]"),
        ("finetune_net", "train --solver=... [--weights=...]"),
        ("test_net", "test --solver=... [--snapshot=...]"),
        ("net_speed_benchmark", "time --solver=... [--iterations=50]"),
    ):
        sp = sub.add_parser(cmd, help=f"deprecated: use tpunet {repl.split()[0]}")
        sp.add_argument("ignored", nargs="*")
        sp.set_defaults(fn=_cmd_deprecated(repl))

    from sparknet_tpu import pods as _pods

    _pods.add_parser(sub)

    sp = sub.add_parser("bench", help="headline training-throughput benchmark")
    sp.add_argument("--model", default="",
                    help="alexnet|caffenet|googlenet|resnet50|vgg16")
    sp.add_argument("--batch", type=int, default=0)
    sp.add_argument("--dtype", default="",
                    choices=["", "bf16", "bfloat16", "f32"])
    sp.set_defaults(fn=cmd_bench)

    sp = sub.add_parser("serve", help="AOT-batched serving load run")
    sp.add_argument("--requests", type=int, default=504)
    sp.add_argument("--family", default="cifar10_quick",
                    help="cifar10_quick|lenet|mobilenet|transformer")
    sp.add_argument("--arm", default="f32",
                    choices=["f32", "fold_bn", "int8"])
    sp.add_argument("--buckets", default="1,8,64,256",
                    help="comma-separated AOT bucket ladder")
    sp.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="deadline bound on any request's queue wait")
    sp.add_argument("--replicas", type=int, default=1,
                    help="K > 1 serves through the replica pod "
                         "(ReplicaRouter, open-loop arrivals)")
    sp.add_argument("--rate", type=float, default=2000.0,
                    help="pod mode: offered open-loop req/s")
    sp.add_argument("--seconds", type=float, default=1.0,
                    help="pod mode: open-loop run length")
    sp.add_argument("--controller", action="store_true",
                    help="pod mode: arm the SLO burn controller "
                         "(loop/autoctl.py — priced join/kill off the "
                         "live burn stream; docs/CONTROL.md)")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser(
        "loop", help="train-to-serve production loop (hot reload)")
    sp.add_argument("--iterations", type=int, default=1,
                    help="train->checkpoint->rollout cycles")
    sp.add_argument("--rounds", type=int, default=2,
                    help="elastic rounds per rollout")
    sp.add_argument("--family", default="cifar10_quick",
                    help="cifar10_quick|lenet|mobilenet|transformer")
    sp.add_argument("--arm", default="f32",
                    choices=["f32", "fold_bn", "int8"])
    sp.add_argument("--buckets", default="1,8",
                    help="comma-separated AOT bucket ladder")
    sp.add_argument("--width", type=int, default=4,
                    help="elastic worker-pool width")
    sp.add_argument("--tau", type=int, default=2,
                    help="local steps per elastic round")
    sp.add_argument("--requests", type=int, default=48,
                    help="in-flight traffic across the cycle")
    sp.add_argument("--max-wait-ms", type=float, default=5.0)
    sp.add_argument("--workdir", default="",
                    help="checkpoint dir (default: a temp dir)")
    sp.add_argument("--controller", action="store_true",
                    help="arm the SLO burn controller (loop/autoctl.py "
                         "— lend/restore training width + canary "
                         "rollback; docs/CONTROL.md)")
    sp.set_defaults(fn=cmd_loop)

    sp = sub.add_parser("device_query", help="show devices")
    sp.set_defaults(fn=cmd_device_query)

    args = p.parse_args(argv)
    from sparknet_tpu.common import enable_compile_cache, force_platform

    if args.platform:
        force_platform(args.platform)
    enable_compile_cache()
    if args.obs:
        # env is the single arming point the Recorder (and any child
        # process the brew spawns, e.g. a process feed) already reads
        os.environ["SPARKNET_OBS"] = args.obs
    overrides = {}
    if getattr(args, "dtype", ""):
        # one application point for every brew that takes --dtype
        # (train/test/time/bench): the global compute dtype must be set
        # before any net is built or jitted — and RESTORED afterwards,
        # because the CLI process may outlive the call (in-process
        # cli.main() from tests or interactive use must not leak bf16
        # into the caller's global config)
        import jax.numpy as jnp

        overrides["compute_dtype"] = (
            jnp.bfloat16 if args.dtype in ("bf16", "bfloat16")
            else jnp.float32)
    if getattr(args, "layout", ""):
        # same discipline for the internal layout knob (ops/layout.py):
        # trace-time config, scoped to this brew
        overrides["layout"] = args.layout
    if getattr(args, "feed", ""):
        # host feed architecture (data/pipeline.py) — scoped like layout
        overrides["feed"] = args.feed
    return args, overrides


if __name__ == "__main__":
    sys.exit(main())
