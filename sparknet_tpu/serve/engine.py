"""The serving engine: AOT bucket programs + priced multi-model residency.

One ``ServeEngine`` holds several zoo models resident at once.  Loading
a model (a) prices its worst-case bucket footprint against the banked
batch-fit table and REFUSES over-HBM loads outright (residency.py),
then (b) pre-compiles one forward program per batch bucket via
``jax.jit(...).lower().compile()`` so steady-state traffic never traces
or compiles anything: a mid-serve recompile would put a full compile on
a request's path.

Request flow: ``submit`` -> per-model ``DynamicBatcher`` -> a flush
(bucket-full or ``max_wait_ms`` deadline) -> zero-padded assembly into
the smallest fitting bucket -> one executable call -> per-row results.
Eval-mode forwards have no cross-example ops, so padded rows change
NOTHING about real rows: batched output row i is bit-identical to a
batch-1 run (the EXACT gate, tests/test_serve.py).

Deploy arms ride the existing inference paths unchanged (and in the
DeployNet ordering — fold BEFORE quantize, models/deploy.py):

* ``f32``     — plain TEST-phase forward.
* ``fold_bn`` — BN(+Scale) chains folded into producers (fold_bn.py).
* ``int8``    — fold, calibrate on synthetic batches, then PTQ via
  ``quant.quantized_inference`` — active at TRACE time, so the engine
  enters it around ``.lower()`` (the quant.py contract).

Every device wall is journaled as a fenced obs span and every request
lands a ``request`` event (queue_wait / batch_assembly / device /
total) — the p50/p99 material tools/serve_bench.py and the obs report
roll up.

Hot reload (the sparknet_tpu/loop production path): ``build_candidate``
AOT-compiles a replacement's whole bucket ladder on the CALLER's thread
— a rollout builder, never the request path — then ``swap_model``
replaces the incumbent atomically under the engine's pump lock and
drains the incumbent's pending tickets with the incumbent's OWN
executables (zero dropped tickets, none served by a torn model).  The
retired model stays resident for one generation so ``rollback``
restores it — same object, same executables, bitwise-identical scores.
Both transitions journal ``serve`` rollout/rollback events, and
``serve_path_compiles`` counts backend compilations attributed (per
thread, obs/sentinel.py) to executable calls — the loop dryrun pins it
at zero across swaps.

ref: apps/FeaturizerApp.scala:1 (the reference's batch-scoring
inference app — RDD-throughput-shaped; the queue/deadline/AOT machinery
is new TPU-first surface).
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

from sparknet_tpu._chaoslock import named_rlock
from sparknet_tpu.serve.batcher import DynamicBatcher, Ticket
from sparknet_tpu.serve.residency import AdmissionPolicy, load_fit_table

__all__ = [
    "SERVE_BUCKETS",
    "SHED_TICK_MS",
    "AdmissionRefused",
    "ServeEngine",
    "ServedModel",
    "build_serve_program",
]

# the AOT bucket ladder: 1 (pure-latency floor), 8 (trickle), 64
# (steady), 256 (the headline throughput batch — models.BENCH_CROPS'
# alexnet shape).  Powers expose padding fractions <= 50% above the
# previous rung, and four programs keep model-load compile time and
# per-model executable residency small.
SERVE_BUCKETS = (1, 8, 64, 256)

# the 1-bucket executes at an internal batch of 2: XLA lowers a
# single-row dot to a gemv whose reduction order differs from the
# batched gemm, so a true batch-1 program is NOT bit-identical to the
# batched buckets — one permanently-zero pad row restores bitwise
# batch-invariance across the whole ladder (the EXACT gate's
# foundation; measured on the CPU mesh, docs/SERVING.md "Exactness").
EXEC_FLOOR = 2


def exec_batch(bucket: int) -> int:
    """The batch a bucket's program is actually compiled at."""
    return max(int(bucket), EXEC_FLOOR)


# one pump tick (ms): the grace the shed gate adds on top of
# max_wait_ms — a flush decision is at most one scheduling tick away,
# so an admitted request can legitimately wait max_wait_ms + one tick.
# Matches tools/serve_bench.py's deadline-bound convention.
SHED_TICK_MS = 15.0


def _exactness_compiler_options() -> dict | None:
    """Per-compile options pinning the EXACT gate on the CPU backend.

    Threaded Eigen gemm partitions its reduction by the batch dimension,
    so the same row summed inside an m=2 program and an m=8 program can
    round differently — exactly the cross-bucket parity the serving
    contract promises.  Single-threading Eigen restores a deterministic
    per-row reduction order across the latency buckets.  The TPU MXU's
    systolic reduction is batch-invariant by architecture, so chips get
    no option (docs/SERVING.md "Exactness")."""
    if jax.default_backend() == "cpu":
        return {"xla_cpu_multi_thread_eigen": False}
    return None

_ARMS = ("f32", "fold_bn", "int8")


class AdmissionRefused(RuntimeError):
    """A model load the batch-fit table predicts won't fit resident HBM
    (the verdict dict rides on ``.verdict``)."""

    def __init__(self, verdict: dict):
        self.verdict = verdict
        super().__init__(
            f"model load refused: {verdict['family']} at bucket "
            f"{verdict['max_bucket']} predicts "
            f"{verdict['predicted_bytes']:,} B next to "
            f"{verdict['resident_bytes']:,} B resident — over the "
            f"{verdict['budget_bytes']:,} B usable-HBM budget")


# ---------------------------------------------------------------------------
# Forward-program construction (shared with parallel/modes.py serve_b*)
# ---------------------------------------------------------------------------


def _score_blob(network) -> str:
    """The blob the engine returns per request: the score/logits blob —
    the first loss/accuracy layer's non-label bottom (every zoo
    classifier wires ``score, label -> loss``), else the net's last
    declared output (label-free families like the autoencoder)."""
    for layer in network.layers:
        if "label" in layer.bottoms:
            return next(b for b in layer.bottoms if b != "label")
    return network.output_blobs()[-1]


def _end_layer(network, blob: str) -> str:
    """The last layer producing ``blob`` — where the serve forward stops
    (in-place chains rebind a blob several times; the LAST producer is
    the value consumers see, compiler/graph.py apply contract)."""
    name = None
    for layer in network.layers:
        if blob in layer.tops:
            name = layer.name
    if name is None:
        raise ValueError(f"no layer produces blob {blob!r}")
    return name


def _forward_fn(network, blob: str, end: str):
    def forward(variables, feeds):
        blobs, _, _ = network.apply(
            variables, feeds, rng=None, train=False, end=end)
        return blobs[blob]
    return forward


def _family(family_name: str):
    from sparknet_tpu.models.zoo import GRAPH_SWEEP_FAMILIES

    if family_name not in GRAPH_SWEEP_FAMILIES:
        raise KeyError(
            f"unknown zoo family {family_name!r}; serveable families: "
            f"{sorted(GRAPH_SWEEP_FAMILIES)}")
    return GRAPH_SWEEP_FAMILIES[family_name]


def _synthetic_feeds(family, batch: int, seed: int = 0) -> dict:
    """Batcher-shaped synthetic feeds (same generator as the graph
    sweep's — parallel/modes.py ``_feeds_for``)."""
    from sparknet_tpu.parallel.modes import _feeds_for

    return _feeds_for(family, batch, np.random.RandomState(seed))


def build_serve_program(family_name: str = "cifar10_quick",
                        bucket: int = 1, seed: int = 0):
    """The EXACT f32 forward the engine AOT-compiles for one bucket,
    exposed for the graph/mem contract twins (``serve_b{N}`` in
    parallel/modes.py): ``(jit_fn, variables, feeds, alt_feeds)`` where
    ``alt_feeds`` carries identical shapes with different values — the
    recompile-hazard audit's second lowering."""
    import jax.numpy as jnp

    from sparknet_tpu.common import Phase
    from sparknet_tpu.compiler.graph import Network

    family = _family(family_name)
    batch = exec_batch(bucket)
    network = Network(family.net(batch), Phase.TEST)
    variables = network.init(jax.random.key(seed))
    blob = _score_blob(network)
    fn = jax.jit(_forward_fn(network, blob, _end_layer(network, blob)))
    feeds = {k: jnp.asarray(v)
             for k, v in _synthetic_feeds(family, batch, seed).items()}
    alt_feeds = {k: jnp.asarray(v)
                 for k, v in _synthetic_feeds(family, batch,
                                              seed + 1).items()}
    return fn, variables, feeds, alt_feeds


# ---------------------------------------------------------------------------
# Served model: per-arm variables + one compiled executable per bucket
# ---------------------------------------------------------------------------


class ServedModel:
    """One resident model: arm-transformed variables, a compiled
    executable per bucket, and its own request batcher.

    ``variables`` injects trained weights (a blob-wise ``NetVars`` —
    e.g. the loop's checkpoint round-trip, loop/deploy.py) instead of
    the seed init; the arm transforms (fold/calibrate) apply to them
    identically.  ``version``/``previous`` are the hot-reload lineage
    the engine maintains: a swapped-in candidate points at the model it
    replaced until the next swap retires it or a rollback restores it.
    """

    def __init__(self, name: str, family_name: str, arm: str,
                 buckets: tuple, max_wait_ms: float, clock,
                 predicted_bytes: int, seed: int = 0,
                 calibration_batches: int = 2, variables=None,
                 device=None):
        from sparknet_tpu.common import Phase
        from sparknet_tpu.compiler.graph import Network, NetVars
        from sparknet_tpu.ops.layout import internal_shape

        self.name = name
        self.family_name = family_name
        self.arm = arm
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.predicted_bytes = int(predicted_bytes)
        # the replica-group placement (serve/router.py): each copy's
        # variables and example shardings pin to ONE mesh device, so K
        # replicas' executables dispatch to K distinct chips; None keeps
        # the single-copy default-device behavior bit-identical
        self.device = device
        self.batcher = DynamicBatcher(self.buckets, max_wait_ms, clock)
        self.qstate: dict | None = None
        self.version = 0
        self.previous: "ServedModel | None" = None

        family = _family(family_name)
        self.family = family
        if family.feed == "tokens":
            self.item_shape: tuple = (family.seq_len,)
            self.item_dtype = np.int32
        else:
            self.item_shape = internal_shape(
                (1, *family.image_shape))[1:]
            self.item_dtype = np.float32

        base = Network(family.net(self.buckets[0]), Phase.TEST)
        if variables is None:
            self.variables = base.init(jax.random.key(seed))
        else:
            # trained weights, host-materialized blob-wise: the serve
            # programs lower against THIS pytree, so the signature is
            # consistent between build and execute by construction
            self.variables = NetVars(
                params={ln: [np.asarray(p) for p in plist]
                        for ln, plist in variables.params.items()},
                state={ln: {k: np.asarray(v) for k, v in s.items()}
                       for ln, s in variables.state.items()})

        def network_for(bucket: int):
            net_param = family.net(exec_batch(bucket))
            if arm in ("fold_bn", "int8"):
                from sparknet_tpu.models.fold_bn import fold_batchnorm

                folded_net, params, state, _ = fold_batchnorm(
                    net_param, self.variables.params,
                    self.variables.state)
                return Network(folded_net, Phase.TEST), \
                    NetVars(params=params, state=state)
            return Network(net_param, Phase.TEST), self.variables

        # arm transforms happen ONCE, at the smallest bucket (the fold
        # algebra and the calibration stream are batch-invariant); every
        # bucket then serves the same variables pytree bit-for-bit
        net0, self.variables = network_for(self.buckets[0])
        if arm == "int8":
            from sparknet_tpu import quant

            self.qstate = quant.calibrate(
                net0, self.variables,
                (_synthetic_feeds(family, 8, seed=s + 1)
                 for s in range(calibration_batches)),
                num_batches=calibration_batches)

        if device is not None:
            self.variables = jax.device_put(self.variables, device)

        self.score_blob = _score_blob(net0)
        self.executables: dict[int, object] = {}
        self.compile_wall_s = 0.0
        t0 = time.perf_counter()
        for bucket in self.buckets:
            net_b, _ = network_for(bucket)
            fn = _forward_fn(net_b, self.score_blob,
                             _end_layer(net_b, self.score_blob))
            ctx = (quant_ctx(self.qstate) if arm == "int8"
                   else contextlib.nullcontext())
            example = self._example_feeds(bucket)
            with ctx:
                lowered = jax.jit(fn).lower(self.variables, example)
            self.executables[bucket] = lowered.compile(
                compiler_options=_exactness_compiler_options())
        self.compile_wall_s = time.perf_counter() - t0

        # rolled per-request latencies (ms), the serve_bench material
        self.lat_total_ms: list[float] = []
        self.lat_queue_ms: list[float] = []
        self.lat_device_ms: list[float] = []
        self.requests = 0
        self.batches = 0
        self.padded_rows = 0

    def _example_feeds(self, bucket: int) -> dict:
        """Shape/dtype templates for ``.lower()`` — abstract structs, so
        AOT compilation allocates nothing batch-sized.  Shaped at the
        EXEC batch (>= EXEC_FLOOR), not the ladder bucket."""
        n = exec_batch(bucket)
        sharding = (jax.sharding.SingleDeviceSharding(self.device)
                    if self.device is not None else None)
        data = jax.ShapeDtypeStruct((n, *self.item_shape),
                                    self.item_dtype, sharding=sharding)
        label = jax.ShapeDtypeStruct((n,), np.int32, sharding=sharding)
        return {"data": data, "label": label}


def quant_ctx(qstate: dict):
    from sparknet_tpu import quant

    return quant.quantized_inference(qstate)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Multi-model serving front end: priced loads, dynamic batching,
    AOT-bucket execution, per-request telemetry.

    ``clock`` is injectable (batcher deadline tests drive a fake one);
    device walls always come from the real ``time.perf_counter`` and
    are fence-stamped — the injectable clock orders queue events, it
    never times the chip.
    """

    def __init__(self, buckets: tuple = SERVE_BUCKETS,
                 max_wait_ms: float = 5.0, *,
                 fit_table: dict | None = None,
                 hbm_bytes: int | None = None,
                 clock=time.monotonic,
                 calibration_batches: int = 2,
                 device=None):
        from sparknet_tpu.analysis.mem_model import V5E_HBM_BYTES

        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.max_wait_ms = float(max_wait_ms)
        self.clock = clock
        self.calibration_batches = int(calibration_batches)
        # replica placement: every model this engine loads pins its
        # variables + executables to this one device (router.py gives
        # each replica its own engine on its own mesh device)
        self.device = device
        self.policy = AdmissionPolicy(
            fit_table if fit_table is not None else load_fit_table(),
            hbm_bytes=hbm_bytes or V5E_HBM_BYTES)
        self._models: dict[str, ServedModel] = {}
        self._resident_bytes = 0
        self._closed = False
        # the pump lock: makes a hot swap atomic against submits — a
        # ticket lands either in the retiring model's queue (drained by
        # the swap, served by the OLD executables) or the candidate's,
        # never in a drained queue.  Execution itself runs outside the
        # lock (a captured ServedModel is immutable after construction),
        # so the swap-gap is the dict flip + queue steal, not a device
        # call.
        self._lock = named_rlock("ServeEngine._lock")
        # backend compilations attributed to executable calls (the
        # serving path), per-thread-accounted via obs/sentinel.py; the
        # AOT contract — and the loop dryrun's gate — is that this
        # never moves after warmup, rollouts included.
        self.serve_path_compiles = 0
        # deadline-shed ledger (batcher.shed): rejections are journaled
        # THROTTLED — at most one ``serve/shed`` line per interval with
        # the count since the last line — so a saturating loadgen can't
        # swamp the journal with per-ticket rejections
        self.shed_total = 0
        self._shed_pending = 0
        self._shed_last_emit: float | None = None
        self._shed_emit_interval_s = 0.25

    # -- model lifecycle ---------------------------------------------------

    def resident_bytes(self) -> int:
        return self._resident_bytes

    def models(self) -> list[str]:
        return list(self._models)

    def load_model(self, name: str, family: str = "cifar10_quick",
                   arm: str = "f32", buckets: tuple | None = None,
                   seed: int = 0, variables=None) -> ServedModel:
        """Price, maybe refuse, else AOT-compile every bucket.  The
        refusal happens BEFORE any jax work — a refused load journals
        its verdict and costs zero compile seconds and zero dials.
        ``variables`` seeds the load with existing weights instead of
        the seed init — a JOINING replica copies the live copy's
        weights so the pool stays score-consistent (router.py)."""
        from sparknet_tpu.obs.recorder import get_recorder

        if arm not in _ARMS:
            raise ValueError(f"unknown arm {arm!r}; one of {_ARMS}")
        if name in self._models:
            raise ValueError(f"model {name!r} already resident")
        buckets = tuple(sorted(set(buckets or self.buckets)))
        rec = get_recorder()
        verdict = self.policy.admit(family, buckets[-1],
                                    self._resident_bytes)
        if not verdict["fits"]:
            rec.emit(
                "serve", kind="load_refused", model=name, family=family,
                arm=arm, buckets=list(buckets),
                predicted_bytes=verdict["predicted_bytes"],
                resident_bytes=verdict["resident_bytes"],
                budget_bytes=verdict["budget_bytes"],
                note="batch-fit table predicts over-HBM residency — "
                     "refused before any compile (queue pre-flight "
                     "policy at serve time)")
            raise AdmissionRefused(verdict)
        model = ServedModel(
            name, family, arm, buckets, self.max_wait_ms, self.clock,
            verdict["predicted_bytes"], seed=seed,
            calibration_batches=self.calibration_batches,
            variables=variables, device=self.device)
        with self._lock:
            self._models[name] = model
            self._resident_bytes += model.predicted_bytes
        from sparknet_tpu.obs import lineage as obs_lineage

        # lineage: this load defines generation v0.  Seed-initialized
        # weights are a ROOT (seed:<n>); injected weights adopt the
        # caller's ambient parent when one is pushed (a joining replica
        # copying the live weights, a test harness), else stay parentless
        lin: dict = {"span": obs_lineage.generation_span(
            name, model.version)}
        parent = obs_lineage.current_parent() or (
            obs_lineage.seed_root(seed) if variables is None else None)
        if parent:
            lin["parent"] = parent
        rec.emit(
            "serve", kind="model_loaded", model=name, family=family,
            arm=arm, buckets=list(model.buckets),
            predicted_bytes=model.predicted_bytes,
            resident_bytes=self._resident_bytes,
            budget_bytes=verdict["budget_bytes"],
            wall_s=round(model.compile_wall_s, 6),
            lineage=lin,
            note="all buckets AOT-compiled at load "
                 "(jit().lower().compile())")
        return model

    def unload_model(self, name: str) -> None:
        from sparknet_tpu.obs.recorder import get_recorder

        with self._lock:
            model = self._models.pop(name)
            self._resident_bytes -= model.predicted_bytes
            if model.previous is not None:
                self._resident_bytes -= model.previous.predicted_bytes
                model.previous = None
        model.batcher.close(drain=False)
        get_recorder().emit(
            "serve", kind="model_unloaded", model=name,
            family=model.family_name, arm=model.arm,
            resident_bytes=self._resident_bytes)

    # -- hot reload (the sparknet_tpu/loop rollout path) -------------------

    def build_candidate(self, name: str, family: str = "cifar10_quick",
                        arm: str = "f32", buckets: tuple | None = None,
                        variables=None, seed: int = 0) -> ServedModel:
        """AOT-compile a replacement for resident model ``name`` OFF the
        request path: every bucket executable compiles on the CALLER's
        thread (the rollout builder) before anything touches the live
        engine.  Priced first against the CURRENT resident set — the
        incumbent stays resident through the rollback window, so both
        generations must fit; an over-budget candidate raises
        :class:`AdmissionRefused` with the verdict journaled and the
        incumbent untouched (refused, not fatal)."""
        from sparknet_tpu.obs.recorder import get_recorder

        if arm not in _ARMS:
            raise ValueError(f"unknown arm {arm!r}; one of {_ARMS}")
        if name not in self._models:
            raise ValueError(
                f"no resident model {name!r} to replace — use "
                "load_model for the first generation")
        buckets = tuple(sorted(set(buckets or self.buckets)))
        rec = get_recorder()
        verdict = self.policy.admit(family, buckets[-1],
                                    self._resident_bytes)
        if not verdict["fits"]:
            rec.emit(
                "serve", kind="load_refused", model=name, family=family,
                arm=arm, buckets=list(buckets),
                predicted_bytes=verdict["predicted_bytes"],
                resident_bytes=verdict["resident_bytes"],
                budget_bytes=verdict["budget_bytes"],
                note="rollout candidate refused by the batch-fit "
                     "pricing — incumbent keeps serving, zero compile "
                     "seconds spent")
            raise AdmissionRefused(verdict)
        candidate = ServedModel(
            name, family, arm, buckets, self.max_wait_ms, self.clock,
            verdict["predicted_bytes"], seed=seed,
            calibration_batches=self.calibration_batches,
            variables=variables, device=self.device)
        from sparknet_tpu.obs import lineage as obs_lineage

        fields: dict = {}
        parent = obs_lineage.current_parent()
        if parent:
            # the loop pushed its checkpoint span; the candidate has no
            # generation number until the swap, so it carries the edge
            # only (the rollout event names the generation)
            fields["lineage"] = {"parent": parent}
        rec.emit(
            "serve", kind="candidate_built", model=name, family=family,
            arm=arm, buckets=list(candidate.buckets),
            predicted_bytes=candidate.predicted_bytes,
            wall_s=round(candidate.compile_wall_s, 6),
            note="all buckets AOT-compiled on the builder thread — "
                 "zero request-path compiles", **fields)
        return candidate

    def swap_model(self, name: str, candidate: ServedModel) -> dict:
        """Atomically replace resident model ``name`` with a
        pre-compiled ``candidate`` (from :meth:`build_candidate`).

        Under the pump lock: the routing flips (new submits land in the
        candidate's batcher) and the incumbent's pending tickets are
        stolen; the lock is then released and those tickets execute with
        the incumbent's OWN executables — every submitted ticket
        resolves, none through a half-swapped model.  The incumbent is
        retained as ``candidate.previous`` (one rollback generation;
        the grandparent retires and its bytes are released).  Journals a
        ``serve`` rollout event; returns swap telemetry."""
        from sparknet_tpu.obs.recorder import get_recorder

        t0 = time.perf_counter()
        with self._lock:
            old = self._models[name]
            grand, old.previous = old.previous, None
            candidate.version = old.version + 1
            candidate.previous = old
            self._models[name] = candidate
            self._resident_bytes += candidate.predicted_bytes
            if grand is not None:
                self._resident_bytes -= grand.predicted_bytes
            stale = old.batcher.drain()
        drained = 0
        for batch in stale:
            self._execute(old, batch)
            drained += len(batch)
        wall = time.perf_counter() - t0
        from sparknet_tpu.obs import lineage as obs_lineage

        # lineage: the new generation descends from the loop's ambient
        # checkpoint when one is pushed; a bare swap (router rollout, a
        # test) falls back to the generation it displaced — both parents
        # resolve in-journal
        parent = obs_lineage.current_parent() or \
            obs_lineage.generation_span(name, old.version)
        get_recorder().emit(
            "serve", kind="rollout", model=name,
            family=candidate.family_name, arm=candidate.arm,
            buckets=list(candidate.buckets), version=candidate.version,
            drained=drained, predicted_bytes=candidate.predicted_bytes,
            resident_bytes=self._resident_bytes,
            wall_s=round(wall, 6),
            lineage={"span": obs_lineage.generation_span(
                         name, candidate.version),
                     "parent": parent},
            note="hot swap under the pump lock — incumbent drained "
                 "with its own executables, zero dropped tickets")
        return {"version": candidate.version, "drained": drained,
                "swap_wall_s": wall}

    def rollback(self, name: str) -> ServedModel:
        """Restore the previous generation of resident model ``name`` —
        the SAME ``ServedModel`` object the last swap retired, its
        executables and variables untouched, so post-rollback scores are
        bitwise-identical to pre-rollout scores.  The rolled-back
        candidate's pending tickets drain through the candidate's own
        executables first (zero dropped tickets, symmetrically with the
        swap).  Journals a ``serve`` rollback event."""
        from sparknet_tpu.obs.recorder import get_recorder

        with self._lock:
            cur = self._models[name]
            prev = cur.previous
            if prev is None:
                raise RuntimeError(
                    f"model {name!r} has no previous generation to "
                    "roll back to")
            cur.previous = None
            self._models[name] = prev
            self._resident_bytes -= cur.predicted_bytes
            stale = cur.batcher.drain()
        drained = 0
        for batch in stale:
            self._execute(cur, batch)
            drained += len(batch)
        from sparknet_tpu.obs import lineage as obs_lineage

        get_recorder().emit(
            "serve", kind="rollback", model=name,
            family=prev.family_name, arm=prev.arm,
            buckets=list(prev.buckets), version=prev.version,
            drained=drained, resident_bytes=self._resident_bytes,
            lineage={"span": obs_lineage.generation_span(
                name, prev.version)},
            note="previous ServedModel restored bitwise (same object, "
                 "same executables); rolled-back candidate drained "
                 "with its own executables")
        return prev

    # -- request path ------------------------------------------------------

    def submit(self, model_name: str, item, *,
               shed: bool = False) -> Ticket | None:
        """Enqueue one request (a single example, item-shaped).  Holds
        the pump lock across lookup + enqueue so a concurrent hot swap
        can never strand the ticket in an already-drained queue.

        ``shed=True`` routes through the batcher's deadline-aware
        admission (batcher.shed): a request whose projected queue wait
        already exceeds ``max_wait_ms`` + one pump tick is REJECTED —
        returns None, counts on ``shed_total``, and journals a
        throttled ``serve/shed`` line — instead of growing p99."""
        with self._lock:
            model = self._models[model_name]
            item = np.asarray(item, model.item_dtype)
            if item.shape != model.item_shape:
                raise ValueError(
                    f"request shape {item.shape} != model item shape "
                    f"{model.item_shape}")
            if not shed:
                return model.batcher.submit(item)
            ticket = model.batcher.shed(item, tick_ms=SHED_TICK_MS)
            if ticket is not None:
                return ticket
            self._note_shed_locked(model_name, model, 1)
        return None

    def submit_many(self, model_name: str, items: list, *,
                    shed: bool = False) -> tuple[list, int]:
        """Chunked request path: the whole arrival chunk lands under
        ONE pump-lock acquisition and one batcher lock (batcher
        ``submit_many``) — the pod-rate submit path, where per-request
        locking alone is measurable against the ~85 us/row serving
        budget.  Returns ``(tickets, shed_n)``; the shed tail journals
        through the same throttled ``serve/shed`` ledger as
        :meth:`submit`."""
        with self._lock:
            model = self._models[model_name]
            payloads = []
            for item in items:
                item = np.asarray(item, model.item_dtype)
                if item.shape != model.item_shape:
                    raise ValueError(
                        f"request shape {item.shape} != model item "
                        f"shape {model.item_shape}")
                payloads.append(item)
            tickets, n_shed = model.batcher.submit_many(
                payloads, shed=shed, tick_ms=SHED_TICK_MS)
            if n_shed:
                self._note_shed_locked(model_name, model, n_shed)
        return tickets, n_shed

    def _note_shed_locked(self, model_name: str, model,
                          n: int) -> None:
        """Count ``n`` rejections and journal a throttled
        ``serve/shed`` line (at most one per interval, carrying the
        count since the previous line).  Caller holds the pump lock."""
        self.shed_total += n
        self._shed_pending += n
        now = self.clock()
        due = (self._shed_last_emit is None
               or now - self._shed_last_emit
               >= self._shed_emit_interval_s)
        if not due:
            return
        pending, self._shed_pending = self._shed_pending, 0
        self._shed_last_emit = now
        projected = model.batcher.last_projected_ms
        from sparknet_tpu.obs.recorder import get_recorder

        get_recorder().emit(
            "serve", kind="shed", model=model_name,
            shed=pending, projected_wait_ms=round(projected, 3),
            tick_ms=SHED_TICK_MS,
            note="deadline-aware admission: projected queue wait over "
                 "max_wait_ms + one pump tick — rejected, not queued "
                 "(count aggregated since the previous shed line)")

    def infer(self, model_name: str, item,
              timeout: float | None = 60.0):
        """Synchronous single-request path: submit, flush immediately
        (bucket 1 — no batching win to wait for), return the scores."""
        ticket = self.submit(model_name, item)
        self.pump(force=True)
        return ticket.wait(timeout)

    def pump(self, force: bool = False,
             max_batches: int | None = None) -> int:
        """Drain every model's due batches on the caller's thread;
        returns the number of batches executed.  The synchronous twin of
        :meth:`serve_forever` — tests, the dryrun, and closed-loop
        benches drive this directly.

        ``max_batches`` caps the batches taken PER MODEL in this call.
        A pod pump sweeping several replicas passes 1 (router.py): an
        uncapped drain of a continuously-fed queue never exits — the
        JSQ router keeps routing to the replica being drained (its
        depth keeps hitting zero), and every other replica's tickets
        age unserved for the whole feedback loop."""
        executed = 0
        for model in list(self._models.values()):
            taken = 0
            while max_batches is None or taken < max_batches:
                batch = model.batcher.take(force=force)
                if batch is None:
                    break
                self._execute(model, batch)
                taken += 1
            executed += taken
        return executed

    def serve_forever(self, until=None, poll_s: float = 0.05) -> int:
        """Worker loop: block on flush deadlines, execute batches, exit
        when ``until()`` goes truthy (or the engine shuts down).
        Returns batches executed."""
        executed = 0
        while not self._closed and not (until and until()):
            ready = False
            for model in list(self._models.values()):
                if model.batcher.wait_due(timeout=poll_s):
                    ready = True
                    break
            if ready:
                executed += self.pump()
        return executed

    def shutdown(self) -> int:
        """Drain: every in-flight request is executed before the engine
        stops accepting work — zero requests lost (the batcher close
        contract).  Returns requests served during the drain."""
        from sparknet_tpu.obs.recorder import get_recorder

        self._closed = True
        drained = 0
        for model in list(self._models.values()):
            for batch in model.batcher.close(drain=True):
                self._execute(model, batch)
                drained += len(batch)
        get_recorder().emit(
            "serve", kind="shutdown", requests=drained,
            note="queue drained on shutdown — zero in-flight requests "
                 "lost")
        return drained

    # -- execution ---------------------------------------------------------

    def _execute(self, model: ServedModel, tickets: list) -> None:
        """One padded-bucket executable call; resolves every ticket and
        journals its request record."""
        from sparknet_tpu.obs.recorder import get_recorder

        rec = get_recorder()
        bucket = tickets[0].bucket
        n = exec_batch(bucket)
        asm0 = time.perf_counter()
        data = np.zeros((n, *model.item_shape), model.item_dtype)
        for i, t in enumerate(tickets):
            data[i] = t.payload
        label = np.zeros((n,), np.int32)
        asm_ms = (time.perf_counter() - asm0) * 1e3
        from sparknet_tpu.obs.sentinel import get_sentinel

        sentinel = get_sentinel()
        compiles0 = sentinel.thread_count()
        dev0 = time.perf_counter()
        try:
            with rec.span("serve_device",
                          note=f"{model.name}/b{bucket}") as sp:
                out = model.executables[bucket](
                    model.variables, {"data": data, "label": label})
                # np.asarray on the executable's own output buffer IS
                # the value fence (common.value_fence mechanism) — the
                # whole batch is fetched anyway to scatter rows back
                out_np = np.asarray(out)
                sp.fence_value(float(out_np.ravel()[-1]))
        except Exception as e:
            for t in tickets:
                t.resolve(error=e)
            raise
        device_ms = (time.perf_counter() - dev0) * 1e3
        # per-THREAD attribution: a concurrent rollout builder's
        # compiles land on its own thread's counter, so a nonzero delta
        # here can only mean the executable call itself compiled — the
        # exact AOT violation the loop dryrun gates on.  The delta is
        # computed BEFORE taking the engine lock so the sentinel's own
        # lock is never acquired under it (keeps the static acquisition
        # graph free of an Engine->Sentinel edge).
        compile_delta = sentinel.thread_count() - compiles0
        with self._lock:
            self.serve_path_compiles += compile_delta
        now = self.clock()
        model.batches += 1
        model.padded_rows += bucket - len(tickets)
        # the per-request emit is guarded, not just no-op'd: at pod
        # offered rates the kwargs construction alone is measurable
        # against the ~85 us/row budget when the journal is disarmed
        emit = rec.emit if rec.enabled else None
        # one shared lineage dict per BATCH, not per ticket: the parent
        # generation id is the same for every row, and at pod rates a
        # per-request dict build is measurable
        lineage = ({"parent": f"gen:{model.name}:v{model.version}"}
                   if emit is not None else None)
        for i, t in enumerate(tickets):
            t.t_done = now
            queue_ms = max(0.0, (t.t_batch - t.t_submit) * 1e3)
            total_ms = queue_ms + asm_ms + device_ms
            t.resolve(result=out_np[i])
            model.requests += 1
            model.lat_total_ms.append(total_ms)
            model.lat_queue_ms.append(queue_ms)
            model.lat_device_ms.append(device_ms)
            if emit is not None:
                emit(
                    "request", model=model.name, bucket=bucket,
                    queue_wait_ms=round(queue_ms, 4),
                    batch_assembly_ms=round(asm_ms, 4),
                    device_ms=round(device_ms, 4),
                    total_ms=round(total_ms, 4),
                    batch_n=len(tickets), padded=bucket > len(tickets),
                    deadline_flush=bool(t.deadline_flush),
                    lineage=lineage)

    # -- telemetry ---------------------------------------------------------

    def stats(self) -> dict:
        """Per-model latency/throughput roll-up (host-side walls)."""
        out: dict = {}
        for name, model in self._models.items():
            out[name] = {
                "family": model.family_name,
                "arm": model.arm,
                "buckets": list(model.buckets),
                "requests": model.requests,
                "batches": model.batches,
                "padded_rows": model.padded_rows,
                "predicted_bytes": model.predicted_bytes,
                "p50_ms": percentile(model.lat_total_ms, 50),
                "p99_ms": percentile(model.lat_total_ms, 99),
                "queue_p99_ms": percentile(model.lat_queue_ms, 99),
                "device_p50_ms": percentile(model.lat_device_ms, 50),
            }
        return out


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (the latency-report convention: p99 of
    100 samples is the 99th sorted value, no interpolation invented
    between real measurements).  Empty input reads 0.0 so stats paths
    stay arithmetic-safe before any traffic lands."""
    if not values:
        return 0.0
    ordered = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(ordered))))
    return float(ordered[rank - 1])
