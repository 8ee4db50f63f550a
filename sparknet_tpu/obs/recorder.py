"""The obs Recorder: off-by-default JSONL runtime telemetry.

Arm it with ``SPARKNET_OBS=<path>.jsonl`` (the literal ``1`` means
``./obs_journal.jsonl``); anything else — unset, empty, ``0`` — keeps it
OFF, and the off state is a hard contract: instrumented call sites
(``Solver.step``, ``ParallelTrainer.train_round``, bench.py) guard every
JOURNAL touch behind ``if rec:``, so the disabled hot path is
bit-identical — same lowered StableHLO, same dispatch count — which
``tests/test_obs.py`` pins.

A :class:`Span` is also the program's one span type on the PROFILER's
clock: entering it enters a ``jax.profiler.TraceAnnotation`` of the same
name, armed or not, so the ``sn.*`` spans of the feed, the step and the
round lie beside the device ops in any ``tpunet train --profile`` trace
(docs/OBSERVABILITY.md, "Spans on the profiler's clock").  With no
profiler session open the annotation records nothing; it dispatches
nothing either way.  Only the journal line is what ``SPARKNET_OBS``
arms.

And a span keeps what it measured, armed or not, profiler or not: on
exit it appends ``(name, thread ident, start_ns, wall_ns, counts)`` to a
bounded in-memory record that :func:`flight` reads (``start_ns`` is
``time.time_ns()``, the wall clock: a profiler's xplane counts from its
session's start, and a reader anchors one on the other by the step
spans' ``it``; ``wall_ns`` the monotonic duration; ``counts`` the span's
own dict, so what :meth:`Span.set` added rides along).  Set-up and the untraced
window are in it, which no profiler session covers.  It adds two clock
reads and a deque append to a span, no device work and no dispatch
(docs/OBSERVABILITY.md, "The record").

Walls are only evidence when they are FENCE-STAMPED.  A span that
encloses device work must close through :meth:`Span.fence`, which fetches
the VALUE of the producing program's own output via
``common.value_fence`` — a wall closed without a fence times the
enqueue, not the work.
A span that never touches the device declares ``host=True`` instead.
Spans that do neither are journaled with ``fenced: false`` and the
report renderer refuses their walls.  The ``obs-fenced-span`` graftlint
rule machine-checks call sites for the same contract.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time

from sparknet_tpu._chaoslock import named_lock
from sparknet_tpu.obs import schema
from sparknet_tpu.obs.metrics import MetricsHub
from sparknet_tpu.obs.sentinel import get_sentinel

__all__ = ["Recorder", "Span", "feed_counts", "flight", "get_recorder",
           "set_recorder", "stages"]

_ENV = "SPARKNET_OBS"

# loss EMA decay for per-round records: ~"average of the last 10 rounds",
# the observability analog of SolverParameter.average_loss
_EMA_DECAY = 0.9


# the record: the newest FLIGHT_MAX spans of the process, oldest first.
# A 30 s window of the busiest benchmark cell is ~7 k spans.  An append
# is atomic under the interpreter lock; the count beside it is not, and
# two threads that exit a span at once may count one: it only says how
# many spans the bound has dropped
FLIGHT_MAX = 65536
_flight: collections.deque = collections.deque(maxlen=FLIGHT_MAX)
_flight_count = 0

# the stages of a job's set-up, in the order they run, and the stats a
# span opened with ``compile_stats`` carries
SETUP_STAGES = ("sn.main", "sn.setup.net", "sn.solver.build",
                "sn.solver.nets", "sn.solver.init", "sn.trainer.build",
                "sn.feed.open")
COMPILE_STATS = ("compiles", "compile_s", "cache_hits")
# a trace that ends in a jit-cache hit costs ~0.1 ms and compiles
# nothing: under this many seconds, with no compile, a span stays as a
# warm span was
_COMPILE_FLOOR_S = 1e-3


def flight() -> tuple[list, int]:
    """``(spans, dropped)``: a snapshot of the record, oldest first, each
    ``(name, thread ident, start_ns, wall_ns, counts)``, and how many
    older spans the bound has pushed out."""
    count = _flight_count
    spans = list(_flight)
    return spans, max(0, count - len(spans))


def stages(spans, names=SETUP_STAGES) -> list[dict]:
    """The record reduced by stage: for each of ``names`` that ``spans``
    (rows of :func:`flight`, or of its JSON form) holds, in that order,
    ``{"name", "count", "wall_s", "compiles", "compile_s", "cache_hits",
    "stats"}``: the sums over its spans, and under ``stats`` every other
    stat they carry (``params``, ``layers``, ``nets``, ``devices``,
    ``source``) with its values in the spans' order.  The one reduction
    behind ``tpunet train``'s ``set-up:`` line and the benchmark's set-up
    table."""
    rows = []
    for name in names:
        mine = [s for s in spans if s[0] == name]
        if not mine:
            continue
        stats: dict = {}
        for s in mine:
            for k, v in s[4].items():
                if k not in COMPILE_STATS and k != "it":
                    stats.setdefault(k, []).append(v)
        rows.append({
            "name": name, "count": len(mine),
            "wall_s": sum(s[3] for s in mine) / 1e9,
            **{k: sum(s[4].get(k, 0) for s in mine) for k in COMPILE_STATS},
            "stats": stats})
    return rows


_TraceAnnotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use so
    ``sparknet_tpu.obs`` stays import-light."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation


def feed_counts(feeds, lead_axes: int = 1) -> dict:
    """``images``/``bytes`` of one feed dict, as a span's counts:
    images from the first ``lead_axes`` axes of the first array (2 for
    a ``[tau, B, ...]`` round), bytes over every array."""
    images = 0
    for v in feeds.values():
        shape = getattr(v, "shape", ())
        if len(shape) >= lead_axes:
            images = 1
            for n in shape[:lead_axes]:
                images *= int(n)
            break
    return {"images": images,
            "bytes": sum(int(getattr(v, "nbytes", 0)) for v in feeds.values())}


class Span:
    """One wall, on two clocks and in the record.  Use as a context
    manager off :meth:`Recorder.span`.

    On the profiler's clock, always: a ``TraceAnnotation(name,
    **counts)`` is open for as long as the span is (``counts``: ``it``,
    the batch or round index the spans of one batch share, and
    ``images``/``bytes`` where the work has a size; :meth:`set` adds
    what only the work itself can tell).  One span per batch or round —
    never per record, layer or parameter leaf.

    In the record (:func:`flight`), always, with the same counts.

    In the journal, only when the Recorder is armed: close device-work
    spans with :meth:`fence` (or :meth:`fence_value` when the caller
    already materialized the producing program's own output)."""

    __slots__ = ("_rec", "name", "host", "note", "counts", "_ann", "_t0",
                 "_t0_ns", "_compiled0", "_fenced", "_fence_value")

    def __init__(self, rec: "Recorder | None", name: str,
                 host: bool = False, note: str | None = None,
                 step: int | None = None, compile_stats: bool = False,
                 **counts):
        """``step``: the span is one training step or round — a
        ``StepTraceAnnotation`` (``step_num``) on the profiler's clock,
        ``it`` in the record.  ``compile_stats``: the span carries what
        its thread compiled while it was open (``compiles``,
        ``compile_s``, ``cache_hits``: obs/sentinel.py), each only when
        not zero, and installs the compile listener if nothing has."""
        self._rec = rec if (rec is not None and rec.enabled) else None
        self.name = name
        self.host = bool(host)
        self.note = note
        self.counts = counts
        if step is None:
            self._ann = _trace_annotation()(name, **counts)
        else:
            # what jax.profiler.StepTraceAnnotation builds
            self._ann = _trace_annotation()(name, _r=1, step_num=step)
            counts["it"] = step
        self._t0 = 0.0
        self._t0_ns = 0
        # None: not asked for; else the thread's totals at entry
        self._compiled0 = () if compile_stats else None
        self._fenced = False
        self._fence_value: float | None = None

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        if self._compiled0 is not None:
            self._compiled0 = get_sentinel().install().thread_compile()
        self._t0_ns = time.time_ns()
        self._t0 = time.perf_counter()
        return self

    def set(self, **counts) -> None:
        """Counts known only once the work is done (``alloc_bytes``: what
        a read had to allocate), added to the open span on both clocks."""
        self.counts.update(counts)
        self._ann.set_metadata(**counts)

    def fence(self, out) -> float | None:
        """Fence-stamp this span on the VALUE of ``out`` (the enclosed
        program's own output pytree; last leaf must be a small scalar —
        see ``common.value_fence``).  No-op when obs is disabled, so a
        guarded call site stays dispatch-identical."""
        if self._rec is None:
            return None
        from sparknet_tpu.common import value_fence

        self._fence_value = value_fence(out)
        self._fenced = True
        return self._fence_value

    def fence_value(self, value: float) -> float:
        """Fence-stamp with an ALREADY-MATERIALIZED value.  Caller
        contract: ``value`` was fetched from the producing program's own
        output (e.g. ``float(loss_arr)`` on the step's loss) — passing a
        host-computed number here forges the stamp."""
        self._fence_value = float(value)
        self._fenced = True
        return self._fence_value

    def __exit__(self, exc_type, exc, tb) -> None:
        wall = time.perf_counter() - self._t0
        if self._compiled0 is not None:
            now = get_sentinel().thread_compile()
            delta = {k: b - a for k, a, b in zip(
                COMPILE_STATS, self._compiled0, now) if b - a}
            if "compiles" in delta or \
                    delta.get("compile_s", 0.0) >= _COMPILE_FLOOR_S:
                self.set(**delta)
        self._ann.__exit__(exc_type, exc, tb)
        global _flight_count
        _flight.append((self.name, threading.get_ident(), self._t0_ns,
                        int(wall * 1e9), self.counts))
        _flight_count += 1
        if self._rec is None:
            return
        fields: dict = {
            "name": self.name,
            "wall_s": round(wall, 6),
            "fenced": self._fenced and not self.host,
        }
        if self.host:
            fields["host"] = True
        if self._fence_value is not None:
            fields["fence_value"] = self._fence_value
        note = self.note or " ".join(
            f"{k}={v}" for k, v in self.counts.items())
        if note:
            fields["note"] = note
        self._rec._emit_span(fields)


class Recorder:
    """Append-only JSONL journal of schema-validated obs events."""

    def __init__(self, path: str | None, run_id: str | None = None,
                 metrics_flush_every: int = 256):
        self.path = path
        self.enabled = bool(path)
        self._lock = named_lock("Recorder._lock")
        self._started = False
        # the streaming-metrics hub: every journaled event is folded
        # into bounded counters/histograms in-process, and the
        # cumulative state flushes as a periodic ``metrics`` snapshot
        # event (obs/metrics.py) — so reports and `obs top` never need
        # the raw request lines
        self._hub = MetricsHub(metrics_flush_every) if path else None
        self._n_rounds = 0
        self._n_spans = 0
        self._ema: dict[str, float] = {}
        self._warm_modes: set[str] = set()
        self._last_compiles = 0
        self._compiles0 = 0
        self.sentinel = get_sentinel()
        if self.enabled:
            self.run_id = run_id or f"{os.getpid():x}-{time.time_ns() & 0xFFFFFF:06x}"
            self.sentinel.install()
            self._compiles0 = self._last_compiles = self.sentinel.count
            from sparknet_tpu import common

            common.add_bank_observer(self._on_bank)
        else:
            self.run_id = run_id or "off"

    @classmethod
    def from_env(cls) -> "Recorder":
        raw = os.environ.get(_ENV, "").strip()
        if raw in ("", "0"):
            return cls(None)
        return cls("obs_journal.jsonl" if raw == "1" else raw)

    def __bool__(self) -> bool:
        return self.enabled

    # -- low-level emit ----------------------------------------------------

    def emit(self, event: str, **fields) -> None:
        """Validate against the schema and append one journal line.
        Never raises out of an armed training run: a schema bug or a
        read-only disk prints to stderr and drops the line — telemetry
        must not take the run down."""
        if not self.enabled:
            return
        try:
            line = schema.make_event(event, run_id=self.run_id, **fields)
        except ValueError as e:
            print(f"obs: dropped invalid event: {e}", file=sys.stderr)
            return
        payload = json.dumps(line)
        with self._lock:
            if not self._started:
                self._started = True
                start = schema.make_event(
                    "run_start", run_id=self.run_id, pid=os.getpid(),
                    argv=list(sys.argv))
                self._write(json.dumps(start))
            self._write(payload)
            self._fold_locked(event, fields)

    def _fold_locked(self, event: str, fields: dict) -> None:
        """Fold one just-journaled event into the metrics hub and write
        the periodic ``metrics`` snapshot when one is due (caller holds
        the lock; the snapshot line is written directly, not re-folded).
        """
        if self._hub is None or event == "metrics":
            return
        try:
            snap = self._hub.observe_event(event, fields)
            if snap:
                mline = schema.make_event(
                    "metrics", run_id=self.run_id, **snap)
                self._write(json.dumps(mline))
        except Exception as e:  # telemetry must not take the run down
            print(f"obs: metrics fold failed: {e}", file=sys.stderr)

    def _write(self, payload: str) -> None:
        try:
            with open(self.path, "a", encoding="utf-8") as f:
                f.write(payload + "\n")
        except OSError as e:
            print(f"obs: could not append to {self.path}: {e}",
                  file=sys.stderr)

    def _emit_span(self, fields: dict) -> None:
        self._n_spans += 1
        self.emit("span", **fields)

    # -- public surface ----------------------------------------------------

    def span(self, name: str, host: bool = False,
             note: str | None = None, **counts) -> Span:
        """A fenced-wall context manager.  With obs off it journals
        nothing and is still a ``TraceAnnotation(name, **counts)`` on
        the profiler's clock.  ``host=True`` declares the span never
        encloses device work and exempts it from the fence contract."""
        return Span(self, name, host=host, note=note, **counts)

    def round(self, *, mode: str, tau: int, devices: int, iters: int,
              batch: int, wall_s: float, loss: float, fenced: bool,
              comm: dict | None = None, iteration: int | None = None,
              workers: int | None = None, lineage: dict | None = None,
              expected_compiles: bool = False) -> None:
        """One per-round training record.  ``batch`` is images per local
        step; throughput is ``iters * batch / wall_s``.  Also drives the
        recompile sentinel: any backend compilation between rounds of an
        already-warm mode is flagged live as a ``recompile`` event —
        ``expected_compiles=True`` lets a caller that KNOWS this round
        built a new program (the elastic trainer compiling its first
        round at an unseen mesh width) stamp the event ``expected`` so
        the compiles-zero SLO gate does not count it as a burn."""
        if not self.enabled:
            return
        loss = float(loss)
        ema = self._ema.get(mode)
        ema = loss if ema is None else (
            _EMA_DECAY * ema + (1.0 - _EMA_DECAY) * loss)
        self._ema[mode] = ema

        total = self.sentinel.count
        compiles = total - self._last_compiles
        self._last_compiles = total
        if compiles > 0 and mode in self._warm_modes:
            self.emit("recompile", count=compiles,
                      total=total - self._compiles0, where=mode,
                      expected=bool(expected_compiles))
        self._warm_modes.add(mode)

        images_per_sec = (iters * batch / wall_s) if wall_s > 0 else 0.0
        fields: dict = {
            "mode": mode, "tau": int(tau), "devices": int(devices),
            "iters": int(iters), "batch": int(batch),
            "wall_s": round(float(wall_s), 6),
            "images_per_sec": round(images_per_sec, 1),
            "loss": loss, "loss_ema": round(ema, 6),
            "fenced": bool(fenced), "compiles": compiles,
        }
        if comm is not None:
            fields["comm"] = comm
        if iteration is not None:
            fields["iteration"] = int(iteration)
        if workers is not None:
            fields["workers"] = int(workers)
        if lineage is not None:
            fields["lineage"] = lineage
        self._n_rounds += 1
        self.emit("round", **fields)

    def absorb_compiles(self, where: str) -> int:
        """Fold backend compiles since the last round record into the
        by-design ledger: a deploy-arm candidate build / AOT warmup
        between training rounds compiles on purpose, and without this
        resync the NEXT round's record would claim those compiles as
        its own unexpected recompiles (the compiles-zero SLO gate and
        the streaming burn engine would both count a phantom burn).
        Journals the delta as an ``expected`` recompile event so the
        compile ledger stays complete; returns the delta."""
        if not self.enabled:
            return 0
        total = self.sentinel.count
        n = total - self._last_compiles
        self._last_compiles = total
        if n > 0:
            self.emit("recompile", count=n,
                      total=total - self._compiles0, where=where,
                      expected=True)
        return n

    def bench(self, record: dict, *, wall_s: float | None = None,
              fence_value: float | None = None,
              fenced: bool = False) -> None:
        """Journal one bench.py record whole (the record's keys are
        bench.py's contract; the schema wraps, it does not re-specify)."""
        if not self.enabled:
            return
        fields: dict = {
            "metric": str(record.get("metric", "?")),
            "measured": bool(record.get("measured")),
            "fenced": bool(fenced),
            "record": dict(record),
        }
        if wall_s is not None:
            fields["wall_s"] = round(float(wall_s), 6)
        if fence_value is not None:
            fields["fence_value"] = float(fence_value)
        self.emit("bench", **fields)

    def _on_bank(self, path: str, payload, measured: bool) -> None:
        """common.bank_guard observer: every banked-evidence write lands
        in the journal too, measured-stamping shared with the sink."""
        fields: dict = {"path": path, "measured": bool(measured)}
        if isinstance(payload, dict):
            if isinstance(payload.get("metric"), str):
                fields["metric"] = payload["metric"]
            value = payload.get("value")
            if value is None or isinstance(value, (int, float)):
                fields["value"] = value
            if payload.get("rehearsal"):
                fields["rehearsal"] = True
        self.emit("bank", **fields)

    def close(self) -> None:
        """Emit the final metrics snapshot and the run summary
        (idempotent enough for atexit use)."""
        if not self.enabled or not self._started:
            return
        if self._hub is not None:
            snap = self._hub.flush_fields()
            if snap:
                self.emit("metrics", **snap)
        self.emit("run_end", rounds=self._n_rounds, spans=self._n_spans,
                  compiles=self.sentinel.count - self._compiles0)

    def detach(self) -> None:
        """Deregister this Recorder's bank observer (tests; replaced
        singletons) so a retired Recorder stops journaling."""
        if self.enabled:
            from sparknet_tpu import common

            common.remove_bank_observer(self._on_bank)


_recorder: Recorder | None = None


def get_recorder() -> Recorder:
    """The process singleton, built from ``SPARKNET_OBS`` on first use."""
    global _recorder
    if _recorder is None:
        _recorder = Recorder.from_env()
    return _recorder


def set_recorder(rec: Recorder | None) -> Recorder | None:
    """Replace the singleton (tests; the dryrun CLI).  ``None`` resets
    to lazy env-driven construction.  The outgoing Recorder is detached
    so it stops observing bank_guard writes."""
    global _recorder
    if _recorder is not None:
        _recorder.detach()
    _recorder = rec
    return rec
