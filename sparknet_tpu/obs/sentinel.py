"""Recompile sentinel: count XLA backend compilations per process.

graphcheck's static ``graph-recompile-hazard`` audit proves a step's
StableHLO is iteration-stable at lowering time; this sentinel is the
RUNTIME complement — it counts actual backend compilations through
jax's monitoring hooks so a live run can flag the recompiles the static
check cannot see (shape-polymorphic feeds, a Python value captured in a
closure, a cache-defeating donation change).  A recompile of a big
step is tens of seconds of chip time, so "the step compiled again" is
an operational incident, not a curiosity.

Counts ``/jax/core/compile/backend_compile_duration`` events: one fires
per program jax hands to the compiler (a single ``jit`` call may
legitimately emit a few — sub-computations compile separately; a
program served from the persistent compilation cache still fires,
since the event wraps the lookup); a jit-cache hit fires none.  That
asymmetry is all the Recorder needs: zero new events between rounds of
a warm mode means no recompile, anything else is flagged.

The listener registry is ``jax._src.monitoring`` — private, so it is
imported plainly: if a jax upgrade moves it, ``install`` raises rather
than leaving every "zero post-warm-up compiles" gate passing on a
counter that cannot count.

Per-thread attribution: the monitoring listener runs synchronously on
the thread that performed the compilation, so the sentinel can also
keep a per-thread count (``thread_count``).  That is the serving
loop's proof obligation (sparknet_tpu/loop): a rollout legitimately
compiles fresh bucket executables on its BUILDER thread while the
serving thread's own count must not move — a process-wide total
cannot tell those apart, the per-thread ledger can.

The listener is also handed every compile's DURATION, and keeps it: per
thread and per event name the seconds jax spent tracing
(``jaxpr_trace_duration``), lowering (``jaxpr_to_mlir_module_duration``)
and compiling or loading from the persistent cache
(``backend_compile_duration``, which encloses
``cache_retrieval_time_sec`` on a cache hit; ``compile_time_saved_sec``
is what that hit saved), and the wall-clock time the last backend
compilation ended.
A :class:`~sparknet_tpu.obs.recorder.Span` opened with
``compile_stats=True`` reads its thread's delta (``thread_compile``) and
carries ``compiles`` / ``compile_s`` / ``cache_hits`` when they are not
zero: which step compiled, and for how long.
"""

from __future__ import annotations

import threading
import time

from sparknet_tpu._chaoslock import named_lock

__all__ = ["EVENT_LABELS", "RecompileSentinel", "get_sentinel"]

# the event name jax (0.9.0) records one of per backend compilation
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# a cache hit's retrieval, inside the backend-compile event that it serves
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# what a span's ``compile_s`` covers: the stages of a compilation.  They
# nest (a jitted function traced inside another's trace reports both), so
# the seconds are those of their union, not their sum
_STAGE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _COMPILE_EVENT,
)
# every event whose seconds are kept (``thread_seconds``), under the
# word a reader prints it by: the set-up line of ``tpunet train`` and the
# benchmark's set-up table.  A cache load lies inside its backend-compile
# event; what it saved is the compile time the cache's entry records
EVENT_LABELS = {
    _STAGE_EVENTS[0]: "trace",
    _STAGE_EVENTS[1]: "lower",
    _COMPILE_EVENT: "compile or load",
    _CACHE_HIT_EVENT: "of it cache loads",
    "/jax/compilation_cache/compile_time_saved_sec": "which saved",
}


class RecompileSentinel:
    """Process-wide backend-compilation counter (install once)."""

    def __init__(self):
        self._lock = named_lock("RecompileSentinel._lock")
        self._count = 0
        self._by_thread: dict[int, int] = {}
        # thread -> event name -> [events, seconds]
        self._timed: dict[int, dict[str, list]] = {}
        # thread -> [union ns of its stage events, their newest disjoint
        # intervals [start_ns, end_ns] in order]
        self._busy: dict[int, list] = {}
        self._last_ns = 0
        self._installed = False
        self.available = False

    def install(self) -> "RecompileSentinel":
        """Register the jax monitoring listener (idempotent).  Imports
        jax lazily so this module stays importable without paying a
        backend-adjacent import."""
        if self._installed:  # every compile-counting span asks
            return self
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        from jax._src import monitoring

        def _on_duration(name: str, duration: float, **_kw) -> None:
            if name not in EVENT_LABELS:
                return
            tid = threading.get_ident()
            now = time.time_ns()  # jax reports an event as it ends
            with self._lock:
                cell = self._timed.setdefault(tid, {}).setdefault(
                    name, [0, 0.0])
                cell[0] += 1
                cell[1] += duration
                if name in _STAGE_EVENTS:
                    self._cover(tid, now - int(duration * 1e9), now)
                if name == _COMPILE_EVENT:
                    self._last_ns = now
                    self._count += 1
                    self._by_thread[tid] = \
                        self._by_thread.get(tid, 0) + 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        self.available = True
        return self

    def _cover(self, tid: int, start: int, end: int) -> None:
        """Add ``[start, end]`` to a thread's union of stage intervals
        (caller holds the lock).  A thread's events arrive in the order
        they end, so an enclosing event follows what it encloses and
        takes its place."""
        busy = self._busy.setdefault(tid, [0, []])
        spans = busy[1]
        while spans and spans[-1][0] >= start:
            a, b = spans.pop()
            busy[0] -= b - a
        if spans and spans[-1][1] > start:
            start = spans[-1][1]
        if end > start:
            spans.append((start, end))
            busy[0] += end - start
        if len(spans) > 4096:  # only the newest can still be enclosed
            del spans[:2048]

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    def thread_count(self, tid: int | None = None) -> int:
        """Backend compilations attributed to one thread (default: the
        calling thread).  The listener fires on the compiling thread,
        so a serving thread that never compiles reads 0 here even while
        a concurrent rollout builder's count climbs."""
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            return self._by_thread.get(tid, 0)

    def thread_seconds(self, tid: int | None = None) -> dict[str, float]:
        """Seconds by event name (the keys of ``EVENT_LABELS``) that jax
        reported on one thread (default: the calling thread): the three
        stages of a compilation, a cache hit's retrieval (part of its
        backend-compile event) and the compile time that hit saved.
        Sums, where ``thread_compile``'s seconds are a union: nested
        traces count twice here."""
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            return {k: v[1] for k, v in self._timed.get(tid, {}).items()}

    def thread_compile(self, tid: int | None = None) -> tuple:
        """``(compiles, compile_s, cache_hits)`` of one thread (default:
        the calling thread) so far: programs handed to the compiler,
        the seconds it spent tracing, lowering and compiling or loading
        them (the union of those events: they nest), and how many of them
        the persistent cache served."""
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            return (self._by_thread.get(tid, 0),
                    self._busy.get(tid, (0,))[0] / 1e9,
                    self._timed.get(tid, {}).get(_CACHE_HIT_EVENT, (0,))[0])

    def thread_lowerings(self, tid: int | None = None) -> int:
        """Functions jax lowered afresh on one thread (default: the
        calling thread) so far; a lowering served from jax's own cache
        fires no event.  ``utils/profiling.step_account`` asks it before
        and after it lowers, and takes no account of a fresh one."""
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            return self._timed.get(tid, {}).get(_STAGE_EVENTS[1], (0,))[0]

    @property
    def last_compile_ns(self) -> int:
        """``time.time_ns()`` when the newest backend compilation of any
        thread ended (0: none yet) — on the clock of a span's
        ``start_ns``.  A trace that ends in a jit-cache hit is no compile
        and does not move it."""
        with self._lock:
            return self._last_ns


_sentinel: RecompileSentinel | None = None


def get_sentinel() -> RecompileSentinel:
    global _sentinel
    if _sentinel is None:
        _sentinel = RecompileSentinel()
    return _sentinel
