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
"""

from __future__ import annotations

import threading

from sparknet_tpu._chaoslock import named_lock

__all__ = ["RecompileSentinel", "get_sentinel"]

# the event name jax (0.9.0) records one of per backend compilation
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompileSentinel:
    """Process-wide backend-compilation counter (install once)."""

    def __init__(self):
        self._lock = named_lock("RecompileSentinel._lock")
        self._count = 0
        self._by_thread: dict[int, int] = {}
        self._installed = False
        self.available = False

    def install(self) -> "RecompileSentinel":
        """Register the jax monitoring listener (idempotent).  Imports
        jax lazily so this module stays importable without paying a
        backend-adjacent import."""
        with self._lock:
            if self._installed:
                return self
            self._installed = True
        from jax._src import monitoring

        def _on_duration(name: str, duration: float, **_kw) -> None:
            if name == _COMPILE_EVENT:
                tid = threading.get_ident()
                with self._lock:
                    self._count += 1
                    self._by_thread[tid] = \
                        self._by_thread.get(tid, 0) + 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        self.available = True
        return self

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


_sentinel: RecompileSentinel | None = None


def get_sentinel() -> RecompileSentinel:
    global _sentinel
    if _sentinel is None:
        _sentinel = RecompileSentinel()
    return _sentinel
