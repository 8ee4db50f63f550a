"""What the program's record (``obs.recorder.flight``) says of the step
program's memory and of the path its kernels took (PR 52), read at the
end of a traced run.

Since PR 52 the ``sn.step`` / ``sn.round`` span inside which a step
program was compiled or loaded carries that program's HBM account by
class, per device, as XLA's ``memory_analysis()`` gives it for the
executable that ran (``utils/profiling.step_account``: ``hbm_args_bytes``,
``hbm_out_bytes``, ``hbm_alias_bytes``, ``hbm_temps_bytes``,
``hbm_code_bytes``, ``hbm_devices``, ``hbm_limit_bytes`` and the
``hbm_account_ms`` it took), and every ``sn.step.fence`` /
``sn.round.fence`` the ``hbm_live_bytes`` the fullest chip held after
the fence.  The fences have carried ``ssm_kernel_layers`` /
``gdn_kernel_layers`` / ``attn_kernel_layers`` beside ``ssm_layers`` /
``gdn_layers`` / ``attn_core_layers`` since PRs 33, 43 and 47, and the
feed thread's ``sn.feed.augment`` its ``fused`` since PR 39: nothing
read them.

The TIMED program is the newest account-carrying step or round of the
record among those with the most ``hbm_devices``: in
``alexnet-tau10-x4`` the four-chip trainer's, not the round check's
(older) and not the one-device phase's (fewer devices).

Two intervals, both ``_flight.py``'s: the timed one, from the process's
last backend compile to the traced window (``feed.ahead_share``'s), for
the live bytes and the fused share; the traced window itself
(``_decoder_scopes.fence_mean``'s) for the kernel-path counters.

The record is taken once a process (``_flight.take``), anchored on the
newest xplane, and its lines printed on stderr.  Where the program keeps
no record, or a span or a stat is absent (the parent of PR 52 has no
``hbm_*`` stat), the readers get None and the line leaves the metric
out.  A summary may carry the record itself (``flight``: tests).
"""

from __future__ import annotations

import statistics
import sys
import time

from benchmarks.metrics import _flight

ACCOUNT = ("hbm_args_bytes", "hbm_out_bytes", "hbm_alias_bytes",
           "hbm_temps_bytes", "hbm_code_bytes")
# per kind of kernel-taking layer: how many there are, how many took it
KERNEL_LAYERS = (("ssm_layers", "ssm_kernel_layers"),
                 ("gdn_layers", "gdn_kernel_layers"),
                 ("attn_core_layers", "attn_kernel_layers"))
AUGMENT = "sn.feed.augment"
_MISSING = object()
_cached = _MISSING


def intervals(rec: dict, spans) -> tuple:
    """``(timed, traced)``: the spans wholly between the last compile and
    the traced window, as ``_flight.reduce`` cuts them, and the traced
    window ``[start_ns, end_ns]`` on the record's clock (None where the
    record was not anchored)."""
    window = (rec.get("trace") or {}).get("window")
    t_hi = window[0] if window else _flight._end(spans[-1]) + 1
    t_lo = rec.get("last_compile_ns") or 0
    if not 0 < t_lo <= t_hi:
        t_lo = max((_flight._end(s) for s in _flight._top_level(spans)
                    if _flight._end(s) <= t_hi), default=0)
    timed = [s for s in spans if s[2] >= t_lo and _flight._end(s) <= t_hi]
    return timed, window


def reduce(rec: dict) -> dict | None:
    """``{"metrics": {name: value}, "program": the timed program's span or
    None, "accounts": every account-carrying span, "notes": [what was
    refused and why]}`` from the neutral form; None where the record holds
    no span."""
    spans = sorted(rec.get("spans") or (), key=lambda s: s[2])
    if not spans:
        return None
    out: dict = {"metrics": {}, "notes": []}
    m = out["metrics"]
    timed, window = intervals(rec, spans)

    accounts = out["accounts"] = [
        s for s in spans if s[0] in _flight.STEPS
        and all(k in s[4] for k in ACCOUNT)]
    # the timed program: the newest of those that span the most devices
    program = out["program"] = max(
        accounts, key=lambda s: (s[4].get("hbm_devices", 1), s[2]),
        default=None)
    if program:
        a = program[4]
        m["model_step.args_hbm_gb"] = a["hbm_args_bytes"] / 1e9
        m["model_step.temps_hbm_gb"] = a["hbm_temps_bytes"] / 1e9
        m["model_step.program_hbm_gb"] = (
            a["hbm_args_bytes"] + a["hbm_out_bytes"] - a["hbm_alias_bytes"]
            + a["hbm_temps_bytes"] + a["hbm_code_bytes"]) / 1e9

    live = [s[4]["hbm_live_bytes"] for s in timed
            if s[0] in _flight.FENCES and "hbm_live_bytes" in s[4]]
    if live:
        m["device.live_hbm_gb"] = max(live) / 1e9
        out["live"] = {"fences": len(live), "min": min(live),
                       "first": live[0], "last": live[-1], "max": max(live)}
    limit = program[4].get("hbm_limit_bytes") if program else None
    if live and limit:
        fill = 100.0 * (max(live) + program[4]["hbm_temps_bytes"]) / limit
        if fill <= 100.0:
            m["device.hbm_fill"] = fill
        else:
            out["notes"].append(
                f"device.hbm_fill refused: {max(live)} live bytes + "
                f"{program[4]['hbm_temps_bytes']} of temporaries are "
                f"{fill:.1f} % of the chip's {limit}: a step that ran cannot "
                "have needed more than the chip gives, so a counter is wrong")

    if window:
        took = layers = 0
        for s in spans:
            if s[0] in _flight.FENCES and window[0] <= s[2] <= window[1]:
                for have, kernel in KERNEL_LAYERS:
                    if have in s[4] and kernel in s[4]:
                        layers += int(s[4][have])
                        took += int(s[4][kernel])
        if layers:
            m["kernels.path_share"] = 100.0 * took / layers

    fused = [s[4]["fused"] for s in timed
             if s[0] == AUGMENT and "fused" in s[4]]
    if fused:
        m["feed.fused_share"] = 100.0 * sum(1 for f in fused if f) / len(fused)
    return out


def table(red: dict) -> str:
    if not red["accounts"] and "live" not in red:
        return ("the record holds no step program's account and no live "
                "bytes (a program older than PR 52)")
    lines = ["the step program's account (utils/profiling.step_account, on "
             "the span that compiled it), GB a device:",
             f"{'span':10s} {'it':>6s} {'devices':>7s} {'args':>8s} "
             f"{'out':>8s} {'aliased':>8s} {'temps':>8s} {'code':>8s} "
             f"{'limit':>8s} {'took_ms':>8s} {'compiles':>8s}"]
    for s in red["accounts"]:
        a = s[4]
        gb = [f"{a[k] / 1e9:8.3f}" if k in a else f"{'-':>8s}"
              for k in (*ACCOUNT, "hbm_limit_bytes")]
        took = a.get("hbm_account_ms")
        lines.append(
            f"{s[0]:10s} {a.get('it', '-')!s:>6s} "
            f"{a.get('hbm_devices', '-')!s:>7s} " + " ".join(gb)
            + (f" {took:8.3f}" if took is not None else f" {'-':>8s}")
            + f" {a.get('compiles', 0):8d}"
            + ("  <- the timed program" if s is red["program"] else ""))
    live = red.get("live")
    if live:
        lines.append(
            f"hbm_live_bytes over {live['fences']} fences from the last "
            f"compile to the traced window: first {live['first'] / 1e9:.3f} "
            f"GB, last {live['last'] / 1e9:.3f}, min {live['min'] / 1e9:.3f}, "
            f"max {live['max'] / 1e9:.3f}")
    lines += red["notes"]
    return "\n".join(lines)


def _fence_read_us() -> float | None:
    """What one fence's reading costs here: the median of 20 reads of
    every local device's ``memory_stats()``, in microseconds."""
    import jax

    devices = jax.local_devices()
    if not devices[0].memory_stats():
        return None
    walls = []
    for _ in range(20):
        t = time.perf_counter()
        for d in devices:
            d.memory_stats()
        walls.append(time.perf_counter() - t)
    return 1e6 * statistics.median(walls)


def account_metrics(summary) -> dict | None:
    """``{metric name: value}`` for this run: the summary's own record
    where it carries one (tests), else the process's, taken once."""
    global _cached
    if not summary:
        return None
    if "flight" in summary:
        red = reduce(summary["flight"])
        for note in red["notes"] if red else ():
            print(note, file=sys.stderr)
        return red["metrics"] if red else None
    if _cached is _MISSING:
        from benchmarks.metrics._program_spans import newest_xplane

        _cached = None
        rec = _flight.take()
        if rec is not None:
            path = newest_xplane()
            rec["trace"] = _flight.anchor(rec, path) if path else None
            red = reduce(rec)
            if red:
                _cached = red["metrics"]
                print(table(red), file=sys.stderr, flush=True)
                us = _fence_read_us()
                if us is not None:
                    print(f"one read of memory_stats() on every local device "
                          f"takes {us:.1f} us here (median of 20, after the "
                          "windows)", file=sys.stderr, flush=True)
    return _cached


def metric(summary, name: str) -> float | None:
    return (account_metrics(summary) or {}).get(name)
