"""What one of the program's spans costs with no profiler session and no
journal: nanoseconds for enter + exit of a ``Span`` and of a step span
(``utils/profiling.step_span``), on whatever host this runs on.

    python tools/span_cost.py [--spans 200000]

It touches no device.  The same file runs on a checkout from before the
record (PR 34), so a parent and a change can be timed in one call; on
such a checkout the line says ``"record": false``.  Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def ns_each(make, n: int, repeats: int = 5) -> float:
    """Median over ``repeats`` loops of ``n`` spans, nanoseconds a span."""
    out = []
    for _ in range(repeats):
        t = time.perf_counter()
        for i in range(n):
            with make(i):
                pass
        out.append((time.perf_counter() - t) / n * 1e9)
    return statistics.median(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=200_000)
    args = ap.parse_args()

    from sparknet_tpu.obs import recorder
    from sparknet_tpu.obs.recorder import Span
    from sparknet_tpu.utils.profiling import step_span

    def bare(i):
        return Span(None, "sn.cost.span", host=True, it=i)

    def stats(i):
        sp = Span(None, "sn.cost.set", host=True, it=i)
        sp.set(ready=1)
        return sp

    print(json.dumps({
        "record": hasattr(recorder, "flight"),
        "spans": args.spans,
        "span_ns": ns_each(bare, args.spans),
        "span_with_set_ns": ns_each(stats, args.spans),
        "step_span_ns": ns_each(
            lambda i: step_span("sn.cost.step", i), args.spans),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
