"""Look at one trace by hand (on-chip-measurement guide, section 6):
planes, lines, the first events of each line with every stat (event and
metadata), then the benchmark's own reduction.

    python benchmarks/scratch/xplane_dump.py <trace dir> <out.json>

Run by hand after a traced run; never part of one."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.harness import trace, xplane  # noqa: E402


def main() -> None:
    d, out = sys.argv[1], sys.argv[2]
    path = trace.find_xplane(d)
    planes = [
        {"plane": p["name"], "lines": [
            {"line": l["name"], "events": len(l["events"]),
             "first": [{**e, "stats": {k: str(v)[:300] for k, v in e["stats"].items()}}
                       for e in l["events"][:6]]}
            for l in p["lines"]]}
        for p in xplane.read(path)]
    t = trace.load_xplane(path)
    with open(out, "w") as f:
        json.dump({"xplane": path, "bytes": os.path.getsize(path),
                   "planes": planes, "host_spans": t["host"][:40],
                   "summary": trace.summarize(t)}, f, indent=1)
    print(f"xplane {os.path.getsize(path)} bytes -> {out}")


if __name__ == "__main__":
    main()
