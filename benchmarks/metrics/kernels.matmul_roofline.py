"""Over the Convolution and InnerProduct layers: the least time the chip
could take (per layer max(ops/peak, bytes/peak), three passes, from
shapes) over the device self time measured under those layers' scopes.
Layers that left no scoped op in the trace are left out of both sums."""

from benchmarks.harness.flops import layer_floor_s
from benchmarks.metrics._common import first_chip, layer_s


def read(summary, run):
    chip = first_chip(summary)
    if chip is None or "peaks" not in run or not run.get("steps_traced"):
        return None
    p = run["peaks"]
    floor = measured = 0.0
    for row in run["layer_rows"]:
        t = layer_s(chip, row["name"]) / run["steps_traced"]
        if t > 0:
            floor += layer_floor_s(row, p["bf16_flops"], p["hbm_bytes_per_s"])[0]
            measured += t
    return 100.0 * floor / measured if measured else None
