"""Operations the forward and backward passes REQUIRE per step
(harness/flops.py, from layer shapes) over (device busy time per step x
the chip's bf16 peak).  Utilization of the time the chip is busy; idle
time is ``device.idle_share``'s."""

from benchmarks.metrics._common import device_step_s


def read(summary, run):
    s = device_step_s(summary, run)
    if not s or "peaks" not in run:
        return None
    return 100.0 * run["flops_per_step"] / (s * run["peaks"]["bf16_flops"])
