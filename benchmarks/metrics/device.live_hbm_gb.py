"""What the process keeps on its fullest chip BETWEEN steps, in GB: the
largest ``hbm_live_bytes`` (``memory_stats()["bytes_in_use"]`` read after
the fence, largest over the program's devices; PR 52) over the
``sn.step.fence`` / ``sn.round.fence`` spans from the process's last
compile to the traced window (``feed.ahead_share``'s interval): the
state, the feed's placed batches and whatever else was never let go (in
``alexnet-tau10-x4`` the one-device trainer's replica on chip 0).  Its
growth from fence to fence is a leak; the table on stderr gives the
first, last, least and largest reading.  A program without the stat (the
parent of PR 52) gives nothing."""

from benchmarks.metrics._step_account import metric


def read(summary, run):
    return metric(summary, "device.live_hbm_gb")
