"""Share of the feed thread's ``sn.feed.augment`` spans whose ``fused``
stat is 1 (the batch took the augment's one pass, ``crop_mirror``'s
kernel: uint8 in, the f32 crop out; 0 = the eager ``_augment`` dispatched
op by op, which costs ``alexnet-solo`` a fifth of its rate; PR 39) from
the process's last compile to the traced window.  Nothing from a feed
without a device augment."""

from benchmarks.metrics._step_account import metric


def read(summary, run):
    return metric(summary, "feed.fused_share")
