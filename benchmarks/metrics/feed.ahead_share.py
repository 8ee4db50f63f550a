"""Share of the program's ``sn.feed.wait`` spans whose ``ready`` stat is 1
(the batch was there before it was asked for), over every wait between
the process's last compile and the traced window: how often the feed was
ahead of the step loop, over the whole timed window."""

from benchmarks.metrics._flight import metric


def read(summary, run):
    return metric(summary, "feed.ahead_share")
