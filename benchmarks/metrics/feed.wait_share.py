"""Share of the window's wall time the training loop is blocked on input.

solo: the benchmark's ``bench.feed_wait`` span around ``next(prefetcher)``.
tau_round: ``bench.pack`` (``cli._stack_tau``) + ``bench.put``
(``ParallelTrainer._put_feeds``), both inside ``train_round`` before
anything is dispatched.  Host clock, over the whole mesh-phase window."""


def read(summary, run):
    wall = run.get("window_wall_s")
    if not wall or "feed_wait_s" not in run:
        return None
    return 100.0 * run["feed_wait_s"] / wall
