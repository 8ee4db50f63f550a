"""Share of chip 0's device self time under the looped region's scope
(``LOOP.<name>``: all the passes of the region's blocks and the final
norm, forward and backward) over the self total."""

from benchmarks.metrics._loop_scopes import body_share


def read(summary, run):
    return body_share(summary)
