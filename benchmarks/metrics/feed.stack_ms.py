"""Host milliseconds per 1,000 images in the program's ``sn.feed.stack``
spans (``cli._stack_tau``: the ``np.concatenate`` + ``np.stack`` of a
round's host batches, apart from the reads that ``bench.pack`` also
covers)."""

from benchmarks.metrics._program_spans import per_kimg, program_spans


def read(summary, run):
    return per_kimg(program_spans(summary), "sn.feed.stack")
