"""Host milliseconds per 1,000 images in the program's ``sn.feed.read``
spans (``cli._data_fns``' db: train fn: cursor -> decoded, collated,
cast batch; the feed thread in the solo job, the main thread inside
``_stack_tau`` in the trainer's): total duration of the spans wholly
inside the traced window over their ``images``.  1000 / (read + put +
stack) is the rate one feed thread can reach, in thousands of images/s."""

from benchmarks.metrics._program_spans import per_kimg, program_spans


def read(summary, run):
    return per_kimg(program_spans(summary), "sn.feed.read")
