"""Host milliseconds per 1,000 images in the program's ``sn.feed.put``
spans (``DevicePrefetcher._worker`` around ``jax.device_put``;
``ParallelTrainer`` around ``_put_feeds``): host -> HBM placement with
its host-side relayout, as the host sees it (dispatched, not awaited)."""

from benchmarks.metrics._program_spans import per_kimg, program_spans


def read(summary, run):
    return per_kimg(program_spans(summary), "sn.feed.put")
