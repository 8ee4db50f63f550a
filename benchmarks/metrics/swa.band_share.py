"""Of the windowed gated-attention layers, the share whose core ran the
band-following backward at its last trace: the program's
``swa_band_layers`` over ``swa_window_layers`` on its ``sn.step.fence``
spans (``solvers/solver.py _fence_stats``; ``ops/attention.py
band_backward`` reads the form off S, the window and the blocks), the
mean over the fences of the traced window.  Under that form no grid step
exists for a (query block, key block) pair the window never reaches and
dq is summed in f32 over a query block's few key blocks and written
once; under the other (the fused backward) the grid is every pair below
and above the diagonal and dq is one q-sized partial a key block, summed
by XLA.  100 in ``laguna-solo-s8192`` since PR 51; a program without the
counter (the parent of PR 51) gives nothing."""

from benchmarks.metrics._decoder_scopes import fence_mean


def read(summary, run):
    return fence_mean(
        summary, "swa_band_layers",
        lambda s: 100.0 * float(s["swa_band_layers"])
        / float(s["swa_window_layers"]))
