"""Of the causal (query block, key block) pairs of the windowed cores, at
the width ``attention_core`` hands its kernels, the share that holds a key
some query sees: the program's ``swa_block_share`` counter on its
``sn.step.fence`` spans (``ops/attention.py window_blocks`` of the layers'
last trace, from ``core_block``, the one place the width is chosen), the
mean over the fences of the traced window.  31 of 136 512-wide pairs at
8,192 tokens under a window of 512: 22.79 %, which hold TWICE the (query,
key) pairs the mask asks (the half-masked blocks at the window's two
edges).  Narrower blocks, or key blocks that follow the window, lower it
toward the mask's own 12.1 % of the causal pairs; ``swa.window_core_roofline``
is the time that follows."""

from benchmarks.metrics._decoder_scopes import fence_mean


def read(summary, run):
    return fence_mean(summary, "swa_block_share",
                      lambda s: float(s["swa_block_share"]))
