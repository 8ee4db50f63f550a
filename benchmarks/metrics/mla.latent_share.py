"""Share of chip 0's device self time under latent attention's
``A.latent`` scope: the five projections, the two inner norms, RoPE on
the rotary parts, the assembly of k from the per-head part and the one
shared rotary key, forward and backward; everything of the layer that is
not its core."""

from benchmarks.metrics._decoder_scopes import share_of_busy


def read(summary, run):
    return share_of_busy(summary, "A.latent")
