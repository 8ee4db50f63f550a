"""Latent attention's core against its roofline: the least time the chip
could take for QK^T over keys of 192, the softmax and PV over values of
128, forward and backward, the causal half only
(``harness/decoder_flops.py mla_core_row``: max(ops / 197 T, bytes / 819 G)
over three passes), over chip 0's device self time under the program's
``A.core`` scope.  The backward's recomputed QK^T is time and not work, so
a perfect kernel reads under 100 %."""

from benchmarks.metrics._decoder_scopes import part_roofline


def read(summary, run):
    return part_roofline(summary, run, "mla_core", "A.core")
