"""Of the layers that can take a Pallas kernel, the share that took it
at their last trace: (``ssm_kernel_layers`` + ``gdn_kernel_layers`` +
``attn_kernel_layers``) / (``ssm_layers`` + ``gdn_layers`` +
``attn_core_layers``) summed over the ``sn.step.fence`` spans of the
traced window (``Solver._fence_stats``: the selective scan's kernels,
PR 33; the delta rule's, PR 48; jax's splash kernels or the band
kernels under ``A.core``, PRs 43 and 51).  100 in the decoder cells; a
silent fall-back to the XLA formulation (a shape that stops tiling, a
backend check that changes with a jax upgrade) shows here before it
shows in ``images_per_s``.  Nothing from a net without such a layer."""

from benchmarks.metrics._step_account import metric


def read(summary, run):
    return metric(summary, "kernels.path_share")
