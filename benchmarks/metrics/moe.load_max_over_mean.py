"""Tokens at the fullest expert over the mean tokens per expert, from the
program's own counter (the MoE layers' ``load`` state, which
``Solver.step`` puts on its ``sn.step.fence`` span as ``moe_load_max`` /
``moe_pairs`` / ``moe_experts``): the mean over the fences of the traced
window.  1.0 is a perfectly balanced router."""

from benchmarks.metrics._lm_scopes import lm_scopes


def read(summary, run):
    ls = lm_scopes(summary)
    if not ls or not ls["load"]:
        return None
    ratios = [int(s["moe_load_max"]) * int(s["moe_experts"])
              / int(s["moe_pairs"]) for s in ls["load"]]
    return sum(ratios) / len(ratios)
