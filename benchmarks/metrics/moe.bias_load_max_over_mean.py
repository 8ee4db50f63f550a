"""Tokens at the fullest router output over the mean, over ALL the
router's outputs (held or not), where the router selects with a balancing
bias: ``moe_load_max`` x ``moe_experts`` / ``moe_pairs`` from the fences
that also carry the bias's extremes, the mean over the fences of the
traced window.  1.0 is a level router, 32 a layer that sends every token
to the same 8 of 256 outputs.  It is the load of ONE step of one sequence:
near initialisation that is 31.5 to 32.0 whatever the bias holds (every
fence of the second pass's runs), since the bias can level a stream and
not a sequence (PERF.md section 6)."""

from benchmarks.metrics._decoder_scopes import fence_mean


def read(summary, run):
    return fence_mean(
        summary, "moe_bias_max",
        lambda s: int(s["moe_load_max"]) * int(s["moe_experts"])
        / int(s["moe_pairs"]))
