"""Tokens at the fullest router output over the mean, over ALL 256
outputs of a router (held or not), where the routers select by their
sigmoid scores alone (no balancing bias): ``moe_load_max`` x
``moe_experts`` / ``moe_pairs`` from the program's counters on its
``sn.step.fence`` spans, the mean over the fences of the traced window.
1.0 is a level router, 32 a layer that sends every token to the same 8 of
256 outputs: what ONE sequence's routers do near initialisation (PERF.md
section 7), and what the held experts' lumpy share
(``swa.held_pair_share``) comes from."""

from benchmarks.metrics._decoder_scopes import fence_mean


def read(summary, run):
    return fence_mean(
        summary, "moe_pairs_held",
        lambda s: int(s["moe_load_max"]) * int(s["moe_experts"])
        / int(s["moe_pairs"]))
