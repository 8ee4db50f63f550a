"""Helpers the metric readers share.  A reader is
``read(summary, run) -> float | None``: ``summary`` is
``harness.trace.summarize``'s output for the traced window (None when
nothing was traced), ``run`` the job's facts.  A reader that finds
nothing to read returns None and the harness leaves the metric out."""


def first_chip(summary):
    if not summary or not summary.get("chips"):
        return None
    chips = summary["chips"]
    return chips[min(chips, key=int)]


def self_total(chip) -> float:
    return (sum(chip["layer_fwd_s"].values()) + sum(chip["layer_bwd_s"].values())
            + sum(chip["unscoped_s"].values()))


def layer_s(chip, name: str) -> float:
    key = name.replace("/", ".")
    return chip["layer_fwd_s"].get(key, 0.0) + chip["layer_bwd_s"].get(key, 0.0)


def device_step_s(summary, run):
    """Device busy seconds per training step, mean over the chips used."""
    if not summary or not summary.get("chips") or not run.get("steps_traced"):
        return None
    busy = [c["busy_s"] for c in summary["chips"].values()]
    return sum(busy) / len(busy) / run["steps_traced"]
