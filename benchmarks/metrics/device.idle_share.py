"""100 x (1 - busy union / traced window) on the chip that idles most."""


def read(summary, run):
    if not summary or not summary.get("chips") or not summary["window_s"]:
        return None
    busy = min(c["busy_s"] for c in summary["chips"].values())
    return 100.0 * (1.0 - busy / summary["window_s"])
