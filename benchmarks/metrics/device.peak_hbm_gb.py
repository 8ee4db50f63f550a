"""Peak HBM on the fullest chip, set-up and window together:
``memory_stats()`` ``peak_bytes_in_use`` (live buffers) +
``peak_bytes_reserved`` (the scratch compiled programs reserve; libtpu
reports the two apart).  A guard on memory moved into or out of set-up."""


def read(summary, run):
    peak = run.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
