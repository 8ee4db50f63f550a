"""Streaming metrics (sparknet_tpu/obs/metrics.py): the bounded-memory
percentile contract, pinned on adversarial distributions.

The hub's histograms make a precision CLAIM — fixed log boundaries at
40 buckets/decade (~5.93% relative width), nearest-rank percentile on
bucket upper bounds clamped to the observed [min, max], so estimates
are exact at the extremes, never under-report a tail, and sit within
one bucket width of exact everywhere else — and a MERGE claim:
snapshots combine by integer bucket-count addition, associatively.
These tests feed the shapes that break naive implementations (values
ON bucket boundaries, single samples, bimodal mass at the extremes)
and check the claims against exact nearest-rank computed the slow way.

All stdlib + numpy-free, smoke-tier: the obs package must stay
importable (and testable) with no jax anywhere.
"""

from __future__ import annotations

import json
import math

import pytest

from sparknet_tpu.obs import schema
from sparknet_tpu.obs.metrics import (
    BUCKETS_PER_DECADE,
    Histogram,
    JournalTail,
    MetricsHub,
    bucket_index,
    bucket_lower,
    merge_snapshots,
    percentile,
)

pytestmark = pytest.mark.smoke

# one bucket's relative width: 10^(1/40) - 1 (~5.93%) — the histogram's
# own stated estimate bound
_REL = 10.0 ** (1.0 / BUCKETS_PER_DECADE) - 1.0


def _exact_nearest_rank(values: list[float], q: float) -> float:
    """Exact nearest-rank percentile (the definition the histogram
    approximates): the smallest value with at least ceil(q/100 * n)
    observations at or below it."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def _hist_of(values) -> Histogram:
    h = Histogram()
    for v in values:
        h.observe(v)
    return h


# -- bucket geometry --------------------------------------------------------


def test_bucket_boundaries_are_fixed_and_half_open():
    # a value sitting EXACTLY on a bucket's lower boundary belongs to
    # that bucket (half-open [lo, hi)): 10.0 is bucket 40's lower edge
    assert bucket_lower(0) == 1.0
    assert bucket_lower(BUCKETS_PER_DECADE) == pytest.approx(10.0)
    i = bucket_index(10.0)
    assert bucket_lower(i) <= 10.0 < bucket_lower(i + 1)
    # determinism: the same value always lands in the same bucket —
    # no float drift between observe-time and merge-time binning
    assert all(bucket_index(10.0) == i for _ in range(100))


def test_bucket_index_spans_decades():
    for v in (1e-6, 0.004, 1.0, 37.5, 1e4, 1e9):
        i = bucket_index(v)
        assert bucket_lower(i) <= v < bucket_lower(i + 1)


# -- percentile precision on adversarial distributions ----------------------


def test_single_sample_every_percentile_is_exact():
    h = _hist_of([37.2])
    snap = h.snapshot()
    for q in (0.0, 1.0, 50.0, 99.0, 100.0):
        assert percentile(snap, q) == 37.2


def test_boundary_values_hold_the_precision_bound():
    # every observation ON a bucket boundary: the nearest-rank answer
    # IS a boundary, and clamping keeps the estimate exact at both ends
    values = [bucket_lower(i) for i in range(0, 81, 8)]
    snap = _hist_of(values).snapshot()
    for q in (50.0, 90.0, 99.0):
        exact = _exact_nearest_rank(values, q)
        est = percentile(snap, q)
        assert exact <= est <= exact * (1.0 + _REL), (q, exact, est)
    assert percentile(snap, 100.0) == max(values)
    # the low extreme is conservative-side too: never BELOW min, at
    # most one bucket width above it
    assert min(values) <= percentile(snap, 0.0) <= min(values) * (1 + _REL)


def test_bimodal_mass_never_under_reports_the_tail():
    # half the mass at 1, half at 100: p50 must stay in the low mode
    # (within one bucket width), p99/p100 must report the HIGH mode
    # exactly — a tail estimate below 100 would launder a latency spike
    values = [1.0, 1.0, 100.0, 100.0]
    snap = _hist_of(values).snapshot()
    assert 1.0 <= percentile(snap, 50.0) <= 1.0 * (1.0 + _REL)
    assert percentile(snap, 99.0) == 100.0
    assert percentile(snap, 100.0) == 100.0


def test_estimates_within_one_bucket_width_of_exact():
    # a deterministic spread over 3 decades (no RNG in tests that pin
    # numeric claims): j*j+0.5 hits awkward non-boundary values
    values = [(j * j + 0.5) / 7.0 for j in range(1, 120)]
    snap = _hist_of(values).snapshot()
    for q in (25.0, 50.0, 75.0, 95.0, 99.0):
        exact = _exact_nearest_rank(values, q)
        est = percentile(snap, q)
        assert exact * (1.0 - 1e-12) <= est <= exact * (1.0 + _REL), (
            q, exact, est)


def test_zero_and_negative_values_have_their_own_bucket():
    snap = _hist_of([0.0, 0.0, 5.0]).snapshot()
    assert percentile(snap, 50.0) == 0.0
    assert percentile(snap, 100.0) == 5.0


def test_percentile_of_empty_snapshot_is_none():
    assert percentile(Histogram().snapshot(), 50.0) is None


# -- merge: exact and associative -------------------------------------------


def test_merge_equals_single_pass():
    # dyadic values: float sums are exact, so merged == single-pass
    # bitwise, not approximately
    a = [0.5, 2.0, 8.0, 64.0]
    b = [0.25, 4.0, 1024.0]
    merged = merge_snapshots(_hist_of(a).snapshot(), _hist_of(b).snapshot())
    assert merged == _hist_of(a + b).snapshot()


def test_merge_is_associative_and_commutative():
    parts = [[0.5, 1.0], [2.0, 4.0, 8.0], [0.125, 1024.0]]
    sa, sb, sc = (_hist_of(p).snapshot() for p in parts)
    left = merge_snapshots(merge_snapshots(sa, sb), sc)
    right = merge_snapshots(sa, merge_snapshots(sb, sc))
    flipped = merge_snapshots(sc, merge_snapshots(sb, sa))
    assert left == right == flipped
    assert left == _hist_of(parts[0] + parts[1] + parts[2]).snapshot()


def test_merge_with_empty_is_identity():
    s = _hist_of([1.0, 3.0]).snapshot()
    empty = Histogram().snapshot()
    assert merge_snapshots(s, empty) == s
    assert merge_snapshots(empty, s) == s


# -- the hub ----------------------------------------------------------------


def test_hub_folds_request_events_and_flushes_on_schedule():
    hub = MetricsHub(flush_every=3)
    ev = {"model": "live", "bucket": 8, "queue_wait_ms": 1.0,
          "batch_assembly_ms": 0.1, "device_ms": 4.0, "total_ms": 5.1}
    assert hub.observe_event("request", ev) is None
    assert hub.observe_event("request", ev) is None
    snap = hub.observe_event("request", ev)  # third event: flush due
    assert snap is not None and snap["seq"] == 1
    assert snap["counters"]["serve/requests"] == 3
    assert snap["hists"]["serve/total_ms/live/b8"]["count"] == 3
    # snapshots are CUMULATIVE: the next flush supersedes, not deltas
    for _ in range(3):
        nxt = hub.observe_event("request", ev)
    assert nxt["seq"] == 2
    assert nxt["counters"]["serve/requests"] == 6


def test_hub_flush_fields_make_a_schema_valid_metrics_event():
    hub = MetricsHub(flush_every=1 << 62)
    hub.observe_event("round", {"mode": "dp", "wall_s": 0.5,
                                "iters": 1, "batch": 16,
                                "loss_ema": 2.3, "fenced": True})
    fields = hub.flush_fields()
    assert fields is not None
    line = schema.make_event("metrics", run_id="t", **fields)
    assert schema.validate_line(line) == []


def test_hub_with_nothing_to_flush_returns_none():
    assert MetricsHub(flush_every=1).flush_fields() is None


# -- the tail ---------------------------------------------------------------


def test_journal_tail_reads_only_complete_lines(tmp_path):
    path = tmp_path / "j.jsonl"
    tail = JournalTail(str(path))
    assert list(tail.poll()) == []  # file does not exist yet
    with open(path, "w") as f:
        f.write(json.dumps({"event": "a"}) + "\n")
        f.write('{"event": "tor')  # torn mid-append
    got = [ev["event"] for ev in tail.poll()]
    assert got == ["a"]
    with open(path, "a") as f:
        f.write('n"}\n')  # the append completes
    got = [ev["event"] for ev in tail.poll()]
    assert got == ["torn"]
    assert list(tail.poll()) == []  # nothing new
