"""The windowed cores' band-following backward as the fence and the
benchmark see it: ``swa_band_layers`` (``solvers/solver.py _fence_stats``
from the layers' ``band``, what ``ops/attention.py band_backward`` read
off their last trace) and its reader ``benchmarks/metrics/swa.band_share.py``
on a hand-made fence record, and on a record without the counter (the
parent of PR 51)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from benchmarks.harness import load_by_name
from benchmarks.metrics import _decoder_scopes
from sparknet_tpu.common import Phase
from sparknet_tpu.ops import attention
from sparknet_tpu.ops.registry import create_layer
from sparknet_tpu.proto.text_format import parse

US = 1000
TRACE = {"window": [0, 100 * US], "chips": {}, "host": []}
HELD = {"moe_pairs": 65536, "moe_layers": 4, "moe_pairs_held": 16384}


def fences(*stats):
    """One fence 10 us apart inside the window a record, beside the
    counters ``_decoder_scopes.reduce`` keeps a fence for."""
    return [{"start_ns": (i + 1) * 10 * US, "stats": dict(HELD, **s)}
            for i, s in enumerate(stats)]


def read(rows):
    summary = {"decoder_scopes": _decoder_scopes.reduce(TRACE, rows)}
    return load_by_name("metrics", "swa.band_share").read(summary, {})


@pytest.mark.parametrize("rows, want", [
    # every windowed layer on the band at both fences (numbers or the
    # strings a span's arguments come back as)
    (fences({"swa_window_layers": 3, "swa_band_layers": 3},
            {"swa_window_layers": "3", "swa_band_layers": "3"}), 100.0),
    # none (the CPU; a shape the rule leaves to the fused backward)
    (fences({"swa_window_layers": 3, "swa_band_layers": 0}), 0.0),
    # the mean over the fences: a retrace between them moved one layer
    (fences({"swa_window_layers": 3, "swa_band_layers": 3},
            {"swa_window_layers": 3, "swa_band_layers": 0}), 50.0),
    # the parent of PR 51: the window's counters without this one
    (fences({"swa_window_layers": 3, "swa_block_share": 22.79}), None),
    # another cell's fences, and none at all
    (fences({}), None),
    ([], None),
], ids=["all", "none", "mean", "parent", "other_cell", "no_fence"])
def test_the_reader_over_a_hand_made_fence_record(rows, want):
    got = read(rows)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_reader_without_a_trace():
    reader = load_by_name("metrics", "swa.band_share")
    assert reader.read(None, {}) is None
    assert reader.read({"decoder_scopes": None}, {}) is None


def test_a_fence_outside_the_window_is_not_read():
    late = [{"start_ns": 500 * US, "stats": dict(
        HELD, swa_window_layers=3, swa_band_layers=3)}]
    assert read(late) is None


def gated_layer(window: int):
    return create_layer(parse(
        'layer { name: "a" type: "GatedAttention" bottom: "x" top: "y" '
        "attention_param { num_heads: 4 num_kv_heads: 2 head_dim: 128 "
        f"qk_norm: false head_gate: true window: {window} }} }}"
    ).get_all("layer")[0], Phase.TRAIN)


@pytest.mark.parametrize("backend, S, window, kernel, band", [
    ("tpu", 2048, 512, "splash", True),
    ("tpu", 2048, 0, "splash", False),
    ("tpu", 2048, 2048, "splash", False),  # a window that sees every key
    ("tpu", 1024, 512, "xla", False),      # too short for the kernels
    ("cpu", 2048, 512, "xla", False),
])
def test_a_layer_keeps_whether_its_backward_walked_the_band(
        monkeypatch, backend, S, window, kernel, band):
    """``band`` is the rule's answer AT THE TRACE, beside ``kernel``: true
    only where the core ran as the splash kernels under a window that
    hides some key."""
    layer = gated_layer(window)
    assert (layer.kernel, layer.band) == ("", False)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(
        attention, "_splash_causal", lambda q, k, v, *rest, **kw:
        jnp.zeros(q.shape[:3] + v.shape[3:], q.dtype))
    params, _ = jax.eval_shape(
        lambda: layer.init(jax.random.key(0), [(1, S, 64)]))
    jax.eval_shape(
        lambda p, x: layer.apply(p, {}, [x], train=True).outputs[0],
        params, jax.ShapeDtypeStruct((1, S, 64), jnp.bfloat16))
    assert (layer.kernel, layer.band) == (kernel, band)
