"""bench.py helper units: the pieces that must fail fast before anything
compiles (a malformed A/B knob must not cost chip time) and the zoo
guard added for the crop-96 GoogLeNet walkthrough."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import _parse_compiler_options  # noqa: E402


def test_parse_compiler_options_roundtrip():
    assert _parse_compiler_options("") == {}
    assert _parse_compiler_options("a=1") == {"a": "1"}
    assert _parse_compiler_options(" a = 1 , b=x=y ") == {
        "a": "1", "b": "x=y"}


def test_parse_compiler_options_malformed_fails_fast():
    with pytest.raises(SystemExit, match="key=value"):
        _parse_compiler_options("xla_tpu_foo")


def test_googlenet_rejects_non_multiple_of_32_crop():
    """ceil-mode pooling would silently leave pool5 non-global for such
    crops — the builder rejects them loudly."""
    from sparknet_tpu.models import zoo

    with pytest.raises(ValueError, match="multiple of 32"):
        zoo.googlenet(batch=1, num_classes=10, crop=95)


# -- bank_guard: the one blessed evidence sink (graftlint bank-guard) -------


@pytest.mark.smoke
def test_bank_guard_measured_writes_in_place(tmp_path):
    from sparknet_tpu.common import bank_guard

    path = str(tmp_path / "serve_bench_last.json")
    written = bank_guard(path, {"arms": [1, 2]}, measured=True)
    assert written == path
    import json

    with open(path) as f:
        payload = json.load(f)
    assert payload == {"arms": [1, 2]}  # no rehearsal stamp on evidence
    assert not os.path.exists(path + ".tmp")  # atomic: tmp file consumed


@pytest.mark.smoke
def test_bank_guard_unmeasured_diverts_and_stamps(tmp_path):
    """A CPU rehearsal must land OUTSIDE the requested (docs/) location,
    stamped so it can never read as chip evidence."""
    import json
    import tempfile

    from sparknet_tpu.common import bank_guard

    path = str(tmp_path / "docs" / "serve_bench_last.json")
    written = bank_guard(path, {"arms": []}, measured=False)
    assert written is not None
    assert not os.path.exists(path)  # nothing under the evidence path
    assert written == os.path.join(tempfile.gettempdir(),
                                   "serve_bench_last_rehearsal.json")
    with open(written) as f:
        payload = json.load(f)
    assert payload["rehearsal"] is True
    assert payload["arms"] == []


@pytest.mark.smoke
def test_bank_path_idempotent_on_rehearsal_names():
    from sparknet_tpu.common import bank_path

    p1 = bank_path("docs/feed_bench_last.json", measured=False)
    assert bank_path(p1, measured=False) == p1  # no _rehearsal_rehearsal
    assert bank_path("docs/x_last.json", measured=True) == "docs/x_last.json"
