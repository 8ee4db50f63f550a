"""chip_smoke.py off the chip: the CPU rehearsal runs every phase, a run
that finds no TPU fails without printing anything, and the compile-cache
helper places the cache where the contract says.
"""

import os
import subprocess
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")
METRIC_WORDS = ("images_per_sec", "img/s", "per_chip", "mfu", '"ok"')


def _run(args, tmp_path, devices=4):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # keep the rehearsal's compiles out of the checkout's .jax_cache
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    return subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        timeout=900, env=env, cwd=str(tmp_path))


def test_rehearsal_runs_every_phase_on_the_cpu_mesh(tmp_path):
    out_dir = tmp_path / "out"
    res = _run(["--rehearse-cpu", "--out", str(out_dir)], tmp_path)
    assert res.returncode == 0, (res.stdout + res.stderr)[-3000:]
    lines = res.stdout.strip().splitlines()
    # every line says it is a rehearsal — the result line included, so
    # nothing here can be read as the chip contract's JSON
    assert lines and all(l.startswith("REHEARSAL(cpu") for l in lines)
    for phase in ("solo", "tau", "snapshot", "kernels"):
        assert any(f"phase {phase}: ok" in l for l in lines), phase
    assert '"ok": true' in lines[-1] and '"rehearsal": true' in lines[-1]
    assert any("4 worker(s)" in l and "one [1, ...] shard per device" in l
               for l in lines)
    assert any("replicas identical" in l for l in lines)
    assert any("[interpret]" in l and "paged" in l for l in lines)
    assert f"compile cache: {tmp_path / 'cache'}" in res.stdout
    # the snapshot phase cleans up after itself; nothing lands in the cwd
    assert list(out_dir.iterdir()) == []
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache", "out"]


def test_without_a_tpu_the_smoke_fails_and_prints_nothing(tmp_path):
    res = _run([], tmp_path, devices=1)
    assert res.returncode != 0
    assert res.stdout == ""
    assert "no TPU" in res.stderr
    assert not any(w in res.stdout for w in METRIC_WORDS)


def test_compile_cache_env_set_leaves_the_config_alone(monkeypatch):
    from sparknet_tpu.common import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
    assert enable_compile_cache() == "/somewhere/placed"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_env_unset_uses_the_checkout(monkeypatch):
    from sparknet_tpu.common import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = enable_compile_cache()
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
