"""chip_smoke.py off the chip: it refuses to report success without a TPU,
and its phases rehearse at smoke size on the CPU (kernels interpreted)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def _run(script, cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _claims_ok(stdout):
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_refuses_without_a_tpu(tmp_path):
    proc = _run(ROOT / "chip_smoke.py", ROOT, tmp_path)
    assert proc.returncode != 0
    assert not _claims_ok(proc.stdout)
    assert "found no TPU" in proc.stdout + proc.stderr


def test_refuses_outside_the_repository(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", lone)
    proc = _run(lone / "chip_smoke.py", lone, tmp_path)
    assert proc.returncode != 0
    assert not _claims_ok(proc.stdout)


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    key = "jax_compilation_cache_dir"
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing is set in code
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update(key, prev)


def test_compile_clock_counts_nested_spans_once():
    clock = chip_smoke.CompileClock()
    clock("/jax/core/compile/jaxpr_trace_duration", 1.0)     # inner jit
    clock("/jax/core/compile/jaxpr_trace_duration", 2.0)     # its caller
    clock("/jax/other/event", 5.0)
    assert 2.0 <= clock.seconds < 2.5


@pytest.mark.parametrize("phase",["kernel_parity", "lossless", "served"])
def test_phase_rehearses_on_cpu(phase, monkeypatch, tmp_path):
    # a set variable keeps serve.main from turning the cache on in this
    # test process (JAX read the variable at import, when it was unset)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    clock = chip_smoke.CompileClock()
    arch = "qwen2-0.5b-smoke"
    fn = {"kernel_parity": lambda: chip_smoke.kernel_parity(
              B=2, S=64, interpret=True),
          "lossless": lambda: chip_smoke.lossless(arch, n_prompts=2,
                                                  tokens=12),
          "served": lambda: chip_smoke.served(arch, requests=4, tokens=8,
                                              batch=2)}[phase]
    assert chip_smoke.run_phase(phase, fn, clock)
