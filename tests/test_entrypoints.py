"""Entry-point guards that hold on the CPU: the chip smoke check refuses
to run without a TPU, and the compile-cache helper honours the
environment before its fixed default."""

import runpy
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import (
    ENV_VAR, compile_cache_dir, enable_compile_cache,
)

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_the_cpu(monkeypatch, capsys):
    """Under JAX_PLATFORMS=cpu the check exits non-zero before any phase
    and prints no result line (run in-process: no child touches libtpu)."""
    assert jax.devices()[0].platform == "cpu"
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    monkeypatch.setattr(sys, "path", list(sys.path))
    with pytest.raises(SystemExit) as exc:
        runpy.run_path(str(ROOT / "chip_smoke.py"), run_name="__main__")
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir_prefers_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(ENV_VAR)
    assert compile_cache_dir() == str(ROOT / ".jax_cache")


def test_enable_compile_cache_sets_only_the_default(monkeypatch, tmp_path):
    """With the variable set JAX already reads it, so the helper sets
    nothing; without it the helper points JAX at <checkout>/.jax_cache."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert updates == []
    monkeypatch.delenv(ENV_VAR)
    assert enable_compile_cache() == str(ROOT / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir",
                        str(ROOT / ".jax_cache"))]
