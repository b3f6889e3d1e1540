"""Entry-point guards that hold on the CPU: the chip smoke check refuses
to run without a TPU, the compile-cache helper honours the environment
before its fixed default, and the serve launcher runs a traced smoke
serve in each mode."""

import runpy
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import serve
from repro.launch.compile_cache import (
    ENV_VAR, compile_cache_dir, enable_compile_cache,
)
from repro.obs.check import validate_file

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


@pytest.mark.parametrize("mode", ["resident", "kv_offload"])
def test_serve_launcher(mode, monkeypatch, tmp_path, capsys):
    """``python -m repro.launch.serve --smoke`` serves a Poisson trace
    through the scheduler in-process and writes a trace the schema
    checker accepts; under kv_offload the printed hidden fraction is a
    number, so pool transfers were traced."""
    # the process-wide compile cache stays as the other tests left it
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    trace = tmp_path / "trace.json"
    assert serve.main(["--smoke", "--requests", "4", "--mode", mode,
                       "--trace-out", str(trace)]) == 0
    assert validate_file(str(trace)) == []
    out = capsys.readouterr().out
    assert f"serve,{mode},requests:4," in out
    overlap = next(line for line in out.splitlines()
                   if line.startswith("serve,overlap,"))
    if mode == "kv_offload":
        assert not overlap.endswith("hidden_fraction:n/a")
