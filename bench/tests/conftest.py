"""Shared fixtures: the benchmark's modules on the path, and a throwaway
copy of the benchmark whose configurations, serving settings and traffic
are cut to a size the CPU runs in seconds."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

#: widths and depth a CPU test runs at, per configuration. In the served
#: tests the tiny MoE routes every token to all 8 experts: at this size
#: one token whose bf16 route differs from the float32 one (an expert
#: swapped at the top-k boundary, or dropped by capacity) moves a logit
#: by 0.07 against 0.007 elsewhere (seen at seed 2**31 + 4242).
#: ``test_reference`` checks routing and the capacity rule in float32
#: with ``TINY_ROUTED``, where program and reference agree to 1e-6.
TINY_CONFIG = {
    "phi3-mini-3.8b": dict(hidden_size=64, intermediate_size=128,
                           num_hidden_layers=2, num_attention_heads=4,
                           num_key_value_heads=4, head_dim=16,
                           vocab_size=512),
    "granite-moe-3b-a800m": dict(hidden_size=64, intermediate_size=32,
                                 num_hidden_layers=2, num_attention_heads=4,
                                 num_key_value_heads=2, head_dim=16,
                                 num_local_experts=8, num_experts_per_tok=8,
                                 vocab_size=512),
}
#: the widths and depth a CPU test runs ``granite_published`` at: two
#: layers, its 40 experts
TINY_GRANITE = dict(TINY_CONFIG["granite-moe-3b-a800m"], num_local_experts=40)
TINY_ROUTED = dict(num_local_experts=16, num_experts_per_tok=8,
                   capacity_factor=1.25)
#: serving settings a CPU test runs at, per cell
TINY_SERVING = {
    "phi3-resident-chat": dict(max_batch=4, max_seq=384),
    "granite-moe-offload-decode": dict(max_batch=4, max_seq=512,
                                       prefill_tokens=256,
                                       tier_rows=[2, 8]),
}
TINY_PROMPT = {"dist": "choice", "values": [128, 256]}
TINY_OUTPUT = {"dist": "uniform", "lo": 8, "hi": 24}
#: limits at this size, from CPU readings over 3-5 seeds of each cell:
#: the program's widest served-token gap read at most 0.0042 and the
#: float8 control's at least 0.038; the program's mean gap at most 9e-5
#: and the control's at least 2.8e-3. The planted faults read far above.
TINY_LIMITS = {"max_logit_gap": 0.02, "mean_logit_gap": 5e-4}


def load_config(name: str) -> dict:
    """The benchmark's configuration file ``bench/configs/<name>.json``."""
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def granite_published() -> dict:
    """Granite 3.0 3B-A800M as published (``data/``): the four scalars of
    its forward pass and dropless routing, with no ``capacity_factor``.
    The program does not run it yet: weights, counts and the reference
    take it, and ``program.model_config`` refuses it by the first key
    that the program cannot run."""
    return json.loads((BENCH / "tests" / "data"
                       / "granite-3.0-3b-a800m-published.json").read_text())


def _edit(path: Path, **changes) -> None:
    data = json.loads(path.read_text())
    for key, value in changes.items():
        if isinstance(value, dict) and isinstance(data.get(key), dict):
            data[key].update(value)
        else:
            data[key] = value
    path.write_text(json.dumps(data, indent=2))


#: a cell whose files ``bench/`` keeps but ``BENCHMARK.json`` does not
#: list yet (PERF.md, Open questions): the tests add it to the throwaway
#: copy, with its configuration and the per-layer metrics it reports, so
#: that the pool path and the MoE reference stay tested
HELD_CELL = {"name": "granite-moe-offload-decode",
             "config": "granite-moe-3b-a800m",
             "traffic": "backlog-256-768-in-256-out", "chips": 1,
             "why": "the pool path with GQA pages"}
HELD_CONFIG = {"name": "granite-moe-3b-a800m", "source": "test",
               "file": "bench/configs/granite-moe-3b-a800m.json",
               "reduced": [], "why": "test"}
HELD_METRICS = [
    {"name": name, "unit": unit, "better": "lower",
     "source": source, "layer": layer, "moves": "output_tok_s",
     "workloads": [HELD_CELL["name"]]}
    for name, unit, source, layer in (
        ("offload_host_ms_per_step", "ms", "program_span", "scheduler"),
        ("host_bytes_per_token", "B/token", "program_counter", "pool"))]


def _hold(bench: dict) -> None:
    """Add the held cell to ``bench`` where it is not listed."""
    if HELD_CELL["name"] in {w["name"] for w in bench["workloads"]}:
        return
    bench["workloads"].append(dict(HELD_CELL))
    if HELD_CONFIG["name"] not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append(dict(HELD_CONFIG))
    names = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [m for m in HELD_METRICS if m["name"] not in names]
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m and m["name"] in (
                "output_tok_s", "decode_host_ms_per_step", "step_mfu",
                "decode_hbm_share", "device_idle_share"):
            m["workloads"].append(HELD_CELL["name"])


def make_tiny_root(dest: Path) -> Path:
    """A checkout-like directory: ``BENCHMARK.json`` and ``bench/``, with
    every configuration, cell and traffic mix cut to CPU size."""
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    _hold(bench)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=2))
    for c in bench["configs"]:
        _edit(dest / c["file"], **TINY_CONFIG[c["name"]])
    for w in bench["workloads"]:
        workload = dest / "bench" / "workloads" / f"{w['name']}.json"
        limits = json.loads(workload.read_text())["limits"]
        _edit(workload, serving=TINY_SERVING[w["name"]],
              limits={k: TINY_LIMITS[k] for k in limits})
        traffic = dest / "bench" / "traffic" / f"{w['traffic']}.json"
        _edit(traffic, prompt=TINY_PROMPT, output=TINY_OUTPUT)
        if json.loads(traffic.read_text())["arrivals"] == "poisson":
            _edit(traffic, rate_per_s=20.0)
    return dest


@pytest.fixture
def tiny_root(tmp_path: Path) -> Path:
    return make_tiny_root(tmp_path)


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """Let ``run`` drive the CPU: skip the look for a TPU and give the
    CPU the v5e's peaks, so that per-layer arithmetic has a peak to
    divide by. Nothing from such a run is a device measurement. Each test
    traces into a directory of its own, so that traced runs in parallel
    workers do not read or delete each other's profiles."""
    import jax
    from repro.launch import compile_cache

    import peaks
    import run
    monkeypatch.setattr(run, "require_tpu", lambda chips: jax.devices()[0])
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    # CPU programs stay out of the checkout's persistent compile cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    v5e = peaks.lookup("TPU v5 lite")
    monkeypatch.setattr(run.peaks_mod, "lookup", lambda kind: v5e)
    return run
