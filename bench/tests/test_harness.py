"""The harness on the CPU at a tiny size: it refuses the CPU as a device,
finds cells and metrics by name, and its comparison with the reference
passes the program and fails a broken one and the control."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import conftest

SEED = 2 ** 31 + 4242
#: the benchmark's cells, and the cell held for later (conftest)
CELLS = [w["name"] for w in json.loads(
    (conftest.ROOT / "BENCHMARK.json").read_text())["workloads"]]
CELLS += [conftest.HELD_CELL["name"]] * (conftest.HELD_CELL["name"]
                                         not in CELLS)


def _run(run, root, cell, trace=0, seconds=2.0, seed=SEED):
    args = run.parse(["--workload", cell, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    return run.run(args, root=root)


def test_command_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(conftest.BENCH / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU" in p.stderr
    assert "{" not in p.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_program_matches_reference(tiny_root, on_cpu, cell):
    res = _run(on_cpu, tiny_root, cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) >= {"mean_logit_gap"}
    assert set(res["metrics"]) >= {"output_tok_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell,mode", [
    ("granite-moe-offload-decode", {"mode": "resident"}),
    ("phi3-resident-chat", {"mode": "kv_offload", "tier_rows": [2, 8]})])
def test_other_serving_mode_matches_reference(tiny_root, on_cpu, cell, mode):
    """Each family through the serving mode its cell does not take."""
    path = tiny_root / "bench" / "workloads" / f"{cell}.json"
    wl = json.loads(path.read_text())
    wl["serving"].update(mode)
    path.write_text(json.dumps(wl))
    assert _run(on_cpu, tiny_root, cell)["correct"]


def test_traced_run_reports_per_layer_metrics(tiny_root, on_cpu):
    res = _run(on_cpu, tiny_root, "granite-moe-offload-decode", trace=1)
    assert res["correct"]
    assert {"offload_host_ms_per_step", "decode_host_ms_per_step",
            "host_bytes_per_token"} <= set(res["metrics"])
    assert "output_tok_s" not in res["metrics"]
    # the CPU's profile has no TPU plane: the metrics that divide by the
    # device time of the model's programs find nothing and are left out
    assert not {"step_mfu", "decode_hbm_share",
                "device_idle_share"} & set(res["metrics"])


def test_discovers_added_cell_and_metric_without_edits(tiny_root, on_cpu):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    cell = dict(bench["workloads"][0], name="throwaway-cell")
    bench["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "throwaway_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "scheduler",
        "moves": "output_tok_s", "workloads": ["throwaway-cell"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    wl_dir = tiny_root / "bench" / "workloads"
    (wl_dir / "throwaway-cell.json").write_text(
        (wl_dir / f"{bench['workloads'][0]['name']}.json").read_text())
    (tiny_root / "bench" / "metrics" / "throwaway_steps.py").write_text(
        "def read(ctx):\n    return float(len(ctx.steps))\n")
    res = _run(on_cpu, tiny_root, "throwaway-cell", trace=1)
    assert res["metrics"]["throwaway_steps"]["value"] > 0


def test_discovers_added_configuration_and_reference_without_edits(
        tiny_root, on_cpu):
    """A configuration file that names a reference module of its own, new
    beside it, and has experts: weights, program, counts and the
    comparison take it from its keys and its name alone."""
    import flops
    import weights as W
    bench_dir = tiny_root / "bench"
    cfg = json.loads((bench_dir / "configs"
                      / "granite-moe-3b-a800m.json").read_text())
    cfg.update(name="throwaway-moe", reference="throwaway_moe")
    (bench_dir / "configs" / "throwaway-moe.json").write_text(
        json.dumps(cfg))
    (bench_dir / "reference" / "throwaway_moe.py").write_text(
        "from reference.moe import ffn, forward  # noqa: F401\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(conftest.HELD_CONFIG, name="throwaway-moe",
                                 file="bench/configs/throwaway-moe.json"))
    bench["workloads"].append(dict(conftest.HELD_CELL, name="throwaway-cell",
                                   config="throwaway-moe"))
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    wl = json.loads((bench_dir / "workloads"
                     / f"{conftest.HELD_CELL['name']}.json").read_text())
    (bench_dir / "workloads" / "throwaway-cell.json").write_text(
        json.dumps(dict(wl, config="throwaway-moe")))
    assert "router" in W.layer_spec(cfg)
    # the experts a token takes count as one dense feed-forward as wide
    # as all of them, plus the router
    e, k, d, f = (cfg["num_local_experts"], cfg["num_experts_per_tok"],
                  cfg["hidden_size"], cfg["intermediate_size"])
    dense = {key: v for key, v in cfg.items() if key != "num_local_experts"}
    dense["intermediate_size"] = k * f
    assert flops.token_flops(cfg, 1) == flops.token_flops(dense, 1) + \
        2 * cfg["num_hidden_layers"] * d * e
    res = _run(on_cpu, tiny_root, "throwaway-cell")
    assert res["correct"], res["checks"]


def _break_decode(monkeypatch, wrap):
    from repro.sched import scheduler
    real = scheduler.jit_decode

    def broken(model):
        return wrap(real(model))
    monkeypatch.setattr(scheduler, "jit_decode", broken)


def test_altered_token_is_not_correct(tiny_root, on_cpu, monkeypatch):
    """A token altered where it is produced: the decode step's logits
    rolled by one vocabulary entry, so argmax picks a neighbour."""
    _break_decode(monkeypatch, lambda f: lambda *a: (
        lambda out: (jnp.roll(out[0], 1, axis=-1), out[1]))(f(*a)))
    assert not _run(on_cpu, tiny_root, "phi3-resident-chat")["correct"]


def test_step_that_keeps_its_state_is_not_correct(tiny_root, on_cpu,
                                                  monkeypatch):
    """A decode step that returns its cache unchanged: the new token's
    keys and values are never written."""
    def keep_state(f):
        def step(params, cache, tok, pos):
            logits, _ = f(params, jax.tree.map(jnp.copy, cache), tok, pos)
            return logits, cache
        return step
    _break_decode(monkeypatch, keep_state)
    assert not _run(on_cpu, tiny_root,
                    "granite-moe-offload-decode")["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, on_cpu, cell):
    """The float8 control at the same positions reads past the limit."""
    run = on_cpu
    c = run.load_cell(tiny_root, cell)
    served = run.serve(c, SEED, 2.0, False, jax.devices()[0],
                       run.CompileClock())
    picked = run.sample(served.outputs, served.longest, SEED)
    gaps = run.logit_gaps(c.config, SEED, served.outputs, picked,
                          chunk=c.workload["serving"]["chunk_size"],
                          control=True)
    for name, limit in c.workload["limits"].items():
        assert gaps[name] <= limit < gaps[f"control_{name}"]


class _ScriptedStats:
    """A session whose ``stats()`` returns ``snapshots`` in turn and which
    is otherwise the real one."""

    def __init__(self, real, snapshots):
        self._real, self._snapshots = real, list(snapshots)

    def __getattr__(self, name):
        return getattr(self._real, name)

    def stats(self):
        return self._snapshots.pop(0)


def test_counters_are_after_minus_before(tiny_root, on_cpu, monkeypatch):
    """``Reading.counters``: every numeric leaf of ``stats()`` by its
    dotted path, read before the window opens and after it closes, as
    the difference; flags and names are left out."""
    import program
    run = on_cpu
    before = {"sched": {"steps": 5, "dropless": True, "mode": "x"},
              "pool": {"transfer": {"pairs": {"device->host": {
                  "bytes": 10}}}},
              "prefix": None, "gone": 2}
    after = {"sched": {"steps": 12, "dropless": True, "mode": "x"},
             "pool": {"transfer": {"pairs": {"device->host": {"bytes": 25},
                                             "host->device": {"bytes": 4}}}},
             "prefix": None, "new": 3.5}
    real = program.session
    monkeypatch.setattr(program, "session", lambda *a, **k: _ScriptedStats(
        real(*a, **k), [before, after]))
    c = run.load_cell(tiny_root, "phi3-resident-chat")
    served = run.serve(c, SEED, 1.0, False, jax.devices()[0],
                       run.CompileClock())
    assert served.counters == {
        "sched.steps": 7.0, "pool.transfer.pairs.device->host.bytes": 15.0,
        "pool.transfer.pairs.host->device.bytes": 4.0, "new": 3.5}
    assert run.transfer_bytes(served.counters) == {"device->host": 15,
                                                  "host->device": 4}


@pytest.mark.parametrize("trace", [0, 1])
def test_counters_count_the_window_steps(tiny_root, on_cpu, trace):
    """The scheduler's own step counter moves by the steps the harness took
    between the two reads; with telemetry on, the histograms come too."""
    run = on_cpu
    c = run.load_cell(tiny_root, "phi3-resident-chat")
    served = run.serve(c, SEED, 1.0, bool(trace), jax.devices()[0],
                       run.CompileClock())
    assert served.stepped > 0
    assert served.counters["sched.steps"] == served.stepped
    assert served.counters["sched.decoded_tokens"] > 0
    hist = "telemetry.histograms.histograms.req_ttft_seconds"
    assert (f"{hist}.count" in served.counters) == bool(trace)
    assert (f"{hist}.sum" in served.counters) == bool(trace)


def test_scope_and_counter_readers():
    import types

    import trace_reduce
    from metrics import _counters, _scope
    trace = trace_reduce.Reduced(1.0, 10.0, [], [], {},
                                 {"experts": 0.25, "idle_scope": 0.0})
    ctx = types.SimpleNamespace(trace=trace, counters={"sched.steps": 7.0})
    assert _scope.device_s(ctx, "experts") == 0.25
    assert _scope.device_s(ctx, "idle_scope") is None
    assert _scope.device_s(ctx, "absent") is None
    assert _scope.device_s(types.SimpleNamespace(trace=None), "x") is None
    assert _counters.delta(ctx, "sched.steps") == 7.0
    assert _counters.delta(ctx, "sched.absent") is None
