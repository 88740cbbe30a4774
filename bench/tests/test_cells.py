"""CPU rehearsal of every cell's harness path at a tiny size: build,
install the generator, the window, the fixed span S, the drain, the
comparison with the plain reference and the shape of the last line;
the control and the faults that must make ``correct`` false; the
refusal without a TPU; and that cells, traffic and metrics are found by
name alone."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import harness

TINY = {
    "nexmark-q5": {"slots": 64, "rate": 4_000.0, "batch": 32,
                   "warmup_s": 3.0, "span_s": 2.0},
    "ysb-join": {"slots": 256, "rate": 4_000.0, "batch": 64,
                 "warmup_s": 1.0, "span_s": 1.0, "n_ads": 5_000},
}
CELLS = ["q5-hot-evict", "ysb-zipf", "ysb-uniform"]
_runs = {}


def _run(name, seconds=2.0, trace=False, seed=7, control=None, root=None):
    cell = harness.load_cell(name, **({"root": root} if root else {}))
    return harness.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            sizes=TINY[cell.config["name"]],
                            control=control)


def _cached(name):
    if name not in _runs:
        _runs[name] = _run(name, control="bfloat16")
    return _runs[name]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    out = _cached(name)
    line = out.line
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "check"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 1000
    assert set(line["metrics"]) == {"events_per_s", "sim_p99_ms",
                                    "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["check"].values())
    json.loads(json.dumps(line))
    # every program was compiled in set-up: nothing traced in the window
    assert out.extra["window_traces"].get("traces", 0) == 0
    assert out.extra["window_traces"].get("compiles", 0) == 0
    # the cell's own path ran: device batches, store traffic, evictions
    c = out.extra["counters"]
    assert c["batches"] > 0 and c["device_hits"] > 0
    assert c["evictions"] > 0 and out.extra["results"] > 100


@pytest.mark.parametrize("name", ["q5-hot-evict", "ysb-zipf"])
def test_bfloat16_control_is_not_correct(name):
    ctl = _cached(name).extra["control"]
    assert ctl["results_mismatch"]["value"] + \
        ctl["state_mismatch"]["value"] > 0


def test_traced_rehearsal_reports_host_layers():
    out = _run("q5-hot-evict", seconds=6.0, trace=True, seed=11)
    line = out.line
    assert line["correct"] is True
    assert {"engine_ms_per_kevent", "plane_ms_per_kevent",
            "device_calls_per_kevent"} <= set(line["metrics"])
    # no device plane on the CPU: device metrics stay silent, never 0
    assert "fused_step_us" not in line["metrics"]
    assert "tac_probe_roofline" not in line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert list(line)[-1] == "check"


def test_sim_p99_is_fixed_by_the_span_not_the_window():
    short = _run("q5-hot-evict", seconds=0.5, seed=5).line
    long = _run("q5-hot-evict", seconds=3.0, seed=5).line
    assert short["correct"] and long["correct"]
    assert short["metrics"]["sim_p99_ms"]["value"] == \
        long["metrics"]["sim_p99_ms"]["value"]


# ------------------------------------------------ faults under the path
def _unchanged_state(real_step, real_admit):
    def step(state, pages, *a, **k):
        return real_step(state, pages, *a, **k)._replace(state=state,
                                                         pages=pages)

    def admit(state, pages, *a):
        return state, pages, real_admit(state, pages, *a)[2]
    return step, admit


def _half_batch(real_step, real_admit):
    import jax.numpy as jnp

    def step(state, pages, keys, ts, w, fire, valid, *, kind):
        keep = np.arange(keys.shape[0]) < keys.shape[0] // 2
        left = jnp.asarray(valid & ~keep)
        full = real_step(state, pages, keys, ts, w, fire, valid, kind=kind)
        half = real_step(state, pages, keys, ts, w, fire, valid & keep,
                         kind=kind)
        # the second half is left out, yet reported as done
        return full._replace(
            state=half.state, pages=half.pages,
            new_vals=jnp.where(left[:, None], 0.0, half.new_vals),
            present=half.present & ~left)
    return step, real_admit


def _altered_answer(real_step, real_admit):
    def step(*a, **k):
        out = real_step(*a, **k)
        return out._replace(new_vals=out.new_vals + 1.0)
    return step, real_admit


# a read-only join's step leaves its state unchanged by design (and an
# admission that never lands is refetched by the program's cold path,
# correctly): that fault exists only where the step writes state
@pytest.mark.parametrize("name,fault", [
    ("q5-hot-evict", _unchanged_state), ("q5-hot-evict", _half_batch),
    ("q5-hot-evict", _altered_answer), ("ysb-zipf", _half_batch),
    ("ysb-zipf", _altered_answer)])
def test_fault_under_the_timed_path_is_not_correct(name, fault,
                                                   monkeypatch):
    from repro.core import tac_jax
    step, admit = fault(tac_jax.fused_step, tac_jax.fused_admit)
    monkeypatch.setattr(tac_jax, "fused_step", step)
    monkeypatch.setattr(tac_jax, "fused_admit", admit)
    line = _run(name, seconds=1.0).line
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["check"].values())


def test_watermark_ahead_of_the_source_is_not_correct(monkeypatch):
    """A watermark that passes what the source may promise drops and
    fires by the program's own input, which the reference replays: the
    generator's limit catches it."""
    from repro.streaming.engine import SourceOp
    real = SourceOp.emit_watermark
    monkeypatch.setattr(SourceOp, "emit_watermark",
                        lambda self, sub, wm: real(self, sub, wm + 0.25))
    line = _run("q5-hot-evict", seconds=1.0).line
    assert line["correct"] is False
    assert line["check"]["watermark_ahead"]["value"] > 0


def test_input_left_queued_is_not_counted(monkeypatch):
    """A keyed operator that falls behind in simulated time leaves its
    input queued: the window counts only what it took up."""
    from repro.streaming.engine import StatefulOp
    sound = _run("q5-hot-evict", seconds=1.0, seed=9).extra
    assert sound["queued_at_window_end"] < 100
    assert sound["window_events"] > 0.85 * sound["window_source_events"]
    real = StatefulOp._fused_drain
    monkeypatch.setattr(StatefulOp, "_fused_drain",
                        lambda self, sub: 400.0 * real(self, sub))
    slow = _run("q5-hot-evict", seconds=1.0, seed=9).extra
    assert slow["queued_at_window_end"] > 1000
    assert slow["window_events"] < 0.8 * slow["window_source_events"]


# ------------------------------------------------------- the command
def _bench_cmd(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"),
         "--workload", "q5-hot-evict", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_py_refuses_without_a_tpu():
    p = _bench_cmd(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs 1 TPU" in p.stderr


def test_run_py_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _bench_cmd(str(tmp_path), env)
    assert p.returncode != 0 and p.stdout == ""


# --------------------------------------------------- found by name alone
def test_new_traffic_and_metric_are_found_by_name(tmp_path):
    """Copy the benchmark, add one traffic file and one metric file and
    a manifest entry naming them: the harness runs the new cell and
    reports the new metric, with no edit to a file that was there."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    before[tmp_path / "BENCHMARK.json"] = \
        (tmp_path / "BENCHMARK.json").read_bytes()
    traffic = json.loads((tmp_path / "bench" / "traffic" /
                          "q5-hot-evict.json").read_text())
    traffic.update(late_prob=0.2, why="a fifth of the bids late")
    (tmp_path / "bench" / "traffic" / "q5-late.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "metrics" / "window_kevents.py").write_text(
        '"""Keyed events in the traced window, in thousands."""\n\n\n'
        'def read(run):\n    return run["events"] / 1e3\n')
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["workloads"].append(
        {"name": "q5-late", "config": "nexmark-q5",
         "traffic": "q5-late", "chips": 1, "why": "late-heavy bids"})
    manifest["per_layer"].append(
        {"name": "window_kevents", "unit": "kevents", "better": "higher",
         "source": "host_clock", "layer": "engine", "moves": "events_per_s",
         "workloads": ["q5-late"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    copy = harness.load_module(str(tmp_path / "bench" / "harness.py"),
                               "harness_copy")
    cell = copy.load_cell("q5-late", root=str(tmp_path))
    assert cell.traffic["late_prob"] == 0.2
    assert cell.bench == str(tmp_path / "bench")
    assert [m["name"] for m in cell.per_layer] == ["window_kevents"]
    out = copy.run_cell(cell, 13, 1.0, True, time.perf_counter(),
                        sizes=TINY["nexmark-q5"])
    assert out.line["correct"] is True
    assert out.line["metrics"]["window_kevents"]["value"] > 0
    # the old cells still load, and no file that was there changed
    assert copy.load_cell("q5-hot-evict", root=str(tmp_path)).per_layer
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p
