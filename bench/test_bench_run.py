"""CPU tests that drive whole runs of a small cell through the harness.

The cell is minitron's smoke preset (2 layers, d_model 48) under a
small chat-like mix, built in a temporary directory the way a new cell
is added: a configuration, a traffic mix and a cell file, found by
name.  A sound run is correct; the same run with the timed path broken
underneath it, or with the program's int8 path switched on (the
control), is not.  The harness's look for a chip is skipped: these
runs call its functions directly on the CPU.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import faults  # noqa: E402
import harness  # noqa: E402
import roofline  # noqa: E402
import spec  # noqa: E402

#: the smoke cell's limits on the numbers the chip cells compare, set
#: from CPU readings at this size (PERF.md): the first-token logit
#: deviation read 0.0100-0.0256 on 21 seeds of the program and
#: 0.032-0.062 on ten of the int8 control; the mean served-token gap
#: read 3.3e-5-3.9e-4 on twelve seeds of the program and 0.52-2.70 with
#: each planted fault on three
LIMITS = {"mean_gap": 0.01, "first_logit_dev": 0.027}
SEED = 2**33 + 11
SECONDS = 3.0
INT8 = {"quantize": "int8", "kv_quantize": "int8", "act_quantize": "int8"}


def make_cell(root) -> spec.Cell:
    bench = os.path.join(root, "b")
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bench, d), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"), dirs_exist_ok=True)
    config = {"name": "smoke", "arch": "minitron-4b", "preset": "smoke",
              "reference": "dense_gqa", "hidden_size": 48,
              "intermediate_size": 144, "num_hidden_layers": 2,
              "num_attention_heads": 3, "num_key_value_heads": 1,
              "head_dim": 16, "vocab_size": 256, "rope_theta": 500000.0,
              "norm_eps": 1e-5, "hidden_act": "gelu",
              "torch_dtype": "bfloat16",
              "lrd": {"compression": 2.0, "rank_mode": "aligned",
                      "rank_align": 8, "use_pallas": True, "min_dim": 32}}
    traffic = {"name": "tiny", "arrivals": {"process": "poisson"},
               "prompt_len": {"dist": "lognormal", "median": 24,
                              "sigma": 0.5, "min": 4, "max": 100},
               "output_len": {"dist": "uniform", "min": 16, "max": 48}}
    cell = {"slots": 4, "max_seq": 256, "rate_rps": 6.0,
            "check": {"requests": 8, "limits": LIMITS}}
    for sub, name, obj in (("configs", "smoke", config),
                           ("traffic", "tiny", traffic),
                           ("cells", "smoke-chat", cell)):
        with open(os.path.join(bench, sub, f"{name}.json"), "w") as f:
            json.dump(obj, f)
    bm = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    bm["configs"] = [{"name": "smoke", "file": "b/configs/smoke.json"}]
    bm["workloads"] = [{"name": "smoke-chat", "config": "smoke",
                        "traffic": "tiny", "chips": 1}]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return spec.load_cell(str(root), "smoke-chat", bench)


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    roofline.PEAKS.setdefault("cpu", roofline.Peaks(1e12, 1e11,
                                                    "CPU tests only"))
    return make_cell(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def setup(cell):
    return harness.set_up(cell, SEED)


def _run(cell, st, t0=None):
    t0 = t0 or time.perf_counter()
    win = harness.drive_window(cell, st, seed=SEED, seconds=SECONDS,
                               trace_dir=None)
    while st.eng.scheduler.busy():
        st.eng.step()
    oc = harness.judge(cell, st, win, seed=SEED, t_process=t0, keep=True)
    out, lines = harness.result(cell, oc, trace=False)
    return oc, out, lines


def test_a_sound_run_is_correct(cell, setup):
    oc, out, lines = _run(cell, setup)
    assert out["correct"], lines
    assert out["attempted"] == 18 and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "tokens_per_s",
                                   "setup_s"}
    assert list(out)[-1] == "checks"
    assert oc.sampled == 8
    assert all(0.0 <= oc.found[k] <= v for k, v in LIMITS.items())


def _broken(cell, setup, fault):
    undo = faults.plant(setup.eng, fault)
    try:
        oc, out, lines = _run(cell, setup)
    finally:
        undo()
    assert not out["correct"], lines
    assert oc.found["mean_gap"] > LIMITS["mean_gap"], lines


def test_a_decode_step_that_returns_its_state_unchanged_is_caught(
        cell, setup):
    _broken(cell, setup, "unchanged")


def test_half_the_decode_batch_left_out_is_caught(cell, setup):
    _broken(cell, setup, "half")


def test_a_token_altered_where_it_is_produced_is_caught(cell, setup):
    _broken(cell, setup, "altered")


def test_the_int8_control_is_not_correct(cell):
    st = harness.set_up(cell, SEED, INT8)
    assert st.params is None
    _, out, lines = _run(cell, st)
    assert not out["correct"], lines


def test_a_traced_run_reports_per_layer_metrics(cell):
    oc = harness.run_window(cell, seed=SEED + 1, seconds=SECONDS,
                            trace=True, t_process=time.perf_counter())
    out, lines = harness.result(cell, oc, trace=True)
    assert out["correct"], lines
    assert out["device"]["busy_s"] > 0
    assert out["device"]["window_s"] == pytest.approx(SECONDS, rel=0.2)
    assert "decode_step_ms" in out["metrics"]
    assert "idle_share.chat" in out["metrics"]
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert oc.window_compiles == (0, 0)


def test_run_exits_nonzero_without_a_tpu(capsys, monkeypatch):
    import run
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    assert run.main(["--workload", "minitron4b-chat", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_run_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "minitron4b-chat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
