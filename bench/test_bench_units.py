"""CPU tests of the benchmark's yardstick: the traffic generator, the
metric arithmetic, the roofline counts, the trace reducer, the seeded
factor tree and the lookup of cells by name."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import roofline  # noqa: E402
import spec  # noqa: E402
import stats  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic_gen  # noqa: E402

CHAT = spec.load_json(os.path.join(BENCH, "traffic", "chat.json"))


# -- traffic ----------------------------------------------------------------

def _sched(seed, seconds=30.0):
    return traffic_gen.schedule(CHAT, rate=2.0, seconds=seconds, seed=seed,
                                vocab=1000, max_seq=1536)


def test_same_seed_same_schedule():
    a, b = _sched(2**33 + 7), _sched(2**33 + 7)
    assert a == b


def test_seeds_change_the_content_not_the_work():
    a, b = _sched(1), _sched(2)
    assert len(a) == len(b) == 60
    assert [x.due for x in a] == [x.due for x in b]
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new for x in a] == [x.max_new for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert a[0].due == 0.0 and all(x.due < 30.0 for x in a)
    assert all(np.diff([x.due for x in a]) >= 0)


def test_schedule_fits_the_pool():
    for x in _sched(3):
        assert 32 <= len(x.prompt) <= 1024
        assert 1 <= x.max_new and len(x.prompt) + x.max_new <= 1535


# -- metric arithmetic ------------------------------------------------------

def _record():
    """Two requests in a 10 s window, times in seconds."""
    reqs = [
        {"due": 1.0, "admitted": 1.5, "first": 2.0,
         "token_times": [2.0, 2.1, 2.3], "prompt_len": 10,
         "output_len": 3, "status": "finished"},
        {"due": 3.0, "admitted": 3.2, "first": 3.5,
         "token_times": [3.5, 3.6, 9.9, 10.5], "prompt_len": 20,
         "output_len": 4, "status": None},
    ]
    steps = [{"t0": 1.5, "t1": 2.0, "live": 0, "decode_tokens": 0,
              "decode_s": 0.0, "first_tokens": 1,
              "chunks": [(0, 10, 10)]},
             {"t0": 2.0, "t1": 2.1, "live": 1, "decode_tokens": 1,
              "decode_s": 0.1, "first_tokens": 0,
              "chunks": []}]
    return {"window": {"t0": 0.0, "t1": 10.0, "seconds": 10.0},
            "requests": reqs, "steps": steps,
            "setup_s": 5.0}


def _reader(name):
    return spec.load_metric_reader(BENCH, name)


def test_ttft_counts_from_the_due_time():
    assert stats.ttfts(_record()) == [1.0, 0.5]
    assert stats.percentile(stats.ttfts(_record()), 95) == pytest.approx(
        np.percentile([1.0, 0.5], 95))


def test_ttft_of_a_request_never_served_counts_to_the_window_close():
    rec = _record()
    rec["requests"][1]["first"] = None
    assert stats.ttfts(rec) == [1.0, 7.0]


def test_queue_wait_counts_from_the_due_time_to_the_close():
    rec = _record()
    assert stats.queue_waits(rec) == pytest.approx([0.5, 0.2])
    rec["requests"][1]["admitted"] = None
    assert stats.queue_waits(rec) == pytest.approx([0.5, 7.0])
    assert _reader("queue_wait_p95_ms.chat")(rec) == pytest.approx(
        1e3 * np.percentile([0.5, 7.0], 95))


def test_p95_covers_all_requests_and_raises_on_empty():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    rec = _record()
    rec["requests"] = []
    with pytest.raises(ValueError):
        _reader("itl_p95_ms")(rec)


def test_itl_takes_gaps_inside_the_window_only():
    gaps = stats.token_gaps(_record())
    assert gaps == pytest.approx([0.1, 0.2, 0.1, 6.3])


def test_tokens_per_s_covers_the_whole_window():
    # 10 prompt tokens + 1 first token + 1 decoded token over 10 s
    assert _reader("tokens_per_s")(_record()) == pytest.approx(1.2)


def test_decode_step_is_the_mean_over_steps_that_decoded():
    assert _reader("decode_step_ms")(_record()) == pytest.approx(100.0)


def test_setup_s_is_the_record_s():
    assert _reader("setup_s")(_record()) == 5.0


# -- roofline ---------------------------------------------------------------

def test_lowrank_call_ops_and_bytes():
    ops, moved = roofline.lowrank_call(24, 3072, 768, 3072)
    assert ops == 2 * 24 * (3072 * 768 + 768 * 3072)
    assert moved == 2 * (24 * 3072 + 3072 * 768 + 768 * 3072 + 24 * 3072)
    peaks = roofline.PEAKS["TPU v5 lite"]
    t = roofline.least_time(ops, moved, peaks)
    assert t == pytest.approx(moved / 819e9)        # bandwidth-bound


def test_lowrank_cost_from_instruction_text():
    text = ("%lowrank_matmul.37 = bf16[24,256000]{1,0:T(8,128)(2,1)S(1)} "
            "custom-call(bf16[24,3072]{1,0:T(8,128)(2,1)S(1)} %fusion.64, "
            "bf16[3072,1408]{1,0:T(8,128)(2,1)S(1)} %copy-done.3, "
            "bf16[1408,256000]{1,0:T(8,128)(2,1)} %params), "
            "custom_call_target=\"tpu_custom_call\"")
    ops, moved = tr.lowrank_cost(text)
    assert ops == 2 * 24 * (3072 * 1408 + 1408 * 256000)
    assert moved == 2 * 1408 * 256000        # only w1 lives in HBM
    assert tr.kernel_of(text) == "lowrank"
    assert tr.kernel_of("%fusion.3 = bf16[2]{0} fusion()") is None


def test_model_flops_count_linears_attention_and_head():
    cfg = spec.load_json(os.path.join(BENCH, "configs", "minitron-4b.json"))
    ranks = {"blocks/attn/q": 768, "blocks/attn/k": 384, "blocks/attn/v": 384,
             "blocks/attn/o": 768, "blocks/mlp/up": 1152,
             "blocks/mlp/down": 1152, "unembed": 1408}
    lins = roofline.model_linears(cfg, ranks)
    assert {l.name for l in lins} == set(ranks)
    per_layer = 2 * (2 * (3072 * 768 + 768 * 3072)
                     + 2 * (3072 * 384 + 384 * 1024)
                     + (3072 * 1152 + 1152 * 9216)
                     + (9216 * 1152 + 1152 * 3072))
    head = 2 * (3072 * 1408 + 1408 * 256000)
    attn = 4 * 24 * 128 * 100 * 32
    assert roofline.token_flops(cfg, lins, 100, head=True) == pytest.approx(
        32 * per_layer + attn + head)
    # causal prefill of 4 positions from 0 attends 1 + 2 + 3 + 4 keys
    assert roofline.prefill_flops(cfg, lins, 0, 4, head=False) == \
        pytest.approx(4 * 32 * per_layer + 4 * 24 * 128 * 10 * 32)


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        roofline.peaks_for("not a chip")


# -- trace reducer ----------------------------------------------------------

def _ev(name, a, b, module=""):
    return tr.Ev(name, a, b, module)


def test_union_gaps_and_idle_labels():
    ops = [_ev("%a.1 = bf16[2]{0} fusion()", 10, 20, "jit__decode(1)"),
           _ev("%b.2 = bf16[2]{0} fusion()", 15, 30, "jit__decode(1)"),
           _ev("%while.3 = (s32[]) while()", 30, 60, "jit__decode(1)"),
           _ev("%c.4 = bf16[2]{0} fusion()", 50, 60,
               "jit__prefill_chunk(2)")]
    mods = [_ev("jit__decode(1)", 10, 30), _ev("jit__prefill_chunk(2)", 50,
                                              60)]
    spans = [_ev("traced_window", 0, 100), _ev("step", 5, 45),
             _ev("idle", 45, 100)]
    trace = tr.Trace({"/device:TPU:0": tr._attribute(ops, mods)},
                     {"/device:TPU:0": mods}, spans)
    out = tr.reduce(trace, roofline.PEAKS["TPU v5 lite"])
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(30e-9)     # 10-30 and 50-60
    assert out["program_s"] == pytest.approx({"decode": 20e-9,
                                              "prefill_chunk": 10e-9})
    assert out["executions"] == {"decode": 1, "prefill_chunk": 1}
    labels = dict((l, s) for l, s in out["breakdown"]["idle_gaps"])
    assert out["idle_by_span_s"] == pytest.approx({"step": 30e-9,
                                                   "idle": 40e-9})
    assert labels["idle"] == pytest.approx(40e-9)
    assert len(out["breakdown"]["device_ops"]) == 3   # the loop left out


def test_reducer_on_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("idle"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    paths = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert paths
    out = tr.reduce_file(str(paths[0]), ("step", "idle"), None)
    assert out is not None
    assert 0.05 < out["window_s"] < 5.0
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["executions"].get("other", 0) >= 3
    assert out["idle_by_span_s"].get("idle", 0) > 0.04
    assert out["breakdown"]["idle_gaps"][0][0] == "idle"


# -- the factor tree --------------------------------------------------------

def test_seeded_factor_tree_matches_decompose_model():
    import jax

    import factors
    from repro.configs import registry
    from repro.configs.base import LRDConfig
    from repro.core.surgery import decompose_model
    from repro.models.api import get_model

    cfg = registry.get("minitron-4b").smoke
    lrd = LRDConfig(enabled=True, rank_mode="aligned", compression=2.0,
                    rank_align=8, min_dim=32)
    params, axes = get_model(cfg).init(jax.random.PRNGKey(0))
    want, _, _ = decompose_model(params, axes, lrd)
    plan = factors.plan_tree(cfg, lrd)
    got = factors.build(plan, seed=2**40 + 3)
    sig = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert sig(got) == sig(want)
    # same seed, same tree; another seed, another tree
    again = factors.build(plan, seed=2**40 + 3)
    other = factors.build(plan, seed=5)
    leaf = lambda t: np.asarray(t["blocks"]["attn"]["q"]["w0"], np.float32)
    assert np.array_equal(leaf(got), leaf(again))
    assert not np.array_equal(leaf(got), leaf(other))
    # w0 @ w1 has the dense init's variance, 1 / fan_in
    w = leaf(got)[0] @ np.asarray(got["blocks"]["attn"]["q"]["w1"][0],
                                  np.float32)
    assert np.var(w) == pytest.approx(1 / cfg.d_model, rel=0.3)


# -- cells are found by name ------------------------------------------------

def test_new_config_traffic_cell_and_metric_found_by_name(tmp_path):
    bench = tmp_path / "b"
    for d in ("configs", "traffic", "cells", "metrics"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "tiny.json").write_text(json.dumps({"name": "t"}))
    (bench / "traffic" / "bursty.json").write_text(json.dumps(CHAT))
    (bench / "cells" / "tiny-bursty.json").write_text(
        json.dumps({"slots": 2, "max_seq": 64}))
    (bench / "metrics" / "answer.x.py").write_text(
        "def read(rec):\n    return 42.0\n")
    shutil.copy(os.path.join(BENCH, "metrics", "setup_s.py"),
                bench / "metrics" / "setup_s.py")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "b/configs/tiny.json"}],
        "workloads": [{"name": "tiny-bursty", "config": "tiny",
                       "traffic": "bursty", "chips": 1},
                      {"name": "other", "config": "tiny",
                       "traffic": "bursty", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "source": "host_clock"}],
        "per_layer": [{"name": "answer.x", "unit": "1", "better": "higher",
                       "source": "program_counter",
                       "workloads": ["tiny-bursty"]}]}))
    cell = spec.load_cell(str(tmp_path), "tiny-bursty", str(bench))
    assert cell.config == {"name": "t"} and cell.cell["slots"] == 2
    assert cell.traffic == CHAT
    assert [m.name for m in cell.per_layer] == ["answer.x"]
    assert cell.per_layer[0].read({}) == 42.0
    assert cell.end_to_end[0].read({"setup_s": 3.0}) == 3.0
    with pytest.raises(FileNotFoundError):
        spec.load_cell(str(tmp_path), "other", str(bench))
    with pytest.raises(KeyError):
        spec.load_cell(str(tmp_path), "missing", str(bench))


def test_the_benchmark_names_a_file_for_everything():
    bench = spec.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m.name for m in cell.end_to_end]
