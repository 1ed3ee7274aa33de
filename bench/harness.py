"""One run of one cell: set up, drive the window, check, and report.

Set-up builds the cell's factor tree on the device from the seed,
builds the ``ServeEngine`` over it and warms every shape the window can
use; the window offers the cell's traffic open-loop; then the peak
memory is read, the engine is freed and the served tokens are compared
with the plain reference.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import glob
import os
import shutil
import tempfile
import time

import numpy as np

import check
import factors
import roofline
import serve_window as sw
import traffic_gen

FAILED_STATUSES = ("failed", "dropped", "cancelled", "deadline_exceeded")
#: a traced run traces the window's last this many seconds
TRACE_SECONDS = 6.0
#: bytes of one K or V element in the pool, by the configuration's dtype
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(cell):
    """The registry's configuration for the cell, cut as the cell's
    configuration file says, and checked against the file's sizes."""
    from repro.configs import registry
    c = cell.config
    entry = registry.get(c["arch"])
    preset = entry.smoke if c.get("preset") == "smoke" else entry.full
    cfg = dataclasses.replace(preset, num_layers=c["num_hidden_layers"])
    want = {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
            "num_heads": c["num_attention_heads"],
            "num_kv_heads": c["num_key_value_heads"],
            "head_dim": c["head_dim"], "vocab_size": c["vocab_size"],
            "rope_theta": float(c["rope_theta"]), "dtype": c["torch_dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"{c['name']}: the registry's sizes {got} are "
                         f"not the file's {want}")
    return entry, cfg


def lrd_config(cell, variant: dict | None = None):
    from repro.configs.base import LRDConfig
    lrd = cell.config["lrd"]
    return LRDConfig(enabled=True, compression=lrd["compression"],
                     rank_mode=lrd["rank_mode"], rank_align=lrd["rank_align"],
                     min_dim=lrd["min_dim"], use_pallas=lrd["use_pallas"],
                     **(variant or {}))


def build_engine(cell, entry, cfg, lrd, params):
    from repro.configs.base import RunConfig
    from repro.serve.engine import ServeEngine
    run = RunConfig(model=cfg, lrd=lrd, parallel=entry.parallel("decode"))
    return ServeEngine(run, params, slots=cell.cell["slots"],
                       max_seq=cell.cell["max_seq"])


def request_record(tr) -> dict:
    r = tr.req
    return {"due": tr.due, "admitted": tr.admitted,
            "first": r.first_token_time, "token_times": list(r.token_times),
            "prompt_len": len(r.prompt), "output_len": len(r.output),
            "status": r.status}


@dataclasses.dataclass
class Outcome:
    record: dict
    attempted: int
    failed: int
    peak_bytes: int | None
    found: dict                 # the check's readings
    sampled: int
    window_compiles: tuple[int, int]
    late: list


@dataclasses.dataclass
class Setup:
    entry: object
    cfg: object
    plan: object
    params: object              # None once handed to a quantizing engine
    eng: object


def quantize_by_subtree(tree: dict, lrd) -> dict:
    """The program's own ``quantize_tree``, applied one linear subtree
    at a time in place, so that each full-width subtree is dropped as
    soon as its quantized copy exists (the engine's whole-tree pass then
    finds nothing left to do; quantizing the whole tree at once needs
    the full-width tree, the quantized one and float32 temporaries of
    the largest leaf together, more than a chip holds at nemo's size)."""
    import jax
    from repro.quant import quantize_tree
    if lrd.quantize == "none":
        return tree
    for key in list(tree):
        node = tree[key]
        if isinstance(node, dict) and any(isinstance(v, dict)
                                          for v in node.values()):
            quantize_by_subtree(node, lrd)
        elif isinstance(node, dict):
            tree[key] = quantize_tree(node, mode=lrd.quantize,
                                      targets=lrd.quant_targets)
            del node
            jax.block_until_ready(tree[key])
    return tree


def set_up(cell, seed: int, variant: dict | None = None) -> Setup:
    """Draw the factors from ``seed``, build the engine over them (with
    ``variant``'s LRD options, e.g. a lower precision) and warm every
    shape the window can use."""
    from repro.serve.engine import Request

    entry, cfg = model_config(cell)
    plan = factors.plan_tree(cfg, lrd_config(cell))
    params = factors.build(plan, seed)
    lrd = lrd_config(cell, variant)
    if variant:
        # the engine serves a quantized copy; the reference redraws the
        # served factors from the seed after the window
        params = quantize_by_subtree(params, lrd)
    eng = build_engine(cell, entry, cfg, lrd, params)
    if variant:
        params = None
        gc.collect()
    sw.warm_up(eng, Request)
    return Setup(entry, cfg, plan, params, eng)


def drive_window(cell, st: Setup, *, seed: int, seconds: float,
                 trace_dir: str | None, rate: float | None = None) -> dict:
    """Offer the cell's traffic (at ``rate``, default the cell's) for
    ``seconds``; trace the window's last ``TRACE_SECONDS`` into
    ``trace_dir`` when given."""
    import jax
    from repro.serve.engine import Request

    c = cell.cell
    arrivals = traffic_gen.schedule(
        cell.traffic, rate=rate or c["rate_rps"], seconds=seconds,
        seed=seed, vocab=st.cfg.vocab_size, max_seq=c["max_seq"])
    jax.block_until_ready(st.eng.pool.cache)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return sw.drive(
        st.eng, arrivals, Request, t_start=time.perf_counter(),
        seconds=seconds,
        trace_seconds=min(TRACE_SECONDS, seconds) if trace_dir else 0.0,
        on_trace_start=lambda: jax.profiler.start_trace(
            trace_dir, profiler_options=opts),
        on_trace_stop=jax.profiler.stop_trace)


def make_record(cell, st: Setup, win: dict, t_process: float) -> dict:
    import jax
    ranks = factors.ranks_of(st.plan)
    return {
        "setup_s": win["t0"] - t_process,
        "window": {"t0": win["t0"], "t1": win["t1"],
                   "seconds": win["seconds"]},
        "requests": [request_record(tr) for tr in win["requests"]],
        "steps": [dataclasses.asdict(s) for s in win["steps"]],
        "config": cell.config,
        "linears": roofline.model_linears(cell.config, ranks),
        "peaks": roofline.peaks_for(jax.devices()[0].device_kind),
        "trace_host": win["trace_host"],
        "trace": None,
        "gc": win["gc"],
        "steal_s": win["steal_s"],
        "pool": {"slots": cell.cell["slots"], "max_seq": cell.cell["max_seq"],
                 "bytes_per_position": kv_bytes_per_position(cell.config)},
    }


def kv_bytes_per_position(config: dict) -> int:
    """HBM bytes one token's K and V take in the pool, over all layers."""
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"] * DTYPE_BYTES[config["torch_dtype"]])


def run_window(cell, *, seed: int, seconds: float, trace: bool,
               t_process: float, variant: dict | None = None) -> Outcome:
    """Set up, drive one window, and compare with the reference."""
    import jax

    counter = sw.CompileCounter()
    st = set_up(cell, seed, variant)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    compiles0 = counter.snapshot()
    win = drive_window(cell, st, seed=seed, seconds=seconds,
                       trace_dir=trace_dir)
    compiles1 = counter.snapshot()
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    return judge(cell, st, win, seed=seed, t_process=t_process, peak=peak,
                 compiles=(compiles1[0] - compiles0[0],
                           compiles1[1] - compiles0[1]),
                 trace_dir=trace_dir)


def judge(cell, st: Setup, win: dict, *, seed: int, t_process: float,
          peak: int | None = None, compiles: tuple[int, int] = (0, 0),
          trace_dir: str | None = None, keep: bool = False) -> Outcome:
    """After the window: the record, the trace's reduction and the
    comparison with the reference.  Unless ``keep``, the engine is freed
    first and the factors after."""
    tracked = win["requests"]
    failed = sum(tr.req.status in FAILED_STATUSES for tr in tracked)
    finished = [tr.req for tr in tracked if tr.req.status == "finished"]
    record = make_record(cell, st, win, t_process)
    if not keep:
        st.eng = None
        gc.collect()
    if trace_dir:
        import trace_reduce
        paths = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        try:
            record["trace"] = trace_reduce.reduce_file(
                paths[0], sw.SPANS, record["peaks"]) if paths else None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    params = st.params if st.params is not None else factors.build(
        st.plan, seed)
    if not keep:
        st.params = None
    found, sampled = compare(cell, params, finished, seed)
    del params
    gc.collect()
    return Outcome(record, len(tracked), failed, peak, found, sampled,
                   compiles, win["late"])


def compare(cell, params, finished: list, seed: int) -> tuple[dict, int]:
    """The check's readings over a sample of ``finished``, drawn from
    ``seed``, against the reference over ``params``."""
    c = cell.cell
    picked = check.sample(finished, c["check"]["requests"], seed)
    if not picked:
        return {}, 0
    gaps, devs = check.served_gaps(params, cell.config, picked,
                                   seq_len=c["max_seq"],
                                   rows=cell.traffic["output_len"]["max"])
    return check.readings(gaps, devs), len(picked)


def device_line(peak: int | None) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def read_metrics(cell, record: dict, per_layer: bool) -> dict:
    out = {}
    for m in (cell.per_layer if per_layer else cell.end_to_end):
        v = m.read(record)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def result(cell, oc: Outcome, trace: bool) -> tuple[dict, list[str]]:
    """The result line and the check lines of a run: each number the
    cell's file limits, beside its limit, and the failed requests."""
    limits = cell.cell["check"]["limits"]
    checks = {name: {"value": oc.found.get(name, float("inf")),
                     "limit": limit} for name, limit in limits.items()}
    checks["failed_requests"] = {"value": oc.failed, "limit": 0}
    correct = bool(oc.sampled > 0
                   and all(c["value"] <= c["limit"] for c in checks.values()))
    lines = [f"check {name} {c['value']!r} limit {c['limit']!r}"
             for name, c in checks.items()]
    device = device_line(oc.peak_bytes)
    out = {"correct": correct, "attempted": oc.attempted,
           "failed": oc.failed,
           "metrics": read_metrics(cell, oc.record, trace),
           "device": device}
    tr = oc.record["trace"]
    if trace and tr is not None:
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = tr["breakdown"]
    out["checks"] = checks
    return out, lines


def late_line(oc: Outcome) -> str:
    import stats
    late = np.asarray(oc.late) * 1e3
    ttft = {f"p{q}": 1e3 * stats.percentile(stats.ttfts(oc.record), q)
            for q in (50, 90, 95)}
    return (f"ttft_ms {json.dumps(ttft)}; "
            f"generator late_ms max {float(late.max()):.3f} "
            f"p95 {float(np.percentile(late, 95)):.3f} "
            f"mean {float(late.mean()):.3f} over {late.size} arrivals; "
            f"window compiles lowered {oc.window_compiles[0]} "
            f"compiled {oc.window_compiles[1]}; check readings over "
            f"{oc.sampled} requests {json.dumps(oc.found)}")


def host_line(oc: Outcome) -> str:
    """What the host did in the window, to find a stall: the longest
    step (wall, this thread's CPU time, the engine's decode seconds),
    the garbage collections, the CPU time the hypervisor took; and how
    full the pool was, weighted by step time."""
    rec = oc.record
    steps = rec["steps"]
    if not steps:
        return "host: no steps in the window"
    worst = max(steps, key=lambda s: s["t1"] - s["t0"])
    dt = np.array([s["t1"] - s["t0"] for s in steps])
    live = float(np.average([s["live"] for s in steps], weights=dt))
    filled = float(np.average([s["filled"] for s in steps], weights=dt))
    pool = rec["pool"]
    gen2 = [d for g, d in rec["gc"] if g == 2]
    gc_s = sum(d for _, d in rec["gc"])
    return (f"host: longest step wall_ms {1e3 * (worst['t1'] - worst['t0']):.3f}"
            f" cpu_ms {1e3 * worst['cpu_s']:.3f} decode_ms "
            f"{1e3 * worst['decode_s']:.3f} live {worst['live']} chunks "
            f"{len(worst['chunks'])}; steps {len(steps)} over 100 ms "
            f"{int((dt > 0.1).sum())}; gc {len(rec['gc'])} collections "
            f"{1e3 * gc_s:.3f} ms, gen2 {len(gen2)} max_ms "
            f"{1e3 * max(gen2, default=0.0):.3f}; steal_s {rec['steal_s']}; "
            f"pool mean live slots {live:.3f} of {pool['slots']}, mean "
            f"positions filled {filled:.1f} of "
            f"{pool['slots'] * pool['max_seq']} "
            f"({filled * pool['bytes_per_position'] / 2**30:.3f} GiB of KV)")
