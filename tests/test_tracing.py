"""The serve engine's phases and spans, the request's admission stamp,
and the model's named scopes.

* every step record carries ``phase_s`` under the fixed phase names, and
  the engine's existing clocks (``seconds``, ``prefill_seconds``,
  ``admit_seconds``, ``service_s``) are sums of those same readings;
* the phases cover the step's wall time;
* ``Request.admit_time`` is stamped once, when the request first leaves
  the waiting queue, and survives a preemption;
* the spans land in a profiler trace, where a ``perf_counter`` stamp
  taken beside a span's start places the engine's steps on the trace's
  clock;
* scopes are metadata only: the compiled decode, chunk and sampler
  programs are the same instructions with ``jax.named_scope`` disabled.
"""
import contextlib
import dataclasses
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs import registry
from repro.configs.base import ParallelConfig, RunConfig
from repro.models.api import get_model
from repro.serve.engine import Request, ServeEngine
from repro.serve.runner import ModelRunner
from repro.train.steps import block_opts

PROMPTS = [[(i * 7 + j) % 50 + 1 for j in range(n)]
           for i, n in enumerate((21, 5, 13, 3))]


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(registry.get("minitron-4b").smoke,
                              dtype="float32")
    run = RunConfig(model=cfg, parallel=ParallelConfig())
    params, _ = get_model(cfg).init(jax.random.PRNGKey(0))
    return run, params


def _engine(setup, **kw):
    run, params = setup
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(run, params, **kw)


def _serve(eng, prompts=PROMPTS, n=5):
    """Serve ``prompts``, timing each ``step()`` from outside."""
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=n)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.add_request(r)
    walls = []
    while eng.scheduler.busy():
        t0 = time.perf_counter()
        eng.step()
        walls.append(time.perf_counter() - t0)
    return reqs, walls


# -- phases and counters ----------------------------------------------------

@pytest.mark.parametrize("admission", ["continuous", "blocking"])
def test_every_step_record_carries_the_fixed_phases(setup, admission):
    eng = _engine(setup, admission=admission)
    reqs, _ = _serve(eng)
    assert all(r.status == "finished" for r in reqs)
    stats = list(eng.stats)
    assert stats
    for s in stats:
        assert tuple(s["phase_s"]) == tracing.PHASES
        assert all(v >= 0.0 for v in s["phase_s"].values())
        dec = sum(s["phase_s"][p] for p in tracing.DECODE_PHASES)
        pre = sum(s["phase_s"][p] for p in tracing.PREFILL_PHASES)
        assert s["seconds"] == pytest.approx(dec, rel=1e-12, abs=0.0)
        # blocking admission reports its whole prefills as admission
        pf = "prefill_seconds" if admission == "continuous" \
            else "admit_seconds"
        assert s[pf] == pytest.approx(pre, rel=1e-12, abs=0.0)
        if s["live"]:
            assert s["phase_s"][tracing.DECODE_SYNC] > 0
    assert sum(s["admitted"] for s in stats) == len(PROMPTS)
    if admission == "continuous":
        # 8-token chunks: 21 -> 3, 5 -> 1, 13 -> 2, 3 -> 1
        assert sum(s["chunks"] for s in stats) == 7
    else:
        assert sum(s["chunks"] for s in stats) == len(PROMPTS)
    # the service clock is the sum of the same readings
    assert eng.service_s == pytest.approx(
        sum(s["seconds"] + s["prefill_seconds"] + s["admit_seconds"]
            for s in stats), rel=1e-9)


def test_phases_cover_the_step_wall_time(setup):
    eng = _engine(setup)
    _serve(eng, n=3)                     # compile every shape first
    eng.stats.clear()
    _, walls = _serve(eng, n=6)
    phased = sum(sum(s["phase_s"].values()) for s in eng.stats)
    assert len(eng.stats) == len(walls)
    assert phased >= 0.95 * sum(walls)
    assert phased <= sum(walls)


def test_straggler_detector_reads_the_phase_sum(setup):
    eng = _engine(setup)
    seen = []
    observe = eng.stragglers.observe

    def spy(step, dt):
        seen.append(dt)
        return observe(step, dt)
    eng.stragglers.observe = spy
    _serve(eng, n=3)
    assert len(seen) == len(eng.stats)
    assert seen == [sum(s["phase_s"].values()) for s in eng.stats]


def test_admit_time_is_stamped_once_between_submit_and_first_token(setup):
    eng = _engine(setup)
    reqs, _ = _serve(eng)
    for r in reqs:
        assert r.submit_time <= r.admit_time <= r.first_token_time


def test_admit_time_survives_a_preemption(setup):
    eng = _engine(setup)
    budget = int(eng.pool.bytes_per_token * 14)
    eng = _engine(setup, kv_byte_budget=budget)
    first = {}
    admit = eng.scheduler.admit

    def spy(pool):
        started = admit(pool)
        for ps in started:
            first.setdefault(ps.req.uid, ps.req.admit_time)
        return started
    eng.scheduler.admit = spy
    reqs, _ = _serve(eng, [[1, 2, 3, 4], [9, 8, 7]], n=10)
    assert eng.preemptions > 0
    preempted = [r for r in reqs if r.preemptions]
    assert preempted
    for r in reqs:
        assert r.admit_time == first[r.uid]
        assert r.submit_time <= r.admit_time <= r.first_token_time


# -- spans on the trace's clock ---------------------------------------------

def _host_events(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out


def test_spans_land_in_the_trace_and_steps_place_on_its_clock(setup,
                                                              tmp_path):
    eng = _engine(setup)
    _serve(eng, n=3)                     # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        t_window = time.perf_counter()
        reqs = [Request(uid=10 + i, prompt=list(p), max_new_tokens=4)
                for i, p in enumerate(PROMPTS)]
        for r in reqs:
            eng.add_request(r)
        steps = []
        while eng.scheduler.busy():
            t0 = time.perf_counter()
            eng.step()
            steps.append((t0, time.perf_counter()))
    jax.profiler.stop_trace()
    paths = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert paths
    events = _host_events(str(paths[0]))
    names = {e[0] for e in events}
    assert set(tracing.SPANS) <= names
    # the chunk spans carry their request's uid, the steps their number
    uids = {e[3].get("uid") for e in events
            if e[0] == tracing.PREFILL_DISPATCH}
    assert {r.uid for r in reqs} <= uids
    window = [e for e in events if e[0] == "window"]
    spans = sorted(e[1:] for e in events if e[0] == tracing.STEP)
    assert len(window) == 1 and len(spans) == len(steps)
    assert [s[2]["step_num"] for s in spans] == list(
        range(eng._step_idx - len(steps) + 1, eng._step_idx + 1))
    # perf_counter -> trace clock, from the window span's start and the
    # perf_counter stamp taken as it opened.  Each step record, stamped
    # just outside its span, then holds that span and none other, to
    # 100 us
    offset = window[0][1] - t_window * 1e9
    errs, prev_end = [], window[0][1]
    for (t0, t1), (a, b, _) in zip(steps, spans):
        a0, b0 = t0 * 1e9 + offset, t1 * 1e9 + offset
        assert a0 - 100e3 <= a and b <= b0 + 100e3
        assert a0 >= prev_end - 100e3
        errs += [abs(a - a0), abs(b - b0)]
        prev_end = b
    assert np.median(errs) < 100e3


# -- scopes are metadata only -----------------------------------------------

_META = re.compile(r",? metadata=\{[^}]*\}")


def _instructions(text: str) -> str:
    """The compiled module's computations, with the metadata (op names,
    source lines) stripped."""
    body = text[text.index("\n%"):] if "\n%" in text else text
    return _META.sub("", body)


def _compiled(run, params):
    """Compiled text of the decode, chunk and sampler programs of a
    fresh runner (so nothing comes from another runner's trace cache)."""
    model = get_model(run.model)
    r = ModelRunner(model, params, block_opts(run), max_seq=32)
    pool = model.init_cache(2, 32)
    stream = r.new_stream_cache()
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    decode = r.jit_decode.lower(params, jnp.zeros((2, 1), jnp.int32),
                                jnp.zeros((2,), jnp.int32), pool)
    chunk = r.jit_prefill_chunk.lower(
        params, {"tokens": jnp.zeros((1, 8), jnp.int32)}, stream, i32(8),
        i32(13))
    sample = r.jit_sample_all.lower(
        jax.random.PRNGKey(0), jnp.zeros((2, run.model.vocab_size)),
        jnp.zeros((2,)))
    return {k: v.compile().as_text()
            for k, v in (("decode", decode), ("chunk", chunk),
                         ("sample", sample))}


def test_scopes_leave_the_compiled_programs_unchanged(setup, monkeypatch):
    run, params = setup
    scoped = _compiled(run, params)
    for name in (tracing.EMBED, tracing.LAYERS, tracing.QKV_PROJ,
                 tracing.KV_WRITE, tracing.ATTEND, tracing.O_PROJ,
                 tracing.MLP, tracing.NORM, tracing.UNEMBED):
        for prog in ("decode", "chunk"):
            assert f"/{name}/" in scoped[prog], (prog, name)
    assert f"/{tracing.SAMPLE}/" in scoped["sample"]
    # the layer scan's own slices run under `layers` and no part
    assert re.search(rf"/{tracing.LAYERS}/while/body/dynamic_slice",
                     scoped["decode"])
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compiled(run, params)
    assert not re.search(r'op_name="[^"]*/layers/', plain["decode"])
    for prog in scoped:
        assert _instructions(scoped[prog]) == _instructions(plain[prog]), \
            prog
