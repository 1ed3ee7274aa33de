"""Set up the system under test and drive it open-loop for one window.

The window drives ``ServeEngine.add_request`` and ``step()`` from one
thread: each request is submitted when it falls due on the arrival
schedule (its ``submit_time`` is the due time, so the engine's own
clocks count from there too), and the engine steps whenever it has
work.  The harness reads from the engine only what it exposes: the
scheduler's queues (to see when a request leaves the waiting queue and
how far each prompt has been prefilled), the per-step stats record and
the requests' token timestamps.

Host spans (``jax.profiler.TraceAnnotation``) mark what the harness is
doing, so that a trace can label the device's idle gaps: ``generator``
(submitting due arrivals), ``add_request``, ``step`` and ``idle``
(waiting for the next arrival).
"""
from __future__ import annotations

import dataclasses
import gc
import os
import time
from typing import Any, Callable

SPAN_GENERATOR = "generator"
SPAN_ADD = "add_request"
SPAN_STEP = "step"
SPAN_IDLE = "idle"
SPAN_TRACED = "traced_window"
SPANS = (SPAN_GENERATOR, SPAN_ADD, SPAN_STEP, SPAN_IDLE)


@dataclasses.dataclass
class Tracked:
    """One request of the schedule and what the harness saw of it."""
    due: float                       # absolute, host perf_counter
    req: Any                         # repro.serve.engine.Request
    submitted: float = 0.0
    admitted: float | None = None    # start of the step that admitted it

    @property
    def prompt_len(self) -> int:
        return len(self.req.prompt)


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    live: int
    decode_tokens: int
    decode_s: float
    first_tokens: int
    chunks: list[tuple[int, int, int]]   # (start, real tokens, prompt)
    cpu_s: float                     # this thread's CPU time in the step
    filled: int                      # pool positions written, after it


class GcClock:
    """Times every garbage collection between ``start`` and ``stop``: a
    list of (generation, seconds)."""

    def __init__(self):
        self.collections: list[tuple[int, float]] = []
        self._t = 0.0

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.collections.append((info["generation"],
                                     time.perf_counter() - self._t))

    def start(self) -> "GcClock":
        gc.callbacks.append(self._on)
        return self

    def stop(self) -> None:
        gc.callbacks.remove(self._on)


def steal_s() -> float | None:
    """Seconds the hypervisor took from this machine's CPUs, summed over
    them, since boot (``/proc/stat``); None where there is none."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


class CompileCounter:
    """Counts programs JAX lowers and compiles (in-memory cache misses),
    from its monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENTS[0]:
            self.lowered += 1
        elif event == self.EVENTS[1]:
            self.compiled += 1

    def snapshot(self) -> tuple[int, int]:
        return self.lowered, self.compiled


def power_of_two_lengths(limit: int) -> list[int]:
    out, n = [], 1
    while n <= limit:
        out.append(n)
        n *= 2
    return out


def warm_up(eng, request_cls) -> int:
    """Run every shape the window can use through the engine's public
    entry points: a final prefill chunk of each power-of-two length up
    to the chunk size (every length bucket), a prompt of several
    chunks, ``k`` prefills completing in one step for every ``k`` up to
    the slot count (each ``k`` is its own batched-sampling shape), and
    the decode step at the pool's slot count.  Returns the steps run."""
    uid = [-1]
    steps = 0

    def serve(prompts, max_new=2):
        nonlocal steps
        for p in prompts:
            uid[0] -= 1
            eng.add_request(request_cls(uid=uid[0], prompt=p,
                                        max_new_tokens=max_new))
        while eng.scheduler.busy():
            eng.step()
            steps += 1

    chunk = eng.prefill_chunk
    for n in power_of_two_lengths(chunk):
        serve([[1] * n])
    serve([[1] * min(2 * chunk + 1, eng.max_seq - 2)])
    for k in range(1, eng.slots + 1):
        serve([[1]] * k)
    return steps


def _progress(tr: Tracked, prefilling: dict) -> int:
    """Prompt tokens of ``tr`` prefilled so far."""
    ps = prefilling.get(id(tr.req))
    if ps is not None:
        return ps.written
    if tr.req.output or tr.req.done:
        return tr.prompt_len
    return 0


def drive(eng, arrivals, request_cls, *, t_start: float, seconds: float,
          trace_seconds: float = 0.0,
          on_trace_start: Callable[[], None] | None = None,
          on_trace_stop: Callable[[], None] | None = None) -> dict:
    """Offer ``arrivals`` open-loop from ``t_start`` for ``seconds``,
    stepping ``eng`` whenever it has work.  When ``trace_seconds`` is
    set, ``on_trace_start`` is called that long before the window
    closes and ``on_trace_stop`` right after it.

    Returns the window's record: its bounds, the tracked requests, the
    steps, how late the generator ran, the garbage collections and the
    CPU time the hypervisor took in the window."""
    import jax

    sched = eng.scheduler
    t_end = t_start + seconds
    t_trace = t_end - trace_seconds if trace_seconds > 0 else None
    tracked: list[Tracked] = []
    inflight: list[Tracked] = []         # submitted, prefill not done
    waiting: list[Tracked] = []          # submitted, not admitted
    steps: list[StepRecord] = []
    late: list[float] = []
    i, n = 0, len(arrivals)
    tracing = None
    span = None
    trace_host = [None, None]
    prev_stats = eng.stats[-1] if eng.stats else None

    def one_step() -> None:
        nonlocal prev_stats
        prefilling = {id(ps.req): ps for ps in sched.prefilling}
        before = [(tr, _progress(tr, prefilling)) for tr in inflight]
        c0 = time.thread_time()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            eng.step()
        t1 = time.perf_counter()
        cpu = time.thread_time() - c0
        filled = int(eng.pool.positions.sum())
        prefilling = {id(ps.req): ps for ps in sched.prefilling}
        chunks = []
        for tr, was in before:
            now_ = _progress(tr, prefilling)
            if now_ > was:
                chunks.append((was, now_ - was, tr.prompt_len))
        inflight[:] = [tr for tr in inflight
                       if _progress(tr, prefilling) < tr.prompt_len
                       and not tr.req.done]
        if waiting:
            queued = {id(r) for r in sched.waiting}
            still = []
            for tr in waiting:
                if id(tr.req) in queued:
                    still.append(tr)
                else:
                    tr.admitted = t0
            waiting[:] = still
        st = eng.stats[-1] if eng.stats else None
        if st is not None and st is not prev_stats:
            steps.append(StepRecord(t0, t1, st["live"], st["tokens"],
                                    st["seconds"], st["first_tokens"],
                                    chunks, cpu, filled))
        else:
            steps.append(StepRecord(t0, t1, 0, 0, 0.0, 0, chunks, cpu,
                                    filled))
        prev_stats = st

    steal0 = steal_s()
    gcs = GcClock().start()
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if t_trace is not None and tracing is None and now >= t_trace:
            on_trace_start()
            span = jax.profiler.TraceAnnotation(SPAN_TRACED)
            span.__enter__()
            tracing = True
            trace_host[0] = time.perf_counter()
        if i < n and t_start + arrivals[i].due <= now:
            with jax.profiler.TraceAnnotation(SPAN_GENERATOR):
                while i < n and t_start + arrivals[i].due <= now:
                    a = arrivals[i]
                    req = request_cls(uid=i, prompt=a.prompt,
                                      max_new_tokens=a.max_new,
                                      temperature=0.0, keep_logits=True)
                    due = t_start + a.due
                    req.submit_time = due
                    tr = Tracked(due, req)
                    with jax.profiler.TraceAnnotation(SPAN_ADD):
                        eng.add_request(req)
                    tr.submitted = time.perf_counter()
                    late.append(tr.submitted - due)
                    tracked.append(tr)
                    inflight.append(tr)
                    waiting.append(tr)
                    i += 1
        if sched.busy():
            one_step()
        else:
            nxt = t_start + arrivals[i].due if i < n else t_end
            with jax.profiler.TraceAnnotation(SPAN_IDLE):
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
    t_close = max(t_end, steps[-1].t1 if steps else t_end)
    gcs.stop()
    steal1 = steal_s()
    if tracing:
        trace_host[1] = time.perf_counter()
        span.__exit__(None, None, None)
        on_trace_stop()
    return {"t0": t_start, "t1": t_close, "seconds": t_close - t_start,
            "requests": tracked, "steps": steps, "late": late,
            "gc": gcs.collections,
            "steal_s": (steal1 - steal0 if steal0 is not None
                        and steal1 is not None else None),
            "trace_host": tuple(trace_host) if tracing else None}
