"""Names of the serve engine's host spans and of the model's scopes,
and the clock that times each engine phase.

A host span is a :class:`jax.profiler.TraceAnnotation`: when a
profiler session is active it lands in the same trace as the device
ops; otherwise it costs about a microsecond.  (On a TPU the trace puts
device ops on the host's clock only to within a few milliseconds per
session, so lining spans up with device ops needs an alignment first.)
A scope is a :func:`jax.named_scope`: metadata on the compiled ops
(``op_name``), with no effect on the instructions themselves.

:class:`PhaseClock` is the one place a step is timed: each phase's
``perf_counter`` seconds go into the step's ``phase_s`` record, and the
engine's ``seconds`` / ``prefill_seconds`` / straggler and service
clocks are sums of those same readings.
"""
from __future__ import annotations

import time

import jax

#: the whole of ``ServeEngine._step``, a StepTraceAnnotation (step_num)
STEP = "engine.step"
#: deadline expiry, pressure preemption, admission
SCHEDULE = "engine.schedule"
#: decode tokens to the device, the decode program enqueued, the
#: sampler's inputs (logits row, key, temperatures)
DECODE_DISPATCH = "engine.decode.dispatch"
#: the sampler enqueued and the host's wait for its tokens and flags
DECODE_SYNC = "engine.decode.sync"
#: tokens appended, pool growth, finish and release
DECODE_EMIT = "engine.decode.emit"
#: per chunk (with its request's ``uid``): staging cache, prefix
#: gather, padding, the chunk program enqueued; and the first tokens'
#: sampler inputs
PREFILL_DISPATCH = "engine.prefill.dispatch"
#: completed streams' staging caches written into the pool
PREFILL_INSERT = "engine.prefill.insert"
#: the first tokens' sampler enqueued and the host's wait for them
PREFILL_SYNC = "engine.prefill.sync"
#: first tokens appended, finish
PREFILL_EMIT = "engine.prefill.emit"

DECODE_PHASES = (DECODE_DISPATCH, DECODE_SYNC, DECODE_EMIT)
PREFILL_PHASES = (PREFILL_DISPATCH, PREFILL_INSERT, PREFILL_SYNC,
                  PREFILL_EMIT)
#: every phase of a step; phases do not overlap
PHASES = (SCHEDULE,) + DECODE_PHASES + PREFILL_PHASES
#: the phases in which the host waits for the device (besides enqueuing
#: the sampler, which ``ModelRunner.sample`` does in the same call)
SYNC_PHASES = (DECODE_SYNC, PREFILL_SYNC)
SPANS = (STEP,) + PHASES

# Scopes of the compiled programs.  Inside ``layers`` each layer's ops
# run under one part scope; the ops the layer scan itself adds (the
# per-layer slices of the stacked factors and cache, the cache's
# write-back) carry ``layers`` and no part.
EMBED = "embed"
LAYERS = "layers"
QKV_PROJ = "qkv_proj"
KV_WRITE = "kv_write"
ATTEND = "attend"
O_PROJ = "o_proj"
MLP = "mlp"
NORM = "norm"
UNEMBED = "unembed"
SAMPLE = "sample"
LAYER_PARTS = (QKV_PROJ, KV_WRITE, ATTEND, O_PROJ, MLP, NORM)
SCOPES = (EMBED, LAYERS) + LAYER_PARTS + (UNEMBED, SAMPLE)


class _Phase:
    __slots__ = ("clock", "name", "span", "t0")

    def __init__(self, clock: "PhaseClock", name: str, span):
        self.clock, self.name, self.span = clock, name, span

    def __enter__(self) -> None:
        self.span.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        self.clock.seconds[self.name] += time.perf_counter() - self.t0
        self.span.__exit__(*exc)


class PhaseClock:
    """Seconds of each phase of one step, from ``perf_counter``."""

    def __init__(self):
        self.seconds = dict.fromkeys(PHASES, 0.0)

    def phase(self, name: str, **args) -> _Phase:
        """Time ``name`` into :attr:`seconds` inside a host span of that
        name; ``args`` (e.g. ``uid``) ride on the span."""
        return _Phase(self, name, jax.profiler.TraceAnnotation(name, **args))

    def total(self, names=PHASES) -> float:
        return sum(self.seconds[n] for n in names)
