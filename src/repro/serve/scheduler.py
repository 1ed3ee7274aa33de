"""Scheduler: token-budget continuous batching (decode-first policy).

One engine step is one *step plan* filled against ``step_token_budget``:

1. **Preempt** — if the :class:`~repro.serve.pool.KVPoolManager` is
   over its byte budget, the youngest stream(s) are evicted.  An
   evicted request re-enters the waiting queue (at the front) holding
   its generated prefix: on readmission it prefills
   ``prompt + output`` and keeps decoding — bit-exact under greedy
   sampling because chunked prefill == whole prefill == decode.
2. **Admit** — waiting requests take free slots while the pool's byte
   budget allows.  The queue is a :class:`ClassedQueue`: FIFO within a
   priority class, ``interactive`` ahead of ``batch`` across classes
   (pure submission-order FIFO when ``priority_aware=False``).
   Admission only *starts* a prefill stream; there is no blocking
   whole-prompt prefill on this path.
3. **Decode first** — every live stream decodes one token per step,
   unconditionally.  A long prompt can never head-of-line-block live
   decode streams.
4. **Prefill with the remainder** — leftover budget
   (``step_token_budget - live``) is spent on chunked-prefill segments
   of at most ``prefill_chunk`` tokens, oldest prefilling stream
   first.  Chunk *compute* shapes are power-of-2 bucketed by the
   engine (compile once per bucket); the budget counts real tokens.

If the budget is smaller than the live batch, decode still runs in
full (decode-first is strict) and prefill waits; with no live streams
at least one bucket of prefill always proceeds, so the queue can never
deadlock.

The scheduler is family-agnostic — which families take continuous
admission is the engine's gate (dense GQA *and* dense MLA latent
stacks chunk; recurrent/MoE-capacity/VLM stay blocking), and cache
layout is the :class:`repro.layers.cache.CachePlan`'s concern.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any

from repro.serve.paging import PoolExhausted

PyTree = Any

#: admission pads prompts (and prefill chunks) up to at least this
#: power-of-2 length bucket
PREFILL_BUCKET_MIN = 8

#: terminal request states.  Every request that leaves the engine
#: carries exactly one of these in :attr:`Request.status`:
#:
#: * ``finished`` — ran to EOS / ``max_new_tokens``;
#: * ``cancelled`` — :meth:`repro.serve.engine.ServeEngine.cancel`;
#: * ``deadline_exceeded`` — ``deadline_s`` / ``max_queue_s`` expired;
#: * ``failed`` — quarantined by the numerical watchdog, or swept by
#:   the no-progress watchdog;
#: * ``dropped`` — preemption-retry budget spent (``max_preemptions``
#:   evictions) — terminated instead of thrashing the pool forever.
STATUSES = ("finished", "cancelled", "deadline_exceeded", "failed",
            "dropped")

#: request priority classes, highest first.  ``interactive`` streams
#: are admitted and chunk-planned ahead of ``batch`` at every decision
#: point; ``batch`` fills whatever budget is left.
PRIORITIES = ("interactive", "batch")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: list[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: int | None = None
    #: wall-clock SLO: seconds from submit to completion; expired
    #: requests terminate ``deadline_exceeded`` wherever they are
    #: (waiting, prefilling, or decoding)
    deadline_s: float | None = None
    #: max seconds a request may sit *unadmitted* in the waiting queue
    max_queue_s: float | None = None
    #: preemption-retry budget: one more eviction than this terminates
    #: the request ``dropped``
    max_preemptions: int = 8
    #: one of :data:`PRIORITIES` — interactive streams decode/admit
    #: first, batch fills residual budget
    priority: str = "interactive"
    #: keep the (V,) f32 logits the first token is sampled from (the
    #: prompt's last position) in ``first_logits`` — for comparing
    #: serving paths by their logits
    keep_logits: bool = False
    # filled by the engine:
    output: list[int] = dataclasses.field(default_factory=list)
    first_logits: Any = None
    done: bool = False
    #: one of :data:`STATUSES` once terminal, else ``None``
    status: str | None = None
    # timing / lifecycle bookkeeping (engine-filled):
    submit_time: float | None = None
    #: when the request first left the waiting queue for a slot; a
    #: preempted request keeps its first stamp
    admit_time: float | None = None
    first_token_time: float | None = None
    token_times: list[float] = dataclasses.field(default_factory=list)
    preemptions: int = 0
    #: ``(engine key, engine service seconds at last token)`` — the
    #: engine's service-time ITL accounting; the key guards against a
    #: stale mark after an evacuation re-routes the request
    service_mark: tuple[int, float] | None = None

    @property
    def ttft(self) -> float | None:
        """Time-to-first-token (seconds), once both ends are stamped."""
        if self.submit_time is None or self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time


@dataclasses.dataclass
class PrefillStream:
    """An admitted request whose prompt is being prefilled in chunks."""
    req: Request
    slot: int
    tokens: list[int]            # prompt (+ generated prefix if resumed)
    written: int = 0             # real prompt tokens already processed
    cache: PyTree = None         # full-precision staging cache (lazy)
    last_logits: Any = None      # (V,) logits at the last real row seen

    @property
    def remaining(self) -> int:
        return len(self.tokens) - self.written


class ClassedQueue:
    """Per-priority-class waiting queues behind the old single-deque
    surface.

    Iteration/peek/popleft order is *interactive first, FIFO within
    class* when ``aware`` (the default), or pure submission-order FIFO
    when priority-blind (the baseline the router bench compares
    against).  Every deque operation the engine performs on
    ``Scheduler.waiting`` — truthiness, ``len``, iteration, ``remove``,
    ``append``/``appendleft``/``popleft``, head peek — works unchanged,
    so all existing single-class behavior is bit-identical (a lone
    class is just a lone deque).
    """

    def __init__(self, aware: bool = True):
        self.aware = aware
        self.by_class: dict[str, deque[Request]] = (
            {p: deque() for p in PRIORITIES} if aware
            else {PRIORITIES[0]: deque()})

    def _cls(self, req: Request) -> str:
        return req.priority if self.aware else PRIORITIES[0]

    def append(self, req: Request) -> None:
        self.by_class[self._cls(req)].append(req)

    def appendleft(self, req: Request) -> None:
        self.by_class[self._cls(req)].appendleft(req)

    def popleft(self) -> Request:
        for q in self.by_class.values():
            if q:
                return q.popleft()
        raise IndexError("pop from an empty ClassedQueue")

    def remove(self, req: Request) -> None:
        self.by_class[self._cls(req)].remove(req)

    def count(self, priority: str) -> int:
        if not self.aware:
            return sum(1 for r in self.by_class[PRIORITIES[0]]
                       if r.priority == priority)
        return len(self.by_class[priority])

    def __iter__(self):
        for q in self.by_class.values():
            yield from q

    def __len__(self) -> int:
        return sum(len(q) for q in self.by_class.values())

    def __bool__(self) -> bool:
        return any(self.by_class.values())

    def __getitem__(self, i: int):
        if i == 0:          # head peek — the only index the engine uses
            for q in self.by_class.values():
                if q:
                    return q[0]
            raise IndexError("peek at an empty ClassedQueue")
        return list(self)[i]


class Scheduler:
    """Request lifecycle + per-step segment planning."""

    def __init__(self, slots: int, *, prefill_chunk: int,
                 step_token_budget: int, priority_aware: bool = True,
                 batch_share: float = 1.0):
        self.slots = slots
        self.prefill_chunk = max(1, prefill_chunk)
        self.step_token_budget = max(1, step_token_budget)
        #: honor :attr:`Request.priority` in queueing and planning;
        #: ``False`` degrades to the old single-FIFO behavior (the
        #: priority-blind baseline)
        self.priority_aware = priority_aware
        #: fraction of the per-step prefill quota that ``batch``
        #: prefill segments may take *while interactive work is in
        #: flight* (1.0 = no throttle; batch always gets the full
        #: residual quota once interactive traffic drains)
        self.batch_share = min(max(float(batch_share), 0.0), 1.0)
        self.waiting = ClassedQueue(priority_aware)
        self.prefilling: list[PrefillStream] = []
        self.active: list[Request | None] = [None] * slots
        self.finished: list[Request] = []
        self.preemptions = 0
        #: admissions refused for capacity (byte budget or a real
        #: :class:`~repro.serve.paging.PoolExhausted`) — one of the two
        #: pressure signals the load shedder watches
        self.admit_failures = 0

    # -- lifecycle ----------------------------------------------------------

    def submit(self, req: Request) -> None:
        if req.priority not in PRIORITIES:
            raise ValueError(f"unknown priority {req.priority!r} "
                             f"(want one of {PRIORITIES})")
        self.waiting.append(req)

    def terminal(self, req: Request, status: str) -> Request:
        """Move ``req`` to its terminal state: stamp ``status``, mark
        done, record in ``finished``.  The single exit point every path
        (finish, cancel, deadline, quarantine, drop) funnels through —
        no request leaves the engine without an explicit status."""
        if status not in STATUSES:
            raise ValueError(
                f"unknown terminal status {status!r} "
                f"(want one of {STATUSES})")
        req.status = status
        req.done = True
        self.finished.append(req)
        return req

    def busy(self) -> bool:
        return bool(self.waiting or self.prefilling
                    or any(r is not None for r in self.active))

    def interactive_inflight(self) -> bool:
        """Any interactive stream currently decoding or prefilling?
        (Waiting does not count — an unadmitted request has no tail to
        protect yet.)"""
        return (any(r is not None and r.priority == PRIORITIES[0]
                    for r in self.active)
                or any(ps.req.priority == PRIORITIES[0]
                       for ps in self.prefilling))

    def interactive_pending(self) -> bool:
        """Any interactive work at all — in flight *or* still waiting?
        The router's SLO gate uses this: a batch request admitted while
        interactive requests sit unadmitted would steal their slots and
        prefill budget before the tail is even measurable."""
        return (self.interactive_inflight()
                or self.waiting.count(PRIORITIES[0]) > 0)

    def batch_pending(self) -> bool:
        """Any batch work in flight or waiting?  The router only
        asserts ``slo_pressure`` (early load shedding) on a replica
        that actually has batch load to shed — shedding a
        pure-interactive replica could only hurt the tail it is meant
        to protect."""
        batch = PRIORITIES[1]
        return (any(r is not None and r.priority == batch
                    for r in self.active)
                or any(ps.req.priority == batch for ps in self.prefilling)
                or self.waiting.count(batch) > 0)

    def live_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.active) if r is not None]

    def admit(self, pool) -> list[PrefillStream]:
        """Move waiting requests into free slots while the byte budget
        allows (FIFO — the head blocks rather than being skipped).
        Capacity refusals — ``can_admit`` saying no, or ``allocate``
        itself raising :class:`~repro.serve.paging.PoolExhausted` (the
        radix-informed feasibility check is optimistic about shared
        blocks) — count into ``admit_failures`` and leave the request
        queued at the head; it retries next step."""
        started: list[PrefillStream] = []
        for slot in pool.free_slots():
            if not self.waiting:
                break
            req = self.waiting[0]
            # a preempted request resumes by re-prefilling its prompt
            # plus everything it already generated
            toks = list(req.prompt) + list(req.output)
            if not pool.can_admit(len(toks), tokens=toks):
                self.admit_failures += 1
                break
            # a paged pool prefix-matches the prompt against its radix
            # cache: `matched` leading tokens are already pooled, so the
            # stream starts with them written (the engine gathers their
            # KV into the staging cache before the first chunk)
            try:
                matched = pool.allocate(slot, len(toks), tokens=toks)
            except PoolExhausted:
                self.admit_failures += 1
                break
            self.waiting.popleft()
            if req.admit_time is None:
                req.admit_time = time.perf_counter()
            ps = PrefillStream(req, slot, toks, written=matched)
            self.prefilling.append(ps)
            started.append(ps)
        return started

    def activate(self, ps: PrefillStream) -> None:
        self.prefilling.remove(ps)
        self.active[ps.slot] = ps.req

    def finish(self, slot: int) -> Request:
        req = self.active[slot]
        self.terminal(req, "finished")
        self.active[slot] = None
        return req

    def quarantine(self, slot: int) -> Request:
        """Terminate the stream in ``slot`` (decode-live or
        mid-prefill) as ``failed`` — the numerical watchdog flagged its
        logits.  The caller reclaims the pool slot (with
        ``publish=False``: a poisoned cache must never enter the shared
        radix)."""
        req = self.active[slot]
        if req is not None:
            self.active[slot] = None
        else:
            ps = next(p for p in self.prefilling if p.slot == slot)
            self.prefilling.remove(ps)
            req = ps.req
        return self.terminal(req, "failed")

    def preempt(self, slot: int) -> Request:
        """Evict the stream in ``slot`` (decode-live or mid-prefill).
        Within its retry budget it requeues at the queue head with its
        generated prefix; past the budget it terminates ``dropped``
        (bounded work per request — no preemption thrashing)."""
        req = self.active[slot]
        if req is not None:
            self.active[slot] = None
        else:
            ps = next(p for p in self.prefilling if p.slot == slot)
            self.prefilling.remove(ps)
            req = ps.req
        req.preemptions += 1
        self.preemptions += 1
        if req.preemptions > req.max_preemptions:
            self.terminal(req, "dropped")
        else:
            self.waiting.appendleft(req)
        return req

    # -- per-step planning --------------------------------------------------

    def prefill_quota(self, n_live: int) -> int:
        """Real prefill tokens this step may spend: whatever the budget
        leaves after decode-first, but never zero when nothing is
        decoding (guaranteed progress — the queue cannot stall)."""
        quota = self.step_token_budget - n_live
        if n_live == 0:
            quota = max(quota, 1)
        return max(quota, 0)

    def chunk_plan(self, n_live: int) -> list[tuple[PrefillStream, int]]:
        """(stream, real-token chunk length) segments for this step,
        oldest prefilling stream first, until the quota is spent.

        When :attr:`priority_aware`, interactive streams plan ahead of
        batch regardless of admission order, and — while interactive
        work is in flight — batch segments are additionally capped to
        ``batch_share`` of the quota (interactive prefill takes the
        rest; batch gets the full quota back once interactive drains).
        Progress is guaranteed: with nothing decoding, at least one
        stream always gets a non-empty segment, share-capped or not.

        Non-final segments are always exactly :attr:`prefill_chunk`
        real tokens: a runt segment (leftover quota smaller than the
        chunk) would be a fresh compile shape per distinct residual —
        several streams splitting one step's quota used to generate
        3-token prefill launches whose first-time compiles dwarfed the
        tokens they carried.  A stream whose turn only has runt quota
        left simply waits for the next step; final chunks stay
        arbitrary-length (the engine buckets them to a bounded shape
        set).
        """
        quota = self.prefill_quota(n_live)
        streams = self.prefilling
        batch_quota = quota
        if self.priority_aware:
            first = [ps for ps in self.prefilling
                     if ps.req.priority == PRIORITIES[0]]
            rest = [ps for ps in self.prefilling
                    if ps.req.priority != PRIORITIES[0]]
            streams = first + rest
            if self.batch_share < 1.0 and self.interactive_inflight():
                batch_quota = int(quota * self.batch_share)
        plan: list[tuple[PrefillStream, int]] = []
        for ps in streams:
            if quota <= 0:
                break
            c = min(self.prefill_chunk, quota, ps.remaining)
            if self.priority_aware and ps.req.priority != PRIORITIES[0]:
                c = min(c, batch_quota)
            if c <= 0:
                continue
            if c < self.prefill_chunk and c < ps.remaining:
                continue    # runt non-final segment — wait a step
            plan.append((ps, c))
            quota -= c
            if self.priority_aware and ps.req.priority != PRIORITIES[0]:
                batch_quota -= c
        if not plan and self.prefilling and n_live == 0:
            # every stream was share-capped to zero and nothing is
            # decoding: force one segment so the queue can never stall
            ps = self.prefilling[0]
            c = min(self.prefill_chunk, max(quota, 1), ps.remaining)
            if c > 0:
                plan.append((ps, c))
        return plan


# ---------------------------------------------------------------------------
# Graceful degradation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DegradationPolicy:
    """Watermarks for the :class:`LoadShedder` hysteresis.

    Pressure events (a preemption or an admission failure in a step)
    are counted over a rolling ``window`` of steps.  At or above
    ``engage * window`` events the shedder engages; it only disengages
    once the count falls to ``disengage * window`` or below AND at
    least ``min_engaged_steps`` have passed — the dead band plus the
    minimum dwell prevents flapping at the watermark.
    """
    window: int = 16
    engage: float = 0.5
    disengage: float = 0.0625          # <= 1 event left in the window
    #: engaged ``step_token_budget`` multiplier (less prefill per step
    #: -> fewer concurrent residents -> pool pressure drains)
    budget_factor: float = 0.5
    min_engaged_steps: int = 8


class LoadShedder:
    """Pressure-watching hysteresis switch over the step loop.

    One :meth:`observe` call per engine step with that step's pressure
    bit.  While engaged, the engine (a) runs with ``budget`` — a shrunk
    ``step_token_budget`` — and (b) pauses admission whenever work is
    already in flight (never when the engine is idle: an empty engine
    must always be allowed to start, so shedding can never deadlock the
    queue).  Recovery is automatic when pressure clears.
    """

    def __init__(self, policy: DegradationPolicy, base_budget: int):
        self.policy = policy
        self.base_budget = base_budget
        self.events: deque[int] = deque(maxlen=policy.window)
        self.engaged = False
        self.engaged_steps = 0
        self.engage_count = 0
        self.recover_count = 0

    @property
    def pressure_events(self) -> int:
        return sum(self.events)

    def observe(self, pressure: bool) -> bool:
        """Record one step's pressure bit; returns the (possibly
        toggled) engaged state."""
        self.events.append(1 if pressure else 0)
        p = self.policy
        if self.engaged:
            self.engaged_steps += 1
            if (self.engaged_steps >= p.min_engaged_steps
                    and self.pressure_events <= p.disengage * p.window):
                self.engaged = False
                self.recover_count += 1
        elif self.pressure_events >= p.engage * p.window:
            self.engaged = True
            self.engaged_steps = 0
            self.engage_count += 1
        return self.engaged

    @property
    def budget(self) -> int:
        """The step token budget to run with right now."""
        if self.engaged:
            return max(1, int(self.base_budget * self.policy.budget_factor))
        return self.base_budget
