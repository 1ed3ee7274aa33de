"""Batched serving engine: continuous batching over a fixed slot pool.

``ServeEngine`` is a thin façade over three seams (one file each, one
responsibility each):

* :class:`repro.serve.scheduler.Scheduler` — request lifecycle + the
  per-step *token budget* plan: decode-first (every live stream decodes
  one token per step, unconditionally), then **chunked prefill**
  segments with the leftover budget.  A long prompt is processed
  ``prefill_chunk`` tokens at a time interleaved with decode, so it can
  never head-of-line-block live streams the way the old blocking
  per-admit prefill did.
* :class:`repro.serve.pool.KVPoolManager` — the cache pytree
  ``(..., B_slots, S_max, ...)`` in any :class:`repro.layers.cache.
  CachePlan` family (``gqa_f32 | gqa_int8 | mla_latent |
  mla_latent_int8``), slot allocation, plan-derived byte accounting,
  byte-budget admission, and **KV-pressure preemption**: the youngest
  stream is evicted and requeued with its generated prefix
  (bit-deterministic under greedy — chunked prefill == whole prefill
  == decode).
* :class:`repro.serve.runner.ModelRunner` — params + every jitted step
  function behind one ``step(tokens, positions, seg_kind)`` entry
  (``"decode"`` | ``"prefill_chunk"`` | ``"prefill"``), threading the
  right CachePlan into each segment.

Chunked ("continuous") admission is the default for the dense family —
plain GQA *and* MLA latent stacks (offset latent chunk writes make the
segmented prefill exact); recurrent (SSM/hybrid), MoE-capacity, and
VLM stacks keep the whole-prompt "blocking" admission path (prompt
chunking is not inert for them).  In-flight chunked prompts stage in a
full-precision batch=1 cache and land in the pool in one scatter
(quantizing on insert for int8 pools), so chunked greedy output streams
match whole-prefill exactly for BOTH cache dtypes.

Sampling: greedy or temperature; stop on EOS or max tokens.  One device
call samples all slots per step (and all prefill completions per step)
AND runs the numerical watchdog (:mod:`repro.serve.guard`): a stream
whose logits go non-finite is quarantined — terminated ``failed``, its
slot/blocks reclaimed without publishing to the radix — while its
co-batched neighbors' token streams stay bit-identical.  Per-step stats
(a bounded ring buffer) record each phase's host seconds (``phase_s``,
:mod:`repro.tracing`; each phase is also a profiler span) and their
decode, prefill and admission sums; every request carries submit,
admission and token timestamps.

Hardening (the serve twin of :mod:`repro.train.fault_tolerance`):

* lifecycle — per-request ``deadline_s`` / ``max_queue_s`` expiry,
  :meth:`ServeEngine.cancel` from every state, a preemption-retry
  budget (``max_preemptions`` evictions, then ``dropped``), and a
  terminal :data:`repro.serve.scheduler.STATUSES` status on every
  request that leaves the engine;
* degradation — a :class:`repro.serve.scheduler.LoadShedder` watches
  preemption + admission-failure pressure over the stats window and,
  past its watermark (with hysteresis), shrinks the step token budget
  and pauses admission until pressure clears;
* watchdogs — a no-progress guard in :meth:`run_until_done` (stalled
  engines mark survivors ``failed`` instead of silently returning), a
  per-step :class:`repro.train.fault_tolerance.StragglerDetector`, and
  (under ``debug=True``) the pool's ``check_integrity()`` after every
  step;
* chaos — a :class:`repro.serve.faults.FaultInjector` threads named
  injection points through the pool, runner, and kernel gate
  (``tests/test_serve_faults.py`` drives them all).
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.configs.base import RunConfig
from repro.models.api import get_model
from repro.serve import paging
from repro.serve.faults import NULL_INJECTOR, FaultInjector
from repro.serve.metrics import latency_summary
from repro.serve.paging import PoolExhausted
from repro.serve.pool import KVPoolManager, PagedKVPoolManager
from repro.serve.runner import ModelRunner
from repro.serve.scheduler import (PREFILL_BUCKET_MIN, PRIORITIES,
                                   DegradationPolicy, LoadShedder,
                                   PrefillStream, Request, Scheduler)
from repro.train.fault_tolerance import StragglerDetector
from repro.train.steps import block_opts

__all__ = ["ServeEngine", "Request", "FaultInjector",
           "PREFILL_BUCKET_MIN"]

PyTree = Any

#: default tokens per chunked-prefill segment (LRDConfig.prefill_chunk
#: or the engine kwarg override it)
DEFAULT_PREFILL_CHUNK = 64

#: steps of stats kept (ring buffer — long-running engines must not
#: grow host memory without bound)
STATS_WINDOW = 4096

#: consecutive zero-progress steps (no tokens, no prefill, no
#: admissions, no completions) before :meth:`ServeEngine.run_until_done`
#: declares the engine stalled and fails the survivors
DEFAULT_STALL_STEPS = 64


class ServeEngine:
    #: families where prompt padding is inert: causal attention never
    #: lets a real token see a pad token.  SSM/hybrid recurrent state
    #: *advances* through pad tokens, and MoE expert-capacity routing
    #: lets pads displace real tokens — those families prefill unpadded.
    _BUCKET_FAMILIES = ("dense", "vlm")

    #: families served with chunked continuous admission: attention
    #: stacks where a chunk's K/V (or MLA latents) lands at a sequence
    #: offset and absolute causality makes the segmented prefill exact.
    #: VLM (image KV precompute), MoE capacity routing (per-chunk
    #: expert capacity != whole-prompt capacity), and recurrent state
    #: keep blocking whole-prompt admission.
    _CHUNK_FAMILIES = ("dense",)

    def __init__(self, run: RunConfig, params: PyTree, *, slots: int = 4,
                 max_seq: int = 512, seed: int = 0,
                 quantize: str | None = None,
                 sparsify: str | None = None,
                 kv_quantize: str | None = None,
                 act_quantize: str | None = None,
                 admission: str | None = None,
                 prefill_chunk: int | None = None,
                 step_token_budget: int | None = None,
                 kv_byte_budget: int | None = None,
                 kv_layout: str | None = None,
                 kv_block_size: int | None = None,
                 kv_num_blocks: int | None = None,
                 stats_window: int = STATS_WINDOW,
                 debug: bool = False,
                 faults: FaultInjector | None = None,
                 degradation: DegradationPolicy | bool = True,
                 stall_steps: int = DEFAULT_STALL_STEPS,
                 device: Any = None,
                 priority_aware: bool = True,
                 batch_share: float = 1.0):
        """``quantize`` ("int8" | "fp8") quantizes the decomposed factors
        at load via :mod:`repro.quant`; ``sparsify`` ("2:4") first
        2:4-prunes the ``run.lrd.sparse_targets`` factors
        (:mod:`repro.quant.sparse`), packing their kept values in the
        quantized dtype when ``quantize`` is also set (compound
        compression — the sparse pass subsumes quantization for the
        factors it packs, ``quantize_tree`` then handles the rest);
        ``kv_quantize`` ("int8") stores the runtime KV pool quantized
        (:mod:`repro.quant.kv`) — the GQA K/V pool on plain attention
        stacks, the latent cache on MLA stacks (cache family
        ``gqa_int8`` / ``mla_latent_int8``); ``act_quantize`` ("int8",
        requires ``quantize="int8"``) additionally quantizes prefill
        *activations* per-token on the fly so the fully-int8 plans run
        int8 x int8 on the MXU (prefill/chunk segments only — decode
        stays at full activation width).  All default to ``run.lrd``,
        as do ``prefill_chunk`` / ``step_token_budget`` (0 = engine
        defaults).

        ``admission`` is "continuous" (token-budget chunked prefill;
        default where supported) or "blocking" (one whole prefill per
        admit — the pre-scheduler behavior, kept for unsupported
        families and as the benchmark baseline).  ``kv_byte_budget``
        (bytes of per-position KV across all streams) gates admission
        and triggers youngest-first preemption when decode growth
        crosses it; None = never preempt.

        ``kv_layout`` ("slot" | "paged"; default ``run.lrd.kv_layout``)
        selects the pool memory layout.  "paged" backs the pool with
        fixed-size KV blocks behind per-slot block tables and a radix
        prefix cache (:mod:`repro.serve.paging`): requests sharing a
        block-aligned prompt prefix attach to the same physical blocks
        copy-on-write, and byte accounting / preemption go block-
        granular.  Paged serving needs chunked continuous admission
        (the prefix gather stages into the chunk path) and a dense
        non-MLA stack; ``kv_block_size`` (tokens per block, default
        ``run.lrd.kv_block_size`` or 16) must divide ``max_seq``, and
        ``kv_num_blocks`` sizes the physical pool (default
        ``slots * max_seq / block_size`` — the slot pool's capacity).

        ``debug=True`` runs the pool's ``check_integrity()`` after
        every step (invariant oracle — slow, test/diagnosis only).
        ``faults`` threads a :class:`repro.serve.faults.FaultInjector`
        through the pool, runner, and kernel gate (inert by default).
        ``degradation`` is a :class:`repro.serve.scheduler.
        DegradationPolicy` (True = defaults, False/None = off) for the
        pressure-watching load shedder.  ``stall_steps`` is the
        no-progress watchdog horizon in :meth:`run_until_done`.

        ``device`` pins this engine to one :class:`jax.Device`: params
        and the KV pool are committed there and every step dispatches
        there — how :class:`repro.serve.router.ServeRouter` places N
        replicas data-parallel across a host's devices.  ``None`` (the
        default) keeps JAX's implicit placement.  ``priority_aware`` /
        ``batch_share`` configure the scheduler's priority classes
        (interactive-first queueing and the in-flight batch prefill
        throttle — see :class:`repro.serve.scheduler.Scheduler`).
        """
        self.run = run
        self.model = get_model(run.model)
        assert run.model.has_decode, "serving needs a decoder"
        if quantize is None:
            quantize = run.lrd.quantize
        if sparsify is None:
            sparsify = run.lrd.sparsify
        if sparsify and sparsify != "none":
            # Sparsify BEFORE quantize: the pass prunes + packs (in the
            # quantized dtype when quantize is on), and quantize_tree
            # then skips the already-packed nodes and quantizes the
            # remaining plain factors (xc, non-divisible layers).
            from repro.quant import sparsify_tree
            params = sparsify_tree(
                params, pattern=sparsify,
                mode=(quantize if quantize and quantize != "none"
                      else "none"),
                targets=run.lrd.sparse_targets)
        self.sparsify = sparsify
        if quantize and quantize != "none":
            from repro.quant import quantize_tree
            params = quantize_tree(params, mode=quantize,
                                   targets=run.lrd.quant_targets)
        self.quantize = quantize
        if kv_quantize is None:
            kv_quantize = run.lrd.kv_quantize
        self.kv_quantize = None if kv_quantize == "none" else kv_quantize
        if act_quantize is None:
            act_quantize = getattr(run.lrd, "act_quantize", "none")
        self.act_quantize = None if act_quantize == "none" else act_quantize
        if self.act_quantize and self.act_quantize != "int8":
            raise ValueError(
                f"act_quantize {act_quantize!r} (want 'int8' or 'none')")
        if self.act_quantize and quantize != "int8":
            raise ValueError(
                "act_quantize='int8' needs quantize='int8' — the qa "
                "kernels run int8 x int8 against fully-int8 factor plans")
        self.device = device
        if device is not None:
            # commit the (possibly quantized) params: computations that
            # touch them dispatch on this replica's device regardless of
            # the process-global default
            params = jax.device_put(params, device)
        self.params = params
        # Execution plans, built once at load (not per call): every
        # linear subtree's kind / quantized-pair / kernel decision is
        # resolved here, and the aggregate gives honest weight-stream
        # accounting (param_count excludes scales; quant_bytes separate).
        from repro.layers import plan as lplan
        self.plans = lplan.build_plan_tree(params)
        self.plan_summary = lplan.tree_summary(self.plans)
        self.slots = slots
        self.max_seq = max_seq
        self.opts = block_opts(run)

        if admission is None:
            admission = ("continuous" if self._supports_chunked()
                         else "blocking")
        elif admission == "continuous" and not self._supports_chunked():
            raise ValueError(
                f"family {run.model.family!r} does not support chunked "
                "admission; use admission='blocking'")
        elif admission not in ("continuous", "blocking"):
            raise ValueError(admission)
        self.admission = admission
        chunk = prefill_chunk or run.lrd.prefill_chunk \
            or DEFAULT_PREFILL_CHUNK
        self.prefill_chunk = max(1, min(chunk, max_seq))
        self.step_token_budget = step_token_budget \
            or run.lrd.step_token_budget or (slots + self.prefill_chunk)

        if kv_layout is None:
            kv_layout = getattr(run.lrd, "kv_layout", "slot") or "slot"
        if kv_layout not in ("slot", "paged"):
            raise ValueError(
                f"kv_layout {kv_layout!r} (want 'slot' or 'paged')")
        self.kv_layout = kv_layout
        # pool before runner: the paged runner's pool plan needs the
        # pool's PagedGeometry (block count / size / tables)
        with self._on_device():
            if kv_layout == "paged":
                if self.admission != "continuous":
                    raise ValueError(
                        "kv_layout='paged' needs continuous admission "
                        "(the radix prefix gather stages into the "
                        "chunked prefill path)")
                self.pool = PagedKVPoolManager(
                    self.model, slots, max_seq,
                    kv_quantize=self.kv_quantize,
                    byte_budget=kv_byte_budget,
                    block_size=(kv_block_size or run.lrd.kv_block_size
                                or paging.DEFAULT_BLOCK_SIZE),
                    num_blocks=kv_num_blocks)
            else:
                self.pool = KVPoolManager(self.model, slots, max_seq,
                                          kv_quantize=self.kv_quantize,
                                          byte_budget=kv_byte_budget)
        if device is not None:
            # commit the pool cache too: later ops on it (insert, grow,
            # release) stay pinned even outside the step context
            self.pool.cache = jax.device_put(self.pool.cache, device)
        self.debug = debug
        self.faults = faults if faults is not None else NULL_INJECTOR
        self.pool.faults = self.faults
        if self.faults.configured("kernel_gate"):
            # module-global hook: kernel_fits is consulted at trace /
            # plan time, far from any serve object
            from repro.kernels import ops as kops
            kops.set_fault_injector(self.faults)
        self.runner = ModelRunner(self.model, params, self.opts,
                                  max_seq=max_seq,
                                  kv_quantize=self.kv_quantize,
                                  act_quantize=self.act_quantize,
                                  paged=getattr(self.pool, "geometry",
                                                None),
                                  faults=self.faults,
                                  device=device)
        self.scheduler = Scheduler(slots, prefill_chunk=self.prefill_chunk,
                                   step_token_budget=self.step_token_budget,
                                   priority_aware=priority_aware,
                                   batch_share=batch_share)
        if degradation is True:
            degradation = DegradationPolicy()
        self.shedder = (LoadShedder(degradation, self.step_token_budget)
                        if degradation else None)
        self.stragglers = StragglerDetector()
        self.stall_steps = max(1, stall_steps)
        self.quarantined = 0
        self.deadline_expired = 0
        self._step_idx = 0
        #: the phase clock of the step in progress (or the last one)
        self._clock = tracing.PhaseClock()
        # Decode streams the entire KV pool (masked, not skipped) every
        # step — the runtime twin of ``weight_bytes`` in the roofline,
        # and where kv_quantize="int8" pays.  Both numbers derive from
        # the CachePlans (layers/cache.py), never from hand-kept key
        # lists, so every cache family is costed automatically.
        self.plan_summary["kv_bytes_per_step"] = self.pool.kv_bytes_per_step
        self.plan_summary["kv_layout"] = kv_layout
        if self.pool.plans:
            self.plan_summary["kv_cache_family"] = self.pool.plans[0].family
        self.plan_summary["paths"] = self._path_census()
        self.key = jax.random.PRNGKey(seed)
        self.stats: deque[dict] = deque(maxlen=stats_window)
        # per-priority-class latency sample rings (seconds), bounded
        # like the step stats; they feed the per-class p50/p99 in
        # throughput() and the router's SLO tracker.  ITL samples are
        # *service-time* gaps: this engine's cumulative step seconds
        # between a stream's consecutive tokens — the token cadence a
        # dedicated-device replica delivers.  Wall gaps would charge a
        # replica for its co-tenants whenever several replicas
        # time-share one test device; TTFT stays wall-clock (queue
        # wait is real service latency).
        self.class_itl: dict[str, deque] = {
            p: deque(maxlen=stats_window) for p in PRIORITIES}
        self.class_ttft: dict[str, deque] = {
            p: deque(maxlen=stats_window) for p in PRIORITIES}
        #: set (externally, by the router's SLO tracker) to trip the
        #: load shedder one step early when the interactive ITL target
        #: would regress; consumed and cleared by :meth:`step`
        self.slo_pressure = False
        #: cumulative service seconds (sum of step admit+decode+prefill
        #: time) — the clock the class ITL rings sample against
        self.service_s = 0.0
        self._step_token_reqs: list = []

    def _path_census(self) -> dict:
        """How each step kind executes, decided from the shapes it runs
        at: per linear a fused kernel, ``"ref"`` (a kernel serves the
        layout but its geometry does not fit VMEM, so the jnp reference
        runs) or ``"jnp"``; and the same for decode attention.  Prefill
        is costed at its largest segment (a chunk, or ``max_seq`` for
        blocking admission)."""
        from repro.layers import plan as lplan
        use_pallas = self.opts.use_pallas
        prefill_m = (self.prefill_chunk if self.admission == "continuous"
                     else self.max_seq)
        out = {
            "decode": lplan.kernel_census(self.plans, self.slots,
                                          use_pallas=use_pallas),
            "prefill": lplan.kernel_census(
                self.plans, prefill_m, use_pallas=use_pallas,
                act_quantize=self.act_quantize == "int8"),
        }
        if self.pool.plans:
            out["decode_attention"] = self.pool.plans[0].decode_path(
                self.run.model.num_heads, self.slots, self.max_seq,
                use_pallas)
        return out

    def _supports_chunked(self) -> bool:
        return self.run.model.family in self._CHUNK_FAMILIES

    def _on_device(self):
        """Dispatch context: pin computation to this engine's device
        (no-op when the engine is unplaced)."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    # -- façade views (the pre-split engine surface) -------------------------

    @property
    def cache(self) -> PyTree:
        return self.pool.cache

    @cache.setter
    def cache(self, value: PyTree) -> None:
        self.pool.cache = value

    @property
    def positions(self) -> np.ndarray:
        return self.pool.positions

    @property
    def active(self) -> list[Request | None]:
        return self.scheduler.active

    @property
    def queue(self) -> deque[Request]:
        return self.scheduler.waiting

    @property
    def finished(self) -> list[Request]:
        return self.scheduler.finished

    @property
    def preemptions(self) -> int:
        return self.scheduler.preemptions

    @property
    def _jit_prefill(self):
        """The compiled admission prefill entry (chunked or whole)."""
        return (self.runner.jit_prefill_chunk
                if self.admission == "continuous"
                else self.runner.jit_prefill)

    @property
    def _jit_decode(self):
        return self.runner.jit_decode

    @property
    def _jit_sample_all(self):
        return self.runner.jit_sample_all

    @property
    def _jit_insert(self):
        return self.pool._jit_insert

    # -- admission helpers ---------------------------------------------------

    def add_request(self, req: Request) -> None:
        if len(req.prompt) > self.max_seq - 1:
            # reject up front: admission would otherwise consume a slot
            # and crash mid-prefill.  (Preemption-resumed prompts always
            # fit — decode stops one position short of max_seq.)
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens does not fit "
                f"max_seq={self.max_seq} (need <= {self.max_seq - 1} "
                "to leave room for decode)")
        if req.submit_time is None:
            req.submit_time = time.perf_counter()
        self.scheduler.submit(req)

    def _bucket_len(self, n: int) -> int:
        """Power-of-2 prefill length bucket — one compiled prefill per
        bucket instead of one per distinct prompt (or chunk) length."""
        if self.run.model.family not in self._BUCKET_FAMILIES:
            return n
        return min(max(PREFILL_BUCKET_MIN, 1 << (n - 1).bit_length()),
                   self.max_seq)

    def _append_token(self, req: Request, tok: int, now: float) -> None:
        # ITL is sampled at end of step against self.service_s (the
        # step's duration is not known yet here)
        self._step_token_reqs.append(req)
        req.output.append(tok)
        req.token_times.append(now)
        if req.first_token_time is None:
            req.first_token_time = now
            if req.submit_time is not None:
                self.class_ttft[req.priority].append(
                    now - req.submit_time)

    def _maybe_finish(self, slot: int) -> bool:
        req = self.scheduler.active[slot]
        tok = req.output[-1]
        ended = req.eos_id is not None and tok == req.eos_id
        full = (len(req.output) >= req.max_new_tokens
                or self.pool.positions[slot] >= self.max_seq - 1)
        if ended or full:
            self.scheduler.finish(slot)
            self.pool.release(slot)
            return True
        return False

    def _sample_inputs(self, rows: list[jax.Array],
                       temps_list: list[float]
                       ) -> tuple[jax.Array, jax.Array, np.ndarray]:
        """Key, logits and temperatures to sample k <= slots logits rows
        in ONE device call, padded to the decode path's single compiled
        (slots, V) shape (padding rows are zeros, never flagged)."""
        k = len(rows)
        lg = jnp.stack(rows)
        if k < self.slots:
            lg = jnp.pad(lg, ((0, self.slots - k), (0, 0)))
        temps = np.zeros((self.slots,), np.float32)
        temps[:k] = temps_list
        self.key, sub = jax.random.split(self.key)
        return sub, lg, temps

    def _quarantine(self, slot: int) -> None:
        """Numerical-watchdog casualty: terminate the stream in
        ``slot`` as ``failed`` and reclaim its slot/blocks WITHOUT
        publishing to the radix (a poisoned cache must never seed
        future prompts)."""
        self.scheduler.quarantine(slot)
        self.pool.release(slot, publish=False)
        self.quarantined += 1

    # -- blocking admission (pre-scheduler path; recurrent/MoE/VLM) ---------

    def _prefill_whole(self, started: list[PrefillStream]
                       ) -> tuple[int, int]:
        """One whole prefill per admitted request (admission policy is
        the Scheduler's — same resume/byte-budget rules as the chunked
        path).  Returns (first tokens sampled, prompt tokens prefilled)."""
        if not started:
            return 0, 0
        clock = self._clock
        pf_toks = 0
        for ps in started:
            with clock.phase(tracing.PREFILL_DISPATCH, uid=ps.req.uid):
                cache1 = self._dispatch_whole(ps)
            with clock.phase(tracing.PREFILL_INSERT):
                self.pool.insert(cache1, ps.slot, len(ps.tokens))
                self.scheduler.activate(ps)
            del cache1
            pf_toks += len(ps.tokens)
        return self._first_tokens(started), pf_toks

    def _dispatch_whole(self, ps: PrefillStream) -> PyTree:
        """Enqueue the whole-prompt prefill of ``ps`` into a fresh
        staging cache in the pool's dtype; returns that cache."""
        n = len(ps.tokens)
        padded = np.zeros((1, self._bucket_len(n)), np.int32)
        padded[0, :n] = ps.tokens
        prompt = jnp.asarray(padded)
        cache1 = self.runner.new_stream_cache(kv_quantize=self.kv_quantize)
        if self.run.model.family == "vlm":
            batch = {"tokens": prompt,
                     "image_embeds": jnp.zeros(
                         (1, self.run.model.num_image_tokens,
                          self.run.model.d_model), self.model.dtype)}
        else:
            batch = {"tokens": prompt}
        logits, cache1 = self.runner.step(
            prompt, None, "prefill", cache=cache1, batch=batch,
            last_pos=jnp.asarray(n - 1, jnp.int32))
        ps.last_logits = logits[0, -1, :]
        return cache1

    # -- continuous admission: chunked prefill under the token budget -------

    def _prefill_chunks(self, n_live: int) -> tuple[int, int, int]:
        """Spend the step's leftover token budget on prefill chunks.
        Returns (prompt tokens prefilled, first tokens sampled, chunks
        dispatched)."""
        plan = self.scheduler.chunk_plan(n_live)
        if not plan:
            return 0, 0, 0
        completed: list[PrefillStream] = []
        pf_toks = 0
        for ps, c in plan:
            with self._clock.phase(tracing.PREFILL_DISPATCH, uid=ps.req.uid):
                # the last chunk's logits stay referenced until the
                # step's prefill ends: when device buffers are freed
                # shapes the HBM layout, and the layout with this buffer
                # freed early goes with the decode stall (PERF.md)
                logits = self._dispatch_chunk(ps, c)
            pf_toks += c
            if ps.remaining == 0:
                completed.append(ps)
        first = self._finish_prefills(completed)
        del logits
        return pf_toks, first, len(plan)

    def _dispatch_chunk(self, ps: PrefillStream, c: int) -> jax.Array:
        """Enqueue the chunk program for ``ps``'s next ``c`` tokens;
        returns the chunk's logits."""
        if ps.cache is None:
            # full-precision staging (even over an int8 pool): chunk
            # attention sees the exact K/V prefix, the pool quantizes
            # once at insert -> chunked == whole, bit-exact
            ps.cache = self.runner.new_stream_cache()
            if ps.written:
                # paged prefix hit: the first `written` positions' KV is
                # already pooled — gather it into the staging cache
                # (dequantizing int8 blocks) and chunk-prefill only the
                # suffix
                ps.cache = self.pool.gather_prefix(
                    ps.cache, ps.slot, ps.written)
        b = self._bucket_len(c)
        if ps.written + b > self.max_seq:   # keep the offset write
            b = self.max_seq - ps.written   # inside the slot
        padded = np.zeros((1, b), np.int32)
        padded[0, :c] = ps.tokens[ps.written:ps.written + c]
        # prompt_len = the chunk's real end: bucket-pad rows beyond it
        # are zeroed at the K/V write (attention masks them), so
        # correctness never depends on a later chunk overwriting them.
        # On the final chunk this is the prompt length, which also
        # places the logits gather at the last real token.
        eff_len = min(len(ps.tokens), ps.written + c)
        logits, ps.cache = self.runner.step(
            jnp.asarray(padded), None, "prefill_chunk", cache=ps.cache,
            start_pos=jnp.asarray(ps.written, jnp.int32),
            prompt_len=jnp.asarray(eff_len, jnp.int32))
        ps.written += c
        ps.last_logits = logits[0, 0, :]
        return logits

    def _finish_prefills(self, completed: list[PrefillStream]) -> int:
        """Land completed streams' staging caches in the pool and sample
        their first tokens.  Returns first tokens sampled."""
        if not completed:
            return 0
        with self._clock.phase(tracing.PREFILL_INSERT):
            for ps in completed:
                self.pool.insert(ps.cache, ps.slot, len(ps.tokens),
                                 from_full_precision=True)
                self.scheduler.activate(ps)
                ps.cache = None
        return self._first_tokens(completed)

    def _first_tokens(self, streams: list[PrefillStream]) -> int:
        """Sample the first token of every stream in ``streams`` in ONE
        device call and append it.  Returns first tokens sampled."""
        clock = self._clock
        with clock.phase(tracing.PREFILL_DISPATCH):
            sub, lg, temps = self._sample_inputs(
                [ps.last_logits for ps in streams],
                [max(ps.req.temperature, 0.0) for ps in streams])
        with clock.phase(tracing.PREFILL_SYNC):
            toks, bad = self.runner.sample(sub, lg, jnp.asarray(temps))
            del sub, lg
        with clock.phase(tracing.PREFILL_EMIT):
            now = time.perf_counter()
            first = 0
            for ps, tok, flagged in zip(streams, toks, bad):
                if flagged:
                    self._quarantine(ps.slot)
                    continue
                self._keep_logits(ps.req, ps.last_logits)
                self._append_token(ps.req, int(tok), now)
                first += 1
                self._maybe_finish(ps.slot)
        return first

    @staticmethod
    def _keep_logits(req: Request, logits: jax.Array) -> None:
        if req.keep_logits and req.first_logits is None:
            req.first_logits = logits

    # -- lifecycle: cancel / deadlines --------------------------------------

    def cancel(self, uid: int) -> bool:
        """Cancel request ``uid`` wherever it is — waiting (including
        preempted-and-requeued), chunked-prefilling, or decode-active —
        releasing its slot, blocks, and COW refcounts.  The request
        terminates with status ``cancelled``; returns False when
        ``uid`` is unknown or already terminal."""
        sched, pool = self.scheduler, self.pool
        for req in sched.waiting:
            if req.uid == uid:
                sched.waiting.remove(req)
                sched.terminal(req, "cancelled")
                return True
        for ps in sched.prefilling:
            if ps.req.uid == uid:
                sched.prefilling.remove(ps)
                sched.terminal(ps.req, "cancelled")
                # a mid-prefill slot holds allocated (paged: possibly
                # radix-shared) blocks but no landed KV — release drops
                # exactly the refcounts admission took
                pool.release(ps.slot)
                return True
        for slot, req in enumerate(sched.active):
            if req is not None and req.uid == uid:
                sched.active[slot] = None
                sched.terminal(req, "cancelled")
                pool.release(slot)
                return True
        return False

    def _expire_deadlines(self) -> int:
        """Terminate every request whose ``deadline_s`` (anywhere) or
        ``max_queue_s`` (waiting only) has elapsed; returns the count."""
        sched, pool = self.scheduler, self.pool
        now = time.perf_counter()

        def over(req, budget):
            return (budget is not None and req.submit_time is not None
                    and now - req.submit_time > budget)

        n = 0
        for req in list(sched.waiting):
            if over(req, req.deadline_s) or over(req, req.max_queue_s):
                sched.waiting.remove(req)
                sched.terminal(req, "deadline_exceeded")
                n += 1
        for ps in list(sched.prefilling):
            if over(ps.req, ps.req.deadline_s):
                sched.prefilling.remove(ps)
                sched.terminal(ps.req, "deadline_exceeded")
                pool.release(ps.slot)
                n += 1
        for slot, req in enumerate(sched.active):
            if req is not None and over(req, req.deadline_s):
                sched.active[slot] = None
                sched.terminal(req, "deadline_exceeded")
                pool.release(slot)
                n += 1
        self.deadline_expired += n
        return n

    # -- main loop ----------------------------------------------------------

    def _decode_live(self, live: list[int]) -> int:
        pool, clock = self.pool, self._clock
        with clock.phase(tracing.DECODE_DISPATCH):
            tokens = np.zeros((self.slots, 1), np.int32)
            for i in live:
                tokens[i, 0] = self.active[i].output[-1]
            logits, pool.cache = self.runner.step(
                jnp.asarray(tokens), jnp.asarray(pool.positions), "decode",
                cache=pool.cache)
            lg = logits[:, 0, :]
            temps = np.zeros((self.slots,), np.float32)
            for i in live:
                temps[i] = max(self.active[i].temperature, 0.0)
            self.key, sub = jax.random.split(self.key)
        with clock.phase(tracing.DECODE_SYNC):
            toks, bad = self.runner.sample(sub, lg, jnp.asarray(temps))
        with clock.phase(tracing.DECODE_EMIT):
            return self._emit_decoded(live, tokens, toks, bad)

    def _emit_decoded(self, live: list[int], tokens: np.ndarray,
                      toks: np.ndarray, bad: np.ndarray) -> int:
        pool = self.pool
        now = time.perf_counter()
        produced = 0
        for i in live:
            if bad[i]:
                # non-finite logits: quarantine before the token is
                # appended or any KV growth is accounted — neighbors'
                # streams are untouched (per-row sampling)
                self._quarantine(i)
                continue
            self._append_token(self.active[i], int(toks[i]), now)
            # the KV this step wrote at the slot's position belongs to
            # the *input* token — the paged pool's prefix registry
            # tracks it so released blocks stay radix-matchable
            try:
                pool.grow(i, token=int(tokens[i, 0]))
            except PoolExhausted:
                # no block for the next write: preempt this stream (it
                # resumes by re-prefilling prompt + output, including
                # the token just sampled); `grow` is atomic, so state
                # is exactly pre-call
                self.scheduler.preempt(i)
                pool.release(i)
                produced += 1
                continue
            produced += 1
            self._maybe_finish(i)
        return produced

    def step(self) -> int:
        """One scheduler step: expire deadlines, preempt under KV
        pressure, admit (unless the load shedder pauses it), decode
        every live stream, then spend leftover budget on prefill
        chunks.  Returns tokens produced (decode + first tokens)."""
        self._step_idx += 1
        with self._on_device(), jax.profiler.StepTraceAnnotation(
                tracing.STEP, step_num=self._step_idx):
            return self._step()

    def _step(self) -> int:
        sched, pool = self.scheduler, self.pool
        self._step_token_reqs.clear()
        clock = self._clock = tracing.PhaseClock()
        admit_fail0 = sched.admit_failures
        with clock.phase(tracing.SCHEDULE):
            self._expire_deadlines()
            victims = pool.pressure_victims()
            for slot in victims:
                sched.preempt(slot)
                pool.release(slot)
            shed = False
            if self.shedder is not None:
                # degraded mode: run with the shrunk budget; pause
                # admission only while work is already in flight (an
                # idle engine must always admit — shedding can never
                # deadlock the queue)
                sched.step_token_budget = self.shedder.budget
                shed = self.shedder.engaged and (
                    bool(sched.prefilling)
                    or any(r is not None for r in sched.active))
            started = [] if shed else sched.admit(pool)
        if self.admission == "blocking":
            first, pf_toks = self._prefill_whole(started)
            chunks = len(started)
            live = sched.live_slots()
            produced = self._decode_live(live) if live else 0
            record = bool(live or first)
        else:
            live = sched.live_slots()
            produced = self._decode_live(live) if live else 0
            pf_toks, first, chunks = self._prefill_chunks(len(live))
            record = bool(live or pf_toks or first)
        decode_s = clock.total(tracing.DECODE_PHASES)
        prefill_s = clock.total(tracing.PREFILL_PHASES)
        # blocking admission prefills whole prompts as it admits: its
        # prefill time has always been reported as admission time
        admit_s = prefill_s if self.admission == "blocking" else 0.0
        if self.admission == "blocking":
            prefill_s = 0.0
        event = self.stragglers.observe(self._step_idx, clock.total())
        if self.shedder is not None:
            self.shedder.observe(bool(victims)
                                 or sched.admit_failures > admit_fail0
                                 or self.slo_pressure)
        self.slo_pressure = False
        if record:
            self.stats.append({"live": len(live), "tokens": produced,
                               "seconds": decode_s,
                               "prefill_tokens": pf_toks,
                               "prefill_seconds": prefill_s,
                               "first_tokens": first,
                               "admit_seconds": admit_s,
                               "preempted": len(victims),
                               "admit_failures":
                                   sched.admit_failures - admit_fail0,
                               "shed": int(shed),
                               "straggler": int(event is not None),
                               "phase_s": clock.seconds,
                               "chunks": chunks,
                               "admitted": len(started)})
        # service-time ITL: every non-first token produced this step
        # samples the service seconds since the stream's previous token
        # (usually exactly this step's duration; preemption gaps span
        # the resume's prefill steps too)
        self.service_s += admit_s + decode_s + prefill_s
        key = id(self)
        for req in self._step_token_reqs:
            mark = req.service_mark
            if mark is not None and mark[0] == key:
                self.class_itl[req.priority].append(
                    self.service_s - mark[1])
            req.service_mark = (key, self.service_s)
        self._step_token_reqs.clear()
        if self.debug:
            pool.check_integrity()
        return produced + first

    def _fail_survivors(self) -> int:
        """No-progress watchdog firing: terminate everything still in
        flight or queued as ``failed`` and reclaim its pool state, so
        a stalled engine surfaces explicit statuses instead of
        silently losing requests."""
        sched, pool = self.scheduler, self.pool
        n = 0
        while sched.waiting:
            sched.terminal(sched.waiting.popleft(), "failed")
            n += 1
        for ps in list(sched.prefilling):
            sched.prefilling.remove(ps)
            sched.terminal(ps.req, "failed")
            pool.release(ps.slot, publish=False)
            n += 1
        for slot, req in enumerate(sched.active):
            if req is not None:
                sched.active[slot] = None
                sched.terminal(req, "failed")
                pool.release(slot, publish=False)
                n += 1
        return n

    def run_until_done(self, max_steps: int = 10_000) -> list[Request]:
        """Drive the engine until queue + slots drain; returns the
        requests that completed (any terminal status) during this call,
        in completion order.

        Two watchdogs close the silent-loss holes of the naive loop:
        ``stall_steps`` consecutive steps with zero progress (no
        tokens, no prefill, no admissions, no terminal transitions)
        mark every survivor ``failed`` and return — a scheduler
        deadlock surfaces as explicit statuses; and exhausting
        ``max_steps`` with work still in flight raises instead of
        returning as if drained."""
        sched = self.scheduler
        start = len(self.finished)
        stalled = 0
        for _ in range(max_steps):
            if not sched.busy():
                break
            fin0 = len(self.finished)
            prev = self.stats[-1] if self.stats else None
            produced = self.step()
            entry = (self.stats[-1]
                     if self.stats and self.stats[-1] is not prev
                     else None)
            progressed = (produced > 0
                          or len(self.finished) > fin0
                          or bool(entry and entry["prefill_tokens"]))
            stalled = 0 if progressed else stalled + 1
            if stalled >= self.stall_steps:
                self._fail_survivors()
                break
        else:
            if sched.busy():
                raise RuntimeError(
                    f"run_until_done: {max_steps} steps exhausted with "
                    f"{len(sched.waiting)} waiting, "
                    f"{len(sched.prefilling)} prefilling, "
                    f"{len(sched.live_slots())} active requests still "
                    "in flight")
        return self.finished[start:]

    def class_stats(self, priority: str) -> dict:
        """Per-class p50/p99 inter-token latency + TTFT (milliseconds)
        over the bounded sample rings, plus terminal request count."""
        done = sum(1 for r in self.finished if r.priority == priority)
        return latency_summary(self.class_itl[priority],
                               self.class_ttft[priority], requests=done)

    def throughput(self) -> dict:
        """Aggregate serving stats over the (bounded) stats window.
        Unlike the pre-split engine, the denominator includes the time
        spent admitting/prefilling, not just decode steps — and TTFT is
        reported from per-request timestamps.  ``prefill_seconds`` is
        host time (chunk dispatch, pool insert, the first-token sync),
        not prefill device time: a chunk that is not a prompt's last is
        never waited for, so its device time shows only in a profiler
        trace.

        The key set is identical whether or not any productive step was
        recorded (an idle engine reports zeros, not a narrower dict) —
        the only conditional keys are the ``shed_*``/``degradation_*``
        group, present iff the engine has a load shedder at all.
        """
        stats = list(self.stats)
        status_counts: dict[str, int] = {}
        for r in self.finished:
            key = r.status or "finished"
            status_counts[key] = status_counts.get(key, 0) + 1
        ttfts = [r.ttft for r in self.finished if r.ttft is not None]
        out = {"tokens_per_s": 0.0,
               "steps": len(stats),
               "mean_batch": 0.0,
               "decode_seconds": 0.0,
               "prefill_seconds": 0.0,
               "prefill_tokens": 0,
               "preemptions": self.scheduler.preemptions,
               # hardening counters
               "admit_failures": self.scheduler.admit_failures,
               "quarantined": self.quarantined,
               "deadline_expired": self.deadline_expired,
               "status_counts": status_counts,
               "slow_steps": len(self.stragglers.events),
               "step_ewma_s": self.stragglers.ewma,
               "ttft_mean_s": (sum(ttfts) / len(ttfts)) if ttfts else 0.0,
               "per_class": {p: self.class_stats(p) for p in PRIORITIES}}
        if self.shedder is not None:
            out["shed_steps"] = sum(s.get("shed", 0) for s in stats)
            out["degradation_engaged"] = self.shedder.engaged
            out["degradation_engages"] = self.shedder.engage_count
            out["degradation_recoveries"] = self.shedder.recover_count
        if stats:
            dec = sum(s["tokens"] for s in stats)
            first = sum(s.get("first_tokens", 0) for s in stats)
            dec_s = sum(s["seconds"] for s in stats)
            pf_s = sum(s.get("prefill_seconds", 0.0) for s in stats)
            ad_s = sum(s.get("admit_seconds", 0.0) for s in stats)
            out["tokens_per_s"] = (dec + first) / max(dec_s + pf_s + ad_s,
                                                      1e-9)
            out["mean_batch"] = dec / len(stats)
            out["decode_seconds"] = dec_s
            out["prefill_seconds"] = pf_s + ad_s
            out["prefill_tokens"] = sum(s.get("prefill_tokens", 0)
                                        for s in stats)
        return out
