"""ModelRunner: the serve stack's single compute seam.

Owns the params and every jitted step function (whole prefill, chunked
prefill, decode, batched sampling) and exposes ONE entry —
:meth:`step` ``(tokens, positions, seg_kind, ...)`` — so the scheduler
and engine never touch ``jax.jit`` or the model API directly.  Segment
kinds:

* ``"decode"``:        tokens ``(slots, 1)``, positions ``(slots,)`` —
                       one token for every slot against the shared pool.
* ``"prefill_chunk"``: tokens ``(1, C)`` at sequence offset
                       ``start_pos`` against a batch=1 stream cache
                       (chunked continuous admission).
* ``"prefill"``:       tokens ``(1, S)`` whole-prompt prefill
                       (blocking admission; recurrent/MoE families).

Prefill token arrays are length-bucketed by the caller, so each segment
kind compiles once per bucket, not once per prompt length; ``start_pos``
and ``prompt_len`` ride along as traced scalars.  The chunk entry
donates the staging cache (in-place stream growth).  The whole-pool
decode cache is NOT donated: a caller may still hold the cache it
passed in (the benchmark's planted fault ``bench/faults.py:_unchanged``
hands it back as the step's result), so the decode program copies the
pool once on entry and writes that copy in place, layer by layer.

The runner threads the :class:`repro.layers.cache.CachePlan` for each
segment's cache into the model (static metadata closed over by the
jitted fns): the *pool* plan (``kv_quantize`` family) for decode and
blocking whole-prefill, and the full-precision *stream* plan for
chunked-prefill staging caches — chunk attention runs over the exact
K/V prefix and the pool quantizes once at slot insert.
"""
from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.serve import guard
from repro.serve.faults import NULL_INJECTOR

PyTree = Any

SEG_KINDS = ("decode", "prefill_chunk", "prefill")


class ModelRunner:
    def __init__(self, model, params: PyTree, opts, *, max_seq: int,
                 kv_quantize: str | None = None, act_quantize: str | None = None,
                 paged=None, faults=None, device=None):
        self.model = model
        #: the replica's :class:`jax.Device`, or None for implicit
        #: placement.  Params are committed there, so every jitted step
        #: dispatches on it — data-parallel replicas never contend for
        #: one device's queue.
        self.device = device
        if device is not None:
            params = jax.device_put(params, device)
        self.params = params
        self.opts = opts
        self.max_seq = max_seq
        self.kv_quantize = kv_quantize
        self.act_quantize = act_quantize
        #: fault source for the `nan_logits` / `slow_step` points
        #: (inert by default)
        self.faults = faults if faults is not None else NULL_INJECTOR
        #: plan of the shared pool (slot or, given a PagedGeometry,
        #: block-table paged) and of blocking-admission staging
        self.pool_plan = model.cache_plan(kv_quantize, paged=paged)
        #: plan of a full-precision chunked-prefill staging cache
        self.stream_plan = model.cache_plan(None)
        mdl = model
        # Activation quantization is a prefill-segment decision: the
        # prefill/chunk closures run the M-large MXU-bound dots int8 x
        # int8, decode keeps full-width activations (M = batch rows are
        # too skinny for the throughput term and too noisy for per-row
        # scales).  Decode always closes over the caller's opts.
        prefill_opts = (opts._replace(act_quantize=True)
                        if act_quantize == "int8" else opts)
        self.prefill_opts = prefill_opts

        def _prefill(params, batch, cache1, last_pos):
            return mdl.prefill(params, batch, cache1, last_pos=last_pos,
                               cache_plan=self.pool_plan, opts=prefill_opts)

        def _prefill_chunk(params, batch, cache1, start_pos, prompt_len):
            return mdl.prefill_chunk(params, batch, cache1,
                                     start_pos=start_pos,
                                     prompt_len=prompt_len,
                                     cache_plan=self.stream_plan,
                                     opts=prefill_opts)

        def _decode(params, tokens, positions, cache):
            return mdl.decode_step(params, tokens, positions, cache,
                                   cache_plan=self.pool_plan, opts=opts)

        self.jit_prefill = jax.jit(_prefill)
        self.jit_prefill_chunk = jax.jit(_prefill_chunk,
                                         donate_argnums=(2,))
        self.jit_decode = jax.jit(_decode)
        def _sample_all(key, logits, temps):
            """One device call samples every slot AND runs the
            numerical watchdog (per-row non-finite flags fused into the
            same computation — see :mod:`repro.serve.guard`); the host
            indexes the result, no per-slot round-trips and no extra
            sync for the flags.  A per-runner closure so each engine's
            trace cache stays its own."""
            return guard.sample_and_flag(key, logits, temps)

        self.jit_sample_all = jax.jit(_sample_all)

    def new_stream_cache(self, kv_quantize: str | None = None) -> PyTree:
        """A fresh batch=1 cache for one stream.  Chunked prefill stages
        at full precision (``kv_quantize=None``) regardless of the pool
        dtype — chunk attention then runs over the exact K/V prefix, so
        chunked greedy == whole-prefill greedy bit-for-bit, and the pool
        quantizes once at slot insert."""
        cache1 = self.model.init_cache(1, self.max_seq,
                                       kv_quantize=kv_quantize)
        if self.device is not None:
            cache1 = jax.device_put(cache1, self.device)
        return cache1

    def step(self, tokens: jax.Array, positions: jax.Array | None,
             seg_kind: str, *, cache: PyTree,
             start_pos: jax.Array | None = None,
             prompt_len: jax.Array | None = None,
             last_pos: jax.Array | None = None,
             batch: dict | None = None) -> tuple[jax.Array, PyTree]:
        """Run one compiled segment.  Returns ``(logits, new_cache)``."""
        if seg_kind == "decode":
            out = self.jit_decode(self.params, tokens, positions, cache)
        elif seg_kind == "prefill_chunk":
            out = self.jit_prefill_chunk(self.params, {"tokens": tokens},
                                         cache, start_pos, prompt_len)
        elif seg_kind == "prefill":
            out = self.jit_prefill(self.params,
                                   batch or {"tokens": tokens},
                                   cache, last_pos)
        else:
            raise ValueError(
                f"unknown seg_kind {seg_kind!r} (want one of {SEG_KINDS})")
        if self.faults.active:
            out = self._inject(out, seg_kind)
        return out

    def _inject(self, out: tuple[jax.Array, PyTree],
                seg_kind: str) -> tuple[jax.Array, PyTree]:
        """Post-dispatch fault hooks: ``slow_step`` stalls the step
        wall-clock (straggler detection); ``nan_logits`` poisons the
        logits the model just produced (one slot row on decode —
        ``params={"nan_logits": {"slot": i}}`` — the whole segment on
        prefill paths).  A ``seg`` param restricts which segment kinds
        are even *consulted*, so schedule indices count only matching
        calls ("poison decode call #3")."""
        if self.faults.fire("slow_step"):
            time.sleep(float(self.faults.param("slow_step", "seconds",
                                               0.05)))
        seg = self.faults.param("nan_logits", "seg", None)
        if seg is not None and seg_kind != seg:
            return out
        if self.faults.fire("nan_logits"):
            logits, cache = out
            if seg_kind == "decode":
                row = int(self.faults.param("nan_logits", "slot", 0))
                logits = logits.at[row].set(jnp.nan)
            else:
                logits = jnp.full_like(logits, jnp.nan)
            out = (logits, cache)
        return out

    def sample(self, key: jax.Array, logits: jax.Array,
               temps: jax.Array) -> tuple[np.ndarray, np.ndarray]:
        """Batched greedy/temperature sampling with the fused watchdog;
        host-side ``(tokens, bad)`` arrays — ``bad[i]`` flags slot
        ``i``'s logits as non-finite (the engine quarantines it)."""
        toks, bad = self.jit_sample_all(key, logits, temps)
        return np.asarray(toks), np.asarray(bad)
