#!/usr/bin/env python3
"""Bring-up check: serve a low-rank-decomposed minitron-4b on a TPU.

    python3 chip_smoke.py              # one chip, three serve phases
    python3 chip_smoke.py --chips 4    # four chips: replicas and sharding

Builds minitron-4b at its published widths in bf16 from a seeded init,
decomposes it once (SVD pairs, compression 2, ranks aligned to 128) and
serves 8 seeded requests (prompts of 200-600 tokens, 32 new tokens
each, 8 slots, max_seq 1024) through the same build-and-serve path as
``python -m repro.launch.serve``.  One process drives every phase.

One chip, all on the one decomposed tree:

* ``serve``: the default path (jnp linears, no Pallas kernels).
* ``serve_kernels``: the fused low-rank kernels.  The logits each
  request's first token is sampled from must match ``serve`` within
  max|d| <= 2e-2 * max|logit| (bf16), and no linear that a kernel
  serves may fall back to the jnp reference.
* ``serve_int8``: int8 factors (``lowrank_matmul_q``), an int8 paged KV
  pool and its fused decode kernel.  Every request must finish with
  finite logits; the token match and logit deviation against
  ``serve`` are printed for information.

``--chips 4`` runs only the cross-chip paths and their references: the
requests through ``ServeRouter`` with 4 replicas (one per chip) against
1 replica (identical token streams, no contained replica failure), and
the tree sharded on a ``(data=1, model=4)`` mesh served by one engine
against the 1-replica logits (same tolerance).

Each phase prints a line with the device, seconds of set-up, compile and
serving, the engine's plan summary and peak device bytes.  The last line
of stdout is ``{"ok": true, "device": {...}}``; any failure, or no TPU,
exits nonzero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

#: logits agree when max |a - b| <= LOGIT_RTOL * max |b|  (bf16 serving)
LOGIT_RTOL = 2e-2
ARCH = "minitron-4b"
REQUESTS, PROMPT_LEN, MAX_NEW, SLOTS, MAX_SEQ = 8, (200, 600), 32, 8, 1024


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends lowering to XLA and compiling, from its own
    monitoring events.  (Tracing is left out: a jit traced inside
    another reports its own trace time within its caller's, so a sum
    would count it twice.)"""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def device_line() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(name: str, **fields) -> None:
    print(f"phase {name} " + json.dumps({"device": device_line(), **fields},
                                        default=str), flush=True)


def first_logits(reqs) -> "np.ndarray":
    import numpy as np
    for r in reqs:
        check(r.first_logits is not None, f"request {r.uid}: no logits kept")
    return np.stack([np.asarray(r.first_logits, np.float32) for r in reqs])


def check_finished(name: str, reqs) -> None:
    for r in reqs:
        check(r.status == "finished" and len(r.output) == MAX_NEW,
              f"{name}: request {r.uid} ended {r.status!r} with "
              f"{len(r.output)}/{MAX_NEW} tokens")


def deviation(got, want) -> float:
    """max |got - want| relative to max |want|."""
    import numpy as np
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def one_chip(cfg, entry, params, clock: CompileClock, seed: int) -> None:
    """The three serve phases on one decomposed tree."""
    import numpy as np

    from repro.configs.base import LRDConfig, RunConfig
    from repro.launch.serve import serve, synthetic_requests

    base = LRDConfig(enabled=True, rank_mode="aligned", compression=2.0)
    phases = {
        "serve": base,
        "serve_kernels": dataclasses.replace(base, use_pallas=True),
        "serve_int8": dataclasses.replace(
            base, use_pallas=True, quantize="int8", kv_quantize="int8",
            kv_layout="paged"),
    }
    logits, tokens = {}, {}
    for name, lrd in phases.items():
        run = RunConfig(model=cfg, lrd=lrd, parallel=entry.parallel("decode"))
        reqs = synthetic_requests(cfg, REQUESTS, seed=seed,
                                  prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                                  keep_logits=True)
        clock.lap()
        t0 = time.perf_counter()
        eng = serve(run, params, reqs, slots=SLOTS, max_seq=MAX_SEQ)
        wall = time.perf_counter() - t0
        compile_s = clock.lap()
        check_finished(name, reqs)
        logits[name] = first_logits(reqs)
        tokens[name] = [r.output for r in reqs]
        check(bool(np.all(np.isfinite(logits[name]))),
              f"{name}: non-finite logits")
        fields = {"compile_s": compile_s, "serve_s": wall - compile_s,
                  "plan_summary": eng.plan_summary,
                  "peak_bytes_in_use": peak_bytes()}
        if name != "serve":
            match = np.mean([a == b for ta, tb in zip(tokens[name],
                                                      tokens["serve"])
                             for a, b in zip(ta, tb)])
            fields["token_match_vs_serve"] = float(match)
            fields["logit_dev_vs_serve"] = deviation(logits[name],
                                                     logits["serve"])
        report(name, **fields)
        if name == "serve_kernels":
            paths = eng.plan_summary["paths"]
            for seg in ("decode", "prefill"):
                check("lowrank" in paths[seg] and "ref" not in paths[seg],
                      f"{name}: {seg} linears {paths[seg]} are not all "
                      "on their kernels")
            check(fields["logit_dev_vs_serve"] <= LOGIT_RTOL,
                  f"{name}: logits deviate {fields['logit_dev_vs_serve']} "
                  f"> {LOGIT_RTOL} of max |logit| from serve")
        del eng
        gc.collect()        # free this phase's pool before the next


def four_chips(cfg, entry, params, axes, clock: CompileClock,
               seed: int) -> None:
    """Router replicas vs one replica, and a sharded tree vs one chip."""
    import jax

    from repro.configs.base import LRDConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import serve, synthetic_requests
    from repro.parallel.sharding import make_param_shardings
    from repro.serve.router import ServeRouter

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, got {devices}")
    lrd = LRDConfig(enabled=True, rank_mode="aligned", compression=2.0)
    run = RunConfig(model=cfg, lrd=lrd, parallel=entry.parallel("decode"))

    def requests():
        return synthetic_requests(cfg, REQUESTS, seed=seed,
                                  prompt_len=PROMPT_LEN, max_new=MAX_NEW,
                                  keep_logits=True)

    streams, ref_logits = {}, None
    for n in (1, 4):
        reqs = requests()
        clock.lap()
        t0 = time.perf_counter()
        router = ServeRouter(run, params, replicas=n, devices=devices[:n],
                             slots=SLOTS, max_seq=MAX_SEQ)
        for r in reqs:
            router.add_request(r)
        router.run_until_done()
        wall = time.perf_counter() - t0
        compile_s = clock.lap()
        name = f"router_{n}"
        check_finished(name, reqs)
        failures = {rep.index: rep.guard.step_failures
                    for rep in router.replicas}
        check(not any(failures.values()),
              f"{name}: replica step failures {failures} "
              f"({[repr(r.guard.last_error) for r in router.replicas]})")
        streams[n] = {r.uid: r.output for r in reqs}
        if n == 1:
            ref_logits = first_logits(reqs)
        report(name, compile_s=compile_s, serve_s=wall - compile_s,
               replica_step_failures=failures,
               requests_per_replica=[len(rep.engine.finished)
                                     for rep in router.replicas],
               peak_bytes_in_use=peak_bytes())
        del router
        gc.collect()
    check(streams[4] == streams[1],
          "router_4: token streams differ from 1 replica")

    mesh = make_mesh((1, 4), ("data", "model"))
    sharded = jax.device_put(
        params, make_param_shardings(mesh, params, axes, run.parallel))
    reqs = requests()
    clock.lap()
    t0 = time.perf_counter()
    eng = serve(run, sharded, reqs, slots=SLOTS, max_seq=MAX_SEQ)
    wall = time.perf_counter() - t0
    compile_s = clock.lap()
    check_finished("sharded", reqs)
    dev = deviation(first_logits(reqs), ref_logits)
    report("sharded", compile_s=compile_s, serve_s=wall - compile_s,
           mesh=dict(mesh.shape), logit_dev_vs_one_chip=dev,
           plan_summary=eng.plan_summary, peak_bytes_in_use=peak_bytes())
    check(dev <= LOGIT_RTOL, f"sharded: logits deviate {dev} > "
          f"{LOGIT_RTOL} of max |logit| from one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers (0 = all)")
    args = ap.parse_args()

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax
    if jax.devices()[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {jax.devices()})",
              file=sys.stderr)
        return 2

    from repro.configs import registry
    from repro.configs.base import LRDConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import build_params

    cache_dir = enable_compile_cache()
    cache_warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    clock = CompileClock()
    entry = registry.get(ARCH)
    cfg = entry.full
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    try:
        timings = {}
        params, axes, rep = build_params(
            cfg, LRDConfig(enabled=True, rank_mode="aligned",
                           compression=2.0),
            seed=args.seed, timings=timings)
        report("build", arch=ARCH, num_layers=cfg.num_layers,
               dtype=cfg.dtype, compile_cache=cache_dir,
               compile_cache_warm=cache_warm, **timings,
               compile_s=clock.lap(), lrd=rep.summary(),
               peak_bytes_in_use=peak_bytes())
        if args.chips == 4:
            four_chips(cfg, entry, params, axes, clock, args.seed)
        else:
            one_chip(cfg, entry, params, clock, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_line()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
