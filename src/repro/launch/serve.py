"""Serving launcher: load (or init+decompose) a model and serve a batch of
synthetic requests through the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        [--ckpt-dir DIR] [--requests 8] [--max-new 32] [--pallas]

:func:`build_params` and :func:`serve` are the build-and-serve path this
launcher and ``chip_smoke.py`` share.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import registry
from repro.configs.base import LRDConfig, ModelConfig, RunConfig
from repro.core.surgery import SurgeryReport, decompose_model
from repro.launch.compile_cache import enable_compile_cache
from repro.models.api import get_model
from repro.serve.engine import Request, ServeEngine
from repro.train import checkpoint as ckpt


def build_params(cfg: ModelConfig, lrd: LRDConfig, *, seed: int = 0,
                 ckpt_dir: str | None = None, timings: dict | None = None
                 ) -> tuple[dict, dict, SurgeryReport]:
    """Init ``cfg``'s params from ``seed``, apply LRD surgery when
    ``lrd.enabled``, then restore a checkpoint if one is given.
    Returns ``(params, axes, report)``; ``timings`` (if given) gets the
    seconds of ``init_s`` and ``decompose_s``."""
    timings = {} if timings is None else timings
    t0 = time.perf_counter()
    params, axes = get_model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    timings["init_s"] = time.perf_counter() - t0
    report = SurgeryReport()
    if lrd.enabled:
        t0 = time.perf_counter()
        params, axes, report = decompose_model(params, axes, lrd)
        jax.block_until_ready(params)
        timings["decompose_s"] = time.perf_counter() - t0
    if ckpt_dir:
        got = ckpt.restore_latest(ckpt_dir, {"params": params})
        if got:
            params = got[0]["params"]
            print(f"[restore] step {got[1]['step']}")
    return params, axes, report


def synthetic_requests(cfg: ModelConfig, n: int, *, seed: int = 7,
                       prompt_len: tuple[int, int] = (3, 8),
                       max_new: int = 32, temperature: float = 0.0,
                       keep_logits: bool = False) -> list[Request]:
    """``n`` requests with seeded uniform-random prompts whose lengths
    are drawn from ``prompt_len`` (inclusive)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        length = int(rng.integers(prompt_len[0], prompt_len[1] + 1))
        prompt = rng.integers(0, cfg.vocab_size, length).tolist()
        out.append(Request(uid=i, prompt=prompt, max_new_tokens=max_new,
                           temperature=temperature, keep_logits=keep_logits))
    return out


def serve(run: RunConfig, params, requests: list[Request], *,
          slots: int, max_seq: int, **engine_kwargs) -> ServeEngine:
    """Build a :class:`ServeEngine` over ``params`` and serve
    ``requests`` until all are terminal; returns the engine."""
    eng = ServeEngine(run, params, slots=slots, max_seq=max_seq,
                      **engine_kwargs)
    for req in requests:
        eng.add_request(req)
    eng.run_until_done()
    return eng


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.names())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--lrd", default="aligned",
                    choices=["none", "ratio", "aligned", "search"])
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore params from a training checkpoint")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--pallas", action="store_true",
                    help="run the fused low-rank kernels")
    args = ap.parse_args()

    enable_compile_cache()
    entry = registry.get(args.arch)
    cfg = entry.smoke if args.smoke else entry.full
    if not cfg.has_decode:
        raise SystemExit(f"{args.arch} is encoder-only: nothing to serve")
    lrd = LRDConfig(enabled=args.lrd != "none",
                    rank_mode="aligned" if args.lrd == "none" else args.lrd,
                    min_dim=32 if args.smoke else 256,
                    use_pallas=args.pallas)
    params, _, rep = build_params(cfg, lrd, ckpt_dir=args.ckpt_dir)
    if lrd.enabled:
        print(f"[lrd] {rep.summary()}")
    run = RunConfig(model=cfg, lrd=lrd, parallel=entry.parallel("decode"))
    reqs = synthetic_requests(cfg, args.requests, max_new=args.max_new,
                              temperature=args.temperature)
    eng = serve(run, params, reqs, slots=args.slots, max_seq=args.max_seq)
    print(f"[throughput] {eng.throughput()}")


if __name__ == "__main__":
    main()
