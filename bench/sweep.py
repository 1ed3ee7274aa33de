#!/usr/bin/env python3
"""Find a cell's knee: offer its traffic at several fixed rates, one
window each, on one set-up, and print what each rate gave.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1,1.5,2,2.5

Each rate prints one JSON line: requests offered and finished in the
window, the TTFT and inter-token tails, tokens per second, the queue
left at the close, the mean live batch and the mean decode step.  The
knee is the highest rate whose queue does not grow through the window
(TTFT stays flat, the backlog at the close stays small).  The
benchmark's runs never sweep: a cell's rate is fixed in its cell file.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import spec
    cell = spec.load_cell(run.ROOT, args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    run.enable_cache(jax)
    import numpy as np

    import harness
    import stats
    st = harness.set_up(cell, args.seed)
    print(f"setup_s {time.perf_counter() - T_PROCESS:.3f}", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        win = harness.drive_window(cell, st, seed=args.seed,
                                   seconds=args.seconds, trace_dir=None,
                                   rate=rate)
        queued = len(st.eng.scheduler.waiting)
        rec = harness.make_record(cell, st, win, T_PROCESS)
        live = [s["live"] for s in rec["steps"]]
        dec = [s["decode_s"] for s in rec["steps"] if s["live"] > 0]
        done = sum(1 for r in rec["requests"] if r["status"] == "finished")
        ttft = stats.ttfts(rec)
        line = {"rate": rate, "offered": len(rec["requests"]),
                "finished": done, "queued_at_close": queued,
                "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
                "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
                "itl_p95_ms": 1e3 * stats.percentile(stats.token_gaps(rec),
                                                     95),
                "tokens_per_s": stats.window_tokens(rec)
                / rec["window"]["seconds"],
                "mean_live": float(np.mean(live)) if live else 0.0,
                "decode_step_ms": 1e3 * float(np.mean(dec)) if dec else 0.0}
        print(json.dumps(line), flush=True)
        while st.eng.scheduler.busy():
            st.eng.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
