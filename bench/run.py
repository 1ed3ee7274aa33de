#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic,
pool and metrics are files under ``bench/`` found by name.  The run
builds the cell's factors from ``--seed`` on the device, warms every
shape, offers the traffic open-loop for ``--seconds``, compares the
served tokens with the plain reference, and prints as its last line of
standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, last, ``checks``:
each number compared with its limit, which also end standard error.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not beside it.
JAX's compilation cache is kept in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(jax) -> None:
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        print("run: --seed must be non-negative", file=sys.stderr)
        return 2
    import spec
    cell = spec.load_cell(ROOT, args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    # the program keeps its compilation cache where this variable says
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"sees {devices}", file=sys.stderr)
        return 2
    enable_cache(jax)

    import harness
    oc = harness.run_window(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), t_process=T_PROCESS)
    out, lines = harness.result(cell, oc, bool(args.trace))
    print(harness.late_line(oc), flush=True)
    print(harness.host_line(oc), flush=True)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
