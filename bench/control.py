#!/usr/bin/env python3
"""Readings for a cell's correctness limits, in one process at the
cell's own size and load: the program on many seeds, the control (the
program's own int8 path: int8 factors, int8 KV pool and int8 prefill
activations) and the planted faults of ``faults.py`` on a few.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --program-seeds 1,2,3 --control-seeds 4,5,6 \
        --fault-seeds unchanged:7,half:8,altered:9

Prints one JSON line per run: what ``harness.result`` decides
(``correct``, each number compared beside its limit) and every reading
of ``check.readings``.  The benchmark's own runs never run the control
or plant a fault.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import run  # noqa: E402

#: the program's own lower-precision path
INT8 = {"quantize": "int8", "kv_quantize": "int8", "act_quantize": "int8"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="<fault>:<seed>,... with a fault of faults.FAULTS")
    args = ap.parse_args(argv)
    import spec
    cell = spec.load_cell(run.ROOT, args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    run.enable_cache(jax)
    import faults
    import harness

    seeds = lambda arg: [int(s) for s in arg.split(",") if s]  # noqa: E731
    runs = [("program", s, None, None) for s in seeds(args.program_seeds)]
    runs += [("int8", s, INT8, None) for s in seeds(args.control_seeds)]
    for item in filter(None, args.fault_seeds.split(",")):
        name, seed = item.split(":")
        runs.append((name, int(seed), None, name))
    for kind, seed, variant, fault in runs:
        t0 = time.perf_counter()
        st = harness.set_up(cell, seed, variant)
        if fault:
            faults.plant(st.eng, fault)
        win = harness.drive_window(cell, st, seed=seed,
                                   seconds=args.seconds, trace_dir=None)
        oc = harness.judge(cell, st, win, seed=seed, t_process=t0)
        out, _ = harness.result(cell, oc, trace=False)
        line = {"kind": kind, "seed": seed, "correct": out["correct"],
                "checks": out["checks"], "sampled": oc.sampled,
                "failed": oc.failed, **oc.found,
                "seconds": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
