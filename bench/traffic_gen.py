"""One generator for every traffic mix: an open-loop arrival schedule.

A mix file (``traffic/<name>.json``) gives the arrival process and the
length distributions; the cell gives the rate.  Every seed gets the
same work: the arrival times and the (prompt, output) length pairs are
drawn once from a fixed seed, and ``--seed`` draws the prompt tokens
(and, in the harness, the weights).  The order of the work is fixed
too: a 95th percentile over a hundred requests is set by where the few
longest prompts fall, and runs whose seeds reordered them differed five
times more than two runs of one seed (PERF.md).  So runs with different
seeds differ in content, not in the work they offer, and the same seed
gives the same schedule.

Arrival processes:

* ``poisson``: ``round(rate * seconds)`` arrivals, the first at 0, the
  rest at uniform times in ``[0, seconds)`` (a Poisson process given
  its count).

Length distributions: ``lognormal`` (``median``, ``sigma``) and
``uniform``, each clipped to ``[min, max]``.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

#: the seed of the set of sizes and gaps every run permutes
SHAPE_SEED = 20240727


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float                  # seconds after the window opens
    prompt: list[int]
    max_new: int


def draw_lengths(dist: dict, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    kind = dist["dist"]
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "lognormal":
        x = np.exp(rng.normal(math.log(dist["median"]), dist["sigma"], n))
    elif kind == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def arrival_times(process: dict, rate: float, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    kind = process["process"]
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    n = max(1, int(round(rate * seconds)))
    rest = np.sort(rng.uniform(0.0, seconds, n - 1))
    return np.concatenate([[0.0], rest])


def schedule(traffic: dict, *, rate: float, seconds: float, seed: int,
             vocab: int, max_seq: int) -> list[Arrival]:
    """The arrivals of one run, in due order."""
    shape_rng = np.random.default_rng(SHAPE_SEED)
    times = arrival_times(traffic["arrivals"], rate, seconds, shape_rng)
    n = len(times)
    prompts = draw_lengths(traffic["prompt_len"], n, shape_rng)
    outputs = draw_lengths(traffic["output_len"], n, shape_rng)
    # every request fits the pool: decode stops one short of max_seq
    outputs = np.minimum(outputs, max_seq - 1 - prompts)
    if np.any(outputs < 1):
        raise ValueError("a prompt leaves no room to decode in max_seq")

    rng = np.random.default_rng(seed)
    return [Arrival(float(times[i]),
                    rng.integers(0, vocab, int(prompts[i])).tolist(),
                    int(outputs[i])) for i in range(n)]
