"""Statistics of a run's record, shared by the metric readers."""
from __future__ import annotations

from typing import Iterable

import numpy as np


def percentile(xs: Iterable[float], q: float) -> float:
    """The ``q``-th percentile (linear interpolation) of ``xs``; an
    empty sample is an error, never 0."""
    xs = np.asarray(list(xs), dtype=np.float64)
    if xs.size == 0:
        raise ValueError("percentile of an empty sample")
    return float(np.percentile(xs, q))


def in_window(rec: dict, t: float) -> bool:
    return rec["window"]["t0"] <= t <= rec["window"]["t1"]


def ttfts(rec: dict) -> list[float]:
    """Seconds from each request's due time to its first token; a request
    with no first token by the window's close counts until then."""
    end = rec["window"]["t1"]
    return [(r["first"] if r["first"] is not None else end) - r["due"]
            for r in rec["requests"]]


def queue_waits(rec: dict) -> list[float]:
    """Seconds from each request's due time to the start of the step that
    took it off the waiting queue; a request still waiting at the
    window's close counts until then."""
    end = rec["window"]["t1"]
    return [(r["admitted"] if r["admitted"] is not None else end) - r["due"]
            for r in rec["requests"]]


def token_gaps(rec: dict) -> list[float]:
    """Every gap between consecutive output tokens of a request, both
    inside the window."""
    out = []
    for r in rec["requests"]:
        ts = [t for t in r["token_times"] if in_window(rec, t)]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def window_tokens(rec: dict) -> int:
    """Prompt tokens prefilled plus tokens generated by the window's
    steps."""
    return sum(sum(c[1] for c in s["chunks"]) + s["decode_tokens"]
               + s["first_tokens"] for s in rec["steps"])


def decode_token_flops(rec: dict, flops_of) -> float:
    """Model FLOPs of every token a decode step produced in the window:
    output token ``j >= 1`` attends ``prompt_len + j`` keys."""
    total = 0.0
    for r in rec["requests"]:
        for j, t in enumerate(r["token_times"]):
            if j >= 1 and in_window(rec, t):
                total += flops_of(r["prompt_len"] + j)
    return total
