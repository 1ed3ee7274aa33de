"""Runner: mean of the engine's own seconds per decode step, over the
window's steps that decoded (each ends in the sampler's host sync)."""
import numpy as np


def read(rec):
    secs = [s["decode_s"] for s in rec["steps"] if s["live"] > 0]
    return 1e3 * float(np.mean(secs)) if secs else None
