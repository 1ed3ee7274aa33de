"""Runner: device time of the prefill-chunk programs in the traced
window, per 1000 prompt tokens the window's steps prefilled while the
trace ran (ms)."""


def read(rec):
    tr, host = rec["trace"], rec["trace_host"]
    if tr is None or host is None or not tr["program_s"].get("prefill_chunk"):
        return None
    lo, hi = host
    tokens = sum(c[1] for s in rec["steps"] if lo <= s["t0"] < hi
                 for c in s["chunks"])
    if tokens == 0:
        return None
    return 1e3 * tr["program_s"]["prefill_chunk"] / (tokens / 1e3)
