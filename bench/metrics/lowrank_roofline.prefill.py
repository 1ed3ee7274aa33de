"""Kernels: for the lowrank_matmul calls inside prefill-chunk programs in the
traced window, the sum of their least times (the larger of operations
over the bf16 peak and HBM bytes over HBM bandwidth, from each call's
launched shapes) over the sum of their device times (%)."""


def read(rec):
    tr = rec["trace"]
    key = "lowrank/prefill_chunk"
    if tr is None or not tr["kernel_s"].get(key):
        return None
    return 100.0 * tr["kernel_least_s"][key] / tr["kernel_s"][key]
