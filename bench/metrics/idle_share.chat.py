"""Device: 1 minus the union of the device's operation intervals over
the traced window (%)."""


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
