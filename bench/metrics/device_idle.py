"""device_idle: share of the traced window in which no operation ran on
the device (1 - busy / window). Kept apart per cell kind
(`device_idle.decode`, `device_idle.code`), each moving its cell's
throughput."""


def read(r):
    if r.trace is None or r.trace["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_ns"] / r.trace["window_ns"])
