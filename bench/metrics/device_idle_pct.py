"""device_idle_pct.<split>: 100 x (1 - busy / window), busy being the
union of device-op intervals in the traced window (device trace)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
