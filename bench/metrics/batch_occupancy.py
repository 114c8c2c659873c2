"""batch_occupancy.<split>: real members over dispatched member slots
(padding included) of the service's compress batches in the window, in
percent, from the counters of ``CompressionService.stats()`` before and
after it (program counter)."""


def _members(s):
    c = s["compress"]
    real = round(c["mean_batch"] * c["batches"])
    return real, real + c["padded_members"]


def read(run):
    if run.stats_before is None or run.stats_after is None:
        return None
    r0, d0 = _members(run.stats_before)
    r1, d1 = _members(run.stats_after)
    if d1 - d0 <= 0:
        return None
    return 100.0 * (r1 - r0) / (d1 - d0)
