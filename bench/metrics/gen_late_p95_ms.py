"""gen_late_p95_ms.<split>: 95th percentile of how late the load
generator sent each request of an open loop, against its schedule
(host clock)."""
from bench import arith


def read(run):
    if run.traffic["loop"] != "open":
        return None
    late = [(r.sent - r.due) * 1e3 for r in run.reqs if r.sent == r.sent]
    return arith.percentile(late, 95) if late else None
