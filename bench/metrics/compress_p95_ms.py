"""compress_p95_ms: 95th percentile, over every request due in the
window, of its completion minus its due time (host clock). A request
that failed or had no answer by the end of the drain counts as
infinitely late; the reported value is then the largest finite one
(the run is not correct in that case)."""
import math

from bench import arith


def read(run):
    if run.traffic["op"] != "compress" or run.traffic["loop"] != "open":
        return None
    if not run.reqs:
        return None
    lat = arith.due_latencies_ms([r.due for r in run.reqs],
                                 [r.done if r.ok else None for r in run.reqs])
    p = arith.percentile(lat, 95)
    if math.isinf(p):
        finite = [x for x in lat if math.isfinite(x)]
        return max(finite) if finite else None
    return p
