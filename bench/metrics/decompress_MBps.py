"""decompress_MBps: output bytes (10^6) of every field decompressed in
the window, the one in flight at its close included, over the time from
the window's start to the last completion (host clock)."""
from bench import arith


def read(run):
    if run.traffic["op"] != "decompress" or not run.done:
        return None
    return arith.whole_request_rate([r.bytes_out / 1e6 for r in run.done],
                                    run.window_start,
                                    max(r.done for r in run.done))
