"""queue_wait_ms.<split>: mean milliseconds a compress request waited in
the service's stream queue, from its submit to the scheduler popping it
into a batch, over the requests popped in the window: the change of the
``queue_wait_s`` and ``dispatched`` counters of
``CompressionService.stats()`` across it (program counter).

Read beside the device plane of the traced run, whose idle time it
helps split; where the trace holds none (a CPU rehearsal), nothing is
read. A program without the counters reads as nothing too."""


def read(run):
    if run.trace is None or run.stats_before is None \
            or run.stats_after is None:
        return None
    c0, c1 = run.stats_before["compress"], run.stats_after["compress"]
    if "queue_wait_s" not in c0 or "queue_wait_s" not in c1:
        return None
    n = c1["dispatched"] - c0["dispatched"]
    if n <= 0:
        return None
    return 1e3 * (c1["queue_wait_s"] - c0["queue_wait_s"]) / n
