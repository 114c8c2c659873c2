"""fix_iters.<split>: mean fix-loop passes per compressed member over the
window: the change of the ``fix_iters`` and ``dispatched`` counters of
``CompressionService.stats()`` across it (program counter). A program
without the ``fix_iters`` counter reads as nothing."""


def read(run):
    if run.stats_before is None or run.stats_after is None:
        return None
    c0, c1 = run.stats_before["compress"], run.stats_after["compress"]
    if "fix_iters" not in c0 or "fix_iters" not in c1:
        return None
    n = c1["dispatched"] - c0["dispatched"]
    if n <= 0:
        return None
    return (c1["fix_iters"] - c0["fix_iters"]) / n
