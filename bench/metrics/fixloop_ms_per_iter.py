"""fixloop_ms_per_iter.<split>: device time of the fix-loop programs
(``_fused_fix_impl``, ``_worklist_fix_impl``) in the traced window over
the fix iterations of the fields compressed there (device trace)."""

PROGRAMS = ("_fused_fix_impl", "_worklist_fix_impl")


def read(run):
    t = run.trace
    iters = sum(r.stats.get("fix_iters", 0) for r in run.done)
    if t is None or iters <= 0:
        return None
    dev_s = t.program_seconds(*PROGRAMS)
    if dev_s <= 0:
        return None
    return 1e3 * dev_s / iters
