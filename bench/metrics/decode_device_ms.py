"""decode_device_ms.<split>: device time per decompressed field of the
read path's programs: residual reconstruction (``sz_inverse``), code
unpacking (``unpack_codes``) and the edit scatter
(``_scatter_edits_jit``), in the traced window (device trace)."""

PROGRAMS = ("sz_inverse", "unpack_codes", "_scatter_edits_jit")


def read(run):
    t = run.trace
    if t is None or run.traffic["op"] != "decompress" or not run.done:
        return None
    dev_s = t.program_seconds(*PROGRAMS)
    if dev_s <= 0:
        return None
    return 1e3 * dev_s / len(run.done)
