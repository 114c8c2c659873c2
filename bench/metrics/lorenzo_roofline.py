"""lorenzo_roofline: share of the HBM roofline of the quantize +
Lorenzo transform. The least bytes are the field read once and its int32
codes written once (``arith.transform_least_bytes``, shapes only),
summed over the fields compressed in the traced window; the time is the
device time of the transform's program there (device trace): the
eager ``pallas_call`` of ``kernels/lorenzo.py``, which the trace names
``jit_wrapped``, or a program with ``lorenzo`` in its name."""
import numpy as np

from bench import arith

PROGRAMS = ("jit_wrapped", "lorenzo")


def read(run):
    t = run.trace
    if t is None or run.peaks is None or run.traffic["op"] != "compress":
        return None
    dev_s = t.program_seconds(*PROGRAMS)
    shape = run.config["shape"]
    least = sum(arith.transform_least_bytes(shape, np.dtype(
        run.config["dtype"]).itemsize) for _ in run.done)
    return arith.roofline_pct(least, dev_s, run.peaks["hbm_bytes_per_s"])
