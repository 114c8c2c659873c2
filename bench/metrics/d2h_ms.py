"""d2h_ms.<split>: milliseconds per field compressed in the window of
the program's ``msz.d2h`` span, device-to-host transfers of arrays the
device had finished (the wait for them is ``msz.wait``), read from the
trace's host planes (program span)."""
from bench import spans


def read(run):
    return spans.ms_per_field(run, "msz.d2h")
