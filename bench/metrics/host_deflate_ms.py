"""host_deflate_ms.<split>: milliseconds per field compressed in the
window of the program's ``msz.encode.residuals`` span, the residual
DEFLATE (or its host pack mirror), read from the trace's host planes
(program span)."""
from bench import spans


def read(run):
    return spans.ms_per_field(run, "msz.encode.residuals")
