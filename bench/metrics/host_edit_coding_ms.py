"""host_edit_coding_ms.<split>: milliseconds per field compressed in the
window of the program's ``msz.encode.edits`` span, the varint and zlib
coding of the edit indices and values, read from the trace's host
planes (program span)."""
from bench import spans


def read(run):
    return spans.ms_per_field(run, "msz.encode.edits")
