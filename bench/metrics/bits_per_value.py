"""bits_per_value: 8 x the compressed bytes over the values, summed over
every field compressed in the window (read by the benchmark from the
bytes each answer holds)."""


def read(run):
    done = [r for r in run.done if r.nbytes]
    if run.traffic["op"] != "compress" or not done:
        return None
    return 8.0 * sum(r.nbytes for r in done) / sum(r.values for r in done)
