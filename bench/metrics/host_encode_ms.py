"""host_encode_ms.<split>: mean over the fields compressed in the window
of the artifact's ``t_base - t_transform``: the pull of the residual
codes and the host's entropy and edit coding (program span)."""


def read(run):
    xs = [r.stats["t_base"] - r.stats["t_transform"] for r in run.done
          if "t_base" in r.stats]
    if not xs:
        return None
    return 1e3 * sum(xs) / len(xs)
