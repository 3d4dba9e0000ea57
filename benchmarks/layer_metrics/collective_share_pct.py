"""Share of the traced span one device spends in collective ops
(all-reduce and kin, by XLA's op names). Only where the trace has any."""

NAME = "collective_share_pct"


def read(run):
    r = run.reduced
    if not r or not r["collective_calls"]:
        return None
    return 100.0 * r["collective_s"] / r["window_s"]
