"""Share of the traced span in which a collective runs on the device and
no other op does: the part of the exchange compute does not hide."""

NAME = "collective_exposed_pct"


def read(run):
    r = run.reduced
    if not r or not r["collective_calls"]:
        return None
    return 100.0 * r["collective_exposed_s"] / r["window_s"]
