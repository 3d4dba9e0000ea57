"""Share of the traced span in which no op ran on the device, averaged
over the chips (harness/trace_reduce.py)."""

NAME = "device_idle_pct"


def read(run):
    r = run.reduced
    return None if not r else 100.0 * (1.0 - r["busy_s"] / r["window_s"])
