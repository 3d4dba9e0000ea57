"""One ``replay.sample`` alone on a full ring of the cell's capacity,
fenced (harness/standalone.py). Only where the algorithm has a replay."""

NAME = "replay_sample_us"


def read(run):
    s = run.standalone.get("replay_sample_s")
    return None if s is None else 1e6 * s
