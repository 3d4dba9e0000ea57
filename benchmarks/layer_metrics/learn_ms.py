"""The iteration's learning alone, fenced: one ``learner.learn`` on a
whole rollout (on-policy), or ``updates_per_iter`` sequential ``learn``
calls on sampled batches (off-policy). harness/standalone.py."""

NAME = "learn_ms"


def read(run):
    s = run.standalone.get("learn_s")
    return None if s is None else 1e3 * s
