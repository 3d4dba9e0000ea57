"""Seconds making what the loop carries: the learner's ``init`` (the parameters
and the optimizer's state, 8-10 GB in the two wide cells:
``launch.state_init``) and ``init_loop_state`` (the env carry, the replay
ring: ``launch.carry_init``), in ``Trainer.run`` and
``OffPolicyTrainer.run``. Spans of the program's ``launch`` event
(harness/launch_spans.py)."""

from benchmarks.harness import launch_spans

NAME = "launch_state_init_s"


def read(run):
    return launch_spans.span_s(run, "launch.state_init", "launch.carry_init")
