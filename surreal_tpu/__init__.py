"""surreal_tpu — a TPU-native distributed RL framework.

A ground-up re-design of the capability surface of ``tanwanirahul/surreal``
(a fork of Stanford's SURREAL, CoRL 2018) for TPUs: instead of a zoo of
ZMQ-connected PyTorch processes (actors -> sharded replay -> GPU learner ->
parameter server -> actors), one experiment is one JAX SPMD program —
SEED-RL-style batched inference (``jit(vmap(policy))``), an HBM-resident
trajectory FIFO / replay with on-device GAE / V-trace (``lax.scan``), and a
data-parallel learner whose gradient allreduce rides the ICI mesh via
``shard_map``.

Layer map (mirrors SURVEY.md §1, re-homed for TPU):

- ``surreal_tpu.session``    — config trees, trackers, checkpoint, metrics (ref L6)
- ``surreal_tpu.envs``       — env factory, adapters, wrappers, JAX-native envs (ref L3)
- ``surreal_tpu.ops``        — GAE / V-trace / n-step scans, distributions, ZFilter (ref: inside learners)
- ``surreal_tpu.models``     — flax policy/value networks (ref surreal/model/)
- ``surreal_tpu.replay``     — HBM trajectory FIFO, uniform + prioritized replay (ref L4)
- ``surreal_tpu.agents``     — acting: policy heads + exploration modes (ref L5 agent/)
- ``surreal_tpu.learners``   — PPO / DDPG / IMPALA update rules + train loop (ref L5 learner/)
- ``surreal_tpu.parallel``   — mesh, shardings, collective training steps (replaces ZMQ data plane)
- ``surreal_tpu.distributed``— host<->device transport: ZMQ inference server, exp senders (ref L0/L2)
- ``surreal_tpu.launch``     — experiment launcher / component dispatch (ref L7)
"""

from time import perf_counter as _perf_counter

# the launch record's first boundary (session/telemetry.py): process start
# to this line is the span ``launch.process``, from here to the entry
# point's first call ``launch.import``
IMPORTED_AT = _perf_counter()

__version__ = "0.1.0"
