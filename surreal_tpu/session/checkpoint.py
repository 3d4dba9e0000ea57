"""Checkpoint/resume (parity: reference ``surreal/utils/checkpoint.py`` —
``PeriodicCheckpoint`` with keep-last-N / keep-best retention and a
``restore_folder`` path through learner setup; SURVEY.md §2.1 Checkpoint
row and §5.4), built on orbax.

When orbax is loaded: at the first :class:`CheckpointManager`, not when
this module is imported. ``import orbax.checkpoint`` pulls in its cloud
logger (``google.cloud.logging``) and tensorstore: 26 of the 29.5 s that
``import surreal_tpu.main.launch`` took on the chip host, 44-48 s inside
a launch (PERF.md §6, PR 40), and
``surreal_tpu.session`` re-exports this module, so every process that
imports a config would pay it: env workers, fleet replicas, the gateway,
``diag``. A session with ``checkpoint.every_n_iters=0`` and no
``restore_from`` builds no manager and never imports it; one that does
checkpoint pays the import once, at its first manager, and every manager
hands its sink the seconds it took (0 when orbax was loaded already) as a
``phases`` event with the one phase ``checkpoint-import``: a row of
``surreal_tpu diag``'s phase table.

What is checkpointed: the **learner state pytree** (params, optimizer
state, obs-normalizer stats, adaptive scalars) plus run metadata
(iteration, env_steps). Environment/rollout carries are NOT checkpointed —
on resume, envs reset and refill, exactly as the reference's actors
restarted stateless and re-fetched parameters (SURVEY.md §5.3/§5.4
"agents don't checkpoint"). That makes resume trivially correct for both
the on-policy fused path and the replay path (the replay warms back up
past ``start_sample_size`` before learning resumes).

Layout under ``<session folder>/checkpoints/``:
    <step>/            orbax step dirs, pruned to ``keep_last``
    best/              overwritten copy of the best-metric state (keep_best)
    best_metric.json   the best metric value + the step it came from
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Any

import jax


def _orbax():
    """``orbax.checkpoint``: imported by the first call, found in
    ``sys.modules`` by every later one (the module docstring says why it
    is not imported at this module's top)."""
    import orbax.checkpoint as ocp

    return ocp


class PrecisionMismatchError(ValueError):
    """A checkpoint was saved under a different precision policy than the
    one trying to restore it.

    The policy decides whether a ``LossScaleState`` leaf lives in the
    optimizer pytree (ops/precision.py) and which dtypes the trained
    numerics used — restoring across a mismatch either fails as a cryptic
    orbax structure error or, worse, silently resumes f32-trained
    numerics under a different policy. This error names both policies and
    the fix instead (the PR-5 ``recovery_scale`` pytree-break lesson,
    made a first-class check)."""


def check_precision_metadata(recorded: dict | None, active: dict | None) -> None:
    """Raise :class:`PrecisionMismatchError` when a checkpoint's recorded
    precision metadata disagrees with the active policy. Missing metadata
    (pre-ISSUE-7 sessions) or an unknown active policy passes — the guard
    never blocks legacy restores, it explains the breaks that WOULD
    happen."""
    if not recorded or not active:
        return
    mismatched = {
        k: (recorded.get(k), active.get(k))
        for k in (
            "policy", "param_dtype", "loss_scaling", "compute_dtype",
            "data_dtype", "fp8",
        )
        if k in recorded and recorded.get(k) != active.get(k)
    }
    if mismatched:
        detail = ", ".join(
            f"{k}: checkpoint={a!r} vs active={b!r}"
            for k, (a, b) in sorted(mismatched.items())
        )
        raise PrecisionMismatchError(
            "checkpoint was saved under a different precision policy "
            f"({detail}). Set algo.precision (and optimizer.loss_scaling) "
            "to match the checkpoint to resume it, or point "
            "session.folder at a fresh directory to train under the new "
            "policy from scratch."
        )


class CheckpointManager:
    """Save/restore learner state with keep-last-N + keep-best retention."""

    def __init__(
        self,
        folder: str,
        keep_last: int = 3,
        keep_best: bool = True,
        best_key: str = "episode/return",
        on_event=None,
    ):
        # on_event(type_str, **fields): optional telemetry sink (the
        # session tracer's .event) — restore-fallback decisions must be
        # visible in `surreal_tpu diag`, not only in a log file
        self._on_event = on_event
        loaded = "orbax.checkpoint" in sys.modules
        t0 = time.perf_counter()
        ocp = _orbax()
        import_s = 0.0 if loaded else time.perf_counter() - t0
        if on_event is not None:
            # a row of diag's phase table: the seconds a quiet launch no
            # longer spends show up here in a session that checkpoints
            on_event(
                "phases", step=-1,
                phases={"checkpoint-import": {
                    "count": 1, "total_s": import_s, "max_ms": import_s * 1e3,
                }},
            )
        self.directory = os.path.join(os.path.abspath(folder), "checkpoints")
        os.makedirs(self.directory, exist_ok=True)
        self.keep_best = keep_best
        self.best_key = best_key
        # Checkpointing is single-controller BY DESIGN, even under
        # jax.distributed: the multi-host driver passes host-local numpy
        # state and only rank 0 ever constructs a manager
        # (launch/multihost_trainer.py). Orbax would otherwise detect
        # process_count > 1 and block every save on a cross-process barrier
        # that the other ranks never join. active_processes pins all
        # coordination to the constructing process.
        mp_options = ocp.options.MultiprocessingOptions(
            primary_host=jax.process_index(),
            active_processes={jax.process_index()},
            barrier_sync_key_prefix=f"surreal_tpu_{jax.process_index()}",
        )
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep_last,
                # create=False: orbax refuses create+active_processes; the
                # makedirs above already guarantees the root exists
                create=False,
                # best/ is handled by hand below so keep-last and keep-best
                # retention compose instead of competing in one policy
                multiprocessing_options=mp_options,
            ),
        )
        self._best_dir = os.path.join(self.directory, "best")
        self._best_meta_path = os.path.join(self.directory, "best_metric.json")
        # run-scoped metadata sidecar (precision policy etc.): one file
        # per checkpoint root, not per step — the policy is a build-time
        # constant of the session writing here
        self._run_meta_path = os.path.join(self.directory, "run_meta.json")
        self._best_ckptr = ocp.StandardCheckpointer(
            multiprocessing_options=mp_options
        )
        self._mp_options = mp_options
        self._keep_last = keep_last
        self._extra_mgr = None

    def _extra(self):
        """Lazy manager for auxiliary step-aligned state (the replay
        buffer) — a SEPARATE tree under ``extra/`` so the main payload's
        shape stays stable across configs and old sessions restore fine."""
        if self._extra_mgr is None:
            ocp = _orbax()
            root = os.path.join(self.directory, "extra")
            os.makedirs(root, exist_ok=True)
            self._extra_mgr = ocp.CheckpointManager(
                root,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=self._keep_last,
                    create=False,
                    multiprocessing_options=self._mp_options,
                ),
            )
        return self._extra_mgr

    # -- save ----------------------------------------------------------------
    def save(
        self,
        step: int,
        state: Any,
        *,
        env_steps: int = 0,
        metrics: dict[str, float] | None = None,
    ) -> None:
        """Persist ``state`` at ``step``; update best/ when the tracked
        metric improves."""
        payload = {
            "state": state,
            "meta": {"iteration": step, "env_steps": env_steps},
        }
        self._mgr.save(step, args=_orbax().args.StandardSave(payload))
        self._mgr.wait_until_finished()

        if not (self.keep_best and metrics):
            return
        value = metrics.get(self.best_key)
        if value is None or value != value:  # absent or NaN
            return
        best = self.best_metric()
        if best is not None and value <= best["value"]:
            return
        # orbax's own tmp-dir + rename makes the overwrite atomic
        self._best_ckptr.save(self._best_dir, payload, force=True)
        self._best_ckptr.wait_until_finished()
        # tmp + rename: a SIGKILL mid-write (kill-and-resume is a supported
        # flow) must never leave a truncated meta that crashes the relaunch
        tmp = self._best_meta_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"value": float(value), "step": int(step)}, f)
        os.replace(tmp, self._best_meta_path)

    def save_extra(self, step: int, tree: Any) -> None:
        """Persist auxiliary state aligned to ``step`` (see ``_extra``)."""
        mgr = self._extra()
        mgr.save(step, args=_orbax().args.StandardSave(tree))
        mgr.wait_until_finished()

    def restore_extra(self, template: Any, step: int):
        """Restore the auxiliary tree saved at EXACTLY ``step`` (the step
        the main state restored from); None when absent — callers fall
        back to a fresh buffer, same as resuming an old session."""
        if step not in self._extra().all_steps():
            return None
        ocp = _orbax()
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, template)
        return self._extra().restore(step, args=ocp.args.StandardRestore(abstract))

    # -- run metadata (precision policy sidecar) -----------------------------
    def save_run_metadata(self, meta: dict) -> None:
        """Persist run-scoped metadata (the active precision policy —
        ops/precision.py ``PrecisionPolicy.meta()``) beside the step dirs.
        Atomic (tmp + rename): relaunch pollers race this write."""
        tmp = self._run_meta_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, self._run_meta_path)

    def run_metadata(self) -> dict | None:
        """The recorded run metadata, or None (pre-ISSUE-7 sessions /
        torn writes read as absent — the guard must never turn a legacy
        resume into a crash about metadata bookkeeping)."""
        if not os.path.exists(self._run_meta_path):
            return None
        try:
            with open(self._run_meta_path) as f:
                return json.load(f)
        except (OSError, json.JSONDecodeError):
            return None

    def check_precision(self, active_meta: dict | None) -> None:
        """Fail restore LOUDLY on a precision-policy mismatch (see
        :class:`PrecisionMismatchError`); callers run this BEFORE orbax
        touches the step dirs so the user sees the policy diff, not a
        structure traceback."""
        check_precision_metadata(self.run_metadata(), active_meta)

    # -- restore -------------------------------------------------------------
    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def steps(self) -> list[int]:
        """All retained step numbers, ascending (includes steps whose dirs
        may be damaged — restore() is where damage is discovered)."""
        return sorted(int(s) for s in self._mgr.all_steps())

    def best_metric(self) -> dict | None:
        if not os.path.exists(self._best_meta_path):
            return None
        with open(self._best_meta_path) as f:
            try:
                return json.load(f)
            except json.JSONDecodeError:
                # legacy non-atomic write interrupted by a kill: treat as
                # "no best yet" rather than poisoning every future save
                return None

    def restore(self, template_state: Any, step: int | None = None,
                validate=None):
        """Restore (state, meta) at ``step`` (default latest).

        ``template_state`` supplies the pytree structure/shardings to
        restore into — call sites pass a freshly ``init()``-ed state.
        Returns None when no checkpoint exists.

        Damage fallback: without an explicit ``step``, a latest step dir
        that fails to restore (truncated/corrupt — a SIGKILL mid-save is
        a supported failure, and relaunch-after-kill is exactly when this
        path runs) falls back to the next-older retained step instead of
        crashing the relaunch, emitting a ``recovery`` telemetry event
        (kind ``checkpoint_fallback``). ``validate(state) -> bool`` lets
        callers reject restorable-but-unusable steps (the divergence
        layer passes a finiteness check so a save that raced the NaN
        detection window never becomes the resume point); rejected steps
        emit kind ``skipped_nonfinite_checkpoint`` and the walk continues.
        If steps exist but NONE restores (every dir raised), the walk
        raises the NEWEST step's error — an every-step failure is
        systemic (e.g. the template's optimizer layout changed) and a
        silent fresh start would overwrite the very progress the caller
        asked to resume. All-rejected-by-validate returns None (poison
        everywhere is genuinely unresumable; callers fall back to fresh
        init). An explicit ``step`` is a caller decision and propagates
        its error directly.
        """
        template = {
            "state": template_state,
            "meta": {"iteration": 0, "env_steps": 0},
        }
        ocp = _orbax()
        abstract = jax.tree.map(ocp.utils.to_shape_dtype_struct, template)
        if step is not None:
            payload = self._mgr.restore(
                step, args=ocp.args.StandardRestore(abstract)
            )
            return payload["state"], payload["meta"]
        candidates = sorted(self.steps(), reverse=True)
        first_exc: Exception | None = None
        for i, s in enumerate(candidates):
            try:
                payload = self._mgr.restore(
                    s, args=ocp.args.StandardRestore(abstract)
                )
            except Exception as e:  # orbax raises a zoo of types per damage mode
                if first_exc is None:
                    first_exc = e
                if self._on_event is not None and i < len(candidates) - 1:
                    self._on_event(
                        "recovery", kind="checkpoint_fallback",
                        bad_step=int(s), next_step=int(candidates[i + 1]),
                        error=f"{type(e).__name__}: {e}"[:200],
                    )
                continue
            if validate is not None and not validate(payload["state"]):
                if self._on_event is not None:
                    self._on_event(
                        "recovery", kind="skipped_nonfinite_checkpoint",
                        step=int(s),
                    )
                continue
            return payload["state"], payload["meta"]
        if first_exc is not None:
            raise first_exc  # nothing restored at all: systemic, be loud
        return None

    def restore_best(self, template_state: Any):
        """Restore the keep-best snapshot; None when absent."""
        if self.best_metric() is None:
            return None
        template = {
            "state": template_state,
            "meta": {"iteration": 0, "env_steps": 0},
        }
        abstract = jax.tree.map(_orbax().utils.to_shape_dtype_struct, template)
        payload = self._best_ckptr.restore(self._best_dir, abstract)
        return payload["state"], payload["meta"]

    def close(self) -> None:
        self._mgr.close()
        self._best_ckptr.close()
        if self._extra_mgr is not None:
            self._extra_mgr.close()


def make_checkpoint_manager(session_config, on_event=None) -> CheckpointManager | None:
    """Build from ``session_config.checkpoint``; None when disabled
    (``every_n_iters`` <= 0)."""
    ck = session_config.checkpoint
    if not ck.every_n_iters or ck.every_n_iters <= 0:
        return None
    return CheckpointManager(
        session_config.folder,
        keep_last=ck.keep_last,
        keep_best=ck.keep_best,
        on_event=on_event,
    )
