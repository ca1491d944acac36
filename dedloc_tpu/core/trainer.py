"""Phase-loop trainer: hook dispatch around a jitted train step.

Capability of vissl's SelfSupervisionTrainer + standard_train_step (reference:
swav/vissl/vissl/trainer/trainer_main.py:138-204,
train_steps/standard_train_step.py:87-229): a phase (epoch) loop that pulls
batches, runs the train step, and dispatches cross-cutting hooks at defined
points, with per-phase perf timers around read_sample / step / hooks.

TPU-native shape: the reference's per-event torch phases (forward, loss,
backward, optimizer) are ONE fused XLA program here, so ``step_fn`` is an
opaque jitted callable ``(state, batch) -> (state, metrics)`` and the in-step
events (on_forward/on_loss/on_backward/on_update) fire back-to-back after it
returns — they exist so reference-shaped hooks keep working. The host reads
one scalar (the loss) per step; everything else stays on device.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, Iterator, Optional, Tuple

import jax

from dedloc_tpu.core.hooks import HookList, LoopContext, default_hooks
from dedloc_tpu.telemetry import steps
from dedloc_tpu.telemetry.steps import StepRecorder
from dedloc_tpu.utils.logging import get_logger
from dedloc_tpu.utils.perf import PerfStats

logger = get_logger(__name__)

StepFn = Callable[[Any, Any], Tuple[Any, Dict[str, Any]]]


class Trainer:
    """Generic phase-loop driver.

    ``step_fn(state, batch) -> (new_state, metrics)`` with ``metrics["loss"]``
    a device scalar; optional ``metrics["lr"]`` and ``metrics["global_step"]``
    flow into the hook context (the reference feeds the collaboration-wide
    optimizer step into its loss the same way, standard_train_step.py:153).
    """

    def __init__(
        self,
        step_fn: StepFn,
        hooks: Optional[HookList] = None,
        perf: Optional[PerfStats] = None,
        recorder: Optional[StepRecorder] = None,
    ):
        self.step_fn = step_fn
        self.hooks = hooks if hooks is not None else default_hooks()
        self.perf = perf if perf is not None else PerfStats()
        # step-phase flight recorder (telemetry/steps.py): always times,
        # publishes only while telemetry is enabled. A role passes its own
        # to add a profiler window (telemetry/profile.py)
        self.recorder = recorder if recorder is not None else StepRecorder()

    def train(
        self,
        state: Any,
        batches: Iterator[Any],
        max_steps: int,
        steps_per_phase: Optional[int] = None,
        ctx: Optional[LoopContext] = None,
    ) -> Tuple[Any, LoopContext]:
        """Run up to ``max_steps`` steps, split into phases of
        ``steps_per_phase`` (one phase if None). Returns (state, ctx)."""
        steps_per_phase = steps_per_phase or max_steps
        ctx = ctx or LoopContext()
        ctx.max_steps = max_steps
        ctx.perf = self.perf
        ctx.train_state = state

        try:
            self.hooks.dispatch("on_start", ctx)
            while ctx.local_step < max_steps and not ctx.should_stop:
                self.hooks.dispatch("on_phase_start", ctx)
                phase_end = min(ctx.local_step + steps_per_phase, max_steps)
                while ctx.local_step < phase_end and not ctx.should_stop:
                    state = self._one_step(state, batches, ctx)
                self.hooks.dispatch("on_phase_end", ctx)
                ctx.phase += 1
            self.hooks.dispatch("on_end", ctx)
        finally:
            self.recorder.close()  # a profiler window still open
        return state, ctx

    def _one_step(self, state: Any, batches: Iterator[Any], ctx: LoopContext):
        with self.recorder.step(step=ctx.local_step):
            return self._one_step_inner(state, batches, ctx)

    def _one_step_inner(self, state, batches, ctx):
        self.hooks.dispatch("on_step_begin", ctx)
        with self.perf.timer("read_sample"), steps.phase("data_wait"):
            try:
                batch = next(batches)
            except StopIteration:
                ctx.should_stop = True
                return state
        metrics: Dict[str, Any] = {}
        with self.perf.timer("train_step"):
            # whatever step_fn records itself (a role's h2d, the optimizer's
            # boundary, its post_step) nests under fwd_bwd, whose own time
            # is then what the host spent enqueueing the step
            with steps.phase("fwd_bwd"):
                state, metrics = self.step_fn(state, batch)
            with steps.phase("loss_sync"):
                # the loop's own host sync, once per step: block on the loss
                # only — the rest of the state stays async
                loss = metrics.get("loss")
                if loss is not None:
                    jax.block_until_ready(loss)
                ctx.loss = float(loss) if loss is not None else float("nan")
        ctx.local_step += 1
        ctx.train_state = state
        if "lr" in metrics:
            ctx.lr = float(metrics["lr"])
        if "global_step" in metrics:
            ctx.global_step = int(metrics["global_step"])
        ctx.metrics = {
            k: float(v)
            for k, v in metrics.items()
            if k not in ("global_step",) and _is_scalar(v)
        }
        with self.perf.timer("hooks"), steps.phase("hooks"):
            # fused-step event fan-out (see module docstring)
            for event in ("on_forward", "on_loss", "on_backward", "on_update",
                          "on_step_end"):
                self.hooks.dispatch(event, ctx)
        return state


def _is_scalar(v: Any) -> bool:
    try:
        return getattr(v, "ndim", 0) == 0 or isinstance(v, (int, float))
    except Exception:
        return False
