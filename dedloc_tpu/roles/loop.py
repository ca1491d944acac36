"""The boundary loop: the one place in the tree that iterates accumulation
boundaries, for every model.

Capability parity with albert/run_trainer.py:130-170 (CollaborativeCallback)
and the vissl phase loop with its log / perf / checkpoint / publish hooks
(swav/vissl/vissl/trainer/trainer_main.py:138-204): jitted accumulate per
micro-batch; at every accumulation boundary hand control to the
collaborative optimizer (global-step averaging, NaN rollback); on a global
step read the loss once, publish signed metrics, log, save.

A role (``roles/trainer.py``, ``roles/swav.py``) is a BUILDER: it parses,
builds model, optimizer, DHT and state, and hands this loop a ``LoopModel``
— data and closures, not hooks.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from dedloc_tpu.parallel.train_step import TrainState, zeros_like_grads
from dedloc_tpu.roles.common import open_train_log, publish_step_metrics
from dedloc_tpu.telemetry import steps
from dedloc_tpu.telemetry.profile import profile_gate
from dedloc_tpu.telemetry.steps import StepRecorder, chip_peak_tflops
from dedloc_tpu.utils.backend import hbm_bytes_in_use
from dedloc_tpu.utils.logging import get_logger
from dedloc_tpu.utils.perf import PerfStats

logger = get_logger(__name__)


@dataclasses.dataclass
class LoopModel:
    """What a model brings to the boundary loop."""

    # host micro-batches; a source that ends (StopIteration) ends the run
    # gracefully, any other exception propagates
    batches: Iterator
    # (state, grad_acc, n_acc, batch) -> (grad_acc, n_acc, metrics): enqueue
    # one micro-batch; closes over whatever else the model carries (an rng
    # stream; SwAV's batch_stats and queue)
    micro_step: Callable
    # (state, step) -> None: write the model's checkpoint payload
    save: Callable
    # host batch -> device batch, timed as ``h2d`` (None: the micro-step
    # takes the drawn batch as it is)
    put: Optional[Callable] = None
    # metrics beside the loss that become gauges: summed on the device
    # with the loss and read with it, once per global step (a vector, over
    # passes or expert layers, as ``name.1`` .. ``name.n``; a scalar as
    # ``name``); and those that are counts, the step's total into a counter
    step_gauges: Tuple[str, ...] = ()
    step_counters: Tuple[str, ...] = ()
    # gauges the builder's programs read on the HOST (off their own trace:
    # no output of the device program), a live dict: what it holds goes
    # onto every stepping record beside them
    host_gauges: Dict[str, float] = dataclasses.field(default_factory=dict)
    # ... and running totals they keep on the host, a live dict too: what a
    # total grew by since the last stepping record goes onto this one
    host_counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    # analytic model TFLOPs of one fwd+bwd sample, for the MFU gauge
    # (0: no gauge)
    tflops_per_sample: float = 0.0


def run_boundary_loop(
    args, model: LoopModel, state: TrainState, opt, dht, public_key: bytes,
    tele, tele_close: Callable[[], None], log_perf_steps: int = 0,
) -> TrainState:
    """Train until ``--training.max_local_steps`` boundaries or the end of
    the data, then close recorder, train log, telemetry, optimizer and DHT
    (also on an exception). ``args`` is either role's argument tree;
    ``log_perf_steps`` the global steps between ``perf phases`` reports."""
    t = args.training
    grad_acc = zeros_like_grads(state.params)
    n_acc = jnp.zeros([], jnp.int32)
    # the running loss stays ON DEVICE (a lazy sum) — a float() in the loop
    # would synchronize the host with the accumulate kernels and serialize
    # the input pipeline against XLA dispatch; the host reads it once per
    # GLOBAL step, right where the value is published — and with it, in the
    # same read, the model's per-pass gauges (a looped model's exit
    # distribution and per-pass loss)
    summed = ("loss",) + model.step_gauges + model.step_counters
    sums_dev: dict = {}
    host_counted: Dict[str, float] = {}
    mini_steps = 0
    boundary = 0
    last_saved_step = opt.local_step
    # the flight recorder (telemetry/steps.py) is the loop's one timer: every
    # boundary is a record of nested host spans, always timed, published
    # through the telemetry registry only when that is enabled. It feeds
    # PerfStats (the operator's --training.log_perf_steps report; vissl
    # PerfStats capability, vissl/utils/perf_stats.py:12-249) and, with
    # --telemetry.profile_*, opens the profiler window. Per-micro-batch
    # device time is NOT blocked on (that would serialize the async dispatch
    # chain): it is the ``drain`` span inside opt.step, and the device
    # planes of a profile.
    perf = PerfStats()
    recorder = StepRecorder(
        telemetry=tele,
        model_tflops_per_sample=model.tflops_per_sample,
        peak_tflops=chip_peak_tflops(),
        perf=perf,
        profile=profile_gate(args.telemetry),
    )
    train_log = open_train_log(t.train_log_path)
    samples = opt.batch_size_per_step
    exhausted = False
    # the role's set-up record (telemetry/steps.py) is open until the end of
    # the first global step: the laps below are its share of the loop, and
    # no-ops from then on
    first_micro = steps.current_setup() is not None
    try:
        while True:
            # one accumulation boundary = gradient_accumulation_steps
            # micro-batches = ONE step record, which runs to the start of
            # the next boundary: data_wait/h2d/fwd_bwd here, the optimizer's
            # spans inside opt.step (which also stamps stepped, samples and
            # the running totals on the record), the tail of a global step
            # as post_step
            with recorder.step(step=opt.local_step) as srec:
                for _ in range(t.gradient_accumulation_steps):
                    with steps.phase("data_wait"):
                        batch = next(model.batches, None)
                    if batch is None:
                        exhausted = True
                        break
                    if model.put is not None:
                        with steps.phase("h2d"):
                            batch = model.put(batch)
                    if first_micro:
                        steps.lap("data_source")  # ... and its first batch
                    with steps.phase("fwd_bwd"):
                        # everything the host enqueues for one micro-batch
                        grad_acc, n_acc, metrics = model.micro_step(
                            state, grad_acc, n_acc, batch
                        )
                        sums_dev = {
                            k: sums_dev[k] + metrics[k] if k in sums_dev
                            else metrics[k] for k in summed
                        }
                    if first_micro:
                        first_micro = False
                        # trace + lower + compile-or-load + dispatch; the
                        # device's first run lands in the laps that follow
                        steps.lap("first_micro_batch")
                    mini_steps += 1
                if exhausted:
                    logger.info("the batch source ended; stopping")
                    break
                # the rest of the first global step's micro-batches, and the
                # calls below that only report progress
                steps.lap("accumulate")
                state, grad_acc, n_acc, stepped = opt.step(
                    state, grad_acc, n_acc, samples
                )
                if stepped:
                    # the boundary programs' first calls
                    steps.lap("first_boundary")
                    with steps.phase("post_step"):
                        with steps.phase("loss_sync"):
                            # the one sync per global step
                            sums = jax.device_get(sums_dev)
                        sums_dev = {}
                        loss_sum = float(sums["loss"])
                        loss = loss_sum / max(mini_steps, 1)
                        for name in model.step_gauges:
                            # means over the global step's tokens, onto the
                            # step record and (telemetry on) into gauges
                            mean = sums[name] / max(mini_steps, 1)
                            values = (
                                {name: mean} if mean.ndim == 0 else {
                                    f"{name}.{p}": v
                                    for p, v in enumerate(mean, start=1)
                                }
                            )
                            for key, value in values.items():
                                srec.attrs[key] = float(value)
                                if tele is not None:
                                    tele.gauge(key).set(float(value))  # dedlint: emits=gauge:lm.exit_prob.*,gauge:lm.loss.*,gauge:moe.load_max_over_mean.*,gauge:moe.local_slot_share,gauge:moe.bias_abs_max,gauge:moe.grad_sink_leaves,gauge:moe.compute_copy_leaves,gauge:moe.bulk_row_share,gauge:attn.band_tile_share,gauge:attn.band_visible_share,gauge:attn.gate_mean.*,gauge:attn.bd_tile_share,gauge:diffusion.masked_share,gauge:attn.select_kept_share,gauge:attn.select_tile_share,gauge:attn.index_loss_tile_share,gauge:attn.index_peak.*,gauge:attn.select_tie_block_share.*,gauge:loss.index_kl,gauge:data.image_token_share,gauge:kda.chunk_log_decay_min.*,gauge:kda.beta_mean.*,gauge:kda.state_abs_max.*,gauge:ssd.dt_mean.*,gauge:ssd.chunk_log_decay_min.*,gauge:ssd.state_abs_max.*
                        for key, value in model.host_gauges.items():
                            srec.attrs[key] = value
                            if tele is not None:
                                tele.gauge(key).set(value)  # dedlint: emits=gauge:remat.kept_bytes
                        for key, total in model.host_counters.items():
                            grown = total - host_counted.get(key, 0)
                            host_counted[key] = total
                            srec.attrs[key] = float(grown)
                            if tele is not None:
                                tele.counter(key).inc(grown)  # dedlint: emits=counter:moe.compute_copy_builds,counter:data.draws,counter:data.draws_ready
                        for name in model.step_counters:
                            srec.attrs[name] = float(sums[name])
                            if tele is not None:
                                tele.counter(name).inc(float(sums[name]))  # dedlint: emits=counter:moe.dropped_slots,counter:diffusion.masked_tokens
                        # advertise the loss for the trunk-health gate —
                        # free here, the scalar is already on the host
                        opt.report_loss(loss)
                        sps = float(opt.performance_ema.samples_per_second)
                        # THIS boundary's values, off its record
                        row = steps.train_log_row(srec)
                        with steps.phase("publish"):
                            publish_step_metrics(
                                dht, args, public_key, opt, tele, row,
                                samples=samples, loss=loss_sum,
                                mini_steps=mini_steps, sps=sps,
                                hbm_bytes=hbm_bytes_in_use(),
                            )
                        mini_steps = 0
                        with steps.phase("log"):
                            logger.info(
                                f"global step {opt.local_step}: loss "
                                f"{loss:.4f}"
                            )
                            if train_log is not None:
                                train_log.write(opt, row, loss, sps)
                            if (
                                log_perf_steps
                                and opt.local_step % log_perf_steps == 0
                            ):
                                logger.info(
                                    "perf phases:\n" + perf.report_str()
                                )
                            if (
                                t.save_steps
                                and opt.local_step - last_saved_step
                                >= t.save_steps
                            ):
                                # cadence by DISTANCE, not divisibility: a
                                # collaborative local_step can jump over
                                # exact multiples (catch-ups adopt the
                                # global counter), and a modulo check then
                                # never fires again for the rest of the run
                                model.save(state, opt.local_step)
                                last_saved_step = opt.local_step
                    # the peer is training: the set-up record ends here
                    steps.lap("first_post_step")
                    steps.close_setup(tele)

            boundary += 1
            if t.max_local_steps and boundary >= t.max_local_steps:
                logger.info(f"reached max_local_steps={boundary}; stopping")
                break
    finally:
        recorder.close()
        if train_log is not None:
            train_log.close()
        tele_close()
        opt.shutdown()
        dht.shutdown()
    return state

