"""chip_smoke.py — the quickest proof that the trainer still starts on the chip.

Run with no arguments from the root of a plain copy of the tree (no git, no
network, package not installed) on a machine with a TPU:

    python chip_smoke.py

It drives the repo's main path once — ``roles/trainer.py:run_trainer`` →
``CollaborativeOptimizer`` → DHT/averager — as a solo peer on a loopback DHT
with synthetic batches: ALBERT-large at full width and depth (hidden 1024,
24 scanned layers, 16x64 heads, vocab 30,000, S=512, bf16, random weights
from seed 0), the flagship recipe (flash attention + the fused_ln remat
policy), per-chip micro-batch 12, every boundary a networked global step
(device-flat pipeline, D2H, loopback averaging round, H2D, optimizer apply).
One chip runs a single-device peer; four or more run the same command as one
dp4 slice peer (``--training.mesh_devices 4``).

It exits 0, with ``{"ok": true, "device": {...}}`` as the last line of
stdout, only if every check below held. It exits non-zero and prints no
verdict when JAX finds no TPU, or when run outside a dedloc_tpu checkout.
Everything runs in THIS process — a chip belongs to one process at a time —
and nothing it starts outlives it.

``--cpu-rehearsal`` runs the same drive at the tiny size on the CPU to debug
the script itself; it prints ``platform: cpu`` and never the verdict.
"""
from __future__ import annotations

import argparse
import glob
import json
import logging
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# jitted programs by what their compilation proves about the boundary path
# (names are the functions' own: parallel/train_step.py, device_flat.py,
# collaborative/optimizer.py)
BOUNDARY_PROGRAMS = {
    "grad_flat_prepare": "device-flat gradient pipeline",
    "flat_apply_step": "fused flat optimizer apply",
    "guarded_apply_step": "per-leaf guarded optimizer apply",
    "_fused_mean_clip": "solo on-device mean (no wire)",
}


class _Capture(logging.Handler):
    """Every dedloc_tpu log record of the run, for the checks."""

    def __init__(self) -> None:
        super().__init__(level=logging.INFO)
        self.records = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append((record.levelno, record.name, record.getMessage()))


def _cache_entries(cache_dir: str) -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="tiny model on the CPU, to debug this script; prints no verdict",
    )
    opts = parser.parse_args(argv)
    rehearsal = opts.cpu_rehearsal

    sys.path.insert(0, ROOT)  # the package is not pip-installed
    import jax
    import jaxlib

    try:
        from dedloc_tpu import native
        from dedloc_tpu.core.config import CollaborationArguments, parse_config
        from dedloc_tpu.parallel.mesh import make_mesh, put_batch
        from dedloc_tpu.parallel.train_step import zeros_like_grads
        from dedloc_tpu.roles.common import (
            build_model,
            drop_collator_keys,
            synthetic_mlm_batches,
        )
        from dedloc_tpu.roles.trainer import run_trainer
        from dedloc_tpu.utils.backend import (
            describe_backend,
            ensure_compile_cache,
            pin_cpu,
        )
    except ImportError as e:
        print(f"chip_smoke: not at the root of a dedloc_tpu checkout: {e}")
        return 3

    if rehearsal:
        pin_cpu()
    try:
        backend = describe_backend()
    except RuntimeError as e:  # jax found no usable backend at all
        print(f"chip_smoke: JAX could not start a backend: {e}")
        return 2
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    print(f"platform: {backend['platform']}")
    print(f"device_kind: {backend['device_kind']}")
    print(f"device_count: {backend['device_count']}")
    print(f"kernel_mode: {backend['kernel_mode']}")
    print(
        f"versions: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu_version}"
    )
    on_tpu = backend["platform"] == "tpu"
    if not on_tpu and not rehearsal:
        print(
            f"chip_smoke: FAIL — no accelerator: JAX landed on "
            f"{backend['platform']!r}. This script proves the trainer on a "
            "TPU; --cpu-rehearsal debugs the script itself."
        )
        return 2

    cache_dir = ensure_compile_cache()
    entries_before = _cache_entries(cache_dir)
    print(f"compile_cache: {cache_dir} ({entries_before} entries before)")

    # ---- instrumentation: compile events, lowered IR, the run's own log
    compile_events = []  # (event, fun_name, seconds)
    cache_events = {"hits": 0, "misses": 0}

    def _on_duration(event, duration, **kw):
        if event.startswith("/jax/core/compile/"):
            compile_events.append(
                (event.rsplit("/", 1)[1], kw.get("fun_name", ""), duration)
            )

    def _on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    capture = _Capture()
    logging.getLogger("dedloc_tpu").addHandler(capture)

    n_mesh = 4 if backend["device_count"] >= 4 else 1
    size, seq, micro = ("tiny", 64, 4) if rehearsal else ("large", 512, 12)
    # a boundary reports its samples; the NEXT one sees the target met and
    # steps — two boundaries per global step
    accum, boundaries = 2, 8
    failures = []

    def check(ok: bool, what: str) -> None:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}")
        if not ok:
            failures.append(what)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        # the trainer's own lowered programs, as StableHLO text
        jax.config.update("jax_dump_ir_to", os.path.join(tmp, "ir"))
        train_log = os.path.join(tmp, "train_log.jsonl")
        event_log = os.path.join(tmp, "events.jsonl")
        trainer_argv = [
            "--dht.experiment_prefix", "chip_smoke",
            "--dht.listen_host", "127.0.0.1",
            "--training.model_size", size,
            "--training.seq_length", str(seq),
            "--training.per_device_batch_size", str(micro),
            "--training.gradient_accumulation_steps", str(accum),
            "--training.attention_impl", "flash",
            "--training.remat_policy", "fused_ln",
            "--training.mesh_devices", str(n_mesh),
            # one boundary's samples meet the target
            "--optimizer.target_batch_size", str(micro * n_mesh * accum),
            "--training.max_local_steps", str(boundaries),
            "--training.learning_rate", "0.0015",
            "--training.warmup_steps", "2",
            "--training.total_steps", "100",
            "--training.save_steps", "0",
            "--training.seed", "0",
            "--training.output_dir", os.path.join(tmp, "out"),
            "--training.train_log_path", train_log,
            # a short straggler window, and no alone-grace inside the run:
            # each boundary takes the full networked path (flatten, D2H,
            # matchmaking + a singleton round on loopback, H2D, apply), not
            # the on-device shortcut a long-lived solo peer switches to
            "--averager.averaging_expiration", "2",
            "--averager.metadata_expiration", "3600",
            # the step-phase flight recorder, on the same run
            "--telemetry.enabled", "true",
            "--telemetry.event_log_path", event_log,
        ]
        print("trainer: python -m dedloc_tpu.roles.trainer "
              + " ".join(trainer_argv))
        t0 = time.perf_counter()
        state = run_trainer(parse_config(CollaborationArguments, trainer_argv))
        jax.block_until_ready(state.params)
        wall = time.perf_counter() - t0
        # before this script puts anything of its own on a device
        memory = [d.memory_stats() or {} for d in jax.devices()[:n_mesh]]

        accumulate_ir = "".join(
            open(p).read()
            for p in glob.glob(os.path.join(tmp, "ir", "*accumulate_step*"))
        )
        steps_logged = [json.loads(l) for l in open(train_log)]
        phase_s = {}
        if os.path.exists(event_log):
            for line in open(event_log):
                ev = json.loads(line)
                if ev.get("event") == "step.record":
                    for name, sec in (ev.get("phases") or {}).items():
                        phase_s.setdefault(name, []).append(sec)

    # ---- report
    by_fun = {}
    for kind, fun, sec in compile_events:
        by_fun.setdefault(fun, {}).setdefault(kind, 0.0)
        by_fun[fun][kind] += sec
    backend_compile_s = sum(
        s for k, _, s in compile_events if k == "backend_compile_duration"
    )
    trace_lower_s = sum(
        s for k, _, s in compile_events if k != "backend_compile_duration"
    )
    entries_after = _cache_entries(cache_dir)
    print(f"trainer wall: {wall:.1f} s for {boundaries} boundaries")
    print(
        f"compile seconds: {backend_compile_s:.1f} backend "
        f"(cache hits {cache_events['hits']}, misses "
        f"{cache_events['misses']}) + {trace_lower_s:.1f} trace/lower"
    )
    for fun, kinds in sorted(
        by_fun.items(),
        key=lambda kv: -kv[1].get("backend_compile_duration", 0.0),
    )[:6]:
        print(
            f"  {fun}: "
            f"{kinds.get('backend_compile_duration', 0.0):.2f} s backend"
        )
    print(f"compile_cache: {entries_after} entries after "
          f"({entries_after - entries_before:+d})")
    compiled = {fun.removeprefix("jit(").removesuffix(")") for fun in by_fun}
    ran = [name for name in BOUNDARY_PROGRAMS if name in compiled]
    print("boundary path: "
          + ("; ".join(BOUNDARY_PROGRAMS[n] for n in ran) or "none seen"))
    for name, secs in phase_s.items():
        print(f"  phase {name}: mean {sum(secs) / len(secs) * 1e3:.1f} ms "
              f"over {len(secs)} boundaries")
    for rec in steps_logged:
        print(f"  global step {rec['step']}: loss {rec['loss']:.4f} "
              f"seam_ms {rec['seam_ms']}")

    # ---- checks
    import numpy as np

    print("checks:")
    if not rehearsal:
        check(on_tpu, "jax.devices()[0].platform == 'tpu'")
        n_calls = accumulate_ir.count("tpu_custom_call")
        check(
            n_calls > 0,
            f"accumulate step's lowered HLO holds tpu_custom_call "
            f"({n_calls} Mosaic kernels: compiled, not interpreted)",
        )
    else:
        check(bool(accumulate_ir), "accumulate step's lowered IR was dumped")
    check(native.AVAILABLE, "dedloc_tpu.native.AVAILABLE (wire codec built)")
    losses = [rec["loss"] for rec in steps_logged]
    check(
        len(losses) >= 3 and all(np.isfinite(losses)),
        f"finite loss at each of >= 3 global steps ({len(losses)} logged)",
    )
    check(int(state.step) >= 3, f"state.step advanced to {int(state.step)}")
    cfg, model = build_model(size, "fused_ln", "flash")
    fresh = jax.jit(
        lambda r: model.init(
            r, jax.numpy.zeros((micro, seq), jax.numpy.int32)
        )["params"]
    )(jax.random.PRNGKey(0))
    trained, fresh = jax.device_get((state.params, fresh))
    still = [
        jax.tree_util.keystr(path)
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(trained),
            jax.tree.leaves(fresh),
        )
        if not np.any(a != b)
    ]
    n_leaves = len(jax.tree.leaves(trained))
    finite = all(
        bool(np.all(np.isfinite(a))) for a in jax.tree.leaves(trained)
    )
    # a leaf may rightly stand still (the key bias: softmax is invariant to
    # it, so its gradient is exactly zero and it takes no weight decay)
    check(
        len(still) <= n_leaves // 10 and finite,
        f"params differ from the seed-0 init and are finite "
        f"({n_leaves - len(still)}/{n_leaves} leaves moved; still: {still})",
    )
    warnings_logged = [r for r in capture.records if r[0] >= logging.WARNING]
    for _level, name, msg in warnings_logged:
        print(f"    warning from {name}: {msg}")
    # a healthy solo run on loopback has nothing to warn about: the NaN
    # rollback, the refused device-flat pipeline, the failed flat apply and
    # the failed native build all announce themselves at WARNING
    check(not warnings_logged, "no warning logged (no rollback, no fallback)")
    check(
        "grad_flat_prepare" in ran,
        "boundaries crossed through the device-flat pipeline",
    )
    if n_mesh == 1:
        check("flat_apply_step" in ran, "fused flat optimizer apply ran")
    else:
        # the flat apply is single-device by design; a slice applies per leaf
        mesh = make_mesh(n_mesh)
        n_devs = lambda tree: {
            len(x.sharding.device_set) for x in jax.tree.leaves(tree)
        }
        check(
            n_devs((state.params, state.opt_state)) == {n_mesh},
            f"every param/optimizer leaf lives on all {n_mesh} devices",
        )
        check(
            n_devs(zeros_like_grads(state.params)) == {n_mesh},
            f"every grad-accumulator leaf lives on all {n_mesh} devices",
        )
        batch = put_batch(
            drop_collator_keys(
                next(synthetic_mlm_batches(cfg, micro * n_mesh, seq, 0))
            ),
            mesh,
        )
        rows = {
            s.data.shape[0]
            for x in jax.tree.leaves(batch) for s in x.addressable_shards
        }
        check(rows == {micro}, f"batch shard is {micro} rows per device")
        for key in ("bytes_in_use", "peak_bytes_in_use"):
            vals = [m.get(key) for m in memory]
            print(f"    {key} per device: {vals}")
            if on_tpu:
                check(
                    all(vals) and max(vals) <= 1.1 * min(vals),
                    f"{key} within 10% across the {n_mesh} devices",
                )

    if failures:
        print(f"chip_smoke: FAIL — {len(failures)} check(s): {failures}")
        return 1
    if rehearsal:
        print("chip_smoke: CPU rehearsal passed — this is not a chip result")
        return 0
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": backend["platform"],
            "kind": backend["device_kind"],
            "count": backend["device_count"],
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
