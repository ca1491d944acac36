"""The benchmark: one cell, one run, one JSON line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell runs the role a volunteer runs (``roles/trainer.run_trainer`` or
``roles/swav.run_swav``) in THIS process, every peer of the cell a thread,
bootstrapped from a root DHT built here. The yardstick sits outside the
program (``instrument.py``): the batch source and ``CollaborativeOptimizer.
step`` are wrapped, the log and JAX's compile events are listened to, and
with ``--trace 1`` telemetry is switched on and a ``jax.profiler`` window of
a few global steps is taken. Everything that belongs to one cell, one
configuration, one role or one metric is a file of its own, found by name:

    workloads/<cell>.json    configs/<config>.json    roles/<role>.py
    metrics/<metric>.json -> reducers/<reducer>.py

The last line of stdout is the result. Without a TPU (or with fewer chips
than the cell asks for) the exit code is 2 and there is no result line.
``--rehearse`` runs the tiny presets on the CPU to debug control flow; it
prints its counts under smoke names and never a device metric.
"""
from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse
import glob
import importlib
import json
import logging
import math
import os
import shutil
import statistics
import sys
import tempfile
import threading

# a traced run profiles this many global steps; a run whose window has not
# opened, or not closed, by these limits is stopped without a result
TRACE_STEPS = 2
SETUP_LIMIT_S = 1100.0
CLOSE_GRACE_S = 60.0

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_metrics(cell, kind: str):
    """Every metric file of ``kind`` that lists the cell (no ``workloads``
    key = every cell) or that the cell's own file lists under ``metrics``."""
    found = []
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as f:
            metric = json.load(f)
        if metric["kind"] != kind:
            continue
        cells = metric.get("workloads")
        if (
            cells is None or cell["name"] in cells
            or metric["name"] in cell.get("metrics", [])
        ):
            found.append(metric)
    return found


def say(message: str) -> None:
    print(f"[bench {time.perf_counter() - _PROCESS_START:7.1f}s] {message}",
          flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=45.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    opts = parser.parse_args(argv)

    cell = _load("workloads", opts.workload)
    config = _load("configs", cell["config"])
    chips = int(cell["chips"])
    n_peers = int(cell.get("peers", 1))
    if opts.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(chips, 1)}"
        )
    try:
        import jax

        from dedloc_tpu.utils.backend import ensure_compile_cache
    except ImportError as e:
        print(f"benchmark: not at the root of a dedloc_tpu checkout: {e}",
              file=sys.stderr)
        return 3
    role = importlib.import_module(f"benchmark.roles.{config['role']}")

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"benchmark: JAX could not start a backend: {e}", file=sys.stderr)
        return 2
    platform = devices[0].platform
    if not opts.rehearse and (platform != "tpu" or len(devices) < chips):
        print(
            f"benchmark: cell {cell['name']} needs {chips} TPU chip(s); JAX "
            f"found {len(devices)} {platform} device(s). Nothing falls back.",
            file=sys.stderr,
        )
        return 2
    device_kind = devices[0].device_kind
    if not opts.rehearse:
        from benchmark import peaks

        peaks.chip_peaks(device_kind)  # an unknown chip is an error, early

    # the program's own cache directory (<checkout>/.jax_cache, or
    # JAX_COMPILATION_CACHE_DIR), holding EVERY program: the roles run
    # hundreds of sub-second compiles (SwAV's eager init) that JAX's default
    # thresholds would leave out and every later run would pay again
    cache_dir = ensure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(f"cell {cell['name']} seed {opts.seed} seconds {opts.seconds} trace "
        f"{opts.trace} | {platform} {device_kind} x{len(devices)} | "
        f"compile cache {cache_dir}")

    from benchmark import instrument
    from benchmark.rundata import RunData

    recorder = instrument.Recorder(
        n_peers, int(cell.get("warmup_steps", 2)), opts.seconds
    )
    uninstall = [
        instrument.install(recorder),
        role.install_source(recorder, opts.seed),
    ]
    workdir = tempfile.mkdtemp(prefix="dedloc_bench_")
    trace_dir = os.path.join(workdir, "trace")
    root_dht = None
    threads = []
    try:
        def peer_argv(peer: int, initial_peers: str):
            return role.build_argv(
                config, cell, peer, opts.seed, workdir, initial_peers,
                bool(opts.trace), opts.rehearse,
            )

        args0 = role.parse(peer_argv(0, ""))

        # ---- set-up, part 1: the role against its plain reference
        check = role.reference_check(config, args0, rehearse=opts.rehearse)
        say("reference check: " + json.dumps(check, sort_keys=True))
        scratch = role.accumulate_scratch_bytes(args0)
        say(f"accumulate program scratch (compiler's memory analysis, one "
            f"device's micro-batch): {scratch} bytes")

        # ---- set-up, part 2: the peers, as threads of this process
        from dedloc_tpu.roles.common import build_dht

        root_dht, _ = build_dht(args0)
        address = root_dht.get_visible_address()

        def peer_main(index: int) -> None:
            recorder.bind(index)
            record = recorder.peers[index]
            try:
                role.run(role.parse(peer_argv(index, address)))
            except role.STOP:
                pass
            except Exception as e:  # noqa: BLE001 — the boundary: reported below
                record.error = e
                recorder.abort = True
                logging.getLogger("dedloc_tpu.benchmark").exception(
                    f"peer {index} died"
                )
            finally:
                record.finished = True

        for index in range(n_peers):
            thread = threading.Thread(
                target=peer_main, args=(index,), daemon=True,
                name=f"bench-peer{index}",
            )
            thread.start()
            threads.append(thread)

        # ---- the window
        trace_state = {"started_at": None, "stopped": False, "from_step": None}
        while not all(p.finished for p in recorder.peers):
            time.sleep(0.02)
            now = time.perf_counter()
            if recorder.deadline is None:
                if now - _PROCESS_START > SETUP_LIMIT_S:
                    say("set-up limit reached before the window opened")
                    recorder.abort = True
                continue
            if now > recorder.deadline + CLOSE_GRACE_S and not recorder.abort:
                say("the window did not close in time; stopping the peers")
                recorder.abort = True
            if opts.trace and not trace_state["stopped"]:
                done = sum(1 for c in recorder.peers[0].opt_calls if c[2])
                if trace_state["from_step"] is None:
                    trace_state["from_step"] = done + 1
                elif (trace_state["started_at"] is None
                      and done >= trace_state["from_step"]):
                    _start_trace(jax, trace_dir)
                    trace_state["started_at"] = done
                elif (trace_state["started_at"] is not None
                      and done >= trace_state["started_at"] + TRACE_STEPS):
                    jax.profiler.stop_trace()
                    trace_state["stopped"] = True
        for thread in threads:
            thread.join(timeout=60)
        if trace_state["started_at"] is not None and not trace_state["stopped"]:
            jax.profiler.stop_trace()
            trace_state["stopped"] = True

        # ---- after the window
        run = RunData(
            recorder=recorder, cell=cell, config=config, role=role,
            args=args0, chips=chips, device_kind=device_kind,
            process_start=_PROCESS_START,
            memory=_memory(devices[:max(chips, 1)], scratch),
        )
        verdict = _verdict(run, check, opts)
        if opts.trace:
            from benchmark import trace as T

            run.trace = T.load_xplane(trace_dir)
            run.step_records = _step_records(workdir, recorder)
    finally:
        for undo in uninstall:
            undo()
        if root_dht is not None:
            root_dht.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)

    for line in verdict["evidence"]:
        say(line)
    if recorder.window() is None:
        say("no complete window: no result")
        return 1

    metrics = {}
    kind = "per_layer" if opts.trace else "end_to_end"
    for metric in load_metrics(cell, kind):
        reducer = importlib.import_module(
            f"benchmark.reducers.{metric['reducer']}"
        )
        value = reducer.reduce(run, metric.get("params", {}))
        if value is None or not math.isfinite(value):
            continue
        name = metric["name"]
        unit = metric["unit"]
        if opts.rehearse:
            name, unit = f"smoke.{name}", "cpu_count"
        metrics[name] = {"value": value, "unit": unit}

    result = {
        "correct": verdict["correct"],
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": {
            "platform": platform, "kind": device_kind, "count": len(devices),
            "memory_peak_bytes": run.memory["memory_peak_bytes"],
        },
        "window": verdict["window"],
    }
    if opts.trace and run.trace:
        from benchmark import trace as T

        busy = T.device_busy(run.trace)
        if busy:
            result["device"]["busy_s"] = sum(b for b, _w in busy.values()) / len(busy)
            result["device"]["window_s"] = max(w for _b, w in busy.values())
        result["breakdown"] = {
            "device_ops": T.top_ops(run.trace, 10),
            "idle_gaps": T.idle_gaps(run.trace, run.program("accumulate"), 10),
        }
    if opts.rehearse:
        say("CPU rehearsal: counts under smoke names, not a chip result")
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


def _start_trace(jax, trace_dir: str) -> None:
    """Device trace with the host tracers turned down: the Python tracer
    would slow the very threads whose gaps the trace is to show."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def _memory(devices, scratch_bytes: int):
    """Peak on the fullest chip. The TPU allocator's ``peak_bytes_in_use``
    counts live buffers only — a running program's scratch (the activations
    of a micro-batch) is not in it — so the peak is the larger of the
    allocator's own peak and the buffers alive at the end of the window plus
    the accumulate program's scratch (the compiler's memory analysis)."""
    stats = [d.memory_stats() or {} for d in devices]
    allocator_peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    in_use = max((s.get("bytes_in_use", 0) for s in stats), default=0)
    return {
        "allocator_peak_bytes": int(allocator_peak),
        "in_use_after_window_bytes": int(in_use),
        "accumulate_scratch_bytes": int(scratch_bytes),
        "bytes_limit": int(max((s.get("bytes_limit", 0) for s in stats), default=0)),
        "memory_peak_bytes": int(max(allocator_peak, in_use + scratch_bytes)),
    }


def _step_records(workdir: str, recorder):
    """The flight recorder's ``step.record`` events inside the window (the
    event log's clock is wall time; the window's wall start was noted when
    it opened)."""
    start, end = recorder.window()
    start_wall = recorder.window_open_wall + (start - recorder.window_open_t)
    end_wall = start_wall + (end - start)
    records = []
    for path in glob.glob(os.path.join(workdir, "events_peer*.jsonl")):
        with open(path) as f:
            for line in f:
                try:
                    event = json.loads(line)
                except ValueError:
                    continue
                if (event.get("event") == "step.record"
                        and start_wall < event.get("t", 0.0) <= end_wall + 0.5):
                    records.append(event)
    return records


def _verdict(run, check, opts):
    """attempted / failed / correct, and the evidence lines that show the
    path the cell names is the path that ran."""
    import numpy as np

    recorder, cell = run.recorder, run.cell
    evidence = []
    window = recorder.window()
    n_peers = recorder.n_peers
    attempted = failed = 0
    compiles_in_window = []
    warnings_in_window = []
    losses_ok = steps_ok = True
    if window is not None:
        start, end = window
        for peer in recorder.peers:
            p_start, p_end = recorder.peer_window(peer)
            stepped = [
                after for _t0, t1, did, after in peer.opt_calls
                if did and p_start < t1 <= p_end
            ]
            attempted += len(stepped)
            failed += sum(
                1 for after in stepped if peer.groups.get(after, 0) != n_peers
            )
            losses = [v for t, v in peer.losses if p_start < t <= p_end + 1.0]
            losses_ok &= bool(losses) and bool(np.isfinite(losses).all())
            total_steps = sum(1 for c in peer.opt_calls if c[2])
            final_step = int(peer.last_state.step)
            advanced = final_step - (peer.first_state_step or 0)
            steps_ok &= advanced == total_steps
            evidence.append(
                f"peer {peer.index}: {len(stepped)} global steps in the "
                f"window (local_step {recorder.start_step} -> "
                f"{recorder.final_step}), groups "
                f"{sorted(set(peer.groups.get(a, 0) for a in stepped))}, "
                f"state.step advanced {advanced} over {total_steps} steps, "
                f"losses {losses[0]:.4f} .. {losses[-1]:.4f}"
                if losses else f"peer {peer.index}: no loss reported"
            )
        warnings_in_window = [
            (name, message) for t, level, _peer, name, message in recorder.log
            if level >= logging.WARNING and start < t <= end
        ]
        # a warning inside the window is a step that did not go as intended:
        # failed rounds, local-apply fallbacks, rollbacks and dropped
        # gradients all announce themselves at WARNING
        failed += len(warnings_in_window)
        attempted = max(attempted, failed)
        compiles_in_window = [
            (kind, fun) for t, kind, fun, _s in recorder.compiles
            if start < t <= end and kind == "backend_compile_duration"
        ]
    if window is not None:
        from benchmark.reducers import throughput

        rates = throughput.step_rates(run)
        longest = {
            "boundary": max(run.opt_calls_in_window(True), default=0.0),
            "report": max(run.opt_calls_in_window(False), default=0.0),
            "next(batches)": max(
                (seconds for seconds, _n in run.draws_in_window()), default=0.0
            ),
        }
        if rates:
            evidence.append(
                f"samples/s/chip over the whole window "
                f"{throughput.whole_window_rate(run):.2f}; per global step: "
                f"least {min(rates):.2f} median "
                f"{statistics.median(rates):.2f} greatest {max(rates):.2f} "
                f"over {len(rates)} steps; longest calls in "
                "the window (ms): " + ", ".join(
                    f"{k} {v * 1e3:.1f}" for k, v in sorted(longest.items())
                )
            )
    for name, message in warnings_in_window[:10]:
        evidence.append(f"warning in the window from {name}: {message}")

    compiled = {
        fun.removeprefix("jit(").removesuffix(")")
        for _t, _kind, fun, _s in recorder.compiles
    }
    path = cell.get("path", {})
    required = [run.program(p) for p in path.get("required", [])]
    forbidden = [run.program(p) for p in path.get("forbidden", [])]
    path_ok = (
        all(p in compiled for p in required)
        and not any(p in compiled for p in forbidden)
    )
    boundary_programs = sorted(
        p for p in compiled if p in set(run.role.PROGRAMS.values())
    )
    evidence.append(
        f"programs compiled or loaded in this process: {boundary_programs}; "
        f"required {required}, forbidden {forbidden}: "
        f"{'ok' if path_ok else 'WRONG PATH'}"
    )
    backend_s = sum(
        s for _t, kind, _f, s in recorder.compiles
        if kind == "backend_compile_duration"
    )
    evidence.append(
        f"compile: {backend_s:.1f} s backend over "
        f"{sum(1 for c in recorder.compiles if c[1] == 'backend_compile_duration')} "
        f"programs, cache hits {recorder.cache['hits']} misses "
        f"{recorder.cache['misses']}; compilations inside the window: "
        f"{len(compiles_in_window)} {compiles_in_window[:5]}"
    )

    peers_agree = True
    if n_peers > 1 and window is not None:
        import jax

        final = [int(p.last_state.step) for p in recorder.peers]
        locals_ = [p.last_local_step for p in recorder.peers]
        peers_agree = max(locals_) - min(locals_) <= 1
        if len(set(locals_)) == 1:
            trees = [
                jax.tree.leaves(jax.device_get(p.last_state.params))
                for p in recorder.peers
            ]
            ref_norm = math.sqrt(sum(float(np.sum(np.square(x, dtype=np.float64))) for x in trees[0]))
            worst = 0.0
            for other in trees[1:]:
                diff = math.sqrt(sum(
                    float(np.sum(np.square(a.astype(np.float64) - b)))
                    for a, b in zip(other, trees[0])
                ))
                worst = max(worst, diff / max(ref_norm, 1e-30))
            tolerance = float(cell["peers_agree_rel_l2"])
            peers_agree &= worst <= tolerance
            evidence.append(
                f"peers at local steps {locals_} (state.step {final}): "
                f"parameters differ by {worst:.3e} relative L2 "
                f"(tolerance {tolerance:g})"
            )
        else:
            peers_agree = False
            evidence.append(
                f"peers ended at different local steps {locals_}: no common "
                "step to compare parameters at"
            )
    errors = [repr(p.error) for p in recorder.peers if p.error is not None]
    for error in errors:
        evidence.append(f"peer error: {error}")
    evidence.append("memory: " + json.dumps(run.memory, sort_keys=True))

    correct = bool(
        window is not None and check["ok"] and losses_ok and steps_ok
        and path_ok and peers_agree and not compiles_in_window
        and not errors and attempted > 0
    )
    evidence.append(
        f"correct={correct}: reference {check['ok']}, finite losses "
        f"{losses_ok}, state.step advanced by the steps counted {steps_ok}, "
        f"path {path_ok}, peers agree {peers_agree}, no compilation in the "
        f"window {not compiles_in_window}, no peer error {not errors}"
    )
    window_info = None
    if window is not None:
        window_info = {
            "seconds": window[1] - window[0],
            "global_steps": (recorder.final_step - recorder.start_step),
            "stepping_boundaries": attempted,
        }
    return {
        "correct": correct, "attempted": attempted, "failed": failed,
        "evidence": evidence, "window": window_info,
    }


if __name__ == "__main__":
    sys.exit(main())
