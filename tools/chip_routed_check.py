"""On the chip: the routed experts' path ALONE (``parallel/moe.routed_experts``
forward + hand-written backward into gradient sinks) at the four expert
cells' shapes, under routings of a chosen skew, for a list of walks:
``m<a>`` = ``routed_experts(run_tiles=a)`` at the cells' tile of 256 (``m1``
is the single-size walk every tile loop ran until PR 42, ``m2`` what ships,
``m4`` bulk iterations of 1,024 rows), ``u<rows>`` = ONE tile size of that
many rows and no bulk (every group padded to it). (PR 42's ladders of more
than two levels, ``m4.2`` in PERF.md, were read with this tool before the
walk was written as its two loops; it cannot build them any more.)

    chiprun --chips 1 -- python tools/chip_routed_check.py
    chiprun --chips 1 -- python tools/chip_routed_check.py --cells sdar \
        --walks m1 m2 --ops 12

Prints one JSON line a (cell, walk): ``host_ms`` a call (forward + backward:
the median of ``--reps`` timed calls on the HOST's clock around
``block_until_ready`` — a dispatch and a wait on top of the device's time,
the same for every walk) under each routing, ``bulk_row_share`` beside it,
and with ``--ops <n>`` the n costliest ops of a profiler window over the
same calls (name + result shape, DEVICE ms a call) — where a tile's time
goes. Exit code 3 where the first device is no TPU: a time from anything
else says nothing about the walks.
What the routings are: every token draws its k experts without replacement
from a popularity that falls geometrically so that the busiest expert holds
``skew`` x the mean share (1 = balanced), the experts permuted by the seed,
so whether a busy one is among the HELD differs by seed as it does between
the cells' seeds. It times a layer, not a step: what a change is worth end to
end is the benchmark's to say."""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.trace import OPS, load_xplane
from dedloc_tpu.parallel import moe

NO_TPU = 3  # exit code
# tokens a micro-batch, hidden, expert width, router outputs, top-k, held,
# the gate's activation: the cells' configuration files
CELLS = {
    "smallthinker": (16384, 2560, 768, 64, 6, 8, "relu"),
    "sdar": (8192, 2048, 768, 128, 8, 16, "silu"),
    "lfm2": (4096, 2048, 1536, 64, 4, 8, "silu"),
    "kanana2": (4096, 2048, 768, 128, 6, 8, "silu"),
}


def routing(rng, tokens, experts, k, skew):
    """(choice [T, k] int32, weights [T, k] float32) at ``skew``."""
    if skew <= 1.0:
        popularity = np.ones(experts)
    else:  # r with max / mean = skew: E (1 - r) / (1 - r^E) = skew
        lo, hi = 0.0, 1.0
        for _ in range(60):
            r = (lo + hi) / 2
            if experts * (1 - r) / (1 - r ** experts) < skew:
                hi = r
            else:
                lo = r
        popularity = r ** np.arange(experts)
    popularity = rng.permutation(popularity / popularity.sum())
    gumbel = rng.gumbel(size=(tokens, experts))
    choice = np.argsort(-(np.log(popularity) + gumbel), axis=-1)[:, :k]
    weights = rng.random((tokens, k), dtype=np.float32) + 0.5
    return choice.astype(np.int32), weights / weights.sum(-1, keepdims=True)


def layer_step(held, tile, run_tiles, activation):
    def loss(x, weights, sinks, mats, choice, cotangent):
        y, stats = moe.routed_experts(
            x, choice, weights, *mats, (0, held), tile=tile,
            grad_sinks=sinks, activation=activation, run_tiles=run_tiles,
        )
        return jnp.sum(y * cotangent), stats

    return jax.jit(jax.grad(loss, (0, 1, 2), has_aux=True), donate_argnums=2)


def top_ops(trace_dir, calls, n):
    found = {}
    for lines in load_xplane(trace_dir).values():
        for name, _start, duration in lines.get(OPS, []):
            if " while(" not in name:  # a loop's event spans its body's
                key = " ".join(name.split(" ")[:3])[:72]
                found[key] = found.get(key, 0.0) + duration / 1e6 / calls
    return sorted(found.items(), key=lambda kv: -kv[1])[:n]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="+", default=list(CELLS), choices=CELLS)
    ap.add_argument("--walks", nargs="+",
                    default=["m1", "m2", "m4", "u512", "u1024"])
    ap.add_argument("--skews", nargs="+", type=float, default=[1.0, 2.5, 9.0])
    ap.add_argument("--seeds", nargs="+", type=int, default=[1, 2])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--ops", type=int, default=0)
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU here ({device.device_kind})", file=sys.stderr)
        return NO_TPU
    for cell in args.cells:
        tokens, hidden, width, experts, k, held, activation = CELLS[cell]
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        x = jax.random.normal(keys[0], (tokens, hidden), jnp.bfloat16)
        cotangent = jax.random.normal(keys[1], (tokens, hidden), jnp.float32)
        mats = tuple(
            (jax.random.normal(key, shape) * 0.02).astype(jnp.bfloat16)
            for key, shape in zip(keys[2:], (
                (held, hidden, width), (held, hidden, width),
                (held, width, hidden),
            ))
        )
        routings = {
            (skew, seed): jax.device_put(routing(
                np.random.default_rng(seed), tokens, experts, k, skew
            )) for skew in args.skews for seed in args.seeds
        }
        for walk in args.walks:
            bulk = walk[0] == "m"
            step = layer_step(
                held, 256 if bulk else int(walk[1:]),
                int(walk[1:]) if bulk else 1, activation,
            )
            sinks = tuple(jnp.zeros(m.shape, jnp.float32) for m in mats)
            row = {"cell": cell, "walk": walk, "device": device.device_kind,
                   "host_ms": {}, "bulk_row_share": {}}

            def call(key, sinks):
                choice, weights = routings[key]
                (_dx, _dw, sinks), stats = step(
                    x, weights, sinks, mats, choice, cotangent
                )
                jax.block_until_ready(sinks)
                return sinks, stats

            for key in routings:
                sinks, stats = call(key, sinks)  # compiles, warms
                times = []
                for _ in range(args.reps):
                    start = time.perf_counter()
                    sinks, stats = call(key, sinks)
                    times.append((time.perf_counter() - start) * 1e3)
                name = f"skew{key[0]:g}.seed{key[1]}"
                row["host_ms"][name] = round(statistics.median(times), 3)
                row["bulk_row_share"][name] = round(
                    float(stats["bulk_row_share"]), 4
                )
            row["host_ms_sum"] = round(sum(row["host_ms"].values()), 3)
            if args.ops:
                with tempfile.TemporaryDirectory() as trace_dir:
                    jax.profiler.start_trace(trace_dir)
                    for key in routings:
                        sinks, _ = call(key, sinks)
                    jax.profiler.stop_trace()
                    row["device_ops_ms_a_call"] = top_ops(
                        trace_dir, len(routings), args.ops
                    )
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
