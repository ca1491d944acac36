"""On the chip: Mamba-2's scan as a kernel pair (``ops/ssd.py``) ALONE
against the float32 token-by-token recurrence at the Nemotron cell's shape
(1 row of 8,192 tokens, 32 heads of 64 in 4 groups, state 128), forward and
all six gradients (x, dt, a, B, C, D), on operands as the mixer makes them
(x, B, C a SiLU of a normal draw, dt a softplus around a step log-uniform in
1e-3 … 1e-1, a = -dt · A with A = 1 … heads, D = 1); then each kernel's
DEVICE time a call with its share of the roofline — a profiler window over
the same calls, read as the benchmark reads ``nemotron.ssd_*_roofline``
(``benchmark/trace.py``, ``benchmark/flops_nemotron.ssd_kernel_cost``).

    chiprun --chips 1 -- python tools/chip_ssd_check.py

Prints one JSON line; exit code 1 if an error exceeds 0.03 relative L2 (bf16
rounding of the operands alone is ~0.004), 3 where there is no TPU."""
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

from benchmark.flops import roofline_seconds
from benchmark.flops_nemotron import ssd_kernel_cost
from benchmark.peaks import chip_peaks
from benchmark.reference.nemotron_h import selective_scan
from benchmark.trace import OPS, load_xplane, op_name
from dedloc_tpu.ops import ssd as ssd_ops

KERNELS = ("ssd_fwd", "ssd_bwd")
NAMES = ("dx", "ddt", "da", "dB", "dC", "dD")


def operands(batch, seq, heads, dim, groups, state, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.nn.silu(jax.random.normal(keys[0], (batch, seq, heads, dim)))
    b, c = (
        jax.nn.silu(jax.random.normal(key, (batch, seq, groups, state)))
        for key in keys[1:3]
    )
    step = jnp.exp(jax.random.uniform(
        keys[3], (heads,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)
    ))
    dt = jax.nn.softplus(
        jax.random.normal(keys[4], (batch, seq, heads))
        + jnp.log(jnp.expm1(step))
    )
    a = -dt * jnp.arange(1, heads + 1, dtype=jnp.float32)
    w = jax.random.normal(keys[5], (batch, seq, heads, dim))
    return (x, dt, a, b, c, jnp.ones((heads,), jnp.float32)), w


def blocked_recurrence(x, dt, a, b, c, d):
    """The reference's token-by-token rule a block of tokens at a time under
    jax.checkpoint: its backward keeps one state a block, not one a token
    (8.6 GB)."""
    with jax.default_matmul_precision("highest"):
        return selective_scan(
            *(v.astype(jnp.float32) for v in (x, dt, a, b, c, d)),
            checkpoint=True,
        )


def device_times(run, shape, chunk, calls: int = 5) -> dict:
    batch, seq, heads, dim, groups, state = shape
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                result = run()
            jax.block_until_ready(result)
        trace = load_xplane(trace_dir)
    ops = [
        (op_name(name), duration / 1e9) for lines in trace.values()
        for name, _start, duration in lines.get(OPS, [])
    ]
    if not ops:
        return {}
    peaks = chip_peaks(jax.devices()[0].device_kind)
    out = {}
    for kernel in KERNELS:
        seconds = [d for name, d in ops if kernel in name]
        if not seconds:
            continue
        least, which = roofline_seconds(
            *ssd_kernel_cost(kernel, batch, heads, groups, seq, dim, state,
                             chunk), peaks
        )
        median = statistics.median(seconds)
        out[kernel] = {
            "calls": len(seconds), "device_ms": median * 1e3,
            "roofline_pct": 100.0 * least / median, "bound": which,
        }
    # what XLA runs around the kernels (the cumulative sums, the layouts)
    other = [d for name, d in ops if not any(k in name for k in KERNELS)]
    out["other_ops_ms_a_call"] = sum(other) / calls * 1e3
    return out


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--groups", type=int, default=4)
    parser.add_argument("--state", type=int, default=128)
    parser.add_argument("--chunk", type=int, nargs="+",
                        default=[ssd_ops.CHUNK])
    parser.add_argument("--reference", type=int, default=1)
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("no TPU here", file=sys.stderr)
        return 3
    shape = (1, args.seq, args.heads, args.head_dim, args.groups, args.state)
    (x, dt, a, b, c, d), w = operands(*shape)
    bf = lambda v: v.astype(jnp.bfloat16)  # noqa: E731
    ins = (bf(x), dt, a, bf(b), bf(c), d)

    def loss_of(fn):
        def loss(*xs):
            out = fn(*xs)
            return jnp.sum(out.astype(jnp.float32) * w), out

        return jax.jit(jax.value_and_grad(loss, tuple(range(6)), has_aux=True))

    report = {"device": jax.devices()[0].device_kind, "shape": list(shape),
              "variants": {}}
    worst = 0.0
    if args.reference:
        # the reference sees the same bf16-rounded operands, in float32
        rounded = tuple(v.astype(jnp.float32) for v in ins)
        (_, ref_out), ref_grads = loss_of(blocked_recurrence)(*rounded)
    for chunk in args.chunk:
        kernel = loss_of(lambda *xs: ssd_ops.ssd(*xs, chunk=chunk))
        start = time.perf_counter()
        (_, out), grads = jax.block_until_ready(kernel(*ins))
        entry = {"first_call_s": time.perf_counter() - start}
        if args.reference:
            entry["relative_l2"] = {"y": rel(out, ref_out), **{
                n: rel(g, r) for n, g, r in zip(NAMES, grads, ref_grads)
            }}
            worst = max(worst, *entry["relative_l2"].values())
        start = time.perf_counter()
        for _ in range(10):
            result = kernel(*ins)
        jax.block_until_ready(result)
        entry["fwd_plus_bwd_wall_ms"] = (time.perf_counter() - start) / 10 * 1e3
        entry["kernels"] = device_times(lambda: kernel(*ins), shape, chunk)
        report["variants"][str(chunk)] = entry
    print(json.dumps(report))
    return 0 if worst <= 0.03 else 1


if __name__ == "__main__":
    sys.exit(main())
