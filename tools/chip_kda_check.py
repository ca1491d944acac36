"""On the chip: Kimi Delta Attention's kernel pair (``ops/kda.py``) ALONE
against the float32 token-by-token recurrence at the Kimi cell's shape
(1 row of 8,192 tokens, 8 heads of 128), forward and all five gradients
(q, k, v, g, beta), on operands as the mixer makes them (q and k of unit
length, q scaled by d^-1/2, g = -A softplus(.) with A ~ U(1, 16) a head and
a step of 1e-3 … 1e-1, beta a sigmoid); then each kernel's DEVICE time a
call with its share of the roofline — a profiler window over the same
calls, read as the benchmark reads ``kimi.kda_*_roofline``
(``benchmark/trace.py``, ``benchmark/flops_kimi.kda_kernel_cost``) — for a
list of ``--heads-per-step`` (the heads of one grid step).

    chiprun --chips 1 -- python tools/chip_kda_check.py

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
from benchmark.flops_kimi import kda_kernel_cost
from benchmark.peaks import chip_peaks
from benchmark.reference.kimi_linear import delta_rule
from benchmark.trace import OPS, load_xplane, op_name
from dedloc_tpu.ops import kda as kda_ops

KERNELS = ("kda_fwd", "kda_bwd")


def operands(batch, seq, heads, dim, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    shape = (batch, seq, heads, dim)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(keys[0], shape)) * dim ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.nn.silu(jax.random.normal(keys[2], shape))
    a = jax.random.uniform(keys[3], (heads, 1), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(
        keys[4], (heads, dim), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)
    ))
    g = -a * jax.nn.softplus(
        jax.random.normal(keys[5], shape) + jnp.log(jnp.expm1(dt))
    )
    beta = jax.nn.sigmoid(jax.random.normal(keys[6], shape[:3]))
    w = jax.random.normal(keys[7], shape)
    return (q, k, v, g, beta), w


def blocked_recurrence(q, k, v, g, beta):
    """The reference's token-by-token rule a block of tokens at a time under
    jax.checkpoint: its backward keeps one state a block, not one a token
    (4.3 GB)."""
    with jax.default_matmul_precision("highest"):
        return delta_rule(
            *(x.astype(jnp.float32) for x in (q, k, v, g, beta)),
            checkpoint=True,
        )


def device_times(run, shape, calls: int = 5) -> dict:
    batch, seq, heads, dim = shape
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
            *kda_kernel_cost(kernel, batch, heads, seq, dim, dim,
                             kda_ops.CHUNK), peaks
        )
        median = statistics.median(seconds)
        out[kernel] = {
            "calls": len(seconds), "device_ms": median * 1e3,
            "roofline_pct": 100.0 * least / median, "bound": which,
        }
    return out


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=8192)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=128)
    parser.add_argument("--heads-per-step", type=int, nargs="+",
                        default=[kda_ops.HEADS_PER_STEP])
    parser.add_argument("--reference", type=int, default=1)
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("no TPU here", file=sys.stderr)
        return 3
    shape = (1, args.seq, args.heads, args.head_dim)
    (q, k, v, g, beta), w = operands(*shape)
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    ins = (bf(q), bf(k), bf(v), g, beta)

    def loss_of(fn):
        def loss(*xs):
            out = fn(*xs)
            return jnp.sum(out.astype(jnp.float32) * w), out

        return jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4), has_aux=True))

    report = {"device": jax.devices()[0].device_kind, "shape": list(shape),
              "chunk": kda_ops.CHUNK, "variants": {}}
    worst = 0.0
    if args.reference:
        # the reference sees the same bf16-rounded operands, in float32
        rounded = tuple(x.astype(jnp.float32) for x in ins)
        (_, ref_out), ref_grads = loss_of(blocked_recurrence)(*rounded)
    for per in args.heads_per_step:
        kda_ops.HEADS_PER_STEP = per
        kernel = loss_of(kda_ops.kda)
        start = time.perf_counter()
        (_, out), grads = jax.block_until_ready(kernel(*ins))
        entry = {"first_call_s": time.perf_counter() - start}
        if args.reference:
            entry["relative_l2"] = {"o": rel(out, ref_out), **{
                n: rel(a, b) for n, a, b in zip(
                    ("dq", "dk", "dv", "dg", "dbeta"), grads, ref_grads
                )
            }}
            worst = max(worst, *entry["relative_l2"].values())
        start = time.perf_counter()
        for _ in range(10):
            result = kernel(*ins)
        jax.block_until_ready(result)
        entry["fwd_plus_bwd_wall_ms"] = (time.perf_counter() - start) / 10 * 1e3
        entry["kernels"] = device_times(lambda: kernel(*ins), shape)
        report["variants"][str(per)] = entry
    print(json.dumps(report))
    return 0 if worst <= 0.03 else 1


if __name__ == "__main__":
    sys.exit(main())
