"""On the chip: the two-width causal flash kernels (latent attention) against
dense causal attention at kanana-2's shape (32 heads, q/k 192 wide, v and
out 128, S=4,096: 8 x 8 tiles of 512), forward and the three gradients, in
bf16 against a float32 reference at matmul precision 'highest'; then the
kernels' wall per forward + backward, and each kernel's DEVICE time per call
with its share of the roofline — a profiler window over the same calls,
read as the benchmark reads its ``flash_mla_*_roofline`` metrics
(``benchmark/trace.py``, ``benchmark/flops_moe.mla_kernel_cost``).

    chiprun --chips 1 -- python tools/chip_mla_check.py

Prints one JSON line; exit code 1 if an error exceeds 0.02 relative L2
(bf16 rounding of the operands alone is ~0.004)."""
from __future__ import annotations

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
from benchmark.flops_moe import mla_kernel_cost
from benchmark.peaks import chip_peaks
from benchmark.trace import OPS, load_xplane, op_name
from dedloc_tpu.ops.flash_attention import flash_attention
from tools.chip_gqa_check import bwd_vmem_mb, kernel_cost

B, S, H, D, DV = 1, 4096, 32, 192, 128
KERNELS = ("flash_mla_fwd", "flash_mla_bwd_tiled")


def mla_cost(kernel: str):
    """(FLOPs, bytes) of one call of ``kernel`` at the heads' own widths;
    the one-sweep backward's from the accepted three
    (``chip_gqa_check.kernel_cost``)."""
    return kernel_cost(
        lambda part: mla_kernel_cost(
            f"flash_mla_{part}", B, H, S, D, DV, 512, 512
        ), kernel.removeprefix("flash_mla_"),
    )


def device_times(run, calls: int = 10) -> dict:
    """Per kernel: median device ms of a call over a traced window of
    ``calls`` forward + backward passes, and that time's share of the
    kernel's roofline (real FLOPs and bytes at the heads' own widths)."""
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                result = run()
            jax.block_until_ready(result)
        trace = load_xplane(trace_dir)
    # under a plain jit(grad) the trace names a kernel's op by JAX's name
    # stack around the kernel's name (``transpose_jvp_flash_mla_bwd_tiled__``;
    # inside the trainer's custom-VJP + remat it is the bare name)
    ops = [
        (op_name(name), duration / 1e9) for lines in trace.values()
        for name, _start, duration in lines.get(OPS, [])
    ]
    if not ops:
        return {}  # off the chip: no device plane to read
    peaks = chip_peaks(jax.devices()[0].device_kind)
    out = {}
    for kernel in KERNELS:
        seconds = [d for name, d in ops if kernel in name]
        if not seconds:
            continue
        least, _which = roofline_seconds(*mla_cost(kernel), peaks)
        median = statistics.median(seconds)
        out[kernel] = {
            "calls": len(seconds), "device_ms": median * 1e3,
            "roofline_pct": 100.0 * least / median,
        }
    if not out:
        print(f"no {KERNELS} among the traced ops: "
              f"{sorted({name for name, _d in ops})}", file=sys.stderr)
    return out


def dense(q, k, v):
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main() -> int:
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (
        jax.random.normal(x, (B, S, H, width), jnp.float32)
        for x, width in zip(keys, (D, D, DV, DV))
    )
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def dense_loss(q, k, v):
        out = dense(q, k, v)
        return jnp.sum(out * w), out

    flash = jax.jit(jax.value_and_grad(flash_loss, (0, 1, 2), has_aux=True))
    (_, out), grads = flash(bf(q), bf(k), bf(v))
    # the reference sees the same bf16-rounded operands, in float32
    r = lambda x: bf(x).astype(jnp.float32)  # noqa: E731
    (_, ref_out), ref_grads = jax.jit(
        jax.value_and_grad(dense_loss, (0, 1, 2), has_aux=True)
    )(r(q), r(k), r(v))
    errors = {"out": rel(out, ref_out)}
    errors.update(
        {n: rel(g, rg) for n, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads)}
    )
    jax.block_until_ready(flash(bf(q), bf(k), bf(v)))
    start = time.perf_counter()
    for _ in range(20):
        result = flash(bf(q), bf(k), bf(v))
    jax.block_until_ready(result)
    wall_ms = (time.perf_counter() - start) / 20 * 1e3
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "shape": [B, S, H, D, DV],
        "relative_l2": errors,
        "fwd_plus_bwd_wall_ms": wall_ms,
        "bwd_vmem_mb": bwd_vmem_mb(q, k, v),
        "kernels": device_times(lambda: flash(bf(q), bf(k), bf(v))),
    }))
    return 0 if max(errors.values()) <= 0.02 else 1


if __name__ == "__main__":
    sys.exit(main())
