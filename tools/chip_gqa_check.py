"""On the chip: the grouped-query causal flash kernels and the short
convolution kernels, each alone, at LFM2-24B-A2B's shapes — 32 query heads
over 8 kv heads of 64 at S=4,096 (8 x 8 tiles of 512) against dense causal
attention with k / v repeated per group; (1, 4096, 3 x 2048) against the
shifted-sum convolution — forward and every gradient, in bf16 against a
float32 reference at matmul precision 'highest'; then each kernel's DEVICE
time per call with its share of the roofline, from a profiler window over
the same calls, read as the benchmark reads its ``flash_gqa_*_roofline`` and
``short_conv_*_roofline`` metrics (``benchmark/trace.py``,
``benchmark/flops_lfm2.py``).

    chiprun --chips 1 -- python tools/chip_gqa_check.py

Prints one JSON line; exit code 1 if an error exceeds 0.02 relative L2
(bf16 rounding of the operands alone is ~0.004)."""
from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark.flops import roofline_seconds
from benchmark.flops_lfm2 import conv_kernel_cost, gqa_kernel_cost
from benchmark.peaks import chip_peaks
from benchmark.reducers.conv_kernel_roofline import on_chip_tensors
from benchmark.trace import OPS, load_xplane, op_name
from dedloc_tpu.ops.flash_attention import flash_attention
from dedloc_tpu.ops.short_conv import short_conv, short_conv_reference

B, S, H, KV, D, HIDDEN = 1, 4096, 32, 8, 64, 2048
GQA = ("flash_gqa_fwd", "flash_gqa_bwd_dq", "flash_gqa_bwd_dkv")
CONV = ("short_conv_fwd", "short_conv_bwd")


def cost(kernel: str, on_chip=frozenset()):
    """(FLOPs, bytes) of one call at the shapes checked here."""
    if kernel in GQA:
        return gqa_kernel_cost(kernel, B, H, KV, S, D, 512, 512)
    return conv_kernel_cost(kernel, B, S, HIDDEN, on_chip=on_chip)


def device_times(run, calls: int = 10) -> dict:
    """Per kernel: median device ms of a call over a traced window of
    ``calls`` forward + backward passes, and its share of the roofline."""
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                result = run()
            jax.block_until_ready(result)
        trace = load_xplane(trace_dir)
    # under a plain jit(grad) the trace names a kernel's op by JAX's name
    # stack around the kernel's name
    ops = [
        (op_name(name), duration / 1e9, name) for lines in trace.values()
        for name, _start, duration in lines.get(OPS, [])
    ]
    if not ops:
        return {}  # off the chip: no device plane to read
    peaks = chip_peaks(jax.devices()[0].device_kind)
    out = {}
    for kernel in GQA + CONV:
        events = [(d, text) for name, d, text in ops if kernel in name]
        if not events:
            continue
        median = statistics.median(d for d, _text in events)
        # a conv call is held to the bytes that cross HBM: what its HLO
        # text places in on-chip memory (S(1)) is left out
        on_chip = sorted(
            on_chip_tensors(kernel, events[0][1]) if kernel in CONV else ()
        )
        least, which = roofline_seconds(
            *cost(kernel, frozenset(on_chip)), peaks
        )
        out[kernel] = {
            "calls": len(events), "device_ms": median * 1e3,
            "roofline_pct": 100.0 * least / median, "bound": which,
            "on_chip": on_chip,
        }
    if not out:
        print(f"no {GQA + CONV} among the traced ops: "
              f"{sorted({name for name, _d, _t in ops})}", file=sys.stderr)
    return out


def dense(q, k, v):
    with jax.default_matmul_precision("highest"):
        k, v = (jnp.repeat(x, H // KV, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(D))
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def main() -> int:
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    q, w = (jax.random.normal(x, (B, S, H, D), jnp.float32) for x in keys[:2])
    k, v = (
        jax.random.normal(x, (B, S, KV, D), jnp.float32) for x in keys[2:4]
    )
    bcu = jax.random.normal(keys[4], (B, S, 3 * HIDDEN), jnp.float32)
    taps = jax.random.normal(keys[5], (HIDDEN, 3), jnp.float32)
    t = jax.random.normal(keys[6], (B, S, HIDDEN), jnp.float32)
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    # the references see the same bf16-rounded operands, in float32
    r = lambda x: bf(x).astype(jnp.float32)  # noqa: E731

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def dense_loss(q, k, v):
        out = dense(q, k, v)
        return jnp.sum(out * w), out

    def conv_loss(op):
        def loss(bcu, taps):
            out = op(bcu, taps)
            return jnp.sum(out.astype(jnp.float32) * t), out
        return loss

    flash = jax.jit(jax.value_and_grad(flash_loss, (0, 1, 2), has_aux=True))
    conv = jax.jit(
        jax.value_and_grad(conv_loss(short_conv), (0, 1), has_aux=True)
    )
    (_, out), grads = flash(bf(q), bf(k), bf(v))
    (_, ref_out), ref_grads = jax.jit(
        jax.value_and_grad(dense_loss, (0, 1, 2), has_aux=True)
    )(r(q), r(k), r(v))
    errors = {"out": rel(out, ref_out)}
    errors.update({
        n: rel(g, rg) for n, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads)
    })
    (_, y), conv_grads = conv(bf(bcu), taps)
    (_, ref_y), ref_conv_grads = jax.jit(jax.value_and_grad(
        conv_loss(short_conv_reference), (0, 1), has_aux=True
    ))(r(bcu), taps)
    errors["conv_y"] = rel(y, ref_y)
    errors.update({
        n: rel(g, rg)
        for n, g, rg in zip(("conv_d_bcu", "conv_dw"), conv_grads,
                            ref_conv_grads)
    })

    def both():
        return flash(bf(q), bf(k), bf(v)), conv(bf(bcu), taps)

    jax.block_until_ready(both())
    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "shape": {"attention": [B, S, H, KV, D], "conv": [B, S, 3 * HIDDEN]},
        "relative_l2": errors, "kernels": device_times(both),
    }))
    return 0 if max(errors.values()) <= 0.02 else 1


if __name__ == "__main__":
    sys.exit(main())
