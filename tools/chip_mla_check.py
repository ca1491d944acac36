"""On the chip: the two-width causal flash kernels (latent attention) against
dense causal attention at kanana-2's shape (32 heads, q/k 192 wide, v and
out 128, S=4,096: 8 x 8 tiles of 512), forward and the three gradients, in
bf16 against a float32 reference at matmul precision 'highest'; then the
kernels' wall per forward + backward, and each kernel's DEVICE time per call
with its share of the roofline — a profiler window over the same calls,
read as the benchmark reads its ``flash_mla_*_roofline`` metrics
(``benchmark/trace.py``, ``benchmark/flops_moe.mla_kernel_cost``), beside
what the call chose from its shapes (``plan``: the heads a program of each
direction, ``fwd_vmem_mb`` / ``bwd_vmem_mb``). ``--seq 8192`` is Kimi-Linear's
latent layer (the backward's dk / dv leave room for two heads a program
there, four at 4,096); ``--at-most-heads 4 2`` also times the call at no more
than those heads a program (4 forward, 2 backward: the counts until PR 58)
and holds the forward's output to the chosen count's bit for bit.

    chiprun --chips 1 -- python tools/chip_mla_check.py

Prints one JSON line; exit code 1 if an error exceeds 0.02 relative L2
(bf16 rounding of the operands alone is ~0.004)."""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmark.flops_moe import mla_kernel_cost
from tools.chip_gqa_check import causal_call_report, kernel_cost

B, S, H, D, DV = 1, 4096, 32, 192, 128  # S: the default of --seq
KERNELS = ("flash_mla_fwd", "flash_mla_bwd_tiled")


def mla_cost(kernel: str, seq: int = S):
    """(FLOPs, bytes) of one call of ``kernel`` at the heads' own widths;
    the one-sweep backward's from the accepted three
    (``chip_gqa_check.kernel_cost``)."""
    return kernel_cost(
        lambda part: mla_kernel_cost(
            f"flash_mla_{part}", B, H, seq, D, DV, 512, 512
        ), kernel.removeprefix("flash_mla_"),
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seq", type=int, default=S)
    parser.add_argument("--at-most-heads", type=int, nargs="*", default=[])
    opts = parser.parse_args(argv)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (
        jax.random.normal(x, (B, opts.seq, H, width), jnp.float32)
        for x, width in zip(keys, (D, D, DV, DV))
    )
    report, ok = causal_call_report(q, k, v, w, {
        kernel: (
            lambda _on_chip, kernel=kernel: mla_cost(kernel, opts.seq)
        ) for kernel in KERNELS
    }, opts.at_most_heads)
    print(json.dumps(report))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
