"""On the chip: the causal flash kernels against dense causal attention at
Ouro's shape (16 heads x 128, S=4,096: 8 x 8 tiles of 512), forward and the
three gradients, in bf16 against a float32 reference at matmul precision
'highest'; then the kernels' wall per forward + backward, and each kernel's
DEVICE time per call with its share of the roofline — a profiler window
over the same calls, read as the benchmark reads its
``flash_causal_*_roofline`` metrics (``benchmark/trace.py``,
``benchmark/flops_lm.causal_kernel_cost``), beside what the call chose from
its shapes (``plan``: the heads a program of each direction, ``fwd_vmem_mb``
/ ``bwd_vmem_mb``). ``--at-most-heads 4 2`` also times the call at no more
than those heads a program (4 forward, 2 backward: the counts until PR 58)
and holds the forward's output to the chosen count's bit for bit.

    chiprun --chips 1 -- python tools/chip_causal_check.py

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

from benchmark.flops_lm import causal_kernel_cost
from tools.chip_gqa_check import causal_call_report, kernel_cost

B, S, H, D = 1, 4096, 16, 128
KERNELS = ("flash_causal_fwd", "flash_causal_bwd_tiled")


def causal_cost(kernel: str):
    """(FLOPs, bytes) of one call of ``kernel``; the one-sweep backward's
    from the accepted three (``chip_gqa_check.kernel_cost``)."""
    return kernel_cost(
        lambda part: causal_kernel_cost(
            f"flash_causal_{part}", B, H, S, D, 512, 512
        ), kernel.removeprefix("flash_causal_"),
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--at-most-heads", type=int, nargs="*", default=[])
    opts = parser.parse_args(argv)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(x, (B, S, H, D), jnp.float32) for x in keys)
    report, ok = causal_call_report(q, k, v, w, {
        kernel: (lambda _on_chip, kernel=kernel: causal_cost(kernel))
        for kernel in KERNELS
    }, opts.at_most_heads)
    print(json.dumps(report))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
