"""Ring-attention scaling shape on the virtual CPU mesh (VERDICT r4 #8).

Measures compile + run wall-clock of the ring-attention forward+backward
at long S across sequence-parallel widths on N virtual CPU devices — the
DCN-analogue scaling curve to sit next to the single-chip numbers in
docs/long-context.md. NOT perf-grade (CPU devices, one shared core): the
point is the SHAPE — per-device score memory and compute fall as 1/sp
while the program still compiles and executes end-to-end at every width.

    python tools/ring_scaling.py            # sp in {2,4,8} x S in {16k, 32k}
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"  # a virtual-CPU-mesh tool, by design
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

import dataclasses

import jax.numpy as jnp
import numpy as np


def measure(sp: int, seq: int) -> dict:
    from dedloc_tpu.models.albert import AlbertConfig, AlbertSelfAttention
    from dedloc_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(sp, axis_names=("seq",))
    cfg = AlbertConfig.tiny(
        max_position_embeddings=seq,
        attention_impl="ring",
        mesh=mesh,
    )
    attn = AlbertSelfAttention(cfg, deterministic=True)
    B = 1
    x = jnp.asarray(
        np.random.default_rng(0).normal(0, 1, (B, seq, cfg.hidden_size)),
        cfg.dtype,
    )
    bias = jnp.zeros((B, 1, 1, seq), cfg.dtype)
    params = attn.init(jax.random.PRNGKey(0), x[:, :128], bias[..., :128])[
        "params"
    ]

    def loss(p, v):
        return jnp.mean(attn.apply({"params": p}, v, bias).astype(jnp.float32) ** 2)

    fn = jax.jit(jax.value_and_grad(loss))
    t0 = time.perf_counter()
    compiled = fn.lower(params, x).compile()
    compile_s = time.perf_counter() - t0

    val, grads = compiled(params, x)
    jax.block_until_ready(grads)  # warm run
    t0 = time.perf_counter()
    runs = 3
    for _ in range(runs):
        val, grads = compiled(params, x)
    jax.block_until_ready(grads)
    run_s = (time.perf_counter() - t0) / runs
    assert np.isfinite(float(val))
    return {
        "sp": sp,
        "seq": seq,
        "compile_s": round(compile_s, 1),
        "fwd_bwd_s": round(run_s, 2),
        "tok_per_s": round(seq / run_s, 0),
        # per-device score-block footprint: (S/sp)^2 fp32 per (batch, head)
        "score_block_mb_per_device": round(
            (seq / sp) * (seq / sp) * 4 / 2**20, 1
        ),
    }


if __name__ == "__main__":
    rows = []
    for sp, seq in [(2, 16384), (4, 16384), (8, 16384), (4, 32768), (8, 32768)]:
        rows.append(measure(sp, seq))
        print(json.dumps(rows[-1]), flush=True)
