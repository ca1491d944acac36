"""On the chip: the indexer's loss kernels (``ops/index_loss.py``) alone at
Keye-VL-2.0's shape — 16 index heads of 64 and one key head beside 32 / 4
main heads of 128, S=16,384, top-2,048 — against the XLA block loop they
replace (``models/keye_vl2.index_loss`` under ``attention_impl="dense"``)
over the SAME bf16 operands: the loss, the peak gauge and the three
gradients by relative L2, then the host-clock ms of each side's forward and
forward + backward (a call is tens of ms: the dispatch is noise).

    chiprun --chips 1 -- python tools/chip_index_loss_check.py
    python tools/chip_index_loss_check.py --seq 256 --topk 32   # here, CPU

The selection is the layer's own (``select_keys`` over the random indexer
operands), the log-sum-exp the selected flash kernels'. Prints one JSON
line; exit code 1 if a gradient is further than 0.02 from the loop's, 3
where there is no TPU (unless ``--seq`` is small enough for the CPU)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


def _timed(fn, *args, repeats: int = 5) -> float:
    jax.block_until_ready(fn(*args))  # compiles
    start = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / repeats * 1e3


def _rel(a, b) -> float:
    a, b = (x.astype(jnp.float32) for x in (a, b))
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def main(argv=None) -> int:
    from dedloc_tpu.models import keye_vl2
    from dedloc_tpu.ops.flash_attention import flash_attention

    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=16384)
    parser.add_argument("--topk", type=int, default=2048)
    parser.add_argument("--block", type=int, default=512)
    parser.add_argument("--batch", type=int, default=1)
    parser.add_argument("--dense", type=int, default=1,
                        help="0: time the kernels alone")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu" and args.seq > 1024:
        print("no TPU here: a CPU run takes --seq up to 1024",
              file=sys.stderr)
        return 3
    b, s = args.batch, args.seq
    cfgs = {
        impl: keye_vl2.KeyeVL2Config(
            index_topk=args.topk, attention_impl=impl,
            attention_block_size=args.block,
        ) for impl in ("flash", "dense")
    }
    cfg = cfgs["flash"]
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shapes = (
        (b, s, cfg.index_n_heads, cfg.index_head_dim),
        (b, s, cfg.index_head_dim),
        (b, s, cfg.num_attention_heads, cfg.head_dim),
        (b, s, cfg.num_key_value_heads, cfg.head_dim),
        (b, s, cfg.num_key_value_heads, cfg.head_dim),
    )
    q_index, k_index, q, k, v = (
        jax.random.normal(key, shape, jnp.float32).astype(cfg.dtype)
        for key, shape in zip(keys, shapes)
    )
    weights = jax.random.normal(keys[5], (b, s, cfg.index_n_heads),
                                jnp.float32)
    selection = jax.jit(
        lambda *x: keye_vl2.select_keys(cfg, *x)[0]
    )(q_index, k_index, weights)
    _out, lse = jax.jit(lambda q, k, v, sel: flash_attention(
        q, k, v, selection=sel, block_q=args.block, block_k=args.block,
    ))(q, k, v, selection)

    def sides(impl):
        def loss(q_index, k_index, weights):
            return keye_vl2.index_loss(
                cfgs[impl], q_index, k_index, weights, selection, q, k, lse
            )

        return (jax.jit(loss),
                jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)))

    operands = (q_index, k_index, weights)
    fwd, both = sides("flash")
    (value, peak), grads = both(*operands)
    result = {
        "device_kind": jax.devices()[0].device_kind, "seq": s,
        "block": args.block, "loss": float(value), "peak": float(peak),
        "kernels_fwd_ms": _timed(fwd, *operands),
        "kernels_fwd_bwd_ms": _timed(both, *operands),
    }
    worst = 0.0
    if args.dense:
        fwd, both = sides("dense")
        (ref_value, ref_peak), ref_grads = both(*operands)
        apart = {
            name: _rel(got, ref) for name, got, ref in zip(
                ("d_q_index", "d_k_index", "d_weights"), grads, ref_grads
            )
        }
        worst = max(apart.values())
        result.update(
            loop_loss=float(ref_value), loop_peak=float(ref_peak),
            apart=apart, loop_fwd_ms=_timed(fwd, *operands),
            loop_fwd_bwd_ms=_timed(both, *operands),
        )
    print(json.dumps(result), flush=True)
    return int(worst > 0.02)


if __name__ == "__main__":
    sys.exit(main())
