"""On the chip: the grouped-query causal flash kernels and the short
convolution kernels, each alone, at LFM2-24B-A2B's shapes — 32 query heads
over 8 kv heads of 64 at S=4,096 (8 x 8 tiles of 512) against dense causal
attention with k / v repeated per group; (1, 4096, 3 x 2048) against the
shifted-sum convolution — forward and every gradient, in bf16 against a
float32 reference at matmul precision 'highest'; then each kernel's DEVICE
time per call with its share of the roofline, from a profiler window over
the same calls, read as the benchmark reads its ``flash_gqa_*_roofline`` and
``short_conv_*_roofline`` metrics (``benchmark/trace.py``,
``benchmark/flops_lfm2.py``; the ONE backward kernel, ``*_bwd_tiled``, by
``kernel_cost``: 5 matmuls a tile), and what the call chose from its shapes
(``plan``: the query heads a program of each direction, ``fwd_vmem_mb`` and
``bwd_vmem_mb`` — the scoped VMEM each asks for; the backward holds dk and dv
for the whole sequence).

    chiprun --chips 1 -- python tools/chip_gqa_check.py

Another shape and a BAND (a sliding window inside the kernels, named
``flash_band_*`` and held to ``benchmark/flops_smallthinker.py``'s count of
the tiles inside the band) by flags — SmallThinker's 28 query heads over 4 kv
heads of 128 at S=16,384, its band of 4,096 and the global layer's full
triangle, each also with ONE query head a program in place of the whole
group of seven the kernels take (the question PR 36 settled on the chip;
``--at-most-heads 4 2`` times a group of eight's call at the four heads a
program forward and the two backward it took until PR 58, and holds the
forward's output to the chosen count's bit for bit):

    chiprun --chips 1 -- python tools/chip_gqa_check.py --heads 28 \
        --kv-heads 4 --head-dim 128 --seq 16384 --band 4096 none \
        --at-most-heads 1 --conv 0

The two-stream BLOCK-DIFFUSION rule (``flash_bd_*``, held to
``benchmark/flops_sdar.py``'s count of the rule's tiles) by ``--block-diffusion
<block length>``, ``--seq`` counting both streams — SDAR's 32 query heads
over 4 kv heads of 128 at 2 x 4,096 positions, blocks of 4:

    chiprun --chips 1 -- python tools/chip_gqa_check.py --heads 32 \
        --kv-heads 4 --head-dim 128 --seq 8192 --block-diffusion 4 --conv 0

Laguna-XS.2's two calls (a band EQUAL to the tile under a group of eight,
the full triangle under a whole group of SIX a program), the band also at a
key tile of 256 and of 128 (``--block-k``: the kernels' existing ``block_k``
argument; a roofline share then counts the narrower tiles the band holds —
62 and 124 for the 31 of 512 — so the device ms a call is the number to
compare across tiles):

    chiprun --chips 1 -- python tools/chip_gqa_check.py --heads 64 \
        --kv-heads 8 --head-dim 128 --seq 8192 --band 512 \
        --block-k 512 256 128 --conv 0
    chiprun --chips 1 -- python tools/chip_gqa_check.py --heads 48 \
        --kv-heads 8 --head-dim 128 --seq 8192 --band none --conv 0

The per-head output gate's kernel pair (``ops/head_gate.py``) alone, at
``(1, --seq, --heads x --head-dim)``, held to XLA's expression over the SAME
bf16 operands (the forward and ``d_ctx`` by the share of elements whose bits
differ, ``d_gate`` by relative L2) and timed against the bytes a call moves:

    chiprun --chips 1 -- python tools/chip_gqa_check.py --heads 64 \
        --kv-heads 8 --head-dim 128 --seq 8192 --band --conv 0 --head-gate 1

A SELECTION (``flash_sel_*``: the visibility an operand, an int8 [S, S]
mask read tile by tile; ``benchmark/flops_keye.py``'s cost over the tiles
that hold a selected pair) by ``--select-topk N --select random|prefix`` IN
PLACE of the bands — Keye-VL-2.0's 32 query heads over 4 kv heads of 128 at
S=16,384, 2,048 keys a query: ``random`` keeps N of each query's keys drawn
uniformly (every tile of the triangle holds a pair), ``prefix`` the first N
(window-shaped: the tiles past the N-th key hold nothing and run no body) —
and, beside the kernels, ``select_ms``: the host-clock ms a call of the
layer's index scores + exact top-N (``models/keye_vl2.select_keys``) on
random indexer operands at the published 16 x 64, by BOTH its paths —
``kernel`` (``ops/index_select.index_select``, what runs behind the flash
kernels) and ``loop`` (the ``"dense"`` path's block loop in XLA, the
kernel's oracle) — with ``select_device_ms`` (the kernel's call in a
profiler window), ``select_rows_ok`` (every row holds min(t + 1, N) ones,
either path), ``select_disagree_share`` (entries of the two masks that
differ: keys within a float32 ulp of a row's threshold, where the kernel
sums a row's sixteen terms in another order than XLA) and
``select_tie_block_share`` (either path):

    chiprun --chips 1 -- python tools/chip_gqa_check.py --heads 32 \
        --kv-heads 4 --head-dim 128 --seq 16384 --select-topk 2048 \
        --select random prefix --conv 0

Prints one JSON line; exit code 1 if an error exceeds 0.02 relative L2
(bf16 rounding of the operands alone is ~0.004) or a gated element's bits
differ."""
from __future__ import annotations

import argparse
import contextlib
import importlib
import itertools
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
from benchmark.flops_lfm2 import conv_kernel_cost, gqa_kernel_cost
from benchmark.flops_keye import sel_kernel_cost, triangle_tiles
from benchmark.flops_sdar import bd_kernel_cost
from benchmark.flops_smallthinker import band_kernel_cost
from benchmark.peaks import chip_peaks
from benchmark.reducers.conv_kernel_roofline import on_chip_tensors
from benchmark.trace import OPS, load_xplane, op_name
from dedloc_tpu.ops.flash_attention import flash_attention
from dedloc_tpu.ops.head_gate import gate_heads, gate_heads_xla
from dedloc_tpu.ops.short_conv import short_conv, short_conv_reference

B, HIDDEN = 1, 2048
KERNELS = ("fwd", "bwd_tiled")
CONV = ("short_conv_fwd", "short_conv_bwd")
# the gate's kernels: [B, S, H·D] bf16 arrays read + written a call, beside
# the float32 gate [B, S, H] once each way
GATE = {"head_gate_fwd": (2, 1), "head_gate_bwd": (3, 2)}


class Blocks(int):
    """A mask spec beside a band's length: the two-stream rule's blocks."""


class Selected:
    """A mask spec: ``topk`` keys a query, chosen ``how`` (random |
    prefix), as the int8 [1, S, S] ``selection`` and the share of the
    triangle's 512 x 512 tiles that hold a selected pair."""

    def __init__(self, topk: int, how: str, seq: int):
        from dedloc_tpu.ops.flash_attention import selection_tile_flags
        from dedloc_tpu.ops.index_select import top_k_mask

        self.topk, self.how = topk, how
        position = jnp.arange(seq)
        rows = min(512, seq)

        def block(args):  # 512 query rows at a time
            first, key = args
            causal = position[None, :] <= first + jnp.arange(rows)[:, None]
            scores = (
                jax.random.uniform(key, (rows, seq)) if how == "random"
                else jnp.broadcast_to(-position.astype(jnp.float32),
                                      (rows, seq))
            )
            return top_k_mask(scores, causal, topk).astype(jnp.int8)

        self.selection = jax.jit(lambda: jax.lax.map(block, (
            jnp.arange(0, seq, rows),
            jax.random.split(jax.random.PRNGKey(7), seq // rows),
        )).reshape(1, seq, seq))()
        self.tile_share = float(
            jnp.sum(selection_tile_flags(self.selection, 512, 512))
        ) / triangle_tiles(seq, 512, 512)

    def __str__(self):
        return f"{self.how}_{self.topk}"


def kernel_cost(cost, kernel: str):
    """(FLOPs, bytes) of one call of ``kernel`` ("fwd" | "bwd_tiled") from
    the accepted cost functions (``cost(kernel)`` for "fwd", "bwd_dq",
    "bwd_dkv"; the benchmark's files have no row for the one sweep yet,
    ROADMAP A12 (13)): the sweep makes the dq and the dkv kernel's products
    less the QK^T and dP it no longer repeats — the forward's two matmuls at
    the same widths — so 3 + 4 - 2 = 5 a tile; its bytes are at least the
    larger of the pair's (it reads what dq read and writes what both
    wrote), which no call's roofline turns on: the MXU bounds them all."""
    if kernel != "bwd_tiled":
        return cost(kernel)
    (fwd, _), (dq, dq_bytes), (dkv, dkv_bytes) = (
        cost(kernel) for kernel in ("fwd", "bwd_dq", "bwd_dkv")
    )
    return dq + dkv - fwd, max(dq_bytes, dkv_bytes)


def call_plan(q, k, v, block_k: int = 512, selected: bool = False):
    """What a tiled call on [B, S, H, D] operands chose from its shapes
    (``ops/flash_attention._heads_a_program``): the query heads ONE PROGRAM
    of the forward and of the backward takes, the MiB of scoped VMEM each
    asks for (None: the compiler's own 16) and, of the backward's, what
    holds dk and dv for the whole sequence."""
    fa = importlib.import_module("dedloc_tpu.ops.flash_attention")
    d, dv = q.shape[-1], v.shape[-1]
    flat = [jax.ShapeDtypeStruct((*x.shape[:2], x.shape[2] * x.shape[3]),
                                 jnp.bfloat16) for x in (q, k)]
    fwd = fa._fwd_geometry(*flat, d, dv, 512, block_k, selected)
    bwd = fa._bwd_geometry(*flat, d, dv, 512, block_k, selected)
    asks = (
        fa._fwd_vmem(flat[0], 512, fwd[6], fwd[4], fwd[8], d, dv, selected),
        fa._bwd_vmem(*flat, d, dv, 512, block_k, selected),
    )
    fwd_mb, bwd_mb = (
        ask and ask.vmem_limit_bytes / 2**20 for ask in asks
    )
    return {
        "heads_a_program": {"fwd": fwd[4], "bwd": bwd[4]},
        "fwd_vmem_mb": fwd_mb, "bwd_vmem_mb": bwd_mb,
        "bwd_resident_mb": sum(
            fa._bwd_resident(q.shape[1], bwd[8], d, dv, 2)
        ) / 2**20,
    }


def attention_cost(kernel: str, shape, band, block_k: int = 512):
    """(FLOPs, bytes) of one ``flash_gqa_*`` / ``flash_band_*`` /
    ``flash_bd_*`` / ``flash_sel_*`` call at query tiles of 512 and key
    tiles of ``block_k``."""
    if kernel == "bwd_tiled":
        return kernel_cost(
            lambda part: attention_cost(part, shape, band, block_k), kernel
        )
    s, h, kv, d = shape
    if isinstance(band, Selected):
        return sel_kernel_cost(
            f"flash_sel_{kernel}", B, h, kv, s, d, 512, block_k,
            band.tile_share,
        )
    if isinstance(band, Blocks):
        return bd_kernel_cost(
            f"flash_bd_{kernel}", B, h, kv, s // 2, d, 512, block_k, int(band)
        )
    if band is None:
        return gqa_kernel_cost(
            f"flash_gqa_{kernel}", B, h, kv, s, d, 512, block_k
        )
    return band_kernel_cost(
        f"flash_band_{kernel}", B, h, kv, s, d, 512, block_k, band
    )


def traced_ops(run, calls: int = 10):
    """[(op name, device seconds, HLO text)] of a traced window of ``calls``
    executions of ``run``."""
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                result = run()
            jax.block_until_ready(result)
        trace = load_xplane(trace_dir)
    # under a plain jit(grad) the trace names a kernel's op by JAX's name
    # stack around the kernel's name
    return [
        (op_name(name), duration / 1e9, name) for lines in trace.values()
        for name, _start, duration in lines.get(OPS, [])
    ]


def device_times(ops, kernels: dict) -> dict:
    """Per kernel of ``kernels`` (name -> cost(on-chip tensors)): median
    device ms of a call among ``ops`` and its share of the roofline."""
    if not ops:
        return {}  # off the chip: no device plane to read
    peaks = chip_peaks(jax.devices()[0].device_kind)
    out = {}
    for kernel, cost in kernels.items():
        events = [(d, text) for name, d, text in ops if kernel in name]
        if not events:
            continue
        median = statistics.median(d for d, _text in events)
        # a conv call is held to the bytes that cross HBM: what its HLO
        # text places in on-chip memory (S(1)) is left out
        on_chip = sorted(
            on_chip_tensors(kernel, events[0][1]) if kernel in CONV else ()
        )
        least, which = roofline_seconds(*cost(frozenset(on_chip)), peaks)
        out[kernel] = {
            "calls": len(events), "device_ms": median * 1e3,
            "roofline_pct": 100.0 * least / median, "bound": which,
            "on_chip": on_chip,
        }
    if not out:
        print(f"no {sorted(kernels)} among the traced ops: "
              f"{sorted({name for name, _d, _t in ops})}", file=sys.stderr)
    return out


def dense(q, k, v, band):
    """Masked attention in float32, a query head at a time under
    ``jax.checkpoint`` (28 heads of 16,384 x 16,384 scores do not fit)."""
    s, group = q.shape[1], q.shape[2] // k.shape[2]
    i = jnp.arange(s)
    if isinstance(band, Selected):
        seen = band.selection[0] != 0
    elif isinstance(band, Blocks):
        # [noisy ; clean]: a clean query sees the clean blocks up to its
        # own, a noisy one those BEFORE its own and its own noisy block
        clean, blk = i >= s // 2, (i % (s // 2)) // int(band)
        apart = blk[:, None] - blk[None, :]  # query's block - key's
        seen = jnp.where(
            clean[:, None], clean[None, :] & (apart >= 0),
            jnp.where(clean[None, :], apart >= 1, apart == 0),
        )
    else:
        seen = i[None, :] <= i[:, None]
        if band is not None:
            seen &= i[:, None] - i[None, :] < band

    @jax.checkpoint
    def head(q, k, v):  # [S, D] each
        with jax.default_matmul_precision("highest"):
            x = jnp.where(seen, q @ k.T / jnp.sqrt(jnp.float32(q.shape[-1])),
                          -jnp.inf)
            return jax.nn.softmax(x, axis=-1) @ v

    k, v = (jnp.repeat(x[0], group, axis=1) for x in (k, v))
    out = jax.lax.map(
        lambda x: head(*x), tuple(jnp.swapaxes(x, 0, 1) for x in (q[0], k, v))
    )
    return jnp.swapaxes(out, 0, 1)[None]


@contextlib.contextmanager
def at_most_heads(n: int):
    """The kernels with at most ``n`` query heads a program — the plans
    ``_heads_a_program`` chooses among cut to those (a group with no such
    divisor but one, seven: ONE head a program, its programs sharing the kv
    block) — to time a call at another count than its shapes choose: 4
    forward and 2 backward were every power-of-two group's until PR 58."""
    fa = importlib.import_module("dedloc_tpu.ops.flash_attention")
    plans = fa._head_plans
    fa._head_plans = lambda h, group, g: [
        plan for plan in plans(h, group, g) if plan[0] <= n
    ] or plans(h, group, g)[-1:]
    try:
        yield
    finally:
        fa._head_plans = plans


def bits_differ(a, b) -> float:
    """Share of elements of two arrays of one dtype whose bits differ."""
    return float(jnp.mean(a != b))


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def causal_call_report(q, k, v, w, costs, at_most=()):
    """What ``chip_causal_check.py`` and ``chip_mla_check.py`` print for
    ONE causal flash call on float32 [B, S, H, D] operands (``w``: the
    weights of the scalar loss; ``costs``: ``device_times``' kernels): bf16
    against the float32 ``dense`` on the same rounded operands, the wall of
    a forward + backward, each kernel's device ms and roofline share and
    the call's ``plan`` — then the same with at most each of ``at_most``
    heads a program, the forward held to the chosen count's bit for bit.
    Returns (the report, whether every error is inside its limit)."""
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    r = lambda x: bf(x).astype(jnp.float32)  # noqa: E731
    operands = bf(q), bf(k), bf(v)

    def loss(op):
        def scalar(q, k, v):
            out = op(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(scalar, (0, 1, 2), has_aux=True))

    def build():
        return loss(lambda q, k, v: flash_attention(q, k, v, causal=True))

    def timed(step):
        jax.block_until_ready(step(*operands))
        start = time.perf_counter()
        for _ in range(20):
            result = step(*operands)
        jax.block_until_ready(result)
        return {
            "fwd_plus_bwd_wall_ms": (time.perf_counter() - start) / 20 * 1e3,
            "plan": call_plan(q, k, v),
            "kernels": device_times(
                traced_ops(lambda: step(*operands)), costs
            ),
        }

    flash = build()
    (_, out), grads = flash(*operands)
    (_, ref_out), ref_grads = loss(
        lambda q, k, v: dense(q, k, v, None)
    )(r(q), r(k), r(v))
    errors = {"out": rel(out, ref_out)}
    errors.update({
        n: rel(g, rg) for n, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads)
    })
    del ref_out, ref_grads
    found, exact = timed(flash), {}
    for most in at_most:
        with at_most_heads(most):
            capped = build()
            (_, out1), grads1 = capped(*operands)
            exact[f"at_most_{most}.out_bits_differ"] = bits_differ(out1, out)
            errors.update({
                f"at_most_{most}.{n}": rel(g1, g)
                for n, g1, g in zip(("dq", "dk", "dv"), grads1, grads)
            })
            found[f"at_most_{most}"] = timed(capped)
    report = {
        "device": jax.devices()[0].device_kind, "shape": list(q.shape),
        "v_width": v.shape[-1], "relative_l2": errors, "bits_differ": exact,
        **found,
    }
    same = max(exact.values(), default=0.0) == 0.0
    return report, same and max(errors.values()) <= 0.02


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--kv-heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--seq", type=int, default=4096)
    parser.add_argument(
        "--band", nargs="*", default=["none"],
        help="bands to check, each a length or 'none' (the causal mask)",
    )
    parser.add_argument(
        "--at-most-heads", type=int, nargs="*", default=[],
        help="also time each band with at most this many query heads a "
             "program (each count given), the forward held to the chosen "
             "count's bit for bit",
    )
    parser.add_argument(
        "--block-diffusion", type=int, default=0,
        help="check the two-stream block rule at this block length IN PLACE "
             "of the bands (--seq counts both streams)",
    )
    parser.add_argument(
        "--block-k", type=int, nargs="+", default=[512],
        help="key tiles to run every band at (query tiles stay 512); a "
             "tile other than 512 is tagged .bk<n>",
    )
    parser.add_argument(
        "--select-topk", type=int, default=0,
        help="check the selected kernels at this many keys a query IN PLACE "
             "of the bands, under each selection of --select",
    )
    parser.add_argument(
        "--select", nargs="+", default=["random"],
        choices=("random", "prefix"),
    )
    parser.add_argument("--conv", type=int, choices=(0, 1), default=1)
    parser.add_argument("--head-gate", type=int, choices=(0, 1), default=0)
    opts = parser.parse_args(argv)
    shape = (opts.seq, opts.heads, opts.kv_heads, opts.head_dim)
    S, H, KV, D = shape
    bands = [None if b == "none" else int(b) for b in opts.band]
    if opts.block_diffusion:
        bands = [Blocks(opts.block_diffusion)]
    if opts.select_topk:
        bands = [Selected(opts.select_topk, how, S) for how in opts.select]

    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    q, w = (jax.random.normal(x, (B, S, H, D), jnp.float32) for x in keys[:2])
    k, v = (
        jax.random.normal(x, (B, S, KV, D), jnp.float32) for x in keys[2:4]
    )
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    # the references see the same bf16-rounded operands, in float32
    r = lambda x: bf(x).astype(jnp.float32)  # noqa: E731

    def attention(op, band):
        def loss(q, k, v):
            out = op(q, k, v, band)
            return jnp.sum(out.astype(jnp.float32) * w), out
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))

    def flash_at(block_k):
        def flash(q, k, v, band):
            if isinstance(band, Selected):
                return flash_attention(
                    q, k, v, selection=band.selection,
                    block_k=block_k,
                )[0]
            if isinstance(band, Blocks):
                return flash_attention(
                    q, k, v, block_diffusion=int(band), block_k=block_k
                )
            return flash_attention(
                q, k, v, causal=True, band=band, block_k=block_k
            )
        return flash

    errors, exact, kernels, plans = {}, {}, {}, {}
    for band, block_k in itertools.product(bands, opts.block_k):
        tag = "causal" if band is None else f"band_{band}"
        family = "flash_gqa" if band is None else "flash_band"
        if isinstance(band, Blocks):
            tag, family = f"block_diffusion_{band}", "flash_bd"
        if isinstance(band, Selected):
            tag, family = f"selected_{band}", "flash_sel"
        if block_k != 512:
            tag += f".bk{block_k}"
        flash = flash_at(block_k)
        selected = isinstance(band, Selected)
        plans[tag] = call_plan(q, k, v, block_k, selected)
        step = attention(flash, band)
        (_, out), grads = step(bf(q), bf(k), bf(v))
        (_, ref_out), ref_grads = attention(dense, band)(r(q), r(k), r(v))
        errors[f"{tag}.out"] = rel(out, ref_out)
        errors.update({
            f"{tag}.{n}": rel(g, rg)
            for n, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads)
        })
        del ref_out, ref_grads
        costs = {
            f"{family}_{kernel}": (
                lambda _on_chip, kernel=kernel: attention_cost(
                    kernel, shape, band, block_k
                )
            ) for kernel in KERNELS
        }
        kernels[tag] = device_times(
            traced_ops(lambda: step(bf(q), bf(k), bf(v))), costs
        )
        for most in opts.at_most_heads:
            with at_most_heads(most):
                capped, at = attention(flash, band), f"{tag}.at_most_{most}"
                plans[at] = call_plan(q, k, v, block_k, selected)
                (_, out1), grads1 = capped(bf(q), bf(k), bf(v))
                # heads are independent in the forward: the same bits
                exact[f"{at}.out_bits_differ"] = bits_differ(out1, out)
                errors.update({
                    f"{at}.{n}": rel(g1, g) for n, g1, g in
                    zip(("dq", "dk", "dv"), grads1, grads)
                })
                kernels[at] = device_times(
                    traced_ops(lambda: capped(bf(q), bf(k), bf(v))), costs
                )

    extra = {}
    if opts.select_topk:
        from dedloc_tpu.models.keye_vl2 import KeyeVL2Config, select_keys

        q_index = bf(jax.random.normal(keys[4], (B, S, 16, 64)))
        k_index = bf(jax.random.normal(keys[5], (B, S, 64)))
        weights = jax.random.normal(keys[6], (B, S, 16))
        rows = jnp.minimum(jnp.arange(S) + 1, opts.select_topk)
        chosen, extra["select_ms"] = {}, {}
        for path, impl in (("kernel", "flash"), ("loop", "dense")):
            cfg = KeyeVL2Config(index_topk=opts.select_topk,
                                attention_impl=impl)
            select = jax.jit(lambda *x, cfg=cfg: select_keys(cfg, *x))
            chosen[path], tied = jax.block_until_ready(
                select(q_index, k_index, weights)
            )
            start = time.perf_counter()
            for _ in range(5):
                out = select(q_index, k_index, weights)
            jax.block_until_ready(out)
            extra["select_ms"][path] = (time.perf_counter() - start) / 5 * 1e3
            extra.setdefault("select_rows_ok", {})[path] = bool(jnp.all(
                jnp.sum(chosen[path][0], axis=-1, dtype=jnp.int32) == rows
            ))
            extra.setdefault("select_tie_block_share", {})[path] = float(tied)
            if path == "kernel":
                extra["select_device_ms"] = device_times(
                    traced_ops(lambda: select(q_index, k_index, weights), 5),
                    {"index_select": lambda _on_chip: (0.0, 0.0)},
                ).get("index_select", {}).get("device_ms")
        extra["select_disagree_share"] = float(
            jnp.mean(chosen["kernel"] != chosen["loop"])
        )
        del chosen
        extra["select_tile_share"] = {
            str(band): band.tile_share for band in bands
        }

    if opts.conv:
        bcu = jax.random.normal(keys[4], (B, S, 3 * HIDDEN), jnp.float32)
        taps = jax.random.normal(keys[5], (HIDDEN, 3), jnp.float32)
        t = jax.random.normal(keys[6], (B, S, HIDDEN), jnp.float32)

        def conv_loss(op):
            def loss(bcu, taps):
                out = op(bcu, taps)
                return jnp.sum(out.astype(jnp.float32) * t), out
            return loss

        conv = jax.jit(
            jax.value_and_grad(conv_loss(short_conv), (0, 1), has_aux=True)
        )
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
        kernels["conv"] = device_times(
            traced_ops(lambda: conv(bf(bcu), taps)), {
                kernel: (lambda on_chip, kernel=kernel: conv_kernel_cost(
                    kernel, B, S, HIDDEN, on_chip=on_chip
                )) for kernel in CONV
            },
        )

    if opts.head_gate:
        ctx, dy = (
            bf(jax.random.normal(x, (B, S, H * D), jnp.float32))
            for x in keys[4:6]
        )
        gate = jax.nn.sigmoid(jax.random.normal(keys[6], (B, S, H)))

        def gated(op):
            def pair(ctx, gate, dy):
                out, vjp = jax.vjp(op, ctx, gate)
                return (out,) + vjp(dy)
            return jax.jit(pair)

        pair = gated(gate_heads)
        out, d_ctx, d_gate = pair(ctx, gate, dy)
        ref_out, ref_d_ctx, ref_d_gate = gated(gate_heads_xla)(ctx, gate, dy)
        exact["gate.out_bits_differ"] = bits_differ(out, ref_out)
        exact["gate.d_ctx_bits_differ"] = bits_differ(d_ctx, ref_d_ctx)
        errors["gate.d_gate"] = rel(d_gate, ref_d_gate)
        kernels["head_gate"] = device_times(
            traced_ops(lambda: pair(ctx, gate, dy)), {
                kernel: (lambda _on_chip, wide=wide, narrow=narrow: (
                    0.0, B * S * (wide * H * D * 2 + narrow * H * 4)
                )) for kernel, (wide, narrow) in GATE.items()
            },
        )

    print(json.dumps({
        "device": jax.devices()[0].device_kind,
        "shape": {"attention": [B, S, H, KV, D], "conv": [B, S, 3 * HIDDEN]},
        "relative_l2": errors, "bits_differ": exact, "kernels": kernels,
        "plan": plans, **extra,
    }))
    same = max(exact.values(), default=0.0) == 0.0
    return 0 if same and max(errors.values(), default=0.0) <= 0.02 else 1


if __name__ == "__main__":
    sys.exit(main())
