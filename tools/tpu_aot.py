"""Compile the trainer's device programs for a TPU v5e WITHOUT a chip.

libtpu can build a compile-only client from a topology name, so the real
XLA:TPU + Mosaic compilers run on a CPU-only box: what this catches is a
kernel Mosaic refuses and a program XLA:TPU takes minutes to compile — the
two failures only a chip run used to show (PR 21: ``flat_apply_step`` took
~20 minutes to compile on the v5e and 1 s on the CPU). It says nothing about
speed or numerics; those need the chip (``chip_smoke.py``).

    python tools/tpu_aot.py            # every program, one JSON line each
    python tools/tpu_aot.py flat_apply_step kernels
    OURO_LAYERS=4 OURO_BATCH=1 python tools/tpu_aot.py ouro_accumulate_step

Each line: {"program", "compile_s", "tpu_custom_calls", "flash_fwd_forms",
"flash_windows", "flash_vmem_mb", "flash_heads", "layer_body_copies", "memory"} (and, for the programs of
``COUNT_KERNEL_CALLS``, "kernel_calls": call sites by kernel name; for the
expert programs, "expert_grad_passes": ``expert_grad_passes``' counts; for
the seven decoder programs of ``LM_CELLS``, "remat_policy": the layer policy
they were built under — the model's default, or ``<PREFIX>_REMAT`` from the
environment, e.g. ``SMALLTHINKER_REMAT=kernel_outputs``: read ``memory``'s
``temp_bytes`` under both before asking a chip for the stash's room; for
``laguna_accumulate_step``, "attn_gate_float32_mb": ``scoped_float32_mb`` of
the per-head gate's scope, and in its ``kernel_calls`` the gate's own pair —
``head_gate_fwd`` 10 for five layers: the forward is replayed, PR 48; for
``keye_accumulate_step``, "largest_buffers_mb": the largest array shapes the
compiled module names — nothing [heads, S, S], PR 51 — and
"loss_block_transients": ``loss_block_transients`` of the lowered and the
compiled module — [] since the indexer's loss is a kernel pair, PR 52 — and
"select_block_transients": the same at the selection's blocks of 256 rows —
[] since the selection is a kernel, PR 54) —
``flash_windows`` is each flash kernel's lane window beside its column
block, from the call's metadata (``"block"`` for a call that carries none:
D=64, D=128; its head counts for a grouped-query call);
``flash_vmem_mb`` is the scoped VMEM a flash kernel asks for beyond the
compiler's own 16 MiB (the tiled backward holds dk and dv for the whole
sequence there, PR 56); ``flash_heads`` is the query heads ONE PROGRAM of a
tiled flash kernel takes — the most that VMEM holds, a whole kv group where
it fits (``ops/flash_attention._heads_a_program``, PR 58); ``flash_fwd_forms`` counts the flash
forward call SITES of the lowered module by the form their shapes chose
(``one_tile``: one tile covers the sequence; ``tiles``: the online-softmax
kernel). A scanned layer body is one site however often it runs, and a
remat replay of the kernel is a second site in the backward's body: "one
call per scanned layer" holds only when the layer's remat policy keeps the
kernel's outputs (ALBERT's ``fused_ln``, Ouro's ``kernel_outputs``: 1;
Ouro under ``nothing``: 2, PR 28); ``layer_body_copies`` is the
shapes of the ``copy`` instructions inside the compiled program's while
bodies (the scanned layer, forward and backward): relayouts the compiler put
around an op whose layout differs from its neighbours', paid once per layer
iteration; ``memory`` is the compiler's
own analysis (argument / output / temp / alias bytes on the one device): what
a large state leaves for activations is read here before a chip is asked.
The Ouro programs (the looped decoder at its published widths) take depth,
rows and sequence length from ``benchmark/configs/ouro_2p6b_s4096.json``'s
flags; ``OURO_LAYERS`` / ``OURO_BATCH`` override the first two, to size a
cut that cell does not run. Exit code 0
when everything compiled, 3 when no v5e can be described here (no libtpu, or
one without a compile-only client).

A kernel's instruction counts, with no chip:
``LIBTPU_INIT_ARGS=--xla_mosaic_dump_to=<dir> python tools/tpu_aot.py kernels``
writes each Mosaic kernel's final code (``<dir>/*-post-finalize-llo.txt``).
A count is not a time: the one-tile forward dropped 41 % of ``flash_fwd``'s
instructions and 47 % of its time on the v5e, and variants that differ by
256 vector multiplies a head ran within 1.2 % of each other (PERF.md, PR 26).
The SCHEDULE, one line a VLIW bundle, is nearer a time:
``LIBTPU_INIT_ARGS="--xla_jf_dump_to=<dir> --xla_jf_dump_llo_text=true"``
writes ``<dir>/*-<kernel>.1-71-final_bundles.txt`` (``PF:`` marks where a
``pl.when`` region starts: init, a plain tile's body, a crossed tile's, the
flush) and ``*-final_hlo-static-per-bundle-utilization.txt`` (the MXU / XLU /
VALU / EUP / load / store slots each bundle fills, their capacities on the
first lines). ~190 MB and a ``Raising signal 6`` at exit, after the files are
written: one small program a run. Bundles of a tile's body a head tracked the
chip for PR 37's forward (1,896 → 1,127 at D=128; 1.132 → 0.594 ms a call) and
showed WHY before the chip did: MXU slots 49 % full, no unit saturated.
"""
from __future__ import annotations

import functools
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a compile-only client needs no metadata server and must not fight a
# running JAX process for libtpu's lockfile
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from dedloc_tpu.utils.backend import lowering_for_tpu

MICRO_BATCH, SEQ = 12, 512  # the flagship recipe's per-chip shapes


def _on_device(device, tree):
    """Abstract arrays for ``tree``, placed on the (absent) TPU device."""
    sharding = SingleDeviceSharding(device)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def _model_and_state():
    from dedloc_tpu.core.config import CollaborationArguments, parse_config
    from dedloc_tpu.parallel.train_step import TrainState
    from dedloc_tpu.roles.common import build_model, build_optimizer

    args = parse_config(CollaborationArguments, [])
    cfg, model = build_model("large", "fused_ln", "flash")
    tx = build_optimizer(args)
    state = jax.eval_shape(
        lambda r: TrainState.create(
            model.init(r, jnp.zeros((MICRO_BATCH, SEQ), jnp.int32))["params"],
            tx,
        ),
        jax.random.PRNGKey(0),
    )
    return args, cfg, model, state


def accumulate_step(device):
    """ALBERT-large, flash + fused_ln, micro-batch 12, S=512."""
    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import (
        build_loss_fn,
        drop_collator_keys,
        synthetic_mlm_batches,
    )

    _args, cfg, model, state = _model_and_state()
    batch = drop_collator_keys(
        next(synthetic_mlm_batches(cfg, MICRO_BATCH, SEQ, 0))
    )
    grads = jax.eval_shape(zeros_like_grads, state.params)
    return make_accumulate_step(build_loss_fn(model)).lower(*_on_device(
        device,
        (state.params, grads, jnp.zeros([], jnp.int32), batch,
         jax.random.PRNGKey(0)),
    ))


def flat_apply_step(device):
    """The fused flat LAMB apply over ALBERT-large's 17.8M-element buffer."""
    from dedloc_tpu.averaging.device_flat import named_device_leaves
    from dedloc_tpu.parallel.train_step import make_flat_apply_step
    from dedloc_tpu.roles.common import build_flat_opt_factory

    args, _cfg, _model, state = _model_and_state()
    spec = [
        (name, tuple(leaf.shape), np.dtype(np.float32))
        for name, leaf in sorted(named_device_leaves(state.params))
    ]
    total = sum(int(np.prod(shape)) for _n, shape, _d in spec)
    flat_tx = build_flat_opt_factory(args)(spec, state.params)
    return make_flat_apply_step(flat_tx, spec).lower(*_on_device(
        device, (state, jax.ShapeDtypeStruct((total,), jnp.float32))
    ))


def kernels(device):
    """Every Pallas kernel, fwd+bwd, in one program: flash attention at the
    recipe shape (one tile covers S=512: the one-tile forward and the fused
    backward), at one causal tile of D=128 (one head per column block), and
    at S=2048 (several tiles: the online-softmax forward and the one-sweep
    backward every long-sequence run takes); the fused add+LayerNorm at the
    recipe's 6,144 x 1,024 rows."""
    from dedloc_tpu.ops.flash_attention import flash_attention
    from dedloc_tpu.ops.fused_ln import ln_residual

    def loss(q, k, v, q_wide, q_long, x, gamma):
        return (
            jnp.sum(flash_attention(q, k, v).astype(jnp.float32))
            + jnp.sum(flash_attention(
                q_wide, q_wide, q_wide, causal=True
            ).astype(jnp.float32))
            + jnp.sum(
                flash_attention(q_long, q_long, q_long).astype(jnp.float32)
            )
            + jnp.sum(ln_residual(x, x, gamma, gamma).astype(jnp.float32))
        )

    qkv = jax.ShapeDtypeStruct((MICRO_BATCH, SEQ, 16, 64), jnp.bfloat16)
    q_wide = jax.ShapeDtypeStruct((2, SEQ, 16, 128), jnp.bfloat16)
    q_long = jax.ShapeDtypeStruct((1, 2048, 16, 64), jnp.bfloat16)
    x = jax.ShapeDtypeStruct((MICRO_BATCH * SEQ, 1024), jnp.bfloat16)
    gamma = jax.ShapeDtypeStruct((1024,), jnp.float32)
    return jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
        *_on_device(device, (qkv, qkv, qkv, q_wide, q_long, x, gamma))
    )


def mla_kernels(device):
    """The two-width causal kernels (latent attention: q/k 192 wide, v and
    out 128) at the kanana-2 cell's shape, 8 x 8 tiles of 512, fwd+bwd."""
    from dedloc_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)
        )

    qk = jax.ShapeDtypeStruct((1, 4096, 32, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on_device(device, (qk, qk, v))
    )


def gqa_kernels(device):
    """The grouped-query causal kernels at the LFM2 cell's shape (32 query
    heads over 8 kv heads of 64, 8 x 8 tiles of 512), fwd+bwd, and the
    doubly gated short convolution at (1, 4096, 3 x 2048)."""
    from dedloc_tpu.ops.flash_attention import flash_attention
    from dedloc_tpu.ops.short_conv import short_conv

    def loss(q, k, v, bcu, w):
        return jnp.sum(
            flash_attention(q, k, v, causal=True).astype(jnp.float32)
        ) + jnp.sum(short_conv(bcu, w).astype(jnp.float32))

    q = jax.ShapeDtypeStruct((1, 4096, 32, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.bfloat16)
    bcu = jax.ShapeDtypeStruct((1, 4096, 3 * 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.float32)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *_on_device(device, (q, kv, kv, bcu, w))
    )


def band_kernels(device):
    """The band kernels beside the grouped-query causal ones at the
    SmallThinker cell's shape: 28 query heads over 4 kv heads of 128 (a
    whole group of seven a program), S=16,384 in 32 x 32 tiles of 512, a
    band of 4,096 (a sweep of 9 key tiles a query tile), fwd+bwd."""
    from dedloc_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return sum(
            jnp.sum(flash_attention(
                q, k, v, causal=True, band=band
            ).astype(jnp.float32)) for band in (4096, None)
        )

    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on_device(device, (q, kv, kv))
    )


def bd_kernels(device):
    """The block-diffusion kernels at the SDAR cell's shape: 32 query heads
    over 4 kv heads of 128 (a whole group of eight a program), 2 x 4,096
    positions (a noisy then a clean stream) in 16 x 16 tiles of 512 under
    the two-stream rule with blocks of 4 — 80 tiles visited, a sweep of 9
    key tiles a query tile, forward and backward — fwd+bwd."""
    from dedloc_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, block_diffusion=4).astype(jnp.float32)
        )

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on_device(device, (q, kv, kv))
    )


def laguna_kernels(device):
    """The Laguna cell's kernels: the band ones at a band EQUAL to the tile
    (64 query heads over 8 kv heads of 128, a group of eight, S=8,192 in
    16 x 16 tiles of 512, a band of 512: a sweep of 2 key tiles a query
    tile, both crossed) beside the grouped causal ones at 48 / 8 (a whole
    group of SIX a program), fwd+bwd."""
    from dedloc_tpu.ops.flash_attention import flash_attention

    def loss(q_band, q_full, k, v):
        return jnp.sum(flash_attention(
            q_band, k, v, causal=True, band=512
        ).astype(jnp.float32)) + jnp.sum(flash_attention(
            q_full, k, v, causal=True
        ).astype(jnp.float32))

    q_band = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16)
    q_full = jax.ShapeDtypeStruct((1, 8192, 48, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        *_on_device(device, (q_band, q_full, kv, kv))
    )


def sel_kernels(device):
    """The selected kernels at the Keye cell's shape: 32 query heads over 4
    kv heads of 128 (a group of eight), S=16,384 in 32 x 32 tiles of 512
    over the causal triangle (528 tiles), the int8 [S, S] selection read
    tile by tile beside q / k / v and the tile flags from SMEM — fwd+bwd."""
    from dedloc_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v, selection):
        out, _lse = flash_attention(
            q, k, v, selection=selection
        )
        return jnp.sum(out.astype(jnp.float32))

    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    selection = jax.ShapeDtypeStruct((1, 16384, 16384), jnp.int8)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on_device(device, (q, kv, kv, selection))
    )


def long_row_kernels(device):
    """The degrade path of the tiled backward's heads a program
    (``ops/flash_attention._heads_a_program``): the grouped causal kernels
    at 32 query heads over 4 kv heads of 128 and S=32,768 — twice the cells'
    longest row, where dk / dv of a kv block (64 MiB) leave no room for a
    group of eight's transients, so a program takes FOUR heads (91.5 MiB
    asked) and two programs share the kv block; the forward takes the
    eight — fwd+bwd."""
    from dedloc_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(
            jnp.float32
        ))

    q = jax.ShapeDtypeStruct((1, 32768, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 32768, 4, 128), jnp.bfloat16)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on_device(device, (q, kv, kv))
    )


def index_loss_kernels(device):
    """The indexer's loss kernels alone at the Keye cell's shape (16 index
    heads of 64 and one key head beside 32 / 4 main heads of 128, S=16,384
    in 512 x 512 tiles of the causal triangle, the int8 selection a tile
    operand): the forward sweep and, from a cotangent, the one backward
    sweep that holds the key head's whole gradient in VMEM."""
    from dedloc_tpu.ops.index_loss import index_loss_rows

    def loss(q_index, k_index, weights, selection, q, k, lse):
        kl, _peak = index_loss_rows(q_index, k_index, weights, selection, q,
                                    k, lse)
        return jnp.sum(kl)

    seq = 16384
    operands = (
        jax.ShapeDtypeStruct((1, seq, 16, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, seq, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, seq, 16), jnp.float32),
        jax.ShapeDtypeStruct((1, seq, seq), jnp.int8),
        jax.ShapeDtypeStruct((1, seq, 32, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, seq, 4, 128), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, 32, seq), jnp.float32),
    )
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_on_device(device, operands)
    )


def select_kernels(device):
    """The indexer's selection kernel alone at the Keye cell's shape (16
    index heads of 64 and one key head, S=16,384, top-2,048): a block of 256
    query rows' index scores and their exact top-k in VMEM, over the causal
    triangle (``ops/index_select.py``)."""
    from dedloc_tpu.ops.index_select import index_select

    seq = 16384
    operands = (
        jax.ShapeDtypeStruct((1, seq, 16, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, seq, 64), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, seq, 16), jnp.float32),
    )
    return jax.jit(lambda *x: index_select(*x, 2048)).lower(
        *_on_device(device, operands)
    )


def head_gate_kernels(device):
    """The per-head output gate's kernel pair alone at the Laguna cell's two
    shapes: (1, 8192, 64 x 128) and (1, 8192, 48 x 128), bf16 context, float32
    gate; the gated context and, from a cotangent, both gradients."""
    from dedloc_tpu.ops.head_gate import gate_heads

    def both(ctx, gate, dy):
        gated, vjp = jax.vjp(gate_heads, ctx, gate)
        return gated, vjp(dy)

    def pairs(*operands):
        return [both(*operands[:3]), both(*operands[3:])]

    operands = [
        jax.ShapeDtypeStruct((1, 8192, heads * width), dtype)
        for heads in (64, 48)
        for width, dtype in ((128, jnp.bfloat16), (1, jnp.float32),
                             (128, jnp.bfloat16))
    ]
    return jax.jit(pairs).lower(*_on_device(device, operands))


def kda_kernels(device):
    """Kimi Delta Attention's kernel pair alone at the Kimi cell's shape:
    (1, 8192, 8 heads of 128), bf16 q / k / v, float32 log-decays and write
    strengths; the output and, from a cotangent, all five gradients."""
    from dedloc_tpu.ops import kda as kda_ops
    from dedloc_tpu.ops.kda import kda

    # a grid step's share, to size another one offline (bundle counts)
    kda_ops.HEADS_PER_STEP = int(
        os.environ.get("KDA_HEADS_PER_STEP", kda_ops.HEADS_PER_STEP)
    )

    def both(q, k, v, g, beta, do):
        out, vjp = jax.vjp(kda, q, k, v, g, beta)
        return out, vjp(do)

    wide = (1, 8192, 8, 128)
    operands = [jax.ShapeDtypeStruct(wide, jnp.bfloat16)] * 3 + [
        jax.ShapeDtypeStruct(wide, jnp.float32),
        jax.ShapeDtypeStruct(wide[:3], jnp.float32),
        jax.ShapeDtypeStruct(wide, jnp.bfloat16),
    ]
    return jax.jit(both).lower(*_on_device(device, operands))


def ssd_kernels(device):
    """Mamba-2's scan as a kernel pair alone at the Nemotron cell's shape:
    (1, 8192, 32 heads of 64 in 4 groups, state 128), bf16 x / B / C,
    float32 time steps and log-decays; the output and, from a cotangent, all
    six gradients."""
    from dedloc_tpu.ops.ssd import ssd

    def both(x, dt, a, b, c, d, dy):
        out, vjp = jax.vjp(ssd, x, dt, a, b, c, d)
        return out, vjp(dy)

    wide, keys = (1, 8192, 32, 64), (1, 8192, 4, 128)
    operands = [
        jax.ShapeDtypeStruct(wide, jnp.bfloat16),
        jax.ShapeDtypeStruct(wide[:3], jnp.float32),
        jax.ShapeDtypeStruct(wide[:3], jnp.float32),
        jax.ShapeDtypeStruct(keys, jnp.bfloat16),
        jax.ShapeDtypeStruct(keys, jnp.bfloat16),
        jax.ShapeDtypeStruct(wide[2:3], jnp.float32),
        jax.ShapeDtypeStruct(wide, jnp.bfloat16),
    ]
    return jax.jit(both).lower(*_on_device(device, operands))


@functools.lru_cache(maxsize=None)
def _lm_model_and_state(config: str, prefix: str):
    """(args, model, state, ids) of a causal-LM cell's recipe, from its
    configuration file's flags; ``<prefix>_LAYERS`` / ``<prefix>_BATCH`` in
    the environment size another cut, ``<prefix>_REMAT`` names another row
    of the layer remat policy table (``--training.remat_policy``; the
    model's own default without it)."""
    from dedloc_tpu.core.config import CollaborationArguments, parse_config
    from dedloc_tpu.parallel.train_step import TrainState
    from dedloc_tpu.roles.common import build_model, build_optimizer

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark", "configs", config)) as f:
        flags = json.load(f)["flags"]
    flags["--training.num_hidden_layers"] = int(os.environ.get(
        f"{prefix}_LAYERS", flags["--training.num_hidden_layers"]
    ))
    batch = int(os.environ.get(
        f"{prefix}_BATCH", flags["--training.per_device_batch_size"]
    ))
    ids = jnp.zeros((batch, flags["--training.seq_length"]), jnp.int32)
    args = parse_config(
        CollaborationArguments,
        [str(x) for pair in flags.items() for x in pair],
    )
    t = args.training
    _cfg, model = build_model(
        t.model_size, os.environ.get(f"{prefix}_REMAT", t.remat_policy),
        vocab_size=t.vocab_size,
        num_hidden_layers=t.num_hidden_layers, expert_shard=t.expert_shard,
        head_shard=t.head_shard,
    )
    state = jax.eval_shape(
        lambda r: TrainState.create(
            model.init(r, ids)["params"], build_optimizer(args)
        ),
        jax.random.PRNGKey(0),
    )
    return args, model, state, ids


# the decoder cells' accumulate programs: (configuration file, prefix of the
# environment's overrides). Their rows carry ``remat_policy``
LM_CELLS = {
    "ouro_accumulate_step": ("ouro_2p6b_s4096.json", "OURO"),
    "kanana_accumulate_step": ("kanana2_30b_a3b_s4096.json", "KANANA"),
    "lfm2_accumulate_step": ("lfm2_24b_a2b_s4096.json", "LFM2"),
    "smallthinker_accumulate_step": (
        "smallthinker_21b_a3b_s16384.json", "SMALLTHINKER"
    ),
    "sdar_accumulate_step": ("sdar_30b_a3b_s4096.json", "SDAR"),
    "laguna_accumulate_step": ("laguna_xs2_33b_a3b_s8192.json", "LAGUNA"),
    "keye_accumulate_step": ("keye_vl2_30b_a3b_s16384.json", "KEYE"),
    "kimi_accumulate_step": ("kimi_linear_48b_a3b_s8192.json", "KIMI"),
    "nemotron_accumulate_step": (
        "nemotron3_nano_30b_a3b_s8192.json", "NEMOTRON"
    ),
}


def ouro_accumulate_step(device):
    """Ouro-2.6B cut in depth: causal flash attention at D=128 over 8 x 8
    tiles, the scan over layers inside the scan over four passes, the
    chunked head + gated loss."""
    return _lm_accumulate_step(device, *_ouro_model_and_state())


def _ouro_model_and_state():
    return _lm_model_and_state(*LM_CELLS["ouro_accumulate_step"])


def _lm_accumulate_step(device, _args, model, state, ids, **more_of_batch):
    from dedloc_tpu.parallel.train_step import (
        make_accumulate_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import build_loss_fn

    grads = jax.eval_shape(zeros_like_grads, state.params)
    return make_accumulate_step(build_loss_fn(model)).lower(*_on_device(
        device,
        (state.params, grads, jnp.zeros([], jnp.int32),
         dict(input_ids=ids, labels=ids, **more_of_batch),
         jax.random.PRNGKey(0)),
    ))


def kanana_accumulate_step(device):
    """kanana-2-30b-a3b at one chip's share (depth, experts held and
    vocabulary rows from ``benchmark/configs/kanana2_30b_a3b_s4096.json``;
    ``KANANA_LAYERS`` / ``KANANA_BATCH`` size another cut): the two-width
    causal kernels over 8 x 8 tiles, the dense layer and the scanned expert
    layers with the routed tile loop, the chunked head."""
    return _lm_accumulate_step(device, *_lm_model_and_state(
        *LM_CELLS["kanana_accumulate_step"]
    ))


def lfm2_accumulate_step(device):
    """LFM2-24B-A2B at one chip's share (``benchmark/configs/
    lfm2_24b_a2b_s4096.json``; ``LFM2_LAYERS`` / ``LFM2_BATCH`` size another
    cut): four short-convolution mixers and one grouped-query attention, the
    dense layer and one scanned period of expert layers, the tied chunked
    head."""
    return _lm_accumulate_step(device, *_lm_model_and_state(
        *LM_CELLS["lfm2_accumulate_step"]
    ))


def smallthinker_accumulate_step(device):
    """SmallThinker-21BA3B at one chip's share (``benchmark/configs/
    smallthinker_21b_a3b_s16384.json``; ``SMALLTHINKER_LAYERS`` /
    ``SMALLTHINKER_BATCH`` size another cut): one scanned period of a
    global NoPE layer and three band-4096 RoPE layers at S=16,384, every
    layer routed (ReGLU experts, the router fed before attention), the
    untied chunked head."""
    return _lm_accumulate_step(device, *_lm_model_and_state(
        *LM_CELLS["smallthinker_accumulate_step"]
    ))


def sdar_accumulate_step(device):
    """SDAR-30B-A3B-Chat at one chip's share (``benchmark/configs/
    sdar_30b_a3b_s4096.json``; ``SDAR_LAYERS`` / ``SDAR_BATCH`` size another
    cut): four scanned layers over [noisy ; clean] = 2 x 4,096 positions —
    the block-diffusion kernels at a group of eight, q / k normed per head,
    every layer routed (SwiGLU experts, 16 of 128 held) — and the untied
    chunked head with the weighted loss over the noisy stream's 4,096."""
    args, model, state, ids = _lm_model_and_state(
        *LM_CELLS["sdar_accumulate_step"]
    )
    return _lm_accumulate_step(
        device, args, model, state, ids,
        loss_weights=jnp.zeros(ids.shape, jnp.float32),
    )


def laguna_accumulate_step(device):
    """Laguna-XS.2 at one chip's share (``benchmark/configs/
    laguna_xs2_33b_a3b_s8192.json``; ``LAGUNA_LAYERS`` / ``LAGUNA_BATCH``
    size another cut): the leading dense layer under full attention and one
    scanned period of three window-512 layers and a full one at S=8,192 —
    48 and 64 query heads over 8 kv heads, half a full head's lanes rotated
    under YaRN, a gate a head —, sigmoid top-8 of 256 (8 held) beside a
    shared expert, the untied chunked head. Its boundary programs are the
    other expert decoders' (``guarded_apply_step`` over another tree)."""
    return _lm_accumulate_step(device, *_lm_model_and_state(
        *LM_CELLS["laguna_accumulate_step"]
    ))


def kimi_accumulate_step(device):
    """Kimi-Linear-48B-A3B at one chip's share (``benchmark/configs/
    kimi_linear_48b_a3b_s8192.json``; ``KIMI_LAYERS`` / ``KIMI_BATCH`` /
    ``KIMI_REMAT`` size another cut): four KDA mixers (``kda_fwd`` /
    ``kda_bwd`` at 8 held heads of 128) and one latent-attention layer
    without RoPE (the two-width kernels at 8 heads), the dense layer and one
    unrolled period of routed layers, the untied chunked head."""
    return _lm_accumulate_step(device, *_lm_model_and_state(
        *LM_CELLS["kimi_accumulate_step"]
    ))


def nemotron_accumulate_step(device):
    """Nemotron-3-Nano-30B-A3B at one chip's share (``benchmark/configs/
    nemotron3_nano_30b_a3b_s8192.json``; ``NEMOTRON_LAYERS`` /
    ``NEMOTRON_BATCH`` / ``NEMOTRON_REMAT`` size another cut): three Mamba-2
    mixers (``ssd_fwd`` / ``ssd_bwd`` at 32 held heads in 4 groups), one
    NoPE grouped-attention layer (16 query heads over key head 0) and three
    routed layers of un-gated experts (two matrices, two sinks a layer),
    seven unrolled single-sublayer layers, the untied chunked head."""
    return _lm_accumulate_step(device, *_lm_model_and_state(
        *LM_CELLS["nemotron_accumulate_step"]
    ))


def ouro_guarded_apply_step(device):
    """The per-leaf LAMB apply of the solo boundary over the same state:
    its temp bytes are the rollback's second copy of params + moments."""
    from dedloc_tpu.parallel.train_step import (
        make_guarded_apply_step,
        zeros_like_grads,
    )
    from dedloc_tpu.roles.common import build_optimizer

    args, _model, state, _ids = _ouro_model_and_state()
    grads = jax.eval_shape(zeros_like_grads, state.params)
    return make_guarded_apply_step(build_optimizer(args)).lower(
        *_on_device(device, (state, grads))
    )


def layer_body_copies(hlo_text: str) -> list:
    """Result shapes of the ``copy`` instructions that sit directly in a
    while body of an optimized HLO module (``compiled.as_text()``)."""
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo_text))
    copies, inside = [], False
    for line in hlo_text.splitlines():
        if not line.startswith(" "):  # a computation's header, or its "}"
            header = re.match(r"%?([\w.\-]+) \(", line)
            inside = bool(header) and header.group(1) in bodies
        elif inside:
            copy = re.match(r"\s+%?[\w.\-]+ = (\w+\[[\d,]*\])\S* copy\(", line)
            if copy:
                copies.append(copy.group(1))
    return copies


def flash_fwd_forms(lowered_text: str) -> dict:
    """The flash forward call sites of a lowered module, by form: the
    one-tile call carries ``form: one_tile`` in its kernel metadata (the two
    forms share a kernel name, which is what a device trace is read by). A
    site inside a scan body counts once; a remat replay is a site more."""
    calls = [
        line for line in lowered_text.splitlines()
        if re.search(r'kernel_name = "flash_(causal_|mla_|gqa_|band_|bd_|sel_)?fwd"', line)
    ]
    # the serialized kernel body on the same line is base64: no "_" in it
    one_tile = sum("one_tile" in line for line in calls)
    return {"one_tile": one_tile, "tiles": len(calls) - one_tile}


def _flash_metadata(lowered_text: str):
    """(kernel name, its call's kernel metadata: {} where it carries none)
    of each flash call site of a lowered module."""
    for line in lowered_text.splitlines():
        name = re.search(r'kernel_name = "(flash_\w+)"', line)
        if not name:
            continue
        meta = re.search(r'kernel_metadata = "(\{[^"]*\})"', line)
        yield name.group(1), json.loads(
            meta.group(1).replace("\\0A", "").replace("\\22", '"')
        ) if meta else {}


def _by_kernel(found: dict) -> dict:
    """``found`` (kernel name -> what each call site says, repeats dropped):
    the one value where the sites agree, a list where they differ."""
    return {
        name: sites[0] if len(sites) == 1 else sites
        for name, sites in sorted(found.items())
    }


def _site(found: dict, name: str, value) -> None:
    sites = found.setdefault(name, [])
    if value not in sites:
        sites.append(value)


def flash_windows(lowered_text: str) -> dict:
    """The flash kernels of a lowered module, by kernel name: the lanes a
    head's products contract over and land in (``qk_window``, ``v_window``)
    beside the lanes of a column block (``qk_block``, ``v_block``), as a
    call carries them in its kernel metadata: 256 of 384 and 128 of 256 at
    q/k 192, v 128. ``"block"`` for a call that carries none: its windows
    are its column blocks (D=64, two heads a 128-lane tile; D=128), and
    metadata — which moves XLA's choices around a call — is kept off the
    programs that do not need it. A list where call sites of one name
    differ."""
    found = {}
    for name, meta in _flash_metadata(lowered_text):
        _site(found, name, {
            key: value for key, value in meta.items()
            if key not in ("form", "heads_a_program")
        } or "block")
    return _by_kernel(found)


def flash_heads(lowered_text: str) -> dict:
    """The query heads ONE PROGRAM of each tiled flash kernel of a lowered
    module takes, by kernel name — what ``ops/flash_attention.
    _heads_a_program`` chose from the call's shapes and the VMEM it asks
    for: the call's ``heads_a_program``, or one whole group (``heads`` /
    ``kv_heads``) for the grouped call that leaves the field out. Eight for
    Keye's selected kernels, seven for SmallThinker's, 8 forward and 4
    backward for Ouro's sixteen heads (dk / dv of the program's own kv heads
    are resident there). A one-tile call has no entry."""
    found = {}
    for name, meta in _flash_metadata(lowered_text):
        if "heads_a_program" in meta:
            _site(found, name, meta["heads_a_program"])
        elif "kv_heads" in meta:
            _site(found, name, meta["heads"] // meta["kv_heads"])
    return _by_kernel(found)


def flash_vmem_mb(lowered_text: str) -> dict:
    """MiB of scoped VMEM each flash kernel of a lowered module asks for
    (its custom call's ``scoped_memory_configs``), by kernel name; a kernel
    that runs under the compiler's own limit (16 on a v5e) is left out. The
    tiled backward holds a kv block's dk and dv for the whole sequence
    there (``ops/flash_attention._bwd_vmem``): 84 at the Keye cell's shape
    (eight heads a program), 76.75 for SmallThinker's group of seven."""
    found = {}
    for line in lowered_text.splitlines():
        name = re.search(r'kernel_name = "(flash_\w+)"', line)
        size = re.search(
            r'scoped_memory_configs\\22: \[\{[^}]*\\22size\\22: (\d+)', line
        )
        if name and size:
            _site(found, name.group(1),
                  round(int(size.group(1)) / 2**20, 2))
    return _by_kernel(found)


def kernel_calls(lowered_text: str) -> dict:
    """Call SITES of every Pallas kernel of a lowered module, by kernel
    name: which kernels a remat policy replays (a replay is a second site of
    the forward kernel, in the backward) and which it keeps the outputs
    of."""
    names = re.findall(r'kernel_name = "(\w+)"', lowered_text)
    return {name: names.count(name) for name in sorted(set(names))}


def scoped_float32_mb(hlo_text: str, scope: str) -> float:
    """MB of float32 that the instructions under the op name ``scope`` write
    outside fused computations, in an optimized HLO module
    (``compiled.as_text()``; a fusion counts by its result): what an
    element-wise op between bf16 neighbours costs in HBM when XLA does it
    in arrays of its own. Laguna's ``attn_gate`` read 7,267 before the gate
    was a kernel pair (ten float32 ``[1, 8192, 8192 | 6144]`` broadcasts, ten
    reshapes of them and ten relayout copies of the context's cotangent),
    9.4 since (PR 48): the gates and their gradients, [1, 8192, 64 | 48]."""
    total, fused = 0, False
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            fused = "fused_computation" in line
        elif not fused and f"/{scope}/" in line:
            wrote = re.match(
                r"\s+(?:ROOT )?%[\w.\-]+ = f32\[([\d,]+)\]\S* (?!bitcast|get-tuple-element|parameter)",
                line,
            )
            if wrote:
                total += 4 * int(np.prod(
                    [int(d) for d in wrote.group(1).split(",")]
                ))
    return round(total / 1e6, 1)


def expert_grad_passes(hlo_text: str) -> dict:
    """Passes over a held expert matrix's float32 gradient that move no
    FLOP, in an optimized HLO module (``compiled.as_text()``): ``adds`` —
    the entry's add instructions (alone or the root of a fusion) that read
    a ``grad_acc…experts_*`` parameter, the accumulate XLA could not fuse
    into a dynamic-trip-count loop; ``zero_fills`` — float32 ``broadcast``s
    of such a parameter's shape (a layer's matrix, or the scanned stack of
    them) outside fused computations: the backward loop's zeroed carry.
    Both 0 where the tile loop sums into the accumulator itself
    (``parallel/moe.py``'s gradient sinks). And the loops' own structure:
    ``tile_loops`` — the ``while`` instructions of a DYNAMIC trip count whose
    state carries one layer's held matrices (the routed walk: a bulk and a
    tail loop a layer and direction since PR 42, one before); ``fused_adds``
    / ``loose_adds`` — the ``old + term`` adds over one expert's float32
    ``[1, H, F]`` slice inside those loops' bodies, by whether the add rides
    the fusion of its weight-gradient ``convolution`` (the slice read and
    written once, under the dot) or is a pass of its own. ``held_casts`` —
    whole float32 -> bf16 passes over a held matrix (a layer's, or the
    scanned stack of them): ``convert``s outside fused computations and
    fusions that take the float32 matrix and return it in bf16; 0 since PR 50
    (the step is handed the bf16 matrices, ``train_step.
    _StepWithComputeCopies``), 24 in LFM2 and SDAR before (forward + remat
    replay), 3 in kanana-2 (XLA hoisted the stack's casts out of its scan)."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    shapes = set()
    for dims in re.findall(
        r"%grad_acc__\w*____experts_[\w.]+ = f32\[([\d,]+)\]\S* parameter\(", entry
    ):
        dims = dims.split(",")
        shapes.add(",".join(dims[-3:]))
        if any(d != "1" for d in dims[:-3]):
            shapes.add(",".join(dims))
    adds = sum(
        1 for line in entry.splitlines()
        if re.match(r"\s+(ROOT )?%[\w.\-]*add[\w.\-]* = ", line)
        and re.search(r"\(.*%grad_acc__\w*____experts_", line)
    )
    fills, fused = 0, False
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            fused = "fused_computation" in line
        elif not fused:
            fill = re.match(r"\s+%[\w.\-]+ = f32\[([\d,]+)\]\S* broadcast\(", line)
            fills += bool(fill) and fill.group(1) in shapes
    return {"adds": adds, "zero_fills": fills, **_tile_loops(hlo_text, shapes),
            "held_casts": _held_casts(hlo_text, shapes)}


def _held_casts(hlo_text: str, shapes: set) -> int:
    """``expert_grad_passes``' count of float32 -> bf16 passes over a held
    matrix; ``shapes``: the held matrices' dims, ``_tile_loops``'s."""
    held = "|".join(re.escape(dims) for dims in shapes)
    casting = set(re.findall(
        rf"^%?([\w.\-]+) \([^)]*f32\[(?:\d+,)*(?:{held})\][^)]*\) -> "
        rf"bf16\[(?:\d+,)*(?:{held})\]",
        hlo_text, re.M,
    ))
    casts, fused = 0, False
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            fused = "fused_computation" in line
            continue
        made = not fused and re.match(
            rf"\s+(?:ROOT )?%[\w.\-]+ = bf16\[(?:\d+,)*(?:{held})\]\S* "
            r"(convert|fusion)\(", line,
        )
        if made:
            called = re.search(r"\bcalls=%?([\w.\-]+)", line)
            casts += made.group(1) == "convert" or (
                bool(called) and called.group(1) in casting
            )
    return casts


def _tile_loops(hlo_text: str, shapes: set) -> dict:
    """``expert_grad_passes``' loop counts; ``shapes``: the held matrices'
    dims as the entry's ``grad_acc`` parameters have them."""
    computations, name = {}, None
    for line in hlo_text.splitlines():
        if line.startswith(" "):
            computations[name].append(line)
        else:
            header = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(", line)
            name = header.group(1) if header else None
            computations.setdefault(name, [])
    held = [re.escape(f"[{dims}]") for dims in shapes if dims.count(",") == 2]
    slices = {"1," + dims.split(",", 1)[1] for dims in shapes
              if dims.count(",") == 2}

    def is_slice_add(line):
        add = re.match(
            r"\s+(?:ROOT )?%[\w.\-]+ = f32\[([\d,]+)\]\S* add\(", line
        )
        return bool(add) and add.group(1) in slices

    loops = fused = loose = 0
    for lines in computations.values():
        for line in lines:
            body = re.search(r" while\(.*\bbody=%?([\w.\-]+)", line)
            if not body or "known_trip_count" in line or not any(
                re.search(shape, line.split(" while(")[0]) for shape in held
            ):
                continue
            loops += 1
            for inner in computations.get(body.group(1), []):
                loose += is_slice_add(inner)
                called = re.search(r" fusion\(.*\bcalls=%?([\w.\-]+)", inner)
                text = computations.get(called.group(1), []) if called else []
                found = sum(is_slice_add(t) for t in text)
                if any(" convolution(" in t for t in text):
                    fused += found
                else:
                    loose += found
    return {"tile_loops": loops, "fused_adds": fused, "loose_adds": loose}


def keye_accumulate_step(device):
    """Keye-VL-2.0-30B-A3B's language model at one chip's share
    (``benchmark/configs/keye_vl2_30b_a3b_s16384.json``; ``KEYE_LAYERS`` /
    ``KEYE_BATCH`` / ``KEYE_REMAT`` size another cut): four unrolled layers
    at S=16,384 — the indexer's scores and their exact top-2,048 as one
    kernel over the causal triangle (``ops/index_select.py``), the selected
    kernels at a group of eight reading the int8 selection, the indexer's
    loss as its own kernel pair over the same tiles (``ops/index_loss.py``),
    every layer routed (SwiGLU experts, 8 of 128 held) — three
    position streams and a weight a label from the batch, the untied chunked
    head. Its row carries ``largest_buffers_mb``: nothing [heads, S, S]."""
    args, model, state, ids = _lm_model_and_state(
        *LM_CELLS["keye_accumulate_step"]
    )
    return _lm_accumulate_step(
        device, args, model, state, ids,
        position_ids=jnp.zeros((3,) + ids.shape, jnp.int32),
        loss_weights=jnp.zeros(ids.shape, jnp.float32),
    )


def largest_buffers_mb(hlo_text: str, count: int = 6) -> list:
    """The largest array shapes a compiled module names, [[shape, MB], ...]:
    what a program materialises at most (a [heads, S, S] tensor would lead
    the list)."""
    sizes = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
             "u32": 4, "f32": 4}
    found = {}
    for dtype, dims in re.findall(r"\b(pred|s8|u8|bf16|f16|s32|u32|f32)"
                                  r"\[([0-9,]+)\]", hlo_text):
        n = sizes[dtype]
        for d in dims.split(","):
            n *= int(d)
        found[f"{dtype}[{dims}]"] = n / 2**20
    return [
        [shape, round(mb, 1)]
        for shape, mb in sorted(found.items(), key=lambda kv: -kv[1])[:count]
    ]


def loss_block_transients(text: str, rows: int, seq: int) -> list:
    """The float32 arrays of three or more dims — [..., ``seq``] with a dim
    of ``rows`` — and the int8 [blocks, ``rows``, ``seq``] slabs that a
    module's text names (HLO's ``f32[128,16,16384]`` or MLIR's
    ``tensor<128x16x16384xf32>``): the transients of the indexer's loss
    where it is a loop over blocks of ``rows`` query rows (the index scores
    [rows, 16, S], the main scores [4, 8, rows, S], the selection cut into
    its blocks) — and of the selection where IT is such a loop (the index
    scores, the selection written a block at a time). [] since the loss is
    a kernel pair (PR 52) and the selection a kernel (PR 54)."""
    found = set()
    for dtype, dims in re.findall(r"\b(f32|s8)\[([0-9,]+)\]", text) + [
        (dtype, dims.replace("x", ","))
        for dims, dtype in re.findall(r"tensor<([0-9x]+)x(f32|i8)>", text)
    ]:
        sizes = [int(d) for d in dims.split(",")]
        if len(sizes) >= 3 and sizes[-1] == seq and rows in sizes[:-1] and (
            dtype == "f32" or sizes[-2] == rows
        ):
            found.add(f"{dtype}[{dims}]")
    return sorted(found)


# programs whose row also carries ``kernel_calls`` (the others print the
# rows they always did), and those with a routed expert layer, whose row
# carries ``expert_grad_passes``
COUNT_KERNEL_CALLS = {"gqa_kernels", "lfm2_accumulate_step", "band_kernels",
                      "smallthinker_accumulate_step", "bd_kernels",
                      "sdar_accumulate_step", "laguna_kernels",
                      "head_gate_kernels", "laguna_accumulate_step",
                      "sel_kernels", "keye_accumulate_step",
                      "index_loss_kernels", "kda_kernels",
                      "kimi_accumulate_step", "select_kernels",
                      "ssd_kernels", "nemotron_accumulate_step"}
COUNT_EXPERT_GRAD_PASSES = {"kanana_accumulate_step", "lfm2_accumulate_step",
                            "smallthinker_accumulate_step",
                            "sdar_accumulate_step",
                            "laguna_accumulate_step",
                            "keye_accumulate_step",
                            "kimi_accumulate_step",
                            "nemotron_accumulate_step"}
NO_V5E = 3  # exit code: nothing to compile with, which is not a failure


PROGRAMS = {
    fn.__name__: fn for fn in (
        accumulate_step, flat_apply_step, kernels, ouro_accumulate_step,
        ouro_guarded_apply_step, mla_kernels, kanana_accumulate_step,
        gqa_kernels, lfm2_accumulate_step, band_kernels,
        smallthinker_accumulate_step, bd_kernels, sdar_accumulate_step,
        laguna_kernels, laguna_accumulate_step, head_gate_kernels,
        sel_kernels, keye_accumulate_step, index_loss_kernels,
        kda_kernels, kimi_accumulate_step, select_kernels,
        ssd_kernels, nemotron_accumulate_step, long_row_kernels,
    )
}


def v5e_device():
    """One (absent) device of a described v5e host, or None with the reason
    on stderr where this jaxlib and libtpu cannot describe one."""
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices[0]
    except Exception as e:  # whatever this jaxlib raises without libtpu
        print(f"no v5e:2x2 topology can be described here: {e!r}",
              file=sys.stderr)
        return None


def main(argv=None) -> int:
    names = list(argv if argv is not None else sys.argv[1:]) or list(PROGRAMS)
    device = v5e_device()
    if device is None:
        return NO_V5E
    for name in names:
        with lowering_for_tpu():  # Mosaic kernels, not the interpreter
            lowered = PROGRAMS[name](device)
        start = time.perf_counter()
        compiled = lowered.compile()
        seconds = round(time.perf_counter() - start, 2)
        memory = compiled.memory_analysis()
        lowered_text = lowered.as_text()
        compiled_text = compiled.as_text()
        extra = (
            {"kernel_calls": kernel_calls(lowered_text)}
            if name in COUNT_KERNEL_CALLS else {}
        )
        if name in COUNT_EXPERT_GRAD_PASSES:
            extra["expert_grad_passes"] = expert_grad_passes(compiled_text)
        if name == "laguna_accumulate_step":
            extra["attn_gate_float32_mb"] = scoped_float32_mb(
                compiled_text, "attn_gate"
            )
        if name == "keye_accumulate_step":
            from dedloc_tpu.models.keye_vl2 import (
                INDEX_BLOCK_ROWS,
                INDEX_LOSS_BLOCK_ROWS,
            )

            extra["largest_buffers_mb"] = largest_buffers_mb(compiled_text)
            seq = _lm_model_and_state(*LM_CELLS[name])[3].shape[1]
            for key, rows in (("loss", INDEX_LOSS_BLOCK_ROWS),
                              ("select", INDEX_BLOCK_ROWS)):
                extra[f"{key}_block_transients"] = loss_block_transients(
                    lowered_text + compiled_text, rows, seq
                )
        if name in LM_CELLS:
            # the layer policy the program was built under; ``memory`` below
            # is what it costs (``temp_bytes``: the stash is inside it)
            extra["remat_policy"] = _lm_model_and_state(
                *LM_CELLS[name]
            )[1].cfg.remat_policy
        print(json.dumps({
            "program": name,
            "device_kind": device.device_kind,
            "compile_s": seconds,
            "tpu_custom_calls": lowered_text.count("tpu_custom_call"),
            "flash_fwd_forms": flash_fwd_forms(lowered_text),
            "flash_windows": flash_windows(lowered_text),
            "flash_vmem_mb": flash_vmem_mb(lowered_text),
            "flash_heads": flash_heads(lowered_text),
            "layer_body_copies": layer_body_copies(compiled_text),
            "memory": {
                "argument_bytes": memory.argument_size_in_bytes,
                "output_bytes": memory.output_size_in_bytes,
                "temp_bytes": memory.temp_size_in_bytes,
                "alias_bytes": memory.alias_size_in_bytes,
            },
            **extra,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
