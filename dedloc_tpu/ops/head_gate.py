"""A per-head output gate as a Pallas TPU kernel pair, forward + backward.

    gated[.., h·D + d] = ctx[.., h·D + d] · gate[.., h]

for ``ctx`` [B, S, H·D] (bf16: what the flash kernels wrote, what ``o_proj``
reads) and ``gate`` [B, S, H] (float32: ``decoder.head_gate``): the product in
float32, stored in ``ctx``'s dtype — the arithmetic of
``(ctx.reshape(B, S, H, D) * gate[..., None]).astype(ctx.dtype)`` to the bit.
The backward, from ``(dy, ctx, gate)``: ``d_ctx = dy · gate`` (float32
product, stored in ``ctx``'s dtype) and ``d_gate[.., h] = Σ_d dy · ctx`` over
the head's lanes, in float32.

Memory-bound and element-wise, so the op is about bytes. The gate has the
heads on LANES where ``[.., H, D]`` wants them on sublanes, and XLA:TPU does
not make that move inside a fusion: it wrote the gate out as a float32
``[B, S, H·D]`` array twice (a ``broadcast`` and a ``reshape`` of it) in the
forward of every layer and again in its backward, with two float32 relayout
``copy``s of the context's cotangent beside them — 4.4 GB of traffic a layer
for a multiply whose operands are 134 MB (Laguna's cell, PERF.md section 6,
PR 48). Here a head's gate is ONE lane of the gate's tile, broadcast over the
head's ``D`` lanes in registers, and every array is read or written once in
the ``[.., H·D]`` layout both neighbours use.

Kernel structure: rows are (batch, position) pairs, ``[B·S, H·D]``; grid
(row block, group of heads), a program sees ``ROWS`` rows of a group's
``GROUP x D`` lanes (``D`` a multiple of 128) beside the rows' whole gate
tile ``[ROWS, H]`` — fetched once a row block: its block index does not move
with the group — and walks the group in a static loop over lane slices of
``D``. A head's gate column is a masked lane reduction of that tile (one
lane survives: exact), which leaves it replicated over lanes, so the
broadcast over the head's lanes costs nothing more. ``d_gate``'s column of a
head is a lane reduction of ``dy · ctx``, placed by a lane select into the
``[ROWS, H]`` output tile, which stays in place over a row block's groups.
A grid axis over groups keeps a kernel's body at ``GROUP`` heads: a loop
over all 64 made the pair 0.6 s to trace and lower at each of fifteen
sites, 17 s of a trainer's start (PERF.md section 5, PR 48). A last block
that the rows do not fill computes on padding and stores what is inside. On
the v5e at (1, 8192, 64 x 128): 0.40 ms forward and 0.58 ms backward a
call, 83 % and 85 % of what their bytes take at 819 GB/s (their schedules,
1,486 and 2,729 bundles a program x 128 programs, are a third of that).

Off-TPU the same kernels run under ``interpret=True``
(``utils.backend.pallas_interpret``), as the flash kernels do.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from dedloc_tpu.utils.backend import pallas_interpret

LANES = 128
ROWS = 512  # a program's rows: 1 MB a bf16 block at GROUP heads of 128
GROUP = 8  # heads a program, at most: the static loop of a kernel's body


def _columns(gate_ref, heads: int, width: int):
    """(the gate's tile, lanes' head numbers, [(head of the tile, its lanes
    of the context's block)]) of this program's group."""
    first = pl.program_id(1) * heads
    gate = gate_ref[:]
    lane = jax.lax.broadcasted_iota(jnp.int32, gate.shape, 1)
    return gate, lane, [
        (first + h, slice(h * width, (h + 1) * width)) for h in range(heads)
    ]


def _column(gate, lane, head):
    """gate[:, head] as a [rows, 1] column, ``head`` traced: a masked lane
    reduction, whose result Mosaic holds replicated over lanes (the
    broadcast over a head's ``D`` lanes is then free)."""
    return jnp.sum(
        jnp.where(lane == head, gate, 0.0), axis=-1, keepdims=True
    )


def _fwd_kernel(ctx_ref, gate_ref, out_ref, *, heads, width):
    gate, lane, group = _columns(gate_ref, heads, width)
    for head, lanes in group:
        out_ref[:, lanes] = (
            ctx_ref[:, lanes].astype(jnp.float32) * _column(gate, lane, head)
        ).astype(out_ref.dtype)


def _bwd_kernel(dy_ref, ctx_ref, gate_ref, d_ctx_ref, d_gate_ref, *, heads,
                width):
    gate, lane, group = _columns(gate_ref, heads, width)
    # the [rows, H] tile stays in place over a row block's groups
    d_gate = jnp.where(lane < group[0][0], d_gate_ref[:], 0.0)
    for head, lanes in group:
        dy = dy_ref[:, lanes].astype(jnp.float32)
        d_ctx_ref[:, lanes] = (
            dy * _column(gate, lane, head)
        ).astype(d_ctx_ref.dtype)
        d_gate = jnp.where(lane == head, jnp.sum(
            dy * ctx_ref[:, lanes].astype(jnp.float32), axis=-1,
            keepdims=True,
        ), d_gate)
    d_gate_ref[:] = d_gate


def _call(kernel, name, rows_of, gate, out_shapes, interpret):
    """``kernel`` over (row block, group of heads): ``rows_of`` [N, H·D]
    operands, then the gate [N, H]; outputs [N, H·D] or [N, H] by their
    width."""
    count, heads = gate.shape
    group = max(g for g in range(1, GROUP + 1) if heads % g == 0)
    wide = rows_of[0].shape[-1] // heads * group
    rows = min(ROWS, count)
    spec = {
        rows_of[0].shape[-1]: pl.BlockSpec((rows, wide), lambda i, j: (i, j)),
        heads: pl.BlockSpec((rows, heads), lambda i, j: (i, 0)),
    }
    return pl.pallas_call(
        functools.partial(kernel, heads=group, width=wide // group),
        grid=(pl.cdiv(count, rows), heads // group),
        in_specs=[spec[x.shape[-1]] for x in (*rows_of, gate)],
        out_specs=[spec[x.shape[-1]] for x in out_shapes],
        out_shape=out_shapes,
        interpret=interpret,
        name=name,
    )(*rows_of, gate)


def _forward(ctx, gate, interpret):
    return _call(
        _fwd_kernel, "head_gate_fwd", [ctx], gate,
        [jax.ShapeDtypeStruct(ctx.shape, ctx.dtype)], interpret,
    )[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _head_gate(ctx, gate, interpret):
    return _forward(ctx, gate, interpret)


def _head_gate_fwd(ctx, gate, interpret):
    return _forward(ctx, gate, interpret), (ctx, gate)


def _head_gate_bwd(interpret, residuals, dy):
    ctx, gate = residuals
    return tuple(_call(
        _bwd_kernel, "head_gate_bwd", [dy, ctx], gate,
        [jax.ShapeDtypeStruct(ctx.shape, ctx.dtype),
         jax.ShapeDtypeStruct(gate.shape, gate.dtype)], interpret,
    ))


_head_gate.defvjp(_head_gate_fwd, _head_gate_bwd)


def gate_heads(ctx: jnp.ndarray, gate: jnp.ndarray,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """``ctx`` [B, S, H·D] with each head's ``D`` lanes times its ``gate``
    [B, S, H] (float32), in ``ctx``'s dtype and layout. Differentiable in
    both. The kernels take heads of whole 128-lane tiles; another width
    keeps the XLA expression (``gate_heads_xla``). Row- and head-local, but
    a Mosaic kernel cannot be partitioned by GSPMD and this op carries no
    ``shard_map``: a caller on a mesh keeps the XLA expression too
    (``decoder.GroupedQueryAttention`` does; no gated model runs on one)."""
    heads = gate.shape[-1]
    if ctx.shape[-1] % (heads * LANES):
        return gate_heads_xla(ctx, gate)
    if interpret is None:
        interpret = pallas_interpret()
    return _head_gate(
        ctx.reshape(-1, ctx.shape[-1]), gate.reshape(-1, heads), interpret
    ).reshape(ctx.shape)


def gate_heads_xla(ctx, gate):
    """The same function in plain ``jax.numpy``: what the kernels are tested
    against, and what every caller the kernels do not serve runs."""
    heads = gate.shape[-1]
    per_head = ctx.reshape(*gate.shape, ctx.shape[-1] // heads)
    return (per_head * gate[..., None]).astype(ctx.dtype).reshape(ctx.shape)
