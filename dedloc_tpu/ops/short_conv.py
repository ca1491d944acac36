"""The doubly gated short causal convolution of a conv-hybrid decoder
(LFM2's ``conv`` mixer) as a Pallas TPU kernel pair, forward + backward.

    (B | C | u) = bcu [.., S, 3H]     (``in_proj``'s output, as it wrote it)
    z = B ⊙ u
    c_t = w[:, 0] ⊙ z_{t-2} + w[:, 1] ⊙ z_{t-1} + w[:, 2] ⊙ z_t
                                      (causal, depthwise, zeros before the row)
    y = C ⊙ c                         ([.., S, H], what ``out_proj`` reads)

Memory-bound and element-wise, so the op is about bytes: XLA's version splits
``bcu`` into three arrays, pads and shifts ``z`` twice and concatenates the
three gradients; here B, C and u are read IN PLACE as column blocks of the
one ``bcu`` array (lane offsets 0, H, 2H: three BlockSpecs over one operand)
and the backward writes ``d_bcu`` [.., S, 3H] as ONE array — the operand of
``in_proj``'s weight-gradient matmul — through a last grid axis of three
steps that each store one third of what the first computed (the blocks whose
index that axis does not move are not fetched again).

Kernel structure: grid (column block, batch row, row block[, third]); a
program sees a (rows, lanes) tile of each operand plus a HALO — the 16 rows
(one packed bf16 tile) before it for B and u, whose last two carry
``z_{t-1}``, ``z_{t-2}`` across the block edge, and in the backward the 16 rows
after it for C and dy: ``dz_t = w2 g_t + w1 g_{t+1} + w0 g_{t+2}`` with
``g = dy ⊙ C`` looks the other way. Row shifts are sublane rotations with the
halo's rows selected into the rows that wrapped. Products are float32,
storage is the input's dtype (bf16). ``dw`` [H, 3] accumulates in float32 in
its output block over every (batch row, row block) of a column block.

Off-TPU the same kernels run under ``interpret=True``
(``utils.backend.pallas_interpret``), as the flash kernels do.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import Mesh, PartitionSpec as P

from dedloc_tpu.ops.flash_attention import _pick_block, mesh_axis
from dedloc_tpu.utils.backend import pallas_interpret

TAPS = 3
HALO = 16  # rows of one packed bf16 tile: the least a BlockSpec can fetch
ROWS, LANES = 256, 512  # a program's tile: 0.5 MB a float32 temporary


def _rows(ref, live):
    """A halo block in float32, zeros where the row has no neighbour there
    (``live``: a traced bool, the block is inside the row)."""
    x = ref[:].astype(jnp.float32)
    return jnp.where(live, x, jnp.zeros_like(x))


def _shift_down(z, before, n: int):
    """z_{t-n} over a row block: the block rotated down by n rows, the rows
    that wrapped taken from the end of the halo ``before``."""
    out = pltpu.roll(z, n, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
    for r in range(n):
        at = before.shape[0] - n + r
        out = jnp.where(row == r, before[at:at + 1, :], out)
    return out


def _shift_up(g, after, n: int):
    """g_{t+n}: rotated up, the wrapped rows from the start of ``after``."""
    rows = g.shape[0]
    out = pltpu.roll(g, rows - n, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    for r in range(n):
        out = jnp.where(row == rows - n + r, after[r:r + 1, :], out)
    return out


def _conv(z, before, w_ref):
    """(c, z_{t-1}, z_{t-2}) of one tile; ``w_ref`` rows 0..2 the taps."""
    z1, z2 = _shift_down(z, before, 1), _shift_down(z, before, 2)
    return w_ref[2:3, :] * z + w_ref[1:2, :] * z1 + w_ref[0:1, :] * z2, z1, z2


def _fwd_kernel(b_ref, c_ref, u_ref, b_before, u_before, w_ref, y_ref):
    inside = pl.program_id(2) > 0
    z = b_ref[:].astype(jnp.float32) * u_ref[:].astype(jnp.float32)
    before = _rows(b_before, inside) * _rows(u_before, inside)
    conv, _z1, _z2 = _conv(z, before, w_ref)
    y_ref[:] = (c_ref[:].astype(jnp.float32) * conv).astype(y_ref.dtype)


def _bwd_kernel(b_ref, c_ref, u_ref, dy_ref, b_before, u_before, c_after,
                dy_after, w_ref, d_ref, dw_ref, parts_ref):
    third = pl.program_id(3)
    # (program ids are read here: a ``pl.when`` body cannot, off the TPU)
    inside = pl.program_id(2) > 0
    more = pl.program_id(2) < pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0) & (third == 0))
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    @pl.when(third == 0)
    def _compute():
        b = b_ref[:].astype(jnp.float32)
        u = u_ref[:].astype(jnp.float32)
        c = c_ref[:].astype(jnp.float32)
        dy = dy_ref[:].astype(jnp.float32)
        z = b * u
        conv, z1, z2 = _conv(
            z, _rows(b_before, inside) * _rows(u_before, inside), w_ref
        )
        g = dy * c
        after = _rows(c_after, more) * _rows(dy_after, more)
        dz = (
            w_ref[2:3, :] * g + w_ref[1:2, :] * _shift_up(g, after, 1)
            + w_ref[0:1, :] * _shift_up(g, after, 2)
        )
        parts_ref[0] = (dz * u).astype(parts_ref.dtype)  # dB
        parts_ref[1] = (dy * conv).astype(parts_ref.dtype)  # dC
        parts_ref[2] = (dz * b).astype(parts_ref.dtype)  # du
        for tap, shifted in enumerate((z2, z1, z)):
            dw_ref[tap:tap + 1, :] += jnp.sum(
                g * shifted, axis=0, keepdims=True
            )

    d_ref[:] = parts_ref[third]


def _tiles(bcu):
    batch, seq, width = bcu.shape
    hidden = width // TAPS
    rows = _pick_block(seq, ROWS)
    lanes = _pick_block(hidden, LANES)
    if seq % HALO or rows % HALO or (lanes % 128 and lanes != hidden):
        raise ValueError(
            f"short_conv takes rows in whole tiles of {HALO} and lanes of "
            f"128 (or the whole width): got [{seq}, 3 x {hidden}]"
        )
    return batch, seq, hidden, rows, lanes


def _padded_taps(w):
    """w [H, 3] -> [8, H] float32: taps as rows (one sublane tile)."""
    return jnp.zeros((8, w.shape[0]), jnp.float32).at[:TAPS].set(
        w.astype(jnp.float32).T
    )


def _specs(rows, lanes, hidden, seq):
    """BlockSpecs over a [.., S, k·H] array for grid (column block, batch
    row, row block, ...): ``part(k)`` the tile of the k-th H-wide part,
    ``before(k)`` / ``after(k)`` the halo tiles around it."""
    across = hidden // lanes
    per = rows // HALO

    def part(k):
        return pl.BlockSpec(
            (None, rows, lanes), lambda c, n, j, *_: (n, j, k * across + c)
        )

    def before(k):
        return pl.BlockSpec(
            (None, HALO, lanes),
            lambda c, n, j, *_: (
                n, jnp.maximum(j * per - 1, 0), k * across + c
            ),
        )

    def after(k):
        return pl.BlockSpec(
            (None, HALO, lanes),
            lambda c, n, j, *_: (
                n, jnp.minimum((j + 1) * per, seq // HALO - 1),
                k * across + c,
            ),
        )

    taps = pl.BlockSpec((8, lanes), lambda c, n, j, *_: (0, c))
    return part, before, after, taps


def _forward(bcu, w, interpret):
    batch, seq, hidden, rows, lanes = _tiles(bcu)
    part, before, _after, taps = _specs(rows, lanes, hidden, seq)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(hidden // lanes, batch, seq // rows),
        in_specs=[part(0), part(1), part(2), before(0), before(2), taps],
        out_specs=part(0),
        out_shape=jax.ShapeDtypeStruct((batch, seq, hidden), bcu.dtype),
        interpret=interpret,
        name="short_conv_fwd",
    )(bcu, bcu, bcu, bcu, bcu, _padded_taps(w))


def _backward(bcu, w, dy, interpret):
    batch, seq, hidden, rows, lanes = _tiles(bcu)
    part, before, after, taps = _specs(rows, lanes, hidden, seq)
    across = hidden // lanes
    d_bcu, dw = pl.pallas_call(
        _bwd_kernel,
        grid=(across, batch, seq // rows, TAPS),
        in_specs=[
            part(0), part(1), part(2), part(0),  # B, C, u of bcu; dy
            before(0), before(2), after(1), after(0), taps,
        ],
        out_specs=[
            pl.BlockSpec(
                (None, rows, lanes),
                lambda c, n, j, third: (n, j, third * across + c),
            ),
            taps,
        ],
        out_shape=[
            jax.ShapeDtypeStruct(bcu.shape, bcu.dtype),
            jax.ShapeDtypeStruct((8, hidden), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((TAPS, rows, lanes), bcu.dtype)],
        interpret=interpret,
        name="short_conv_bwd",
    )(bcu, bcu, bcu, dy, bcu, bcu, bcu, dy, _padded_taps(w))
    return d_bcu, dw[:TAPS].T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _short_conv(bcu, w, interpret):
    return _forward(bcu, w, interpret)


def _short_conv_fwd(bcu, w, interpret):
    return _forward(bcu, w, interpret), (bcu, w)


def _short_conv_bwd(interpret, residuals, dy):
    bcu, w = residuals
    return _backward(bcu, w, dy, interpret)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv(bcu: jnp.ndarray, w: jnp.ndarray,
               interpret: Optional[bool] = None,
               mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """y [B, S, H] = C ⊙ causal_conv3(B ⊙ u) from ``bcu`` [B, S, 3H] =
    (B | C | u) and the depthwise taps ``w`` [H, 3] (``w[:, 2]`` multiplies
    the current position). Differentiable in both. ``mesh``: as
    ``flash_attention``'s — the op runs per batch shard under ``shard_map``
    (a Mosaic kernel cannot be partitioned by GSPMD)."""
    if interpret is None:
        interpret = pallas_interpret()

    def local(x, taps):
        # named where the kernels take it, as ``_flash_local`` names q / k /
        # v: the ``kernel_operands`` remat policy keeps what the backward
        # kernel reads, as ONE device sees it
        return _short_conv(checkpoint_name(x, "short_conv_bcu"), taps,
                           interpret)

    if mesh is not None:
        rows = P(mesh_axis(mesh, "data"), None, None)
        return jax.shard_map(
            local, mesh=mesh, in_specs=(rows, P()), out_specs=rows,
            check_vma=False,
        )(bcu, w)
    return local(bcu, w)


def short_conv_reference(bcu, w):
    """The same function as a shifted sum in plain ``jax.numpy`` (the
    operands' dtype throughout): what the kernels are tested against."""
    b, c, u = jnp.split(bcu, TAPS, axis=-1)
    z = b * u
    pad = jnp.pad(z, ((0, 0), (TAPS - 1, 0), (0, 0)))
    seq = z.shape[1]
    conv = sum(
        w[:, k].astype(z.dtype) * pad[:, k:k + seq] for k in range(TAPS)
    )
    return c * conv
