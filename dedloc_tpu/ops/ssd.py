"""Mamba-2's state-space scan (SSD, arXiv 2405.21060) — a diagonal linear
recurrence with ONE scalar decay a head and token — chunked, as a Pallas TPU
kernel pair, forward + backward. Per head h of group g, state S [N, P]
(state x head dim), S_0 = 0:

    S_t = e^{a_t} S_{t-1} + dt_t B_{t,g} x_{t,h}ᵀ       a_t = dt_t · A_h <= 0
    y_t = S_tᵀ C_{t,g} + D_h x_{t,h}

B and C are shared by the heads of a GROUP. ``ssd_recurrence`` is that, token
by token, in float32: the kernels' oracle and the model's ``"dense"`` path.
The kernels take a chunk of Q tokens a step (128: the published
``chunk_size``). With G the cumulative sum of a INSIDE a chunk and S the
state entering it:

    CB = C Bᵀ                                   [Q, Q], ONCE a group
    L_h[t, s] = e^{G_t - G_s}  (s <= t), else 0   the DIFFERENCE first, the
                mask before the exp: nothing positive is exponentiated
    Y_h = (L_h ⊙ CB)(dt ⊙ X_h) + e^{G} ⊙ (C S)_h + D_h X_h
    S_next = e^{G_Q} S + Bᵀ (e^{G_Q - G} dt ⊙ X)

One grid step is one chunk of one GROUP: the group's heads lie side by side
on the lanes ([Q, heads · P], the layout the in-projection wrote), and so
do their states, ONE float32 [N, heads · P] array in VMEM scratch carried
across the row's chunks (the chunk axis is sequential). The two products
against the state are one matmul a group each, with the heads' scalars
applied element-wise (``_widen``: a head's scalar over its P lanes); only
the masked intra-chunk product is a head at a time — and there the heads of
one 128-lane tile go through the MXU TOGETHER: their L_h ⊙ CB side by side
along the contraction against their X stacked below each other with the
other heads' lanes zeroed, so no array is ever sliced at half a lane tile
(P = 64). G is a float32 cumulative sum taken by XLA before the call (both
layouts: a column a head for the rows t, a row a head for the columns s —
1 MB a call); x, B, C travel in the compute dtype and every other matmul
operand is rounded to it as the flash kernels round p; dt, a, G, the state
and every accumulation are float32.

The forward also writes each chunk's ENTERING state (float32 [B, S / Q, N,
H · P]); the backward sweeps the chunks in reverse carrying dS, recomputes a
chunk's products from the operands and that state, and returns dx, ddt, da,
dB, dC (summed over a group's heads) and dD. The sums over a head's lanes
(ddt, dG) leave the MXU against a 0/1 matrix, the float32 operand split in
three bf16 parts (exact to float32's 24 bits), already lane-dense (a row a
head); the reverse cumulative sum that turns dG into da is XLA's.

Off-TPU the same kernels run under ``interpret=True``
(``utils.backend.pallas_interpret``), as every kernel of ``ops/`` does.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dedloc_tpu.ops.kda import _parts  # float32 as bf16 parts that sum to it
from dedloc_tpu.utils.backend import pallas_interpret

CHUNK = 128
LANES = 128
_F32 = jnp.float32


def ssd_recurrence(x, dt, a, B, C, D, return_state: bool = False):
    """The recurrence token by token in float32 at full matmul precision.
    x [B, S, H, P]; dt, a [B, S, H]; B, C [B, S, G, N] (head h reads group
    h // (H / G)); D [H]. Returns y [B, S, H, P] float32 (and the final
    state [B, H, N, P])."""
    x, dt, a, B, C, D = (v.astype(_F32) for v in (x, dt, a, B, C, D))
    batch, _seq, heads, dim = x.shape
    per_group = heads // B.shape[2]
    hi = jax.lax.Precision.HIGHEST

    def step(state, inputs):
        x_t, dt_t, a_t, b_t, c_t = inputs
        b_t, c_t = (jnp.repeat(v, per_group, axis=1) for v in (b_t, c_t))
        state = state * jnp.exp(a_t)[..., None, None] + (
            (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
        )
        y_t = jnp.einsum("bhnp,bhn->bhp", state, c_t, precision=hi)
        return state, y_t + D[:, None] * x_t

    state, y = jax.lax.scan(
        step, jnp.zeros((batch, heads, B.shape[-1], dim), _F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, a, B, C)),
    )
    y = jnp.moveaxis(y, 0, 1)
    return (y, state) if return_state else y


# --- inside a kernel: values of one grid step -----------------------------


def _mm(a, b, contract_a: int, contract_b: int):
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((), ())),
        preferred_element_type=_F32,
    )


class _Group:
    """The lane layout of a group's heads and the index arrays of a chunk,
    made once a grid step: ``heads`` heads of ``dim`` lanes side by side,
    in ``tiles`` tiles of ``per`` heads (a tile is 128 lanes, or the whole
    group where it is narrower)."""

    def __init__(self, heads: int, dim: int, chunk: int, dtype):
        width = heads * dim
        self.heads, self.dim, self.chunk, self.dtype = heads, dim, chunk, dtype
        self.tile = min(LANES, width)
        self.per, self.tiles = self.tile // dim, width // self.tile
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.causal = col <= row
        # which head of its tile a lane belongs to
        self.lane_head = jax.lax.broadcasted_iota(
            jnp.int32, (chunk, self.tile), 1
        ) // dim

    def widen(self, cols):
        """[R, heads] -> [R, heads · dim]: a head's scalar over its lanes."""
        rows = cols.shape[0]
        lane_head = self.lane_head[:rows]
        tiles = []
        for t in range(self.tiles):
            first = t * self.per
            wide = jnp.broadcast_to(cols[:, first:first + 1], (rows, self.tile))
            for j in range(1, self.per):
                wide = jnp.where(
                    lane_head >= j,
                    jnp.broadcast_to(
                        cols[:, first + j:first + j + 1], (rows, self.tile)
                    ),
                    wide,
                )
            tiles.append(wide)
        return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)

    def head_rows(self, wide):
        """[R, heads · dim] float32 -> [heads, R]: each head's sum over its
        lanes, by the MXU against a 0/1 matrix, exact to float32 (three bf16
        parts stacked along the contraction), a row a head."""
        width = self.heads * self.dim
        belongs = (
            jax.lax.broadcasted_iota(jnp.int32, (self.heads, width), 1)
            // self.dim
            == jax.lax.broadcasted_iota(jnp.int32, (self.heads, width), 0)
        ).astype(self.dtype)
        parts = _parts(wide, 3, self.dtype)
        if len(parts) == 1:
            return _mm(belongs, parts[0], 1, 1)
        return _mm(
            jnp.concatenate([belongs] * len(parts), axis=1),
            jnp.concatenate(parts, axis=1), 1, 1,
        )

    def decay(self, g_cols, g_rows, head: int):
        """L_h [Q, Q] float32: e^{G_t - G_s} on and under the diagonal."""
        diff = g_cols[:, head:head + 1] - g_rows[head:head + 1, :]
        return jnp.where(self.causal, jnp.exp(jnp.minimum(diff, 0.0)), 0.0)

    def own_lanes(self, tile_values, j: int):
        """A tile's values with the lanes of every head but its ``j``-th
        zeroed."""
        if self.per == 1:
            return tile_values
        return jnp.where(
            self.lane_head == j, tile_values, jnp.zeros_like(tile_values)
        )

    def intra(self, masks, values):
        """Σ_s M_h[t, s] values_h[s] for the heads of ONE tile as one
        matmul: the heads' M side by side along the contraction against
        their values stacked, each with the other heads' lanes zeroed."""
        if self.per == 1:
            return _mm(masks[0], values, 1, 0)
        return _mm(
            jnp.concatenate(masks, axis=1),
            jnp.concatenate(
                [self.own_lanes(values, j) for j in range(self.per)], axis=0
            ), 1, 0,
        )


def _common(lay: _Group, x_ref, b_ref, c_ref, cols_ref, rows_ref):
    cd = lay.dtype
    x, bm, cm = x_ref[:], b_ref[:], c_ref[:]
    cols, g_rows = cols_ref[:], rows_ref[:]
    dt, g_cols = cols[:, :lay.heads], cols[:, lay.heads:]
    xf = x.astype(_F32)
    dt_wide = lay.widen(dt)
    last = g_cols[lay.chunk - 1:lay.chunk, :]
    return dict(
        x=x, xf=xf, bm=bm, cm=cm, g_cols=g_cols, g_rows=g_rows,
        dt_wide=dt_wide, xdt=(xf * dt_wide).astype(cd),
        cb=_mm(cm, bm, 1, 1),
        e_g=lay.widen(jnp.exp(g_cols)),
        # e^{G_Q - G_s}: what is left of a token's write at the chunk's end
        tail=lay.widen(jnp.exp(last - g_cols)),
        e_last=lay.widen(jnp.exp(last)),
    )


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, y_ref,
                states_ref, final_ref, state_ref, *, heads: int, dim: int,
                chunk: int):
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[:] = jnp.zeros_like(state_ref)

    lay = _Group(heads, dim, chunk, cd)
    v = _common(lay, x_ref, b_ref, c_ref, cols_ref, rows_ref)
    state = state_ref[:]
    states_ref[:] = state
    tiles = []
    for t in range(lay.tiles):
        masks = [
            (lay.decay(v["g_cols"], v["g_rows"], t * lay.per + j)
             * v["cb"]).astype(cd)
            for j in range(lay.per)
        ]
        tiles.append(lay.intra(
            masks, v["xdt"][:, t * lay.tile:(t + 1) * lay.tile]
        ))
    y = (tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1))
    y = y + v["e_g"] * _mm(v["cm"], state.astype(cd), 1, 0)
    y_ref[:] = (y + d_ref[:] * v["xf"]).astype(y_ref.dtype)
    state = v["e_last"] * state + _mm(
        v["bm"], (v["xf"] * (v["dt_wide"] * v["tail"])).astype(cd), 0, 0
    )
    state_ref[:] = state
    # resident over the chunk axis: what leaves the row is written back
    final_ref[:] = state


def _bwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, d_ref, states_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dg_ref, dd_ref,
                dstate_ref, *, heads: int, dim: int, chunk: int):
    cd = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_ref[:] = jnp.zeros_like(dstate_ref)
        dd_ref[:] = jnp.zeros_like(dd_ref)

    lay = _Group(heads, dim, chunk, cd)
    v = _common(lay, x_ref, b_ref, c_ref, cols_ref, rows_ref)
    xf, bm, cm, cb = v["xf"], v["bm"], v["cm"], v["cb"]
    dy = dy_ref[:]
    dyf = dy.astype(_F32)
    # against the state that entered the chunk and the cotangent of the one
    # that left it
    state, d_next = states_ref[:], dstate_ref[:]
    state_c, d_next_c = state.astype(cd), d_next.astype(cd)
    read = _mm(cm, state_c, 1, 0)  # C S, [Q, W]
    dy_decayed = (dyf * v["e_g"]).astype(cd)
    written = _mm(bm, d_next_c, 1, 0)  # B dS_next, [Q, W]
    d_cb = jnp.zeros((chunk, chunk), _F32)
    d_xdt, y_intra = [], []
    for t in range(lay.tiles):
        lanes = slice(t * lay.tile, (t + 1) * lay.tile)
        x_tile, dy_tile = v["xdt"][:, lanes], dy[:, lanes]
        masks = []
        for j in range(lay.per):
            decay = lay.decay(v["g_cols"], v["g_rows"], t * lay.per + j)
            # dM_h = dy_h (dt ⊙ x_h)ᵀ; only where L_h is not zero
            d_cb = d_cb + decay * _mm(
                lay.own_lanes(dy_tile, j), x_tile, 1, 1
            )
            masks.append((decay * cb).astype(cd))
        y_intra.append(lay.intra(masks, x_tile))
        # M_hᵀ dy_h: the heads' M below each other, contracted over t
        d_xdt.append(_mm(
            masks[0] if lay.per == 1 else jnp.concatenate(masks, axis=0),
            dy_tile if lay.per == 1 else jnp.concatenate(
                [lay.own_lanes(dy_tile, j) for j in range(lay.per)], axis=0
            ), 0, 0,
        ))
    join = lambda xs: xs[0] if len(xs) == 1 else jnp.concatenate(xs, 1)  # noqa
    d_xdt_intra, y_intra = join(d_xdt), join(y_intra)
    d_xdt = d_xdt_intra + v["tail"] * written
    dx_ref[:] = (v["dt_wide"] * d_xdt + d_ref[:] * dyf).astype(dx_ref.dtype)
    d_dt = d_xdt * xf  # Σ over a head's lanes: ddt
    # dG_t = what e^{G_t} multiplies less what it divides. Inside the chunk
    # that is Σ_s K[t, s] - Σ_s K[s, t] with K = dM ⊙ M, and da — the
    # reverse cumulative sum — keeps only the pairs that STRADDLE a token:
    # under a fast decay a few entries beside the diagonal, against row and
    # column sums the diagonal dominates. So both sums are taken from the
    # SAME rounded factors (M and dt ⊙ x as the matmuls read them, dy as it
    # came): the same products in two orders, equal to float32's own error,
    # where dt ⊙ x unrounded in one of them left 2^-9 of the diagonal
    # standing (da off by 2.6 % a token at 25-32 nats a unit of dt, a
    # mixer's dt_bias by 40 % over 8,192 tokens: PERF.md section 5, PR 57).
    # Across chunks: the read of the entering state, less every token's
    # tail; the chunk's last row also carries e^{G_Q}'s own — the state's
    # decay and the tails
    tails = v["tail"] * written * (v["dt_wide"] * xf)
    d_g = (
        dyf * y_intra - d_xdt_intra * v["xdt"].astype(_F32)
        + dyf * (v["e_g"] * read) - tails
    )
    at_end = jnp.sum(tails, axis=0, keepdims=True) + v["e_last"] * jnp.sum(
        d_next * state, axis=0, keepdims=True
    )
    row = jax.lax.broadcasted_iota(jnp.int32, d_g.shape, 0)
    d_g = jnp.where(row == chunk - 1, d_g + at_end, d_g)
    ddt_ref[:] = lay.head_rows(d_dt)
    dg_ref[:] = lay.head_rows(d_g)
    d_cb_c = d_cb.astype(cd)
    dc_ref[:] = (
        _mm(dy_decayed, state_c, 1, 1) + _mm(d_cb_c, bm, 1, 0)
    ).astype(dc_ref.dtype)
    db_ref[:] = (
        _mm((xf * (v["dt_wide"] * v["tail"])).astype(cd), d_next_c, 1, 1)
        + _mm(d_cb_c, cm, 0, 0)
    ).astype(db_ref.dtype)
    dd_ref[:] += jnp.sum(dyf * xf, axis=0, keepdims=True)
    dstate_ref[:] = v["e_last"] * d_next + _mm(cm, dy_decayed, 0, 0)


# --- the calls ------------------------------------------------------------


def _specs(heads: int, dim: int, state: int, chunk: int, chunks: int,
           reverse: bool):
    """BlockSpecs for grid (batch row, group, chunk): x-like [B, S, H · P],
    B / C [B, S, G · N], the columns [B, G, S, 2 · heads] (dt | G), the rows
    [B, G, heads, S] (G, or a gradient a head), D [1, H · P] and the states
    [B, S / Q, N, H · P]; ``reverse``: the chunks run from the row's end."""

    def at(c):
        return chunks - 1 - c if reverse else c

    width = heads * dim
    return dict(
        x=pl.BlockSpec((None, chunk, width), lambda b, g, c: (b, at(c), g)),
        bc=pl.BlockSpec((None, chunk, state), lambda b, g, c: (b, at(c), g)),
        cols=pl.BlockSpec(
            (None, None, chunk, 2 * heads), lambda b, g, c: (b, g, at(c), 0)
        ),
        rows=pl.BlockSpec(
            (None, None, heads, chunk), lambda b, g, c: (b, g, 0, at(c))
        ),
        d=pl.BlockSpec((1, width), lambda b, g, c: (0, g)),
        states=pl.BlockSpec(
            (None, None, state, width), lambda b, g, c: (b, at(c), 0, g)
        ),
        # resident over the chunk axis
        final=pl.BlockSpec((None, state, width), lambda b, g, c: (b, 0, g)),
        dd=pl.BlockSpec((None, 1, width), lambda b, g, c: (b, 0, g)),
    )


def _compiler_params(width: int, state: int, chunk: int):
    # the [Q, W] float32 temporaries of a step beside the blocks
    need = 4 * (24 * chunk * width + 6 * state * width + 16 * chunk * chunk)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(need + 16 * 2**20, 100 * 2**20),
    )


def _chunk_sums(a, groups: int, chunk: int):
    """G, the cumulative sum of a [B, S, H] inside each chunk, float32, in
    both layouts: a column a head [B, groups, S, H / groups] and a row a
    head [B, groups, H / groups, S]."""
    batch, seq, heads = a.shape
    g = jnp.cumsum(
        a.reshape(batch, seq // chunk, chunk, heads), axis=2
    ).reshape(batch, seq, groups, heads // groups)
    return g.transpose(0, 2, 1, 3), g.transpose(0, 2, 3, 1)


def _grouped(v, groups: int):
    """[B, S, H] -> [B, groups, S, H / groups]."""
    batch, seq, heads = v.shape
    return v.reshape(batch, seq, groups, heads // groups).transpose(0, 2, 1, 3)


def _ungrouped(rows):
    """[B, groups, H / groups, S] -> [B, S, H]."""
    batch, groups, per, seq = rows.shape
    return rows.transpose(0, 3, 1, 2).reshape(batch, seq, groups * per)


def _call(x, dt, a, bm, d, groups: int, chunk: int, reverse: bool):
    """What both calls are made of: (heads a group, head dim, state, the
    BlockSpecs, the grid, the columns dt | G, the rows G, D over its
    lanes)."""
    batch, seq, width = x.shape
    heads = dt.shape[-1] // groups
    dim, state = width // dt.shape[-1], bm.shape[-1] // groups
    g_cols, g_rows = _chunk_sums(a, groups, chunk)
    return (
        heads, dim, state,
        _specs(heads, dim, state, chunk, seq // chunk, reverse),
        (batch, groups, seq // chunk),
        jnp.concatenate([_grouped(dt, groups), g_cols], axis=-1), g_rows,
        jnp.repeat(d.astype(_F32), dim)[None],
    )


def _forward(x, dt, a, bm, cm, d, groups: int, chunk: int, interpret: bool):
    heads, dim, state, s, grid, cols, g_rows, d_wide = _call(
        x, dt, a, bm, d, groups, chunk, False
    )
    batch, _groups, chunks = grid
    width = x.shape[-1]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads, dim=dim, chunk=chunk),
        grid=grid,
        in_specs=[s["x"], s["bc"], s["bc"], s["cols"], s["rows"], s["d"]],
        out_specs=[s["x"], s["states"], s["final"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((batch, chunks, state, width), _F32),
            jax.ShapeDtypeStruct((batch, state, width), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((state, heads * dim), _F32)],
        compiler_params=None if interpret else _compiler_params(
            heads * dim, state, chunk
        ),
        interpret=interpret,
        name="ssd_fwd",
    )(x, bm, cm, cols, g_rows, d_wide)


def _backward(x, dt, a, bm, cm, d, states, dy, groups: int, chunk: int,
              interpret: bool):
    heads, dim, state, s, grid, cols, g_rows, d_wide = _call(
        x, dt, a, bm, d, groups, chunk, True
    )
    batch, _groups, chunks = grid
    seq, width = x.shape[1:]
    rows = jax.ShapeDtypeStruct((batch, groups, heads, seq), _F32)
    dx, db, dc, ddt, dg, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads, dim=dim, chunk=chunk),
        grid=grid,
        in_specs=[s["x"], s["bc"], s["bc"], s["cols"], s["rows"], s["d"],
                  s["states"], s["x"]],
        out_specs=[s["x"], s["bc"], s["bc"], s["rows"], s["rows"], s["dd"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(bm.shape, bm.dtype),
            jax.ShapeDtypeStruct(cm.shape, cm.dtype),
            rows, rows,
            jax.ShapeDtypeStruct((batch, 1, width), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((state, heads * dim), _F32)],
        compiler_params=None if interpret else _compiler_params(
            heads * dim, state, chunk
        ),
        interpret=interpret,
        name="ssd_bwd",
    )(x, bm, cm, cols, g_rows, d_wide, states, dy)
    # a_r moves G_t of every t >= r of its chunk: the reverse cumulative sum
    dg = dg.reshape(batch, groups, heads, chunks, chunk)
    da = jnp.flip(jnp.cumsum(jnp.flip(dg, -1), axis=-1), -1).reshape(
        batch, groups, heads, seq
    )
    dd = jnp.sum(dd.reshape(batch, groups * heads, dim), axis=(0, 2))
    return dx, _ungrouped(ddt), _ungrouped(da), db, dc, dd.astype(d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd(x, dt, a, bm, cm, d, groups, chunk, interpret):
    y, _states, final = _forward(x, dt, a, bm, cm, d, groups, chunk,
                                 interpret)
    return y, final


def _ssd_fwd(x, dt, a, bm, cm, d, groups, chunk, interpret):
    y, states, final = _forward(x, dt, a, bm, cm, d, groups, chunk, interpret)
    return (y, final), (x, dt, a, bm, cm, d, states)


def _ssd_bwd(groups, chunk, interpret, residuals, cotangents):
    # the state that leaves the row is reported, not differentiated
    return _backward(*residuals, cotangents[0], groups, chunk, interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, a, B, C, D, chunk: int = CHUNK,
        interpret: Optional[bool] = None, return_state: bool = False):
    """y [B, S, H, P] of the recurrence above from x [B, S, H, P] and B, C
    [B, S, G, N] in the compute dtype (head h reads group h // (H / G)), the
    time steps dt and the log-decays a = dt · A <= 0 [B, S, H] (float32) and
    the skip D [H]; a row that is no whole number of chunks is padded to one
    with tokens that write nothing and forget nothing. A group's lanes
    (H / G · P) are a whole number of 128-lane tiles and P divides a tile,
    or the group is narrower than one. Differentiable in all six;
    ``return_state``: (y, the state that leaves the row [B, H, N, P],
    float32, detached). The operands are named ``ssd_operands`` where the
    kernels take them, for the policies of ``models/remat.py`` that keep
    what a backward kernel reads."""
    if interpret is None:
        interpret = pallas_interpret()
    batch, seq, heads, dim = x.shape
    groups, state = B.shape[2:]
    width = heads // groups * dim
    tile = min(LANES, width)
    if heads % groups or width % tile or tile % dim:
        raise ValueError(
            f"ssd: {heads} heads of {dim} in {groups} groups — a group's "
            f"{width} lanes must be whole {LANES}-lane tiles of whole heads"
        )
    ragged = -seq % chunk
    if ragged:
        # dt = 0, a = 0 behind the row: causal, so no output before them
        # moves, and the state leaves as it was
        x, dt, a, B, C = (
            jnp.pad(v, ((0, 0), (0, ragged)) + ((0, 0),) * (v.ndim - 2))
            for v in (x, dt, a, B, C)
        )
    rows = seq + ragged
    flat = [
        checkpoint_name(v, "ssd_operands") for v in (
            x.reshape(batch, rows, heads * dim),
            dt.astype(_F32), a.astype(_F32),
            B.astype(x.dtype).reshape(batch, rows, groups * state),
            C.astype(x.dtype).reshape(batch, rows, groups * state),
        )
    ]
    y, final = _ssd(*flat, D, groups, chunk, interpret)
    y = y.reshape(batch, rows, heads, dim)[:, :seq]
    if not return_state:
        return y
    final = final.reshape(batch, state, heads, dim).transpose(0, 2, 1, 3)
    return y, jax.lax.stop_gradient(final)
