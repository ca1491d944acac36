"""The indexer's loss as Pallas TPU kernels over the causal triangle.

A learned sparse attention (``models/keye_vl2.py``) trains its lightning
indexer by a loss of its own: for every query t the KL from pbar_t — the
main attention's probabilities over the selected keys S_t, summed over the
heads / H — to softmax over S_t of the index scores I[t, s] = sum_j w[t, j]
relu(qI[t, j] · kI[s]) x (D_I · J)^-0.5. In XLA that is a loop over blocks
of query rows whose every step makes the block's index scores for ALL keys
([rows, J, S] float32) and the main scores of all H heads again ([H, rows,
S] float32), masks the upper half of the square away, and is run a second
time by its own ``jax.checkpoint`` before it is differentiated — all of it
through HBM (``keye_vl2.index_loss``, which stays as the ``"dense"`` path
and these kernels' oracle). Here a (query tile, key tile) pair's index
scores, main scores and pbar exist in VMEM only, the sweep is the causal
one of the ``flash_sel_*`` kernels (``ops/flash_attention.py``, "selected
tiles": the same index maps, the same int8 selection tile as the mask, the
same tile flags in SMEM — a tile that holds no selected pair runs no body,
tiles above the diagonal are neither fetched nor computed), and the
backward recomputes a tile where it stands.

**Forward, one sweep** (``index_loss_fwd``; grid (batch row, query tile, a
step of the key sweep)). One sweep suffices because

    KL_t = sum_s pbar (log pbar - I) + logZ_t sum_s pbar,
    logZ_t = log sum_{s in S_t} exp(I[t, s]),

so a query row carries the running max of I, and LANE-PARTIAL sums — lane c
of a row's [Bq, 128] float32 state tile holds the terms of keys c, c + 128,
... — of exp(I - max), pbar (log pbar - I), pbar and |S_t|: nothing but the
max crosses lanes before the flush. At the sweep's last step a row's KL,
logZ, the peak gauge's term |S_t| exp(max I - logZ) and sum pbar are written
as ROW vectors ([B·4, 1, S] float32, the layout of the kernels' ``lse``).

**Backward, one sweep** (``index_loss_bwd``; the same grid, every axis
sequential). A piece's I and pbar are recomputed as the forward made them;
with the row's cotangent c_t, dI = c_t (softmax_{S_t}(I) sum pbar - pbar) —
XLA's own derivative of the expression above, ``logZ`` and ``sum pbar``
read from the forward's rows — and, per index head j, in float32:

    dw_j   += sum_s dI relu(qI_j · kI) x scale             (VPU alone)
    d_dots  = dI x scale x w_j x [qI_j · kI > 0]   -> ONE cast to the
                                                      compute dtype
    dqI_j  += d_dots kI          (float32 in VMEM over the key sweep,
                                  rounded once, at the query tile's flush)
    dkI    += d_dotsᵀ qI_j       (float32 over the WHOLE sweep)

dkI is one key head: [S, a lane tile] float32 is the kernel's OUTPUT BLOCK
for the whole batch row (8 MB at S = 16,384), resident in VMEM across both
sweep axes and written once — so ONE kernel, not a dq / dk pair whose
second half would compute every tile's index AND main scores a third time
(80 matmuls a tile here against 64 + 64 for the pair).

Strips. A (query tile, key tile) pair is what the pipeline FETCHES (512 x
512: the selection's tile and its flag, shared with the flash kernels); it
is WORKED OUT a strip of (``STRIP_ROWS`` query rows, one lane tile of keys)
at a time, every head's dots of the strip and the sums they go into held in
vector registers. Taken whole, a tile's [512, 512] float32 scores, carries
and MXU results went through VMEM once a head, and the store slot bounded
both kernels (20.1 ms forward, 49.7 with the backward, a layer's call at the
Keye cell's shape on a v5e); in strips the MXU does (11.5 / 31.6 ms as it
ships; PERF.md section 5, PR 52). The backward keeps a strip's sixteen
index dots from the scores' pass for d_dots (through a VMEM scratch:
cheaper than sixteen more MXU passes, once the MXU is what bounds).

Index heads inside a lane tile. An index head is D_I = 64 lanes: two share
a 128-lane tile of qI [B, S, J·D_I], exactly as two attention heads of 64
share a column block of the flash kernels. A head's products contract over,
and land in, its WINDOW (the whole lane tile), with the other head's lanes
zeroed on one operand: a strip's keys [C, D_I] are laid into slot i of a
window beside zeros (``_key_slots``, once a key strip: head i's dots
contract over its own lanes, and dqI lands in them alone); qI masked to
slot i (once a query tile, into scratch) makes dkI land in slot i of the
accumulator, whose slots are summed in float32 after the call. Where the
widths are no whole lane tiles (the tiny test models) the window is the
whole width. The kernels read ``k_index`` ITSELF: a copy of it made in XLA
(the key head in every slot, say) has the producer's arithmetic — the
LayerNorm, RoPE — fused into it and recomputed, to another last bit than
the array the selection was made from.

What is exact. The same equations and dtypes as the dense path: dots in the
compute dtype with float32 accumulation; the relu-weighted head sum, exp,
pbar, log-sum-exp and KL in float32; q, k, lse and the selection detached.
Not the same bits: the dense path sums a row's terms in XLA's order and
rounds dqI / dkI's partial sums a block of rows at a time.

TPU shapes: main heads of whole lane tiles (D = 128), tiles of 128
multiples (or the whole sequence), one device. Off-TPU the kernels run
under ``interpret=True`` (``utils.backend.pallas_interpret``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dedloc_tpu.ops.flash_attention import (
    NEG_INF,
    STATE_LANES,
    _dot,
    _for_k_step,
    _heads_per_block,
    _k_tile,
    _last_k_tile,
    _Mask,
    _pick_block,
    _selected_kernel,
    _selection_specs,
    _sweep,
    _t,
    selection_tile_flags,
)
from dedloc_tpu.utils.backend import pallas_interpret

_MASK = _Mask(causal=True, selected=True)
# rows of the forward's per-query output [B · 4, 1, S]
_KL, _LOG_Z, _PEAK, _MASS = range(4)
# the backward holds dkI for a whole batch row in VMEM (twice: an output
# block's two buffers); beyond this a dq / dk pair would be the design
_DK_RESIDENT_BYTES = 32 * 2**20
# query rows and keys of a strip (the keys: a lane tile)
STRIP_ROWS, STRIP_KEYS = 256, STATE_LANES


class _Shape(NamedTuple):
    """A call's static geometry."""

    index_heads: int  # J
    index_width: int  # D_I
    window_heads: int  # g: index heads that share a window
    heads: int  # main query heads H
    kv_heads: int
    width: int  # D

    @property
    def window(self) -> int:  # lanes of an index window
        return self.window_heads * self.index_width


def _shape_of(q_index, q, k) -> _Shape:
    j, di = q_index.shape[-2:]
    h, d = q.shape[-2:]
    return _Shape(j, di, _heads_per_block(j, di, di), h, k.shape[-2], d)


def _row_tile(row, lanes: int):
    """A [1, Bq] row of per-query values as a state tile [Bq, lanes]."""
    return jnp.broadcast_to(_t(row), (row.shape[-1], lanes))


def _slot(x, i: int, width: int):
    """``x`` [N, window] with every lane outside slot i zeroed."""
    if x.shape[-1] == width:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= i * width) & (lane < (i + 1) * width), x,
                     jnp.zeros_like(x))


def _key_slots(keys, g: int):
    """The key head [C, D_I] as the g tiles [C, window] that hold it in
    slot i and zeros elsewhere."""
    if g == 1:
        return [keys]
    zeros = jnp.zeros_like(keys)
    return [
        jnp.concatenate([keys if at == i else zeros for at in range(g)],
                        axis=-1)
        for i in range(g)
    ]


class _Plan(NamedTuple):
    """How a tile is worked out: (rows, keys) of a STRIP — the piece of a
    tile whose scores, target and gradient are worked out together, small
    enough that a head's dots and the sum they go into stay in, or near, the
    vector registers (a [256, 128] float32 is 32 of the 64), where a whole
    tile's [512, 512] went through VMEM once per head (stores were the
    fullest slot of the first version's schedule: PERF.md section 5, PR 52);
    on a v5e at the Keye cell's shape, a layer's forward / forward +
    backward: 13.6 / 40.9 ms at 64 rows, 12.0 / 32.3 at 128, 11.3 / 31.1 at
    256."""

    rows: int
    keys: int


def _plan(q, block_q: int, block_k: int) -> _Plan:
    """The module's constants as one static argument of the calls below."""
    seq = q.shape[1]
    return _Plan(
        _pick_block(_pick_block(seq, block_q), STRIP_ROWS),
        _pick_block(_pick_block(seq, block_k), STRIP_KEYS),
    )


def _cache_rows(shape: _Shape, w_ref, lse_ref, wb_ref, lb_ref):
    """The query tile's head weights and log-sum-exps as state tiles: a row
    [1, Bq] each in HBM's layout, turned to columns once a query tile."""
    lanes = wb_ref.shape[-1]
    for j in range(shape.index_heads):
        wb_ref[j] = _row_tile(w_ref[j], lanes)

    def head(h, _):
        lb_ref[h] = _row_tile(lse_ref[h], lanes)
        return _

    jax.lax.fori_loop(0, shape.heads, head, 0)


def _at(i, size: int):
    """Piece ``i`` of ``size`` rows or lanes of a ref, ``i`` traced or not."""
    if isinstance(i, int):
        return slice(i * size, (i + 1) * size)
    return pl.ds(pl.multiple_of(i * size, size), size)


def _loop(n: int, body, carry):
    """``carry = body(i, carry)`` for i in range(n), as a ``fori_loop``. A
    kernel's Python loop is traced and lowered at every call site of every
    trace of the program — four layers, forward, replay and backward, three
    traces a start — and the loops over a tile's strips by hand cost the
    Keye cell 13 s a trace (PERF.md section 5, PR 52). The loops over a
    strip's HEADS stay Python's: what is unrolled is what the scheduler
    overlaps (a step of eight heads ran the MXU 53 % full in the schedule,
    all of a strip's 72-88 %)."""
    if n == 1:
        return body(0, carry)
    return jax.lax.fori_loop(0, n, body, carry)


def _strip_scores(shape: _Shape, qi_ref, q_ref, k_ref, wb_ref, lb_ref, rows,
                  cols, keys, chosen, held_ref=None):
    """(I [R, C] float32 — the index scores, scaled, unmasked —, the target:
    pbar at the selected pairs, 0 elsewhere) of one strip; the J index
    heads' dots [R, C] float32 that I is summed from are left in
    ``held_ref`` where there is one."""
    j_heads, di, g, heads, kv, d = shape

    def window(c, index):
        q = qi_ref[rows, _at(c, shape.window)]
        for i in range(g):
            dots = _dot(q, keys[i], 1, 1)
            if held_ref is not None:
                held_ref[c * g + i] = dots
            index = index + jnp.maximum(dots, 0.0) * wb_ref[c * g + i, rows, :]
        return index

    scale = 1.0 / (d ** 0.5)

    def group(c, pbar):  # a kv head and the query heads it serves
        k_head = k_ref[cols, _at(c, d)]
        for u in range(heads // kv):
            h = c * (heads // kv) + u
            pbar = pbar + jnp.exp(
                _dot(q_ref[rows, _at(h, d)], k_head, 1, 1) * scale
                - lb_ref[h, rows, :]
            )
        return pbar

    index = pbar = jnp.zeros(chosen.shape, jnp.float32)
    for c in range(j_heads // g):
        index = window(c, index)
    for c in range(kv):
        pbar = group(c, pbar)
    return index * ((di * j_heads) ** -0.5), jnp.where(
        chosen, pbar / heads, 0.0
    )


def _for_strips(shape: _Shape, plan: _Plan, sel_tile, ki_ref, bq: int,
                bk: int, strip, init=lambda: 0,
                done=lambda first, carry: None) -> None:
    """A tile's strips, a key strip's row strips innermost: ``carry =
    strip(rows, cols, key slot tiles, chosen [R, C], carry)`` from
    ``init()`` through the row strips, then ``done(the key strip's first
    key in the tile, carry)``."""
    rows_n, cols_n = plan.rows, plan.keys

    def key_strip(s, _):
        cols = _at(s, cols_n)
        keys = _key_slots(ki_ref[cols, :], shape.window_heads)

        def row_strip(r, carry):
            rows = _at(r, rows_n)
            return strip(rows, cols, keys, sel_tile[rows, cols] != 0, carry)

        done(s * cols_n, _loop(bq // rows_n, row_strip, init()))
        return _

    _loop(bk // cols_n, key_strip, 0)


def _selected_step(sel, qi, kb, bq: int, bk: int, seq: int, body) -> None:
    """Run ``body(the selection's tile ref)`` at step ``kb`` of query tile
    ``qi``'s causal sweep where the tile holds a selected pair."""
    _for_k_step(_MASK, qi, kb, bq, bk, seq, lambda _chosen: body(sel[0]),
                sel)


def _fwd_kernel(qi_ref, ki_ref, w_ref, q_ref, k_ref, lse_ref, stats_ref,
                wb_ref, lb_ref, m_ref, l_ref, a_ref, p_ref, n_ref, *,
                shape: _Shape, plan: _Plan, seq: int, sel=None):
    qi, kb, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]

    @pl.when(kb == 0)
    def _init():
        _cache_rows(shape, w_ref, lse_ref, wb_ref, lb_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        for ref in (l_ref, a_ref, p_ref, n_ref):
            ref[:] = jnp.zeros_like(ref)

    def strip(rows, cols, keys, chosen, carry):
        index, target = _strip_scores(
            shape, qi_ref, q_ref, k_ref, wb_ref, lb_ref, rows, cols, keys,
            chosen,
        )
        scores = jnp.where(chosen, index, NEG_INF)
        # m: the row's running max in every lane; l, a, p, n: LANE-PARTIAL
        # sums (lane c of a row holds the terms of keys c, c + 128, ...):
        # nothing but the max crosses lanes before the flush
        m_prev = m_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
        l_ref[rows, :] = l_ref[rows, :] * jnp.exp(m_prev - m_new) + jnp.exp(
            scores - m_new
        )
        m_ref[rows, :] = m_new
        # a selected pair whose pbar underflowed to 0 adds 0, not 0 x -inf
        a_ref[rows, :] += jnp.where(
            target > 0,
            target * (jnp.log(jnp.maximum(target, 1e-38)) - index), 0.0,
        )
        p_ref[rows, :] += target
        n_ref[rows, :] += chosen.astype(jnp.float32)
        return carry

    _selected_step(sel, qi, kb, bq, bk, seq, lambda tile: _for_strips(
        shape, plan, tile, ki_ref, bq, bk, strip
    ))

    @pl.when(kb == nk - 1)
    def _flush():
        m = m_ref[:, :1]
        total, kl_terms, mass, count = (
            jnp.sum(ref[:], axis=-1, keepdims=True)
            for ref in (l_ref, a_ref, p_ref, n_ref)
        )
        log_z = m + jnp.log(jnp.maximum(total, 1e-30))
        for row, value in (
            (_KL, kl_terms + log_z * mass), (_LOG_Z, log_z),
            (_PEAK, count * jnp.exp(m - log_z)), (_MASS, mass),
        ):
            stats_ref[row] = _t(value)


def _bwd_kernel(qi_ref, ki_ref, w_ref, q_ref, k_ref, lse_ref, stats_ref,
                ct_ref, dq_ref, dk_ref, dw_ref, wb_ref, lb_ref, qm_ref,
                rows_ref, dq_acc_ref, dw_acc_ref, held_ref, *, shape: _Shape,
                plan: _Plan, seq: int, sel=None):
    qi, kb, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    j_heads, di, g = shape[:3]
    lanes = wb_ref.shape[-1]
    scale = (di * j_heads) ** -0.5

    @pl.when((qi == 0) & (kb == 0))
    def _init_row():
        dk_ref[:] = jnp.zeros_like(dk_ref)

    @pl.when(kb == 0)
    def _init():
        _cache_rows(shape, w_ref, lse_ref, wb_ref, lb_ref)
        for at, row in enumerate((stats_ref[_LOG_Z], stats_ref[_MASS],
                                  ct_ref[:])):
            rows_ref[at] = _row_tile(row, lanes)
        for j in range(j_heads):  # qI's windows, each head alone in its own
            c, i = divmod(j, g)
            qm_ref[j] = _slot(
                qi_ref[:, c * shape.window:(c + 1) * shape.window], i, di
            )
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)
        dw_acc_ref[:] = jnp.zeros_like(dw_acc_ref)

    def strip(rows, cols, keys, chosen, dk):
        index, target = _strip_scores(
            shape, qi_ref, q_ref, k_ref, wb_ref, lb_ref, rows, cols, keys,
            chosen, held_ref,
        )
        log_z, mass, ct = (rows_ref[at, rows, :] for at in range(3))
        soft = jnp.exp(jnp.where(chosen, index, NEG_INF) - log_z)
        d_index = ct * (soft * mass - target) * scale  # float32 [R, C]

        def window(c, dk):
            dq = None
            for i in range(g):
                j = c * g + i
                dots = held_ref[j]
                live = dots > 0
                dw_acc_ref[j, rows, :] += d_index * jnp.where(live, dots, 0.0)
                # w and the relu's mask in float32, then the one cast
                d_dots = jnp.where(
                    live, d_index * wb_ref[j, rows, :], 0.0
                ).astype(keys[i].dtype)
                term = _dot(d_dots, keys[i], 1, 0)
                dq = term if dq is None else dq + term
                dk = dk + _dot(d_dots, qm_ref[j, rows, :], 0, 0)
            dq_acc_ref[rows, _at(c, shape.window)] += dq
            return dk

        for c in range(j_heads // g):
            dk = window(c, dk)
        return dk

    def tile(sel_tile):
        first = jnp.minimum(kb, _last_k_tile(qi, bq, bk)) * bk
        cols_n = plan.keys

        def add(at, dk):  # float32 over the WHOLE sweep: the resident block
            dk_ref[pl.ds(pl.multiple_of(first + at, cols_n), cols_n), :] += dk

        _for_strips(
            shape, plan, sel_tile, ki_ref, bq, bk, strip,
            lambda: jnp.zeros((cols_n, shape.window), jnp.float32), add,
        )

    _selected_step(sel, qi, kb, bq, bk, seq, tile)

    @pl.when(kb == nk - 1)
    def _flush():
        dq_ref[:] = dq_acc_ref[:].astype(dq_ref.dtype)
        for j in range(j_heads):
            dw_ref[j] = _t(jnp.sum(dw_acc_ref[j], axis=-1, keepdims=True))


def _geometry(q, block_q: int, block_k: int):
    b, s, _ = q.shape
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    return b, s, bq, bk, s // bq, _sweep(_MASK, s // bq, s // bk, bq, bk)


def _in_specs(shape: _Shape, bq: int, bk: int):
    """q_index, the key head, head-weight rows, q, k, lse rows — and the
    selection's tile + flags, last."""
    j_heads, di, _g, heads, kv, d = shape

    def k_at(j, kb):
        return _k_tile(_MASK, j, kb, bq, bk)

    return [
        pl.BlockSpec((None, bq, j_heads * di), lambda n, j, kb: (n, j, 0)),
        pl.BlockSpec((None, bk, di),
                     lambda n, j, kb: (n, k_at(j, kb), 0)),
        pl.BlockSpec((j_heads, 1, bq), lambda n, j, kb: (n, 0, j)),
        pl.BlockSpec((None, bq, heads * d), lambda n, j, kb: (n, j, 0)),
        pl.BlockSpec((None, bk, kv * d),
                     lambda n, j, kb: (n, k_at(j, kb), 0)),
        pl.BlockSpec((heads, 1, bq), lambda n, j, kb: (n, 0, j)),
    ], _selection_specs(bq, bk, lambda n, j, kb: (n, j, k_at(j, kb)))


def _state(bq: int, lanes: int, tiles: int = 1):
    """Per-query float32 state, a tile [Bq, a key strip's lanes] each."""
    shape = (bq, lanes) if tiles == 1 else (tiles, bq, lanes)
    return pltpu.VMEM(shape, jnp.float32)


def _vmem(need: int):
    """Compiler parameters: the scoped-VMEM limit a call asks for (a v5e
    core has 128 MiB; the compiler's own limit is 16)."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(int(need) + 16 * 2**20, 110 * 2**20)
    )


def _blocks_bytes(shape: _Shape, bq: int, bk: int, size: int) -> int:
    """The operands' blocks, twice (the pipeline's two buffers), and the
    cached head weights and log-sum-exps."""
    j_heads, di, _g, heads, kv, d = shape
    blocks = size * (bq * (j_heads * di + heads * d)
                     + bk * (di + kv * d))
    rows = 4 * 8 * bq * (j_heads + heads + 5)  # a [1, Bq] row pads to 8
    state = 4 * bq * STATE_LANES * (j_heads + heads)
    return 2 * (blocks + rows + bq * bk) + state


# Jitted INLINE: the program is what it would be without (the call's
# equations land in the caller's trace), and a layer's forward — traced at
# every lift of the layer, five times a layer and program trace in the
# trainer — traces its kernel's body once (``jax.jit``'s cache; Pallas keeps
# none): 20 traces of ``_fwd`` were 2.8 s of a start's 15.8 s trace
@functools.partial(jax.jit, static_argnums=(0, 1, 4, 5, 6), inline=True)
def _fwd(shape, plan, operands, sel, block_q, block_k, interpret):
    q = operands[3]
    b, s, bq, bk, nq, sweep = _geometry(q, block_q, block_k)
    j_heads, heads = shape.index_heads, shape.heads
    lanes = plan.keys
    in_specs, selection = _in_specs(shape, bq, bk)
    return pl.pallas_call(
        _selected_kernel(
            functools.partial(_fwd_kernel, shape=shape, plan=plan, seq=s), 6
        ),
        grid=(b, nq, sweep),
        in_specs=[*in_specs, *selection],
        out_specs=pl.BlockSpec((4, 1, bq), lambda n, j, kb: (n, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b * 4, 1, s), jnp.float32),
        scratch_shapes=[
            _state(bq, lanes, j_heads), _state(bq, lanes, heads),
            *(_state(bq, lanes) for _ in range(5)),
        ],
        interpret=interpret,
        name="index_loss_fwd",
        compiler_params=_vmem(
            _blocks_bytes(shape, bq, bk, q.dtype.itemsize)
            + 12 * 4 * bq * bk
        ),
    )(*operands, *sel)


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7, 8), inline=True)
def _bwd(shape, plan, operands, sel, stats, ct, block_q, block_k, interpret):
    q_index, q = operands[0], operands[3]
    b, s, bq, bk, nq, sweep = _geometry(q, block_q, block_k)
    j_heads, di, _g, heads = shape[:4]
    if 2 * 4 * s * shape.window > _DK_RESIDENT_BYTES:
        raise ValueError(
            f"index_loss: the key head's gradient of a row of {s} does not "
            "stay in VMEM for the whole sweep"
        )
    in_specs, selection = _in_specs(shape, bq, bk)
    width, lanes = j_heads * di, plan.keys
    return pl.pallas_call(
        _selected_kernel(
            functools.partial(_bwd_kernel, shape=shape, plan=plan, seq=s), 8
        ),
        grid=(b, nq, sweep),
        in_specs=[
            *in_specs,
            pl.BlockSpec((4, 1, bq), lambda n, j, kb: (n, 0, j)),
            pl.BlockSpec((None, 1, bq), lambda n, j, kb: (n, 0, j)),
            *selection,
        ],
        out_specs=[
            pl.BlockSpec((None, bq, width), lambda n, j, kb: (n, j, 0)),
            # the whole batch row's dkI: resident across both sweep axes
            pl.BlockSpec((None, s, shape.window), lambda n, j, kb: (n, 0, 0)),
            pl.BlockSpec((j_heads, 1, bq), lambda n, j, kb: (n, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, width), q_index.dtype),
            jax.ShapeDtypeStruct((b, s, shape.window), jnp.float32),
            jax.ShapeDtypeStruct((b * j_heads, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            _state(bq, lanes, j_heads), _state(bq, lanes, heads),
            pltpu.VMEM((j_heads, bq, shape.window), q_index.dtype),
            _state(bq, lanes, 3),
            pltpu.VMEM((bq, width), jnp.float32),
            _state(bq, lanes, j_heads),
            _state(plan.rows, lanes, j_heads),  # a strip's index dots
        ],
        interpret=interpret,
        name="index_loss_bwd",
        compiler_params=_vmem(
            _blocks_bytes(shape, bq, bk, q.dtype.itemsize)
            + 2 * 4 * s * shape.window  # dkI's block
            + bq * width * (4 + 2 * q_index.dtype.itemsize)
            + j_heads * bq * (shape.window * q_index.dtype.itemsize
                              + 4 * STATE_LANES)
            + 16 * 4 * bq * bk
        ),
    )(*operands, stats, ct, *sel)


def _call(q_index, k_index, weights, q, k, lse, selection, block_q, block_k):
    """(geometry, plan, the kernels' operands, the selection + its tile
    flags) of a call, as both sweeps take them. The operands in the layouts
    the kernels read: qI and q / k as the projections wrote them ([B, S,
    heads · width]: the same bytes), the key head as it is, per-query
    scalars as rows."""
    shape = _shape_of(q_index, q, k)
    b, s = weights.shape[:2]
    operands = (
        q_index.reshape(b, s, -1), k_index,
        weights.astype(jnp.float32).transpose(0, 2, 1).reshape(
            b * shape.index_heads, 1, s
        ),
        q.reshape(b, s, -1), k.reshape(b, s, -1),
        lse.astype(jnp.float32).reshape(b * shape.heads, 1, s),
    )
    sel = (selection,
           selection_tile_flags(selection, block_q, block_k).reshape(-1))
    return shape, _plan(q, block_q, block_k), operands, sel


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _index_kl(q_index, k_index, weights, q, k, lse, selection, block_q,
              block_k, interpret):
    """(KL_t [B, S], the peak gauge's term of each query [B, S])."""
    return _index_kl_fwd(q_index, k_index, weights, q, k, lse, selection,
                         block_q, block_k, interpret)[0]


def _index_kl_fwd(q_index, k_index, weights, q, k, lse, selection, block_q,
                  block_k, interpret):
    shape, plan, operands, sel = _call(
        q_index, k_index, weights, q, k, lse, selection, block_q, block_k
    )
    stats = _fwd(shape, plan, operands, sel, block_q, block_k, interpret)
    rows = stats.reshape(weights.shape[0], 4, -1)
    # logZ and sum pbar reach the backward inside ``stats``, a Pallas
    # output: every layer policy from "kernel_outputs" up keeps it (64 KB a
    # row each), so a remat replay runs no second forward sweep for them
    return (rows[:, _KL], rows[:, _PEAK]), (
        q_index, k_index, weights, q, k, lse, selection, stats
    )


def _index_kl_bwd(block_q, block_k, interpret, residuals, cotangents):
    q_index, k_index, weights, q, k, lse, selection, stats = residuals
    shape, plan, operands, sel = _call(*residuals[:7], block_q, block_k)
    b, s = weights.shape[:2]
    ct = cotangents[0].astype(jnp.float32).reshape(b, 1, s)
    dq, dk, dw = _bwd(shape, plan, operands, sel, stats, ct, block_q,
                      block_k, interpret)
    # the window's slots are one key head's partial sums: float32, then the
    # gradient's one rounding
    dk = jnp.sum(
        dk.reshape(b, s, shape.window_heads, shape.index_width), axis=2
    ).astype(k_index.dtype)
    dw = dw.reshape(b, shape.index_heads, s).transpose(0, 2, 1)
    return (
        dq.reshape(q_index.shape), dk, dw.astype(weights.dtype),
        jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
        np.zeros(selection.shape, jax.dtypes.float0),
    )


_index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


def index_loss_rows(q_index, k_index, weights, selection, q, k, lse,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None):
    """(KL(pbar_t || softmax over S_t of I[t]) [B, S] float32, |S_t| x the
    largest softmax_{S_t}(I)[t, s] [B, S] float32) of every query: q_index
    [B, S, J, D_I], k_index [B, S, D_I] (one key head), weights [B, S, J],
    selection [B, S, S] int8 (rows queries; nothing marked above the
    diagonal), and the main attention's q [B, S, H, D], k [B, S, H_kv, D]
    (as its kernels read them) and lse [B, H, S]. The gradient reaches
    ``q_index``, ``k_index`` and ``weights`` alone; q, k and lse are read
    detached, the second output carries none."""
    if interpret is None:
        interpret = pallas_interpret()
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    kl, peak = _index_kl(q_index, k_index, weights, q, k, lse, selection,
                         block_q, block_k, interpret)
    return kl, jax.lax.stop_gradient(peak)
