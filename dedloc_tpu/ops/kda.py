"""Kimi Delta Attention's recurrence (Kimi Linear, arXiv 2510.26692) — a gated
delta rule with a decay per key CHANNEL — chunked, as a Pallas TPU kernel
pair, forward + backward. Per head, state S [dk, dv] (key x value), S_0 = 0:

    S'_t = Diag(e^{g_t}) S_{t-1}                      g_t [dk] <= 0
    S_t  = S'_t + beta_t k_t (v_t - S'_tᵀ k_t)ᵀ       beta_t a scalar
    o_t  = S_tᵀ q_t

``kda_recurrence`` is that, token by token, in float32: the kernels' oracle
and the model's ``"dense"`` path. The kernels take CHUNK = 64 tokens a step.
With G the cumulative sum of g INSIDE a chunk, S the state entering it:

    Akk[t, s] = Σ_c k_tc k_sc e^{G_tc - G_sc}   (s < t)
    Aqk[t, s] = Σ_c q_tc k_sc e^{G_tc - G_sc}   (s <= t)
    X  = (I + Diag(beta) Akk)^{-1}              unit lower triangular
    U  = X (beta ⊙ V),  W = X (beta ⊙ K ⊙ e^G),  V' = U - W S
    O  = (Q ⊙ e^G) S + Aqk V'
    S_next = Diag(e^{G_C}) S + (K ⊙ e^{G_C - G})ᵀ V'

**The decays.** ``e^{-G}`` alone overflows (g may be -30 a step), so only
DIFFERENCES G_t - G_s with s <= t are ever exponentiated. The pairs (t, s) of
a chunk are split by LEVELS: at the level of block size b a block's upper
half of rows t meets its lower half of columns s, and with m the last row of
the lower half G_t - G_s = (G_t - G_m) + (G_m - G_s), both <= 0 — so a
level is ONE matmul of (k ⊙ e^{G - G_m}) against (k ⊙ e^{G_m - G}) under the
level's mask (rows on the wrong side of m clamp to e^0 and are masked out).
Six levels (b = 2 … 64) cover the strict triangle; the diagonal of Aqk is a
lane sum. G itself is a matmul with a triangle of ones over g split into
three bf16 parts (exact to float32's 24 bits; the MXU does the sublane sum).
The state's decay e^{G_C} and dG_C come out of the MXU the same way, already
in the layout that reads them (a sum over rows lands transposed).

**The inverse** is built by the same levels, smallest first: with X the
inverse of the diagonal blocks of size b/2 and E the level's part of
Diag(beta) Akk, X <- X - X E X is the inverse at block size b (block forward
substitution; a Neumann series cancels catastrophically — a repeated key at
alpha = beta = 1 is binomial coefficients). X stays float32 between the
levels; the two matmuls of a level take it rounded to the compute dtype as
every other operand is. Measured against two bf16 parts an operand (three
passes a matmul): the relative L2 of o and of the five gradients moves by
under 4 % of itself (0.00539 -> 0.00559 at beta near 1 and slow decays,
0.00423 -> 0.00423 at the cell's operands) for a third of the level's MXU
time — the operands' own rounding is the error.

q, k, v travel in the compute dtype (bf16) and every other matmul operand is
rounded to it as the flash kernels round p; g, G, the state, X and every
accumulation are float32. One grid step is one chunk of ``HEADS_PER_STEP``
heads, the heads a leading axis of every array (a stage's matmuls of all
heads are independent work for the scheduler; three passes over split parts
are ONE matmul over a contraction three times as long), the chunk axis
sequential with each head's state in VMEM scratch. The forward also
writes each chunk's ENTERING state (float32 [B, H, S/64, dk, dv]: 64 KB a
head-chunk at 128 x 128); the backward sweeps the chunks in reverse carrying
dS, recomputes a chunk's products from the operands and that state, and
writes dq, dk, dv, dg (float32, the reverse cumulative sum taken in the
kernel) and dbeta.

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

from dedloc_tpu.utils.backend import pallas_interpret

CHUNK = 64
LEVELS = (2, 4, 8, 16, 32, 64)  # block sizes, smallest first
# heads of one grid step (the largest divisor of the call's heads, at most
# this): a LEADING AXIS of every array in the kernels, so a stage's matmuls
# of all heads are independent work for the scheduler. Eight heads unrolled
# one after another ran as slowly as one a step (a chain of ~60 dependent
# small matmuls a head: 2.35 ms a forward call at the cell's shape); as a
# leading axis 0.505 (PERF.md section 5, PR 53). Two chunks a step, one
# below the other, bought nothing (0.504 forward, 1.195 against 1.065
# backward)
HEADS_PER_STEP = 8
_F32 = jnp.float32


def kda_recurrence(q, k, v, g, beta, return_state: bool = False):
    """The recurrence token by token in float32 at full matmul precision.
    q, k, g [B, S, H, dk]; v [B, S, H, dv]; beta [B, S, H]. Returns o
    [B, S, H, dv] float32 (and the final state [B, H, dk, dv])."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    batch, _seq, heads, dk = k.shape
    dv = v.shape[-1]
    hi = jax.lax.Precision.HIGHEST

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        write = b_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)

    state, out = jax.lax.scan(
        step, jnp.zeros((batch, heads, dk, dv), _F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)),
    )
    out = jnp.moveaxis(out, 0, 1)
    return (out, state) if return_state else out


# --- inside a kernel: values of one grid step, [heads, rows, lanes] ---------


def _dot(a, b, contract_a: int = 2, contract_b: int = 1):
    """A matmul a head: a, b [P, ., .], float32 out [P, a's free, b's]."""
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((0,), (0,))),
        preferred_element_type=_F32,
    )


def _parts(x, count: int, dtype):
    """float32 ``x`` as ``count`` arrays of ``dtype`` that sum to it (bf16:
    8 more bits of mantissa a part); float32 is its own one part."""
    if dtype == _F32:
        return [x]
    parts = []
    for _ in range(count):
        part = x.astype(dtype)
        parts.append(part)
        x = x - part.astype(_F32)
    return parts


def _stacked(parts, axis: int):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _dot_exact(ones, x, contract_a: int = 2, contract_b: int = 1):
    """A 0/1 array against float32 ``x`` (or ``x`` against it: whichever is
    float32 is split) to float32's own accuracy — sums of rows or lanes of
    ``x`` done by the MXU — as ONE matmul: the three bf16 parts of ``x``
    stacked along the contraction against as many copies of the 0/1 array,
    so the parts add up inside the MXU and the result is popped once."""
    if x.dtype != _F32:  # the 0/1 array came second
        parts = _parts(ones, 3, x.dtype)
        return _dot(_stacked(parts, contract_a),
                    _stacked([x] * len(parts), contract_b),
                    contract_a, contract_b)
    parts = _parts(x, 3, ones.dtype)
    return _dot(_stacked([ones] * len(parts), contract_a),
                _stacked(parts, contract_b), contract_a, contract_b)


def _block_row(x, block: int, at: int):
    """Row ``at`` of every block of ``block`` rows of x [P, R, D], over the
    block's rows."""
    shape = x.shape
    if block >= 8:
        blocks = x.reshape(-1, block, shape[-1])
        return jnp.broadcast_to(
            blocks[:, at:at + 1, :], blocks.shape
        ).reshape(shape)
    # blocks inside one tile of 8 sublanes
    tiles = x.reshape(-1, 8, shape[-1])
    sublane = jax.lax.broadcasted_iota(jnp.int32, tiles.shape, 1)
    out = jnp.broadcast_to(tiles[:, at:at + 1, :], tiles.shape)
    for first in range(block, 8, block):
        row = jnp.broadcast_to(
            tiles[:, first + at:first + at + 1, :], tiles.shape
        )
        out = jnp.where(sublane >= first, row, out)
    return out.reshape(shape)


class _Chunk:
    """The index matrices of a chunk, made once a grid step; ``heads``: the
    leading axis the 0/1 matrices are broadcast to."""

    def __init__(self, dtype, heads: int):
        row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
        self.dtype, self.heads = dtype, heads
        ones = jnp.bfloat16 if dtype != _F32 else _F32
        each = lambda m: jnp.broadcast_to(m, (heads, CHUNK, CHUNK))  # noqa
        self.lower = each((col <= row).astype(ones))  # a sum over rows <= t
        self.upper = each((col >= row).astype(ones))  # over rows >= t
        self.diagonal = col == row
        self.lower_mask = col <= row
        self.strict_mask = col < row
        self.eye = self.diagonal.astype(_F32)
        self.levels = [
            (block, (row // block == col // block)
             & (row % block >= block // 2) & (col % block < block // 2))
            for block in LEVELS
        ]


def _products(ix: _Chunk, q, k, v, g, beta):
    """What a chunk computes before it reads the state, as a dict: the
    decays, the level operands, Akk, Aqk, X and U, W. q, k, g [P, C, dk];
    v [P, C, dv]; beta [P, C, 1]."""
    cd = ix.dtype
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    G = _dot_exact(ix.lower, g)
    e_G = jnp.exp(G)
    e_tail = jnp.exp(G[:, CHUNK - 1:CHUNK, :] - G)
    kg, qg, kd = kf * e_G, qf * e_G, kf * e_tail
    levels = []
    akk = jnp.zeros((ix.heads, CHUNK, CHUNK), _F32)
    aqk = ix.eye * jnp.sum(qf * kf, axis=2, keepdims=True)
    inverse = jnp.broadcast_to(ix.eye, akk.shape)
    for block, mask in ix.levels:
        ref = _block_row(G, block, block // 2 - 1)
        up = jnp.exp(jnp.minimum(G - ref, 0.0))
        lo = jnp.exp(jnp.minimum(ref - G, 0.0))
        ku, kl, qu = ((x * y).astype(cd) for x, y in
                      ((kf, up), (kf, lo), (qf, up)))
        both = _dot(_stacked([ku, qu], 1), kl, 2, 2)  # [P, 2C, C]
        kk = jnp.where(mask, both[:, :CHUNK], 0.0)
        akk = akk + kk
        aqk = aqk + jnp.where(mask, both[:, CHUNK:], 0.0)
        if block == LEVELS[0]:  # the blocks below are single rows: X = I
            inverse = inverse - beta * kk
        else:
            x_c = inverse.astype(cd)
            inner = _dot((beta * kk).astype(cd), x_c)
            inverse = inverse - _dot(x_c, inner.astype(cd))
        levels.append((mask, up, lo, ku, kl, qu))
    x_c = inverse.astype(cd)
    both = _dot(x_c, _stacked([(beta * vf).astype(cd),
                               (beta * kg).astype(cd)], 2))
    u, w = both[..., :vf.shape[-1]], both[..., vf.shape[-1]:]
    return dict(
        qf=qf, kf=kf, vf=vf, e_G=e_G, e_tail=e_tail, kg=kg, qg=qg, kd=kd,
        levels=levels, akk=akk, aqk=aqk, x_c=x_c, u=u, w=w,
    )


def _state_decay(ix: _Chunk, g, dv: int):
    """e^{G_C} as a [P, dk, dv] array (every lane of a row the same): the
    sum of g [P, C, dk] over the chunk's rows lands transposed out of the
    MXU."""
    ones = jnp.ones((ix.heads, CHUNK, dv), ix.lower.dtype)
    return jnp.exp(_dot_exact(g, ones, 1, 1))


def _heads_of(ref, heads: int, width: int):
    """[C, P · width] of a block -> [P, C, width]."""
    return jnp.stack(
        [ref[:, h * width:(h + 1) * width] for h in range(heads)]
    )


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, states_ref,
                final_ref, state_ref, *, heads: int, dk: int, dv: int):
    cd = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[:] = jnp.zeros_like(state_ref)

    ix = _Chunk(cd, heads)
    g = _heads_of(g_ref, heads, dk)
    p = _products(
        ix, _heads_of(q_ref, heads, dk), _heads_of(k_ref, heads, dk),
        _heads_of(v_ref, heads, dv), g, _heads_of(beta_ref, heads, 1),
    )
    state = state_ref[:]
    states_ref[:] = state
    state_c = state.astype(cd)
    v_new = (p["u"] - _dot(p["w"].astype(cd), state_c)).astype(cd)
    out = _dot(  # (Q e^G) S + Aqk V' as one product
        _stacked([p["qg"].astype(cd), p["aqk"].astype(cd)], 2),
        _stacked([state_c, v_new], 1),
    )
    for h in range(heads):
        o_ref[:, h * dv:(h + 1) * dv] = out[h].astype(o_ref.dtype)
    state = _state_decay(ix, g, dv) * state + _dot(
        p["kd"].astype(cd), v_new, 1, 1
    )
    state_ref[:] = state
    # resident over the chunk axis: what leaves the row is written back
    final_ref[:] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref, *,
                heads: int, dk: int, dv: int):
    cd = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_ref[:] = jnp.zeros_like(dstate_ref)

    ix = _Chunk(cd, heads)
    g, beta = _heads_of(g_ref, heads, dk), _heads_of(beta_ref, heads, 1)
    p = _products(
        ix, _heads_of(q_ref, heads, dk), _heads_of(k_ref, heads, dk),
        _heads_of(v_ref, heads, dv), g, beta,
    )
    qf, kf, x_c = p["qf"], p["kf"], p["x_c"]
    do = _heads_of(do_ref, heads, dv)
    w_c, qg_c, kd_c, aqk_c = (
        p[name].astype(cd) for name in ("w", "qg", "kd", "aqk")
    )
    # against the state that entered the chunk and the cotangent of the one
    # that left it
    state, d_next = states_ref[:], dstate_ref[:]
    state_c, d_next_c = state.astype(cd), d_next.astype(cd)
    v_new = (p["u"] - _dot(w_c, state_c)).astype(cd)
    decay = _state_decay(ix, g, dv)
    # every entry of row c of the state that leaves carries e^{G_C,c}, so
    # dG_C is a lane sum of it against its cotangent
    left = decay * state + _dot(kd_c, v_new, 1, 1)
    d_end_parts = _parts(left * d_next, 2, cd)
    d_end = _dot(
        jnp.ones((heads, 8, dv * len(d_end_parts)), d_end_parts[0].dtype),
        _stacked(d_end_parts, 2), 2, 2,
    )[:, 0:1, :]
    d_vnew_c = (
        _dot(aqk_c, do, 1, 1) + _dot(kd_c, d_next_c)
    ).astype(cd)
    d_qg = _dot(do, state_c, 2, 2)
    d_kd = _dot(v_new, d_next_c, 2, 2)
    d_w = -_dot(d_vnew_c, state_c, 2, 2)
    dstate_ref[:] = (
        _dot(qg_c, do, 1, 1) + decay * d_next - _dot(w_c, d_vnew_c, 1, 1)
    )
    d_aqk = jnp.where(ix.lower_mask, _dot(do, v_new, 2, 2), 0.0)
    d_rhs = _dot(x_c, _stacked([d_vnew_c, d_w.astype(cd)], 2), 1, 1)
    d_rv, d_rk = d_rhs[..., :dv], d_rhs[..., dv:]
    d_a = jnp.where(ix.strict_mask, -_dot(
        d_rhs.astype(cd), _stacked([p["u"].astype(cd), w_c], 2), 2, 2
    ), 0.0)
    d_beta = (
        jnp.sum(d_rv * p["vf"], axis=2, keepdims=True)
        + jnp.sum(d_rk * p["kg"], axis=2, keepdims=True)
        + jnp.sum(d_a * p["akk"], axis=2, keepdims=True)
    )
    d_akk = beta * d_a
    d_kg = beta * d_rk
    d_diag = jnp.sum(jnp.where(ix.diagonal, d_aqk, 0.0), axis=2,
                     keepdims=True)
    d_q = d_qg * p["e_G"] + d_diag * kf
    d_k = d_kg * p["e_G"] + d_kd * p["e_tail"] + d_diag * qf
    d_G = d_kg * p["kg"] + d_qg * p["qg"] - d_kd * p["kd"]
    for mask, up, lo, ku, kl, qu in p["levels"]:
        d_both = _stacked([  # [P, 2C, C]
            jnp.where(mask, d_akk, 0.0).astype(cd),
            jnp.where(mask, d_aqk, 0.0).astype(cd),
        ], 1)
        by_row = _dot(d_both, kl)
        k_row, q_row = by_row[:, :CHUNK] * up, by_row[:, CHUNK:] * up
        k_col = _dot(d_both, _stacked([ku, qu], 1), 1, 1) * lo
        d_q = d_q + q_row
        d_k = d_k + k_row + k_col
        d_G = d_G + qf * q_row + kf * (k_row - k_col)
    row = jax.lax.broadcasted_iota(jnp.int32, d_G.shape, 1)
    d_G = d_G + jnp.where(row == CHUNK - 1, d_end, 0.0)
    d_g = _dot_exact(ix.upper, d_G)
    d_v = beta * d_rv
    lane = jax.lax.broadcasted_iota(jnp.int32, dbeta_ref.shape, 1)
    d_beta_out = jnp.zeros(dbeta_ref.shape, _F32)
    for h in range(heads):
        keys, values = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        dq_ref[:, keys] = d_q[h].astype(dq_ref.dtype)
        dk_ref[:, keys] = d_k[h].astype(dk_ref.dtype)
        dv_ref[:, values] = d_v[h].astype(dv_ref.dtype)
        dg_ref[:, keys] = d_g[h]
        d_beta_out = jnp.where(lane == h, d_beta[h], d_beta_out)
    dbeta_ref[:] = d_beta_out


# --- the calls ------------------------------------------------------------


def _specs(per: int, dk: int, dv: int, chunks: int, reverse: bool):
    """BlockSpecs for grid (batch row, head group, chunk) over [B, S, H·d]
    operands, beta as [B, H / per, S, per] and the states [B, H, S / 64, dk,
    dv]; ``reverse``: the chunks run from the row's end."""

    def at(c):
        return chunks - 1 - c if reverse else c

    def lanes(width):
        return pl.BlockSpec(
            (None, CHUNK, per * width), lambda b, h, c: (b, at(c), h)
        )

    beta = pl.BlockSpec(
        (None, None, CHUNK, per), lambda b, h, c: (b, h, at(c), 0)
    )
    states = pl.BlockSpec(
        (None, per, None, dk, dv), lambda b, h, c: (b, h, at(c), 0, 0)
    )
    return lanes(dk), lanes(dv), beta, states


def _compiler_params(per: int, dk: int, dv: int):
    # a head's float32 temporaries (the levels' factors) beside the blocks
    need = per * (48 * CHUNK * max(dk, dv) * 4 + 8 * dk * dv * 4)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=min(need + 16 * 2**20, 100 * 2**20),
    )


def _group_beta(beta, per: int):
    batch, seq, heads = beta.shape
    return beta.astype(_F32).reshape(batch, seq, heads // per, per).transpose(
        0, 2, 1, 3
    )


def _shape(q, v, heads: int):
    batch, seq, _ = q.shape
    per = max(n for n in range(1, HEADS_PER_STEP + 1) if heads % n == 0)
    return (batch, seq // CHUNK, q.shape[-1] // heads, v.shape[-1] // heads,
            per)


def _forward(q, k, v, g, beta, heads: int, interpret: bool):
    batch, chunks, dk, dv, per = _shape(q, v, heads)
    keys, values, betas, states = _specs(per, dk, dv, chunks, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=per, dk=dk, dv=dv),
        grid=(batch, heads // per, chunks),
        in_specs=[keys, keys, values, keys, betas],
        out_specs=[
            values, states,
            pl.BlockSpec((None, per, dk, dv), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct((batch, heads, chunks, dk, dv), _F32),
            jax.ShapeDtypeStruct((batch, heads, dk, dv), _F32),
        ],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), _F32)],
        compiler_params=None if interpret else _compiler_params(per, dk, dv),
        interpret=interpret,
        name="kda_fwd",
    )(q, k, v, g, _group_beta(beta, per))


def _backward(q, k, v, g, beta, states, do, heads: int, interpret: bool):
    batch, chunks, dk, dv, per = _shape(q, v, heads)
    keys, values, betas, state_spec = _specs(per, dk, dv, chunks, True)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=per, dk=dk, dv=dv),
        grid=(batch, heads // per, chunks),
        in_specs=[keys, keys, values, keys, betas, state_spec, values],
        out_specs=[keys, keys, values, keys, betas],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape, _F32),
            jax.ShapeDtypeStruct((batch, heads // per, chunks * CHUNK, per),
                                 _F32),
        ],
        scratch_shapes=[pltpu.VMEM((per, dk, dv), _F32)],
        compiler_params=None if interpret else _compiler_params(per, dk, dv),
        interpret=interpret,
        name="kda_bwd",
    )(q, k, v, g, _group_beta(beta, per), states, do)
    dbeta = dbeta.transpose(0, 2, 1, 3).reshape(beta.shape)
    return dq, dk_, dv_, dg, dbeta.astype(beta.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, heads, interpret):
    out, _states, final = _forward(q, k, v, g, beta, heads, interpret)
    return out, final


def _kda_fwd(q, k, v, g, beta, heads, interpret):
    out, states, final = _forward(q, k, v, g, beta, heads, interpret)
    return (out, final), (q, k, v, g, beta, states)


def _kda_bwd(heads, interpret, residuals, cotangents):
    # the state that leaves the row is reported, not differentiated
    return _backward(*residuals, cotangents[0], heads, interpret)


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, g, beta, interpret: Optional[bool] = None,
        return_state: bool = False):
    """o [B, S, H, dv] of the recurrence above from q, k [B, S, H, dk] and
    v [B, S, H, dv] in the compute dtype (q already scaled), the log-decays
    g [B, S, H, dk] (float32, <= 0) and the write strengths beta [B, S, H]
    (float32); a row that is no whole number of chunks of 64 is padded to one
    with tokens that write nothing. Differentiable in all five;
    ``return_state``: (o, the state that leaves the row [B, H, dk, dv],
    float32, detached). The
    operands are named ``kda_operands`` where the kernels take them, for the
    policies of ``models/remat.py`` that keep what a backward kernel reads."""
    if interpret is None:
        interpret = pallas_interpret()
    batch, seq, heads, dk = k.shape
    dv = v.shape[-1]
    ragged = -seq % CHUNK
    if ragged:
        # to a whole chunk with tokens that write nothing and forget
        # nothing (beta = 0, g = 0) behind the row: causal, so no output
        # before them moves, and the state leaves as it was
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, ragged)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    rows = seq + ragged
    flat = [
        checkpoint_name(x, "kda_operands") for x in (
            q.reshape(batch, rows, heads * dk),
            k.astype(q.dtype).reshape(batch, rows, heads * dk),
            v.astype(q.dtype).reshape(batch, rows, heads * dv),
            g.astype(_F32).reshape(batch, rows, heads * dk),
            beta.astype(_F32),
        )
    ]
    out, final = _kda(*flat, heads, interpret)
    out = out.reshape(batch, rows, heads, dv)[:, :seq]
    return (out, jax.lax.stop_gradient(final)) if return_state else out
