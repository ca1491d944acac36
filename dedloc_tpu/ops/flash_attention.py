"""Fused flash attention as a Pallas TPU kernel (forward + backward).

The hot op of the ALBERT workload (AlbertSelfAttention, models/albert.py) and
the long-context path. Same exact-softmax math as FlashAttention: the S×S
score matrix never leaves VMEM — logits for one (query-block, kv-block) tile
are computed on the MXU, folded into an online-softmax accumulator, and
discarded. HBM traffic per head drops from O(S²) (XLA's unfused dense path
materializes probs for the backward) to O(S·D + S).

Kernel structure (the canonical Pallas flash shape): the reduction axis is
the INNERMOST GRID DIMENSION, not an in-kernel loop over a resident slab —
TPU grids execute sequentially, so the online-softmax state (acc, m, l) lives
in VMEM scratch across the inner iterations, initialized at the first and
flushed to the output block at the last. VMEM use is O(block), independent of
S: sequence length is bounded by HBM, not VMEM (verified S=16k on a v5e).

Backward follows the standard flash recipe: save only (out, logsumexp) as
residuals, recompute probability tiles on the fly in two kernels (dq over
query blocks, kv innermost; dk/dv over kv blocks, q innermost) using
delta = rowsum(dO ⊙ O).

Layout contract: [B, S, H, D] in/out (the model's layout); internally heads
fold into the grid as [B*H, S, D]. Per-position scalars (bias, lse, delta)
ride as ROW vectors [BH, 1, S]: a [BH, S, 1] column layout would be
128×-padded by the TPU's (8, 128) tiling — 2 GB of HBM for S=16k — so rows
travel packed and are transposed to columns in VMEM where the math needs
them. The additive bias is per KV position (0 keep / -inf drop), broadcast
over heads — exactly the mask bias AlbertModel builds; it is
non-differentiable (it comes from the attention mask).

Off-TPU (CPU tests, CI) the same kernels run under ``interpret=True``
(``utils.backend.pallas_interpret`` decides, once, for every op here).

On a multi-device mesh a Mosaic kernel cannot be partitioned by GSPMD, so
``flash_attention(..., mesh=...)`` runs the custom-VJP op under
``jax.shard_map``: batch over "data", heads over "model" where the mesh has
that axis, the sequence whole on every device.
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

from dedloc_tpu.utils.backend import pallas_interpret

NEG_INF = -1e30


def mesh_axis(mesh: Mesh, name: str) -> Optional[str]:
    """``name`` if the mesh has that axis (shard over it), else None."""
    return name if name in mesh.axis_names else None


def _pick_block(s: int, preferred: int) -> int:
    block = min(preferred, s)
    while s % block:
        block //= 2
    return max(block, 1)


def _t(x):
    """2D transpose (row [1, N] <-> column [N, 1] relayout in VMEM)."""
    return jnp.swapaxes(x, -1, -2)


# ------------------------------------------------------------------ forward


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, gh, packed):
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # gh heads per program (unrolled): one grid step's DMAs and semaphore
    # work amortise over gh heads' matmuls — at D=64 the per-head dots are
    # too small to hide the per-program overhead (measured on v5e).
    for g in range(gh):
        q = q_ref[g]  # [Bq, D]
        k = k_ref[g]  # [Bk, D]
        v = v_ref[g]
        b = bias_ref[g]  # [1, Bk]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + b.astype(jnp.float32)

        # softmax state lives as COLUMNS [Bq, 1] in scratch (it never touches
        # HBM) so the running max/denominator broadcast against s with zero
        # cross-lane relayouts; only the lse OUTPUT is a row (HBM tiling).
        m_prev, l_prev = m_ref[g], l_ref[g]  # [Bq, 1] columns
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)  # [Bq, 1]
        l_ref[g] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[g] = m_new
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[g] = acc_ref[g] * corr + pv

    @pl.when(kb == nk - 1)
    def _flush():
        d = q_ref.shape[-1]
        for g in range(gh):
            safe_l = jnp.maximum(l_ref[g], 1e-30)  # [Bq, 1]
            o = (acc_ref[g] / safe_l).astype(o_ref.dtype)
            if packed:
                # PAIRED output layout: two D=64 heads share one 128-lane
                # tile, so the (remat-saved) output has no lane padding in
                # HBM — half the residual bytes of a [..., 64] layout
                o_ref[g // 2, :, (g % 2) * d:(g % 2 + 1) * d] = o
            else:
                o_ref[g] = o
            lse_ref[g] = _t(m_ref[g] + jnp.log(safe_l))  # -> [1, Bq] row


def _pick_heads(bh: int, block_q: int, block_k: int, budget_mb: float = 6.0):
    """Heads per program: amortise grid-step overhead while keeping the
    per-head transient (fp32 scores + bf16 probs ≈ 6·Bq·Bk bytes) within a
    conservative VMEM budget (~16 MB/core total on v5e)."""
    per_head_mb = 6.0 * block_q * block_k / 2**20
    g = 8
    while g > 1 and (bh % g or g * per_head_mb > budget_mb):
        g //= 2
    return g


def _fwd(q3, k3, v3, bias3, block_q, block_k, interpret):
    """Returns (out, lse). ``out`` is [BH//2, S, 2D] PAIRED when D < 128 and
    the head-group size is even (no lane padding in HBM — matters because
    the remat policy saves this tensor per layer), else [BH, S, D]."""
    bh, s, d = q3.shape
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    gh = _pick_heads(bh, bq, bk)
    packed = d < 128 and gh % 2 == 0
    scale = 1.0 / (d ** 0.5)
    if packed:
        out_spec = pl.BlockSpec((gh // 2, bq, 2 * d),
                                lambda i, j, kb: (i, j, 0))
        out_shape = jax.ShapeDtypeStruct((bh // 2, s, 2 * d), q3.dtype)
    else:
        out_spec = pl.BlockSpec((gh, bq, d), lambda i, j, kb: (i, j, 0))
        out_shape = jax.ShapeDtypeStruct((bh, s, d), q3.dtype)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, gh=gh, packed=packed),
        grid=(bh // gh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((gh, bq, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((gh, bk, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((gh, bk, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((gh, 1, bk), lambda i, j, kb: (i, 0, kb)),
        ],
        out_specs=[
            out_spec,
            pl.BlockSpec((gh, 1, bq), lambda i, j, kb: (i, 0, j)),
        ],
        out_shape=[
            out_shape,
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((gh, bq, d), jnp.float32),
            pltpu.VMEM((gh, bq, 1), jnp.float32),
            pltpu.VMEM((gh, bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3, bias3)
    return out, lse


def _unpack_heads(out, bh: int, d: int):
    """[BH//2, S, 2D] paired -> [BH, S, D] (cheap relayout; inverse pairing
    of the fwd kernel's flush)."""
    if out.shape[0] == bh:
        return out
    half, s, _ = out.shape
    return out.reshape(half, s, 2, d).transpose(0, 2, 1, 3).reshape(bh, s, d)


# ----------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, lse_ref, do_ref, delta_ref,
               dq_ref, dq_acc_ref, *, scale, gh):
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    for g in range(gh):
        q = q_ref[g]
        k = k_ref[g]
        v = v_ref[g]
        b = bias_ref[g]  # [1, Bk]
        do = do_ref[g]  # native (bf16) dtype — MXU runs at full rate
        lse = _t(lse_ref[g])  # [1, Bq] row -> [Bq, 1] column
        delta = _t(delta_ref[g])

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + b.astype(jnp.float32)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale
        dq_acc_ref[g] = dq_acc_ref[g] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kb == nk - 1)
    def _flush():
        for g in range(gh):
            dq_ref[g] = dq_acc_ref[g].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, lse_ref, do_ref, delta_ref,
                dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale, gh):
    qb = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qb == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    for g in range(gh):
        q = q_ref[g]
        k = k_ref[g]
        v = v_ref[g]
        b = bias_ref[g]  # [1, Bk]
        do = do_ref[g]
        lse = _t(lse_ref[g])  # [Bq, 1]
        delta = _t(delta_ref[g])

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + b.astype(jnp.float32)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        dv_acc_ref[g] = dv_acc_ref[g] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale  # [Bq, Bk]
        dk_acc_ref[g] = dk_acc_ref[g] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qb == nq - 1)
    def _flush():
        for g in range(gh):
            dk_ref[g] = dk_acc_ref[g].astype(dk_ref.dtype)
            dv_ref[g] = dv_acc_ref[g].astype(dv_ref.dtype)


def _dqkv_fused_kernel(q_ref, k_ref, v_ref, bias_ref, lse_ref, do_ref,
                       delta_ref, dq_ref, dk_ref, dv_ref, *, scale, gh):
    """Single-block backward: when one (Bq, Bk) tile covers the whole
    sequence, dq/dk/dv share ONE score/prob computation and one set of
    input DMAs instead of recomputing them in two kernels."""
    for g in range(gh):
        q = q_ref[g]
        k = k_ref[g]
        v = v_ref[g]
        b = bias_ref[g]  # [1, Bk]
        do = do_ref[g]
        lse = _t(lse_ref[g])  # [Bq, 1]
        delta = _t(delta_ref[g])

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale + b.astype(jnp.float32)
        p = jnp.exp(s - lse)  # [Bq, Bk]
        pb = p.astype(do.dtype)
        dv_ref[g] = jax.lax.dot_general(
            pb, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [Bq, Bk]
        dq_ref[g] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dq_ref.dtype)
        dk_ref[g] = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dk_ref.dtype)


def _bwd(q3, k3, v3, bias3, lse, do, delta, block_q, block_k, interpret):
    bh, s, d = q3.shape
    bq = _pick_block(s, block_q)
    bk = _pick_block(s, block_k)
    scale = 1.0 / (d ** 0.5)
    if bq == s and bk == s:
        return _bwd_fused(q3, k3, v3, bias3, lse, do, delta, interpret)
    # bwd transients per head are ~3x the fwd's (s, p, dp, ds live at once)
    gh = _pick_heads(bh, bq, bk, budget_mb=4.0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, gh=gh),
        grid=(bh // gh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((gh, bq, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((gh, bk, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((gh, bk, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((gh, 1, bk), lambda i, j, kb: (i, 0, kb)),
            pl.BlockSpec((gh, 1, bq), lambda i, j, kb: (i, 0, j)),
            pl.BlockSpec((gh, bq, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((gh, 1, bq), lambda i, j, kb: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((gh, bq, d), lambda i, j, kb: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((gh, bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, bias3, lse, do, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, gh=gh),
        grid=(bh // gh, s // bk, s // bq),
        in_specs=[
            pl.BlockSpec((gh, bq, d), lambda i, j, qb: (i, qb, 0)),
            pl.BlockSpec((gh, bk, d), lambda i, j, qb: (i, j, 0)),
            pl.BlockSpec((gh, bk, d), lambda i, j, qb: (i, j, 0)),
            pl.BlockSpec((gh, 1, bk), lambda i, j, qb: (i, 0, j)),
            pl.BlockSpec((gh, 1, bq), lambda i, j, qb: (i, 0, qb)),
            pl.BlockSpec((gh, bq, d), lambda i, j, qb: (i, qb, 0)),
            pl.BlockSpec((gh, 1, bq), lambda i, j, qb: (i, 0, qb)),
        ],
        out_specs=[
            pl.BlockSpec((gh, bk, d), lambda i, j, qb: (i, j, 0)),
            pl.BlockSpec((gh, bk, d), lambda i, j, qb: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((gh, bk, d), jnp.float32),
            pltpu.VMEM((gh, bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, bias3, lse, do, delta)
    return dq, dk, dv


def _bwd_fused(q3, k3, v3, bias3, lse, do, delta, interpret):
    bh, s, d = q3.shape
    scale = 1.0 / (d ** 0.5)
    # fused kernel holds s, p, dp, ds (~4 full tiles) at once per head
    gh = _pick_heads(bh, s, s, budget_mb=3.0)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_dqkv_fused_kernel, scale=scale, gh=gh),
        grid=(bh // gh,),
        in_specs=[
            pl.BlockSpec((gh, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, 1, s), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, 1, s), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, 1, s), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gh, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, s, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((gh, s, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v3.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_fused",
    )(q3, k3, v3, bias3, lse, do, delta)
    return dq, dk, dv


# --------------------------------------------------------------- public op


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q3, k3, v3, bias3, block_q, block_k, interpret):
    out, _lse = _fwd(q3, k3, v3, bias3, block_q, block_k, interpret)
    return out


def _flash_fwd(q3, k3, v3, bias3, block_q, block_k, interpret):
    # ``out`` may be head-PAIRED [BH//2, S, 2D] (see _fwd): that exact array
    # is what the dots_no_batch_attn remat policy saves per layer, so the
    # packed layout halves the residual's HBM footprint at D=64
    out, lse = _fwd(q3, k3, v3, bias3, block_q, block_k, interpret)
    return out, (q3, k3, v3, bias3, out, lse)


def _flash_bwd(block_q, block_k, interpret, residuals, g):
    q3, k3, v3, bias3, out, lse = residuals
    bh, _, d = q3.shape
    if out.shape[0] != bh:  # paired layout: delta on packed forms, then
        half = bh // 2      # one cheap permutation for the kernels' do
        prod = g.astype(jnp.float32) * out.astype(jnp.float32)
        s_len = prod.shape[1]
        delta = (
            prod.reshape(half, s_len, 2, d).sum(-1)
            .transpose(0, 2, 1).reshape(bh, 1, s_len)
        )
        do = _unpack_heads(g, bh, d)
    else:
        delta = jnp.sum(
            g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
        )[:, None, :]  # [BH, 1, S] row layout (see module docstring)
        do = g
    dq, dk, dv = _bwd(q3, k3, v3, bias3, lse, do, delta, block_q, block_k,
                      interpret)
    # the mask bias is non-differentiable input
    return dq, dk, dv, jnp.zeros_like(bias3)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_bshd(q, k, v, bias, block_q, block_k, interpret):
    """The op on [B, S, H, D] operands as ONE device sees them (the whole
    arrays off-mesh, this device's batch/head shard under shard_map)."""
    b, s, h, d = q.shape
    # named in KERNEL layout so the fused_ln remat policy saves exactly what
    # the flash backward consumes — the replay then skips the [B,S,H,D] ->
    # [BH,S,D] relayout passes too
    to3 = lambda x, nm: checkpoint_name(
        x.transpose(0, 2, 1, 3).reshape(b * h, s, d), nm
    )
    bias3 = jnp.broadcast_to(
        bias[:, None, :], (b, h, s)
    ).reshape(b * h, 1, s).astype(jnp.float32)
    out3 = _flash(to3(q, "flash_qkv"), to3(k, "flash_qkv"),
                  to3(v, "flash_qkv"), bias3, block_q, block_k, interpret)
    out3 = _unpack_heads(out3, b * h, d)  # paired layout -> [BH, S, D]
    return out3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,  # [B, S_kv] additive
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """Exact fused attention; drop-in for dense/blockwise attention.

    ``interpret=None`` takes ``pallas_interpret()``: compiled on TPU,
    interpreter elsewhere (so CPU tests and the virtual mesh exercise
    identical kernel code). On TPU, effective block sizes must be multiples
    of 128 (or the whole sequence) for the bias/lse BlockSpecs to be
    Mosaic-legal. ``mesh``: the device mesh the caller's jit spans — the op
    then runs per shard under ``shard_map`` (see module docstring).
    """
    if interpret is None:
        interpret = pallas_interpret()
    if bias is None:
        bias = jnp.zeros((k.shape[0], k.shape[1]), jnp.float32)
    op = functools.partial(
        _flash_bshd, block_q=block_q, block_k=block_k, interpret=interpret
    )
    if mesh is not None:
        qkv = P(mesh_axis(mesh, "data"), None, mesh_axis(mesh, "model"), None)
        # check_vma=False: pallas_call outputs carry no varying-axes type
        op = jax.shard_map(
            op, mesh=mesh,
            in_specs=(qkv, qkv, qkv, P(mesh_axis(mesh, "data"), None)),
            out_specs=qkv, check_vma=False,
        )
    return op(q, k, v, bias)
