"""Fused residual-add + LayerNorm as a Pallas TPU kernel (fwd + bwd).

The remat replay's elementwise HBM passes are the second-largest sink in the
ALBERT step after attention (docs/perf.md "Remaining gap"): under
rematerialisation, the backward pass re-runs the layer's add→LayerNorm
chains from saved matmul outputs — each a read+write of a [B,S,H] tensor at
HBM bandwidth, plus fp32 mean/variance recomputation.

This kernel makes the whole post-matmul tail ONE pass each way:

forward   y = LN(x + r) · γ + β      one kernel: reads x, r; writes y and
                                     the backward's residuals (x̂, rstd)
backward  (dy) -> (da, dγ, dβ)       one kernel: da serves both dx and dr
                                     (the residual add backpropagates the
                                     same cotangent to both inputs)

Designed to compose with the ``fused_ln`` remat policy (models/remat.py):
Pallas outputs are saveable, so (y, x̂, rstd) survive remat and the backward
runs straight from them — no add/LN replay at all. The policy drops the two
out-projection matmul saves the adds used to consume (attention out-proj,
FFN down-proj), so the extra x̂ residual is HBM-neutral versus the
``dots_no_batch_attn`` policy.

Layout contract: inputs flatten to [N, H] rows. Per-row scalars (rstd) ride
as ROW vectors [1, N] — a [N, 1] column would be 128×-padded by the TPU's
(8, 128) tiling (same trick as the flash kernel's lse). γ/β ride as [1, H]
rows. dγ/dβ accumulate across the sequential TPU grid directly in their
output blocks (constant index map => the block stays resident in VMEM).

Statistics are fp32 regardless of input dtype; x̂ is stored in the input
dtype (bf16) — the same precision the unfused path's backward sees, since
its replay also recomputes statistics from bf16 activations.

Off-TPU the kernels run under ``interpret=True`` (CPU tests, virtual mesh).

On a multi-device mesh ``ln_residual(..., mesh=...)`` runs the custom-VJP op
under ``jax.shard_map`` (GSPMD cannot partition a Mosaic kernel): rows split
over "data" (and "seq"), γ/β replicated — so the shard_map transpose psums
the per-shard dγ/dβ partial sums.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from dedloc_tpu.ops.flash_attention import _pick_block, mesh_axis
from dedloc_tpu.utils.backend import pallas_interpret


def _t(x):
    return jnp.swapaxes(x, -1, -2)


# ------------------------------------------------------------------ forward


def _fwd_kernel(x_ref, r_ref, gamma_ref, beta_ref, y_ref, xhat_ref,
                rstd_ref, *, eps):
    a = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    mu = jnp.mean(a, axis=-1, keepdims=True)  # [bn, 1] column (VMEM only)
    centred = a - mu
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = centred * rstd
    gamma = gamma_ref[:].astype(jnp.float32)  # [1, H] broadcast row
    beta = beta_ref[:].astype(jnp.float32)
    y_ref[:] = (xhat * gamma + beta).astype(y_ref.dtype)
    if xhat_ref is not None:  # y-only variant for non-differentiating calls
        xhat_ref[:] = xhat.astype(xhat_ref.dtype)
        rstd_ref[:] = _t(rstd)  # -> [1, bn] row (HBM tiling)


def _fwd(x2, r2, gamma, beta, eps, block_n, interpret, with_residuals=True):
    """``with_residuals=False`` emits a y-only kernel: inference/eval calls
    skip the [N, H] x̂ + rstd HBM writes that only the backward needs."""
    n, h = x2.shape
    bn = _pick_block(n, block_n)
    row_spec = pl.BlockSpec((bn, h), lambda i: (i, 0))
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((n, h), x2.dtype)]
    if with_residuals:
        out_specs += [row_spec, pl.BlockSpec((1, bn), lambda i: (0, i))]
        out_shape += [
            jax.ShapeDtypeStruct((n, h), x2.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ]
        kernel = functools.partial(_fwd_kernel, eps=eps)
    else:
        def kernel(x_ref, r_ref, gamma_ref, beta_ref, y_ref):
            _fwd_kernel(x_ref, r_ref, gamma_ref, beta_ref, y_ref,
                        None, None, eps=eps)

    outs = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            row_spec,
            row_spec,
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="ln_residual_fwd",
    )(x2, r2, gamma[None, :], beta[None, :])
    return outs if with_residuals else (outs[0], None, None)


# ----------------------------------------------------------------- backward


def _bwd_kernel(xhat_ref, rstd_ref, gamma_ref, dy_ref, da_ref, dgamma_ref,
                dbeta_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dgamma_ref[:] = jnp.zeros_like(dgamma_ref)
        dbeta_ref[:] = jnp.zeros_like(dbeta_ref)

    xhat = xhat_ref[:].astype(jnp.float32)  # [bn, H]
    dy = dy_ref[:].astype(jnp.float32)
    gamma = gamma_ref[:].astype(jnp.float32)  # [1, H]
    rstd = _t(rstd_ref[:])  # [1, bn] row -> [bn, 1] column

    gdy = dy * gamma
    m1 = jnp.mean(gdy, axis=-1, keepdims=True)  # [bn, 1]
    m2 = jnp.mean(gdy * xhat, axis=-1, keepdims=True)
    da_ref[:] = ((gdy - m1 - xhat * m2) * rstd).astype(da_ref.dtype)

    # γ/β gradients accumulate in the resident output block across the
    # sequential grid (constant index map)
    dgamma_ref[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbeta_ref[:] += jnp.sum(dy, axis=0, keepdims=True)


def _bwd(xhat, rstd, gamma, dy, block_n, interpret):
    n, h = xhat.shape
    bn = _pick_block(n, block_n)
    da, dgamma, dbeta = pl.pallas_call(
        _bwd_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), dy.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
        interpret=interpret,
        name="ln_residual_bwd",
    )(xhat, rstd, gamma[None, :], dy)
    return da, dgamma[0], dbeta[0]


# --------------------------------------------------------------- public op


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ln_residual(x2, r2, gamma, beta, eps, block_n, interpret):
    # primal without differentiation (eval/serving): y-only kernel — the
    # x̂/rstd residuals are only materialized by the vjp-fwd rule below
    y, _, _ = _fwd(x2, r2, gamma, beta, eps, block_n, interpret,
                   with_residuals=False)
    return y


def _ln_residual_fwd(x2, r2, gamma, beta, eps, block_n, interpret):
    # (y, xhat, rstd) are Pallas outputs => saved by the fused_ln remat
    # policy: the backward below never replays the add/LN chain
    y, xhat, rstd = _fwd(x2, r2, gamma, beta, eps, block_n, interpret)
    return y, (xhat, rstd, gamma)


def _ln_residual_bwd(eps, block_n, interpret, residuals, dy):
    xhat, rstd, gamma = residuals
    da, dgamma, dbeta = _bwd(xhat, rstd, gamma, dy, block_n, interpret)
    # the residual add fans the same cotangent to both inputs
    return da, da, dgamma, dbeta


_ln_residual.defvjp(_ln_residual_fwd, _ln_residual_bwd)


def _default_block_n() -> int:
    """Rows per grid step (tunable via DEDLOC_FUSED_LN_BLOCK for sweeps;
    256 measured best on v5e at H=1024 — see docs/perf.md)."""
    import os

    return int(os.environ.get("DEDLOC_FUSED_LN_BLOCK", "256"))


def ln_residual(
    x: jnp.ndarray,  # [..., H] (the matmul-output branch)
    r: jnp.ndarray,  # [..., H] (the residual branch)
    gamma: jnp.ndarray,  # [H] fp32
    beta: jnp.ndarray,  # [H] fp32
    eps: float = 1e-12,
    block_n: int | None = None,
    interpret: bool | None = None,
    mesh: Optional[Mesh] = None,
) -> jnp.ndarray:
    """``LayerNorm(x + r) * gamma + beta`` as one fused pass (fp32 stats),
    returned in ``x.dtype``. ``interpret=None`` takes ``pallas_interpret()``:
    compiled on TPU, interpreter elsewhere. ``mesh``: the device mesh the
    caller's jit spans — the op then runs per row shard under ``shard_map``
    (see module docstring). The row count each device sees must split into
    blocks of a multiple of 8 rows (or be one block); the TPU lowering
    refuses anything else."""
    if interpret is None:
        interpret = pallas_interpret()
    if block_n is None:
        block_n = _default_block_n()

    def op(x, r, gamma, beta):
        h = x.shape[-1]
        y = _ln_residual(
            x.reshape(-1, h), r.reshape(-1, h),
            gamma.astype(jnp.float32), beta.astype(jnp.float32),
            float(eps), block_n, interpret,
        )
        return y.reshape(x.shape)

    if mesh is not None:
        # [B, S, H]: batch over "data", sequence over "seq"; [N, H]: rows
        # over "data"; H whole
        rows = P(
            *(mesh_axis(mesh, "data"), mesh_axis(mesh, "seq"))[: x.ndim - 1]
        )
        # check_vma=False: pallas_call outputs carry no varying-axes type
        op = jax.shard_map(
            op, mesh=mesh, in_specs=(rows, rows, P(), P()), out_specs=rows,
            check_vma=False,
        )
    return op(x, r, gamma, beta)


def ln_residual_reference(x, r, gamma, beta, eps: float = 1e-12):
    """Pure-jnp twin of ``ln_residual``: the numerics oracle for tests and
    the unfused model path (``cfg.fused_ln=False``)."""
    a = x.astype(jnp.float32) + r.astype(jnp.float32)
    mu = jnp.mean(a, axis=-1, keepdims=True)
    centred = a - mu
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    xhat = centred * jax.lax.rsqrt(var + eps)
    y = xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(x.dtype)
