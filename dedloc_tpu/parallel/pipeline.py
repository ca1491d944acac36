"""Pipeline parallelism: GPipe-style microbatch pipelining over a mesh axis.

The reference has NO pipeline parallelism (SURVEY.md §2.5: DP is its only
parallelism; PP listed "not required") — this module is a TPU-native
extension so deep stacks can shard *layers* across devices when tensor
parallelism alone runs out of per-device memory. Design follows the
scaling-book recipe rather than torch-style stage processes: one SPMD
program under ``shard_map``, activations hopping stage→stage with
``lax.ppermute`` while every device computes in lockstep, autodiff
differentiating straight through the loop (the backward pipeline is the
transposed forward — ppermute's transpose is the reverse hop, so GPipe's
reverse schedule falls out of ``jax.grad`` for free).

Schedule: classic GPipe fill-and-drain. With S stages and M microbatches
the loop runs T = M + S - 1 ticks; stage s computes microbatch m at tick
s + m. Bubble fraction = (S-1)/T, amortized by raising M (the collaborative
trainer accumulates many micro-batches per optimizer step anyway, so M is
naturally large here).

Stage parameters may be
- stacked:   every leaf carries a leading ``[S, ...]`` stage axis, sharded
  ``P(axis)`` over the pipe axis so each device holds only its stage's
  slice (the memory win PP exists for), or
- shared:    no stage axis (ALBERT's cross-layer weight sharing) — the same
  params replicated to every stage; each stage then applies the shared
  block a slice of the iteration count.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stage_param_sharding(mesh: Mesh, axis: str = "pipe") -> NamedSharding:
    """Sharding for stacked stage params: leading stage axis over ``axis``."""
    return NamedSharding(mesh, P(axis))


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    microbatches: jnp.ndarray,
    mesh: Mesh,
    axis: str = "pipe",
    stacked_params: bool = True,
    micro_spec: P = P(),
) -> jnp.ndarray:
    """Run ``microbatches`` through S pipelined stages; returns ``[M, ...]``.

    stage_fn(params_s, x) -> y must keep the activation structure (a
    transformer block, a stage of them, ...). ``microbatches`` is ``[M, ...]``
    with M the microbatch count — an array or a pytree of arrays sharing the
    leading M (e.g. ``(hidden, attn_bias)`` when each microbatch carries its
    own mask); non-leading dims may additionally be sharded over other mesh
    axes (e.g. batch over "data") — the pipe loop is independent of them. With ``stacked_params`` every leaf of ``stage_params`` has a
    leading ``[S, ...]`` axis (place it with ``stage_param_sharding`` so the
    slice lives on its stage's device); otherwise params are taken as shared
    and replicated. ``micro_spec`` shards the microbatch array's *other*
    dims over other mesh axes (e.g. ``P(None, "data")`` for a ``[M, B, ...]``
    input batch-sharded over data parallelism); it must not use ``axis``.

    Outputs are returned with the same spec as the inputs: replicated over
    the pipe axis (one psum at the end — costs one activation-sized transfer
    per microbatch; cheap next to the stage compute it ships).
    """
    spec_axes = [
        name
        for entry in tuple(micro_spec)
        for name in (entry if isinstance(entry, tuple) else (entry,))
    ]
    if axis in spec_axes:
        raise ValueError(f"micro_spec must not shard over the pipe axis {axis!r}")
    n_stages = mesh.shape[axis]
    micro_leaves = jax.tree_util.tree_leaves(microbatches)
    n_micro = micro_leaves[0].shape[0]
    if any(leaf.shape[0] != n_micro for leaf in micro_leaves):
        raise ValueError(
            "every microbatch leaf needs the same leading microbatch count; "
            f"got {[leaf.shape[0] for leaf in micro_leaves]}"
        )
    if stacked_params:
        for path, leaf in jax.tree_util.tree_leaves_with_path(stage_params):
            if leaf.shape[:1] != (n_stages,):
                # a multiple of n_stages would legally split under P(axis)
                # and then silently drop all but one stage per device
                raise ValueError(
                    f"stacked stage params need leading dim {n_stages} "
                    f"(= mesh axis {axis!r}); got {leaf.shape} at "
                    f"{jax.tree_util.keystr(path)}"
                )

    param_spec = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params)
        if stacked_params
        else jax.tree_util.tree_map(lambda _: P(), stage_params)
    )
    # other mesh axes (data, model, ...) pass through untouched via
    # micro_spec; the pipe loop itself never shards the microbatch array
    in_specs = (param_spec, micro_spec)
    out_spec = micro_spec

    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    tmap = jax.tree_util.tree_map

    def pipelined(params, micro):
        stage = jax.lax.axis_index(axis)
        if stacked_params:
            # shard_map hands each device its [1, ...] stage slice
            params = tmap(lambda p: p[0], params)

        # T = M + S - 1 ticks: feed zeros during the drain phase (stage 0
        # ignores them once m >= M). micro may be a pytree (e.g. an
        # (activation, per-microbatch-bias) pair) — every op below maps
        # leaf-wise.
        feed = tmap(
            lambda m: jnp.concatenate(
                [m, jnp.zeros((n_stages - 1,) + m.shape[1:], m.dtype)], axis=0
            ),
            micro,
        )

        def tick(buf, x_in):
            # stage 0 ingests the next microbatch; others take the hop input
            x = tmap(lambda i, b: jnp.where(stage == 0, i, b), x_in, buf)
            y = stage_fn(params, x)
            # last stage's result this tick IS a finished microbatch during
            # the drain window; everyone else forwards theirs down the pipe
            hopped = jax.lax.ppermute(y, axis, fwd_perm)
            done = tmap(
                lambda v: jnp.where(stage == n_stages - 1, v, jnp.zeros_like(v)),
                y,
            )
            return hopped, done

        # the carry is device-varying (each stage holds a different
        # activation) while the zeros literal is replicated — mark it so
        # the scan's carry type is stable under shard_map's VMA checks
        buf0 = tmap(
            lambda m: jax.lax.pcast(
                jnp.zeros_like(m[0]), (axis,), to="varying"
            ),
            micro,
        )
        _, dones = jax.lax.scan(tick, buf0, feed)
        # microbatch m finishes at tick m + S - 1 on the last stage; every
        # other device contributed zeros, so a psum replicates the result
        outs = tmap(lambda d: d[n_stages - 1 : n_stages - 1 + n_micro], dones)
        return jax.lax.psum(outs, axis)

    return shard_map(
        pipelined, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
    )(stage_params, microbatches)


def shared_stage_fn(
    block_fn: Callable[[Any, jnp.ndarray], jnp.ndarray], iters_per_stage: int
) -> Callable[[Any, jnp.ndarray], jnp.ndarray]:
    """ALBERT-style stage: apply ONE shared block ``iters_per_stage`` times
    (cross-layer weight sharing means stages differ only in position, models
    /albert.py encoder scan). Use with ``stacked_params=False``."""

    def stage(params, x):
        def body(h, _):
            return block_fn(params, h), None

        out, _ = jax.lax.scan(body, x, None, length=iters_per_stage)
        return out

    return stage
