"""Sparse expert layers: the Switch top-1 FFN (ALBERT's option) and the
dropless top-k routed layer of a fine-grained expert decoder.

**Switch top-1** (``moe_ffn``; the reference repo has no MoE, SURVEY.md
§2.5). ALBERT's OPTIONAL sparse FFN (``--training.moe_experts``), run at toy
widths on a CPU mesh only: experts shard over a mesh axis and the token
shuffle lowers to XLA all-to-alls, in the classic GShard/Switch
dispatch-einsum formulation — no hand-written collectives, the sharding
annotations alone place the communication.

- router logits -> softmax gate, top-1 expert per token;
- capacity C = ceil(T / E · capacity_factor): each expert processes at most
  C tokens per batch, tokens beyond capacity fall through on the residual
  path (standard Switch behavior; static shapes are what the MXU wants);
- dispatch/combine as one-hot einsums: ``[T,E,C]`` masks against token
  activations — under pjit with ``wi/wo`` sharded ``P(axis)`` and tokens
  sharded over data, XLA inserts the all-to-alls;
- auxiliary load-balancing loss (mean gate · mean assignment per expert,
  scaled by E) exactly as in Switch, returned for the trainer to add.

**Dropless top-k** (``route_top_k``, ``routed_experts``; DeepSeek-V3's
``noaux_tc``, the layer ``models/deepseek_v3.RoutedFFN`` runs for that
model and for ``models/lfm2_moe.py``). Scores are sigmoids
over ALL experts; a correction bias enters the CHOICE of the top k and not
their weights; the weights are the chosen scores renormalised and scaled.
(``route_top_k_softmax`` is the other published rule, ``models/
smallthinker.py``'s: the top k of the logits, a softmax over the chosen,
no bias; its experts gate with a ReLU — ``routed_experts(activation=
"relu")`` — where the others gate with a SiLU.)
The layer is TOLD which experts it holds (``held = (first, count)``: one
chip's share of an expert-parallel deployment): it routes over all of them
and computes its own part — slots that chose an absent expert contribute
nothing (on the chips of a deployment their rows would leave over ICI;
nothing here stands in for those chips). No capacity, no dropped slot, no
``[T, E, C]`` mask: the (token, slot) pairs that chose a held expert are
sorted by expert into row tiles of ONE expert each (a group is padded to
whole tiles), and a loop over the tiles IN USE — a dynamic trip count, so
the work follows the rows that came, not the worst case the static shapes
must allow — gathers a tile's rows, runs its expert's SwiGLU and adds the
weighted result back to its tokens. The backward is the same loop written
by hand (a dynamic trip count has no reverse-mode rule): it replays the
tile's forward and accumulates the held experts' gradients in place — into
a zeroed float32 buffer that leaves as the matrices' cotangent, or, handed
``grad_sinks`` (the caller's float32 gradient accumulator for the three held
matrices), into those: the loop's carry starts from the sinks, what comes out
is the sinks' cotangent — ``sink + d`` in float32, never rounded to the
compute dtype — and the matrices' own cotangent is zero. An accumulator that
adds a gradient into a running sum anyway (``parallel/train_step.
make_accumulate_step``) takes the sink's cotangent as its new leaf: no zero
fill, no second pass over a held matrix to add it.

The bias is not trained by a gradient. ``with_load_cotangent`` defines the
bias leaf's COTANGENT as ``load_e − mean load`` of the micro-batch (``load``:
each expert's share of the routed (token, slot) pairs), so the statistic
rides every path a gradient rides — the accumulator, the mean over peers,
the wire, the flat layout — and ``optim`` steps such leaves by its sign.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    hidden_size: int
    ffn_size: int
    num_experts: int
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32


def init_moe_params(cfg: MoEConfig, rng: jax.Array) -> Dict[str, jnp.ndarray]:
    """Router + per-expert FFN stacks (leading expert axis — shard it with
    ``expert_param_sharding`` so each device holds E/n experts)."""
    kr, ki, ko = jax.random.split(rng, 3)
    scale_in = 1.0 / math.sqrt(cfg.hidden_size)
    scale_out = 1.0 / math.sqrt(cfg.ffn_size)
    return {
        "router": (
            jax.random.normal(kr, (cfg.hidden_size, cfg.num_experts)) * scale_in
        ).astype(jnp.float32),
        "wi": (
            jax.random.normal(
                ki, (cfg.num_experts, cfg.hidden_size, cfg.ffn_size)
            ) * scale_in
        ).astype(cfg.dtype),
        "wo": (
            jax.random.normal(
                ko, (cfg.num_experts, cfg.ffn_size, cfg.hidden_size)
            ) * scale_out
        ).astype(cfg.dtype),
    }


def expert_param_sharding(mesh: Mesh, axis: str = "expert"):
    """Pytree of shardings for ``init_moe_params`` output: experts split
    over ``axis``, the router replicated."""
    return {
        "router": NamedSharding(mesh, P()),
        "wi": NamedSharding(mesh, P(axis)),
        "wo": NamedSharding(mesh, P(axis)),
    }


def moe_ffn(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,  # [T, H] tokens (flatten batch x seq first)
    cfg: MoEConfig,
    mesh: Optional[Mesh] = None,
    axis: str = "expert",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [T, H], aux_loss scalar). Over-capacity tokens pass
    through as zeros (add the residual connection outside).

    With ``mesh``, intermediate expert blocks are sharding-constrained to
    ``P(axis)`` so the dispatched tokens travel to their expert's device
    (the all-to-all) and the FFN runs expert-local.
    """
    T = x.shape[0]
    E = cfg.num_experts
    capacity = max(1, math.ceil(T / E * cfg.capacity_factor))

    gate_logits = x.astype(jnp.float32) @ params["router"]  # [T, E]
    gates = jax.nn.softmax(gate_logits, axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)  # [T]
    gate = jnp.take_along_axis(gates, expert_idx[:, None], axis=-1)[:, 0]

    assign = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's queue (0-based)
    position = jnp.cumsum(assign, axis=0) * assign - 1.0
    in_capacity = (position < capacity) & (assign > 0)
    pos_in_expert = jnp.clip(position, 0, capacity - 1).astype(jnp.int32)

    # Switch aux loss: E * Σ_e (fraction of tokens on e) · (mean gate for e)
    density = jnp.mean(assign, axis=0)
    density_proxy = jnp.mean(gates, axis=0)
    aux_loss = E * jnp.sum(density * density_proxy)

    # [T, E, C] dispatch mask (in_capacity already excludes non-assigned
    # slots); combine carries the gate weight
    dispatch = (
        in_capacity.astype(jnp.float32)[:, :, None]
        * jax.nn.one_hot(pos_in_expert, capacity, dtype=jnp.float32)
    )
    combine = dispatch * gate[:, None, None]

    # tokens -> expert blocks (the all-to-all when experts are sharded)
    expert_in = jnp.einsum(
        "tec,th->ech", dispatch.astype(cfg.dtype), x.astype(cfg.dtype)
    )
    if mesh is not None:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(axis))
        )
    h = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in, params["wi"]))
    expert_out = jnp.einsum("ecf,efh->ech", h, params["wo"])
    if mesh is not None:
        expert_out = jax.lax.with_sharding_constraint(
            expert_out, NamedSharding(mesh, P(axis))
        )
    # expert blocks -> tokens (the reverse all-to-all), gate-weighted
    y = jnp.einsum(
        "tec,ech->th", combine.astype(cfg.dtype), expert_out
    )
    return y.astype(x.dtype), aux_loss


# ------------------------------------------------- dropless top-k routing


def route_top_k(scores, bias, top_k: int, scale: float, eps: float = 1e-20):
    """(choice [T, k] int32, weights [T, k] float32) from sigmoid scores
    [T, E] (float32) and the correction bias [E]: the bias enters the choice
    alone; the weights are the chosen scores over their sum (+ ``eps``, the
    published model's own: 1e-20 DeepSeek-V3's, 1e-6 LFM2's), times
    ``scale``."""
    _, choice = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, choice, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    return choice, weights * scale


def route_top_k_softmax(logits, top_k: int):
    """(choice [T, k] int32, weights [T, k] float32) from router LOGITS
    [T, E] (float32): the top k of the logits, a softmax over the chosen
    ones alone — no bias, no scale; the weights sum to 1."""
    picked, choice = jax.lax.top_k(logits, top_k)
    return choice, jax.nn.softmax(picked, axis=-1)


def expert_load(choice, num_experts: int):
    """[E] float32: each expert's share of the routed (token, slot) pairs."""
    counts = jnp.zeros((num_experts,), jnp.float32).at[
        choice.reshape(-1)
    ].add(1.0)
    return counts / choice.size


@jax.custom_vjp
def with_load_cotangent(x, bias, load):
    """``x``, unchanged. In the backward ``bias`` receives ``load − mean
    load`` whatever reaches ``x``: the load statistic is accumulated, averaged
    over peers and shipped as the bias leaf's gradient would be (the module
    docstring says why)."""
    return x


def _load_fwd(x, bias, load):
    return x, load


def _load_bwd(load, g):
    return g, load - jnp.mean(load), jnp.zeros_like(load)


with_load_cotangent.defvjp(_load_fwd, _load_bwd)


def _tile_plan(choice, held: Tuple[int, int], tile: int):
    """Where every (token, slot) pair that chose a held expert goes: rows
    sorted by expert, each expert's group padded to whole tiles.

    Returns ``row_slot`` [R] (the flat slot index t·k + j of each row, -1
    for padding; R = the static worst case), ``tile_expert`` [R / tile]
    (the LOCAL expert of each tile), ``tiles`` (how many are in use) and
    ``dropped`` (valid pairs that found no row: 0 by construction, counted
    so that a run can say so)."""
    first, count = held
    tokens, k = choice.shape
    local = choice.reshape(-1) - first
    valid = (local >= 0) & (local < count)
    key = jnp.where(valid, local, count)  # absent experts sort last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    padded = (sizes + tile - 1) // tile * tile
    ends, padded_ends = jnp.cumsum(sizes), jnp.cumsum(padded)
    # a token chooses an expert once: at most tokens · min(k, count) rows
    rows = tokens * min(k, count) + count * tile
    rows = (rows + tile - 1) // tile * tile
    sorted_key = key[order]
    group = jnp.minimum(sorted_key, count - 1)
    rank = jnp.arange(tokens * k, dtype=jnp.int32) - (ends - sizes)[group]
    position = jnp.where(
        sorted_key < count, (padded_ends - padded)[group] + rank, rows
    )
    row_slot = jnp.full((rows,), -1, jnp.int32).at[position].set(
        order, mode="drop"
    )
    tile_expert = jnp.minimum(
        jnp.searchsorted(
            padded_ends, jnp.arange(rows // tile, dtype=jnp.int32) * tile,
            side="right",
        ),
        count - 1,
    ).astype(jnp.int32)
    tiles = padded_ends[-1] // tile
    dropped = jnp.sum(valid) - jnp.sum(row_slot >= 0)
    return row_slot, tile_expert, tiles, dropped


def _silu_bwd(g, u, d_hidden):
    sig = jax.nn.sigmoid(g)
    return d_hidden * u * sig * (1.0 + g * (1.0 - sig)), d_hidden * g * sig


def _relu_bwd(g, u, d_hidden):
    on = g > 0
    return jnp.where(on, d_hidden * u, 0.0), jnp.where(on, d_hidden * g, 0.0)


# a gated expert's activation: act, and (d g, d u) of hidden = act(g) · u
ACTIVATIONS = {
    "silu": (jax.nn.silu, _silu_bwd),  # SwiGLU
    "relu": (jax.nn.relu, _relu_bwd),  # ReGLU
}


def _tile_forward(x, gate, up, down, tokens, activation):
    """One tile through its expert: (rows, gate·x, up·x, hidden, out)."""
    rows = x.at[tokens].get(mode="promise_in_bounds")
    g = jnp.dot(rows, gate, preferred_element_type=jnp.float32)
    u = jnp.dot(rows, up, preferred_element_type=jnp.float32)
    hidden = (ACTIVATIONS[activation][0](g) * u).astype(x.dtype)
    out = jnp.dot(hidden, down, preferred_element_type=jnp.float32)
    return rows, g, u, hidden, out


def _tile_operands(t, tile, row_token, row_weight, tile_expert, weights):
    tokens = jax.lax.dynamic_slice(row_token, (t * tile,), (tile,))
    scale = jax.lax.dynamic_slice(row_weight, (t * tile,), (tile,))
    expert = tile_expert[t]
    return tokens, scale, tuple(
        jax.lax.dynamic_index_in_dim(w, expert, keepdims=False)
        for w in weights
    ), expert


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _grouped_swiglu(x, row_weight, gate, up, down, sinks, row_token,
                    tile_expert, tiles, tile, activation):
    """``sinks``: None, or float32 (gate, up, down)-shaped buffers the
    forward ignores and the backward accumulates into. ``activation``: the
    gate's, a name of ``ACTIVATIONS`` (the function keeps its first name)."""
    out, _ = _grouped_swiglu_fwd(
        x, row_weight, gate, up, down, sinks, row_token, tile_expert, tiles,
        tile, activation,
    )
    return out


def _grouped_swiglu_fwd(x, row_weight, gate, up, down, sinks, row_token,
                        tile_expert, tiles, tile, activation):
    def body(t, total):
        tokens, scale, weights, _e = _tile_operands(
            t, tile, row_token, row_weight, tile_expert, (gate, up, down)
        )
        out = _tile_forward(x, *weights, tokens, activation)[-1]
        return total.at[tokens].add(out * scale[:, None])

    with jax.named_scope("moe_routed"):
        total = jax.lax.fori_loop(
            0, tiles, body, jnp.zeros(x.shape, jnp.float32)
        )
    return total, (x, row_weight, gate, up, down, sinks, row_token,
                   tile_expert, tiles)


def _grouped_swiglu_bwd(tile, activation, residuals, d_total):
    (x, row_weight, gate, up, down, sinks, row_token, tile_expert,
     tiles) = residuals
    held = (gate, up, down)

    def body(t, carry):
        dx, d_weight, d_gate, d_up, d_down = carry
        tokens, scale, (w_gate, w_up, w_down), expert = _tile_operands(
            t, tile, row_token, row_weight, tile_expert, held
        )
        rows, g, u, hidden, out = _tile_forward(
            x, w_gate, w_up, w_down, tokens, activation
        )
        d_scaled = d_total.at[tokens].get(mode="promise_in_bounds")
        d_weight = jax.lax.dynamic_update_slice(
            d_weight, jnp.sum(d_scaled * out, axis=-1), (t * tile,)
        )
        d_out = (d_scaled * scale[:, None]).astype(x.dtype)
        d_hidden = jax.lax.dot_general(
            d_out, w_down, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        d_g, d_u = (
            d.astype(x.dtype)
            for d in ACTIVATIONS[activation][1](g, u, d_hidden)
        )
        d_rows = jax.lax.dot_general(
            d_g, w_gate, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            d_u, w_up, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        def add(acc, lhs, rhs):  # acc[expert] += lhsᵀ rhs, in place
            term = jax.lax.dot_general(
                lhs, rhs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            old = jax.lax.dynamic_index_in_dim(acc, expert, keepdims=True)
            return jax.lax.dynamic_update_index_in_dim(
                acc, old + term[None], expert, 0
            )

        return (
            dx.at[tokens].add(d_rows), d_weight, add(d_gate, rows, d_g),
            add(d_up, rows, d_u), add(d_down, hidden, d_out),
        )

    with jax.named_scope("moe_routed"):
        dx, d_weight, d_gate, d_up, d_down = jax.lax.fori_loop(
            0, tiles, body, (
                jnp.zeros(x.shape, jnp.float32),
                jnp.zeros(row_weight.shape, jnp.float32),
                # the one difference a sink makes: where the sums start
                *(sinks if sinks is not None else (
                    jnp.zeros(w.shape, jnp.float32) for w in held
                )),
            ),
        )
    summed = (d_gate, d_up, d_down)
    if sinks is None:
        d_held = tuple(d.astype(w.dtype) for d, w in zip(summed, held))
        d_sinks = None
    else:  # the sums left in the sinks, float32 as they are
        d_held = tuple(jnp.zeros_like(w) for w in held)
        d_sinks = summed
    int_zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return (
        dx.astype(x.dtype), d_weight, *d_held, d_sinks,
        int_zero(row_token), int_zero(tile_expert), int_zero(tiles),
    )


_grouped_swiglu.defvjp(_grouped_swiglu_fwd, _grouped_swiglu_bwd)


def routed_experts(x, choice, weights, gate, up, down,
                   held: Tuple[int, int], tile: int = 256, grad_sinks=None,
                   activation: str = "silu"):
    """The held experts' part of Σ_{e in choice} w_e · GLU_e(x), with
    GLU_e(x) = down_e(act(gate_e x) ⊙ up_e x) and ``activation`` the gate's:
    "silu" (SwiGLU) or "relu" (ReGLU).

    ``x`` [T, H] in the compute dtype; ``choice`` / ``weights`` [T, k] from
    ``route_top_k`` or ``route_top_k_softmax``; ``gate`` / ``up`` [n, H, F]
    and ``down`` [n, F, H] the
    HELD experts' matrices in the compute dtype, expert ``held[0] + i`` at
    index i; ``grad_sinks``: None, or three float32 buffers of the held
    matrices' shapes whose COTANGENT is ``sink + d matrix`` while the
    matrices' own is zero (the module docstring says who wants that).
    Returns (y [T, H] float32, stats): ``stats['local_slot_share']``
    the share of routed slots that chose a held expert,
    ``stats['dropped_slots']`` the valid slots the plan lost (0),
    ``stats['grad_sink_leaves']`` the sinks the loop was handed (3 or 0)."""
    tile = min(tile, max(8, x.shape[0]))
    with jax.named_scope("moe_routed"):
        row_slot, tile_expert, tiles, dropped = _tile_plan(choice, held, tile)
        real = row_slot >= 0
        slot = jnp.maximum(row_slot, 0)
        row_token = slot // choice.shape[1]
        row_weight = jnp.where(real, weights.reshape(-1)[slot], 0.0)
    y = _grouped_swiglu(
        x, row_weight, gate, up, down,
        None if grad_sinks is None else tuple(grad_sinks),
        row_token, tile_expert, tiles, tile, activation,
    )
    return y, {
        "grad_sink_leaves": jnp.float32(
            0 if grad_sinks is None else len(grad_sinks)
        ),
        "local_slot_share": jnp.sum(real) / choice.size,
        "dropped_slots": dropped.astype(jnp.float32),
    }
