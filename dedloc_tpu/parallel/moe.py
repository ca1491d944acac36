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
"relu")`` — where the others gate with a SiLU. Nemotron-H's experts have NO
gate: ``routed_experts(..., gate=None, activation="relu2")`` runs
``down_e(relu(up_e x)²)``, two matrices and two sinks an expert, through the
same plan, walk and loops — the form is decided when the loop is traced, a
gated caller traces what it traced before.)
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
fill, no second pass over a held matrix to add it. The loops read the held
matrices in the COMPUTE dtype and never cast: the caller hands them over so
(``models/decoder.held_expert_ffn``). Under sinks the matrices' own cotangent
is zero, so whatever cast made them has no backward to run — which is why the
accumulate step may make them ONCE per set of weights, outside the function
it differentiates, and feed them in as an input (``train_step.
_StepWithComputeCopies``; 2 x 2.2 ms of whole-matrix float32 -> bf16 passes a
micro-batch in the LFM2 cell, forward and remat replay, before PR 50).

**The walk: runs of tiles, then tails.** Rows sorted by expert lie in RUNS,
and an iteration pays some costs whatever its rows: the expert's three bf16
matrices are read (6·HF bytes in the forward, H x F the matrix, 6·HF again in
the backward) and the three float32 gradient slices are read and written
under their weight-gradient dots (24·HF; XLA fuses ``old + term`` into the
dot, so no ``term`` is stored) — 36·HF, against 12 matmul-sized passes of
2·rows·HF FLOPs. On a v5e (197 TFLOP/s over 819 GB/s: 240 FLOP a byte) a
weight-gradient dot that contracts over 256 rows has 64 FLOPs a byte of its
slice and is bound by the slice's bytes, the row-wise matmuls have 256 a byte
of their matrix and sit at the ridge; at 1,024 rows both are the MXU's. On
the chip an iteration costs ~0.12 ms before its first row in SmallThinker's
layer (PERF.md section 6, PR 42). So the loops walk a group of p padded
tiles as p // m BULK iterations of m consecutive tiles of that one expert,
then p % m TAIL iterations of one tile, m = ``RUN_TILES`` = 2: pairs of 512
rows at the cells' tile of 256, then at most one single tile
(``_run_schedule``: the bulk and the tail iterations' start rows, two small
int32 schedules at their static bounds, and the two dynamic counts) — ONE
body in two ``fori_loop``s, which differ in one static integer. Why pairs
and not the 1,024 rows this arithmetic asks for, measured (PERF.md section
6): iterations of four tiles alone are the WORST walk where a balanced
expert holds two to four tiles (SDAR, LFM2: nothing reaches four); above
1,024 rows an iteration's temporaries push the layer's accumulators out of
fast memory and the walk LOSES (SDAR); one uniform tile of 512 or 1,024
pads every near-empty expert to it; and a third loop (fours, pairs, one)
read within 0.4 % of pairs in SmallThinker's step for a loop body more to
trace, five times a start. The plan
does not change: a group is still padded to ``tile`` rows (a near-empty
expert pays one tile, as before), the same static bound, no capacity, no
dropped slot; matrices stay bf16 operands under float32 accumulation; a
bulk iteration's weight gradient is ONE float32-accumulating dot over its
rows where the single-size walk added m partial sums in float32 (another
order of the same additions), summed into the sinks or the zeroed buffers
as before. ``stats['bulk_row_share']`` says how much of the work the bulk
iterations took; a routing whose groups stay at one tile runs the tail loop
alone (the bulk loop's trip count is 0). A row's value can move in
float32's last digits with WHO shares its iteration (the blocking of its
matmuls: 6e-7 on the CPU, ``tests/test_sdar_model.py``), where the
single-size walk (``run_tiles=1``) gives a row the same bits whatever the
other rows do.

**Element-at-a-time passes.** A gather or scatter of scalars is serial on a
TPU (~9 ns an element), and the plan's static bound is T · k slots however
few are held: so the plan takes its sorted keys from the ONE sort that gives
the order, counts the groups by compare-and-sum, and the loops gather a
row's weight — and scatter its gradient — per iteration, over the rows in
use. What is left over the bound is the scatter of the order into rows.

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
    [T, E] (float32) and the correction bias [E] (None: a model without
    one chooses by its scores): the bias enters the choice
    alone; the weights are the chosen scores over their sum (+ ``eps``, the
    published model's own: 1e-20 DeepSeek-V3's, 1e-6 LFM2's), times
    ``scale``."""
    _, choice = jax.lax.top_k(
        scores if bias is None else scores + jax.lax.stop_gradient(bias),
        top_k,
    )
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


# consecutive tiles of ONE expert a bulk iteration of the walk takes (the
# module docstring says why 2; PERF.md section 6, PR 42, what else was read
# on the chip). 1 is the single-size walk, one tile an iteration.
RUN_TILES = 2


def _run_schedule(sizes, padded, tile: int, m: int, rows: int):
    """How the loops walk the plan's groups (``sizes`` real and ``padded``
    padded rows a held expert, ``rows`` the plan's static bound): a group of
    p padded tiles is p // m BULK iterations of ``m`` consecutive tiles, then
    p % m TAIL iterations of one.

    Returns the walk — ``(starts, count)`` for the bulk and for the tail
    iterations: every iteration's start row at the static bound (``rows //
    (m · tile)``; at most m − 1 tails a group) and how many there are — in
    which every padded tile in use lies in exactly one iteration and an
    iteration in one group; and ``bulk_rows``, the real rows the bulk
    iterations hold."""
    count = sizes.shape[0]
    bulk, tail = padded // tile // m, padded // tile % m
    first = jnp.cumsum(padded) - padded  # a group's first row

    def schedule(counts, at, step, bound):
        ends = jnp.cumsum(counts)
        i = jnp.arange(bound, dtype=jnp.int32)
        group = jnp.minimum(  # compare_all: no loop for 16 boundaries
            jnp.searchsorted(ends, i, side="right", method="compare_all"),
            count - 1,
        )
        starts = at[group] + (i - (ends - counts)[group]) * step
        return starts.astype(jnp.int32), ends[-1]

    # a group's bulk iterations come first and hold its first rows
    return (
        schedule(bulk, first, m * tile, rows // (m * tile)),
        schedule(tail, first + bulk * (m * tile), tile,
                 min(rows // tile, count * (m - 1))),
    ), jnp.sum(jnp.minimum(sizes, bulk * (m * tile)))


def _tile_plan(choice, held: Tuple[int, int], tile: int,
               m: int = RUN_TILES):
    """Where every (token, slot) pair that chose a held expert goes: rows
    sorted by expert, each expert's group padded to whole tiles.

    Returns ``row_slot`` [R] (the flat slot index t·k + j of each row, -1
    for padding; R = the static worst case), ``tile_expert`` [R / tile]
    (the LOCAL expert of each tile), ``tiles`` (how many are in use),
    ``dropped`` (valid pairs that found no row: 0 by construction, counted
    so that a run can say so) and ``_run_schedule``'s pair: the walk over
    the tiles in use at ``m`` tiles a bulk iteration, and the real rows the
    bulk iterations hold."""
    first, count = held
    tokens, k = choice.shape
    local = choice.reshape(-1) - first
    valid = (local >= 0) & (local < count)
    key = jnp.where(valid, local, count)  # absent experts sort last
    # ONE sort gives the order and the sorted keys (a gather of T · k
    # elements otherwise), a compare-and-sum the group sizes (a scatter-add
    # of T · k otherwise): element-at-a-time ops on a TPU, ~9 ns each
    sorted_key, order = jax.lax.sort(
        (key, jnp.arange(tokens * k, dtype=jnp.int32)), num_keys=1,
        is_stable=True,
    )
    sizes = jnp.sum(
        key[:, None] == jnp.arange(count, dtype=key.dtype), axis=0,
        dtype=jnp.int32,
    )
    padded = (sizes + tile - 1) // tile * tile
    ends, padded_ends = jnp.cumsum(sizes), jnp.cumsum(padded)
    # a token chooses an expert once: at most tokens · min(k, count) rows
    rows = tokens * min(k, count) + count * tile
    rows = (rows + tile - 1) // tile * tile
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
    return row_slot, tile_expert, tiles, dropped, _run_schedule(
        sizes, padded, tile, m, rows
    )


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
# an UN-gated expert's (two matrices, ``gate`` None): act, and d u of
# hidden = act(u)
PLAIN_ACTIVATIONS = {
    "relu2": (  # relu(u)², Nemotron-H's
        lambda u: jnp.square(jax.nn.relu(u)),
        lambda u, d_hidden: 2.0 * jax.nn.relu(u) * d_hidden,
    ),
}


def _held_matrices(gate, up, down):
    """The matrices an expert HAS: three, or two where it has no gate."""
    return (up, down) if gate is None else (gate, up, down)


def _tile_forward(x, gate, up, down, tokens, activation):
    """One tile through its expert: (rows, gate·x, up·x, hidden, out);
    ``gate`` None: an un-gated expert, hidden = act(up·x)."""
    rows = x.at[tokens].get(mode="promise_in_bounds")
    if gate is None:
        g = None
        u = jnp.dot(rows, up, preferred_element_type=jnp.float32)
        hidden = PLAIN_ACTIVATIONS[activation][0](u).astype(x.dtype)
    else:
        g = jnp.dot(rows, gate, preferred_element_type=jnp.float32)
        u = jnp.dot(rows, up, preferred_element_type=jnp.float32)
        hidden = (ACTIVATIONS[activation][0](g) * u).astype(x.dtype)
    out = jnp.dot(hidden, down, preferred_element_type=jnp.float32)
    return rows, g, u, hidden, out


def _tile_operands(start, rows, plan, slot_weight, weights):
    """An iteration's ``rows`` rows from ``start`` on (whole tiles of ONE
    expert): their flat slots (out of range for padding), tokens and
    weights — gathered HERE, from the rows in use, not over the plan's
    static bound —, that expert's matrices and its index."""
    row_slot, tile_expert, k, tile = plan
    slots = jax.lax.dynamic_slice(row_slot, (start,), (rows,))
    real = slots >= 0
    at = jnp.maximum(slots, 0)
    scale = jnp.where(
        real, slot_weight.at[at].get(mode="promise_in_bounds"), 0.0
    )
    expert = tile_expert[start // tile]
    return jnp.where(real, slots, slot_weight.size), at // k, scale, tuple(
        jax.lax.dynamic_index_in_dim(w, expert, keepdims=False)
        for w in weights
    ), expert


def _walk(body, carry, walk, tile: int, m: int):
    """``body(start, rows, carry)`` over the walk's iterations: the bulk
    loop (``m · tile`` rows an iteration), then the tail loop (``tile``),
    its carry starting from the first's — one body, two loops of a dynamic
    trip count, which differ in one static integer. A loop whose static
    bound is 0 (no tails at m = 1; a plan smaller than a bulk iteration)
    is not built."""
    for (starts, count), rows in zip(walk, (m * tile, tile)):
        if starts.size:
            carry = jax.lax.fori_loop(
                0, count,
                lambda i, c, s=starts, r=rows: body(s[i], r, c), carry,
            )
    return carry


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10))
def _grouped_swiglu(x, slot_weight, gate, up, down, sinks, row_slot,
                    tile_expert, walk, shape, activation):
    """``slot_weight`` [T · k]: every slot's weight, flat; ``row_slot`` /
    ``tile_expert``: ``_tile_plan``'s; ``sinks``: None, or float32 (gate, up,
    down)-shaped buffers the forward ignores and the backward accumulates
    into; ``walk``: ``_run_schedule``'s; ``shape``: (k, tile, tiles a bulk
    iteration); ``activation``: the gate's, a name of ``ACTIVATIONS`` (the
    function keeps its first name) — or, with ``gate`` None (an un-gated
    expert: two matrices, two sinks), a name of ``PLAIN_ACTIVATIONS``."""
    out, _ = _grouped_swiglu_fwd(
        x, slot_weight, gate, up, down, sinks, row_slot, tile_expert, walk,
        shape, activation,
    )
    return out


def _grouped_swiglu_fwd(x, slot_weight, gate, up, down, sinks, row_slot,
                        tile_expert, walk, shape, activation):
    plan = (row_slot, tile_expert, *shape[:2])

    def body(start, rows, total):
        _slots, tokens, scale, weights, _e = _tile_operands(
            start, rows, plan, slot_weight, _held_matrices(gate, up, down)
        )
        if gate is None:
            weights = (None, *weights)
        out = _tile_forward(x, *weights, tokens, activation)[-1]
        return total.at[tokens].add(out * scale[:, None])

    with jax.named_scope("moe_routed"):
        total = _walk(
            body, jnp.zeros(x.shape, jnp.float32), walk, *shape[1:]
        )
    return total, (x, slot_weight, gate, up, down, sinks, row_slot,
                   tile_expert, walk)


def _grouped_swiglu_bwd(shape, activation, residuals, d_total):
    (x, slot_weight, gate, up, down, sinks, row_slot, tile_expert,
     walk) = residuals
    gated = gate is not None
    held = _held_matrices(gate, up, down)
    plan = (row_slot, tile_expert, *shape[:2])

    def body(start, rows, carry):
        dx, d_weight, *d_held = carry
        slots, tokens, scale, weights, expert = (
            _tile_operands(start, rows, plan, slot_weight, held)
        )
        w_gate, w_up, w_down = weights if gated else (None, *weights)
        rows, g, u, hidden, out = _tile_forward(
            x, w_gate, w_up, w_down, tokens, activation
        )
        d_scaled = d_total.at[tokens].get(mode="promise_in_bounds")
        d_weight = d_weight.at[slots].set(  # a slot has one row
            jnp.sum(d_scaled * out, axis=-1), mode="drop"
        )
        d_out = (d_scaled * scale[:, None]).astype(x.dtype)
        d_hidden = jax.lax.dot_general(
            d_out, w_down, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if gated:
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
        else:
            d_u = PLAIN_ACTIVATIONS[activation][1](u, d_hidden).astype(x.dtype)
            d_rows = jax.lax.dot_general(
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

        if not gated:
            d_up, d_down = d_held
            return (
                dx.at[tokens].add(d_rows), d_weight, add(d_up, rows, d_u),
                add(d_down, hidden, d_out),
            )
        d_gate, d_up, d_down = d_held
        return (
            dx.at[tokens].add(d_rows), d_weight, add(d_gate, rows, d_g),
            add(d_up, rows, d_u), add(d_down, hidden, d_out),
        )

    with jax.named_scope("moe_routed"):
        dx, d_weight, *summed = _walk(
            body, (
                jnp.zeros(x.shape, jnp.float32),
                jnp.zeros(slot_weight.shape, jnp.float32),
                # the one difference a sink makes: where the sums start
                *(sinks if sinks is not None else (
                    jnp.zeros(w.shape, jnp.float32) for w in held
                )),
            ), walk, *shape[1:],
        )
    summed = tuple(summed)
    if sinks is None:
        d_held = tuple(d.astype(w.dtype) for d, w in zip(summed, held))
        d_sinks = None
    else:  # the sums left in the sinks, float32 as they are
        d_held = tuple(jnp.zeros_like(w) for w in held)
        d_sinks = summed
    if not gated:
        d_held = (None, *d_held)
    int_zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return (
        dx.astype(x.dtype), d_weight, *d_held, d_sinks,
        int_zero(row_slot), int_zero(tile_expert),
        jax.tree.map(int_zero, walk),
    )


_grouped_swiglu.defvjp(_grouped_swiglu_fwd, _grouped_swiglu_bwd)


def routed_experts(x, choice, weights, gate, up, down,
                   held: Tuple[int, int], tile: int = 256, grad_sinks=None,
                   activation: str = "silu", run_tiles: int = RUN_TILES):
    """The held experts' part of Σ_{e in choice} w_e · GLU_e(x), with
    GLU_e(x) = down_e(act(gate_e x) ⊙ up_e x) and ``activation`` the gate's:
    "silu" (SwiGLU) or "relu" (ReGLU) — or, with ``gate`` None, of
    Σ w_e · down_e(act(up_e x)): an UN-gated expert of two matrices,
    ``activation`` "relu2" (relu(.)², Nemotron-H's), and two sinks.

    ``x`` [T, H] in the compute dtype; ``choice`` / ``weights`` [T, k] from
    ``route_top_k`` or ``route_top_k_softmax``; ``gate`` / ``up`` [n, H, F]
    and ``down`` [n, F, H] the
    HELD experts' matrices ALREADY in the compute dtype (the layer's casts
    of its float32 leaves or, on one device under sinks, the accumulate
    step's copies of them, rebuilt when the weights change), expert
    ``held[0] + i`` at index i; ``grad_sinks``: None, or three float32 buffers of the held
    matrices' shapes whose COTANGENT is ``sink + d matrix`` while the
    matrices' own is zero (the module docstring says who wants that);
    ``run_tiles``: consecutive tiles of one expert a bulk iteration of the
    walk takes — the module's, but for a test or ``tools/
    chip_routed_check.py`` (1: the single-size walk).
    Returns (y [T, H] float32, stats): ``stats['local_slot_share']``
    the share of routed slots that chose a held expert,
    ``stats['dropped_slots']`` the valid slots the plan lost (0),
    ``stats['grad_sink_leaves']`` the sinks the loop was handed (3 or 0),
    ``stats['bulk_row_share']`` the share of those slots' rows that the
    walk's bulk iterations took (0.0 where no held expert drew
    ``run_tiles`` tiles of rows)."""
    tile = min(tile, max(8, x.shape[0]))
    with jax.named_scope("moe_routed"):
        row_slot, tile_expert, _tiles, dropped, (walk, bulk_rows) = (
            _tile_plan(choice, held, tile, run_tiles)
        )
        real = jnp.sum(row_slot >= 0)
    y = _grouped_swiglu(
        x, weights.reshape(-1).astype(jnp.float32), gate, up, down,
        None if grad_sinks is None else tuple(grad_sinks),
        row_slot, tile_expert, walk, (choice.shape[1], tile, run_tiles),
        activation,
    )
    return y, {
        "bulk_row_share": bulk_rows / jnp.maximum(real, 1),
        "grad_sink_leaves": jnp.float32(
            0 if grad_sinks is None else len(grad_sinks)
        ),
        "local_slot_share": real / choice.size,
        "dropped_slots": dropped.astype(jnp.float32),
    }
