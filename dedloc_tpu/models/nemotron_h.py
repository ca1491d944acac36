"""Nemotron-H (``model_type: nemotron_h``; the published model this file was
written for is nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), in Flax: a
pre-norm decoder whose layers are ONE sublayer each — a Mamba-2 mixer, NoPE
grouped-query attention or a bias-balanced routed feed-forward of UN-gated
relu² experts beside a shared expert — by the letters of
``hybrid_override_pattern`` (``M``, ``*``, ``E``).
``benchmark/reference/nemotron_h.py`` carries the same equations in plain
``jax.numpy``:

    x [S, H]; eps 1e-5; layer l:  x <- x + f_l(RMSNorm_l(x)),  u the normed x
    ``M`` (heads of P = 64 in groups of eight sharing B and C of N = 128):
        (z | xBC | dt~) = W_in u
        (x | B | C) = SiLU(conv4(xBC) + b_conv)   causal, depthwise, 4 taps
        dt = softplus(dt~ + dt_bias);  a = dt · (-exp(A_log))   [heads] <= 0
        S_t = e^{a_t} S_{t-1} + dt_t B_t x_tᵀ;  y_t = S_tᵀ C_t + D x_t
                                                  (``ops/ssd.py``)
        out = W_out [RMSNorm_group(y ⊙ SiLU(z)) · w]   the gate BEFORE the
              norm; the norm over each group's lanes
    ``*``: ``decoder.GroupedQueryAttention`` without RoPE, q / k norm or
        window: kv head j serving 16 adjacent query heads of 128
    ``E``: ``decoder.RoutedFFN(activation="relu2")``: sigmoid scores over all
        128, top-6 of s + b, renormalised x 2.5, Σ w_e W_down,e relu(W_up,e
        u)² (no gate matrix) + a shared expert of its own width; b stepped
        by the sign of the load a GLOBAL step
    final RMSNorm; loss: mean next-token cross-entropy under the untied head

The program's shape: a depth below the published one keeps the FIRST
letters of the pattern (7 of 52: ``MEMEM*E``, one whole period); the layers
are unrolled (``layer_<i>``: the kinds have three different parameter trees
and only the ``E`` ones route). The kernel runs behind ``attention_impl``
"flash"; "dense" is the token-by-token recurrence in float32
(``ops/ssd.ssd_recurrence``), the CPU tests' oracle. The prelude —
convolution + SiLU, softplus, the gate, the group norm — is XLA's.

**A chip's share**: ``expert_shard``, ``vocab_size`` and
``num_hidden_layers`` as for the other expert decoders, and ``head_shard =
(index, count)``: a Mamba mixer holds ``n_groups / count`` whole GROUPS — a
group's B and C live with its heads: the z | x | B | C | dt columns of
``W_in``, the taps and their bias, ``A_log``, ``D``, ``dt_bias``, the norm's
weight and the rows of ``W_out`` — and attention ``heads / count`` query
heads over ``max(kv_heads / count, 1)`` key heads (split while the count
allows, shared beyond). ``W_out`` / ``W_o`` of the held heads give the
mixer's PARTIAL sum, and that is what joins the residual stream: the layer
runs without the exchange that would add the other chips' parts, as a slot
that chose an absent expert adds nothing.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dedloc_tpu.models.decoder import (
    BIAS,
    GroupedQueryAttention,
    RMSNorm,
    RoutedFFN,
    Visibility,
    causal_conv_silu,
    dense,
    embed_tokens,
    expert_lm_loss,
    held_heads,
    held_range,
    named_config,
    weight_decay_mask,
)
from dedloc_tpu.models.remat import remat_layer
from dedloc_tpu.ops.ssd import ssd, ssd_recurrence

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
# a Mamba mixer's leaves that are no matrix: exempt from weight decay
MAMBA_VECTORS = ("A_log", "D", "dt_bias", "conv", "conv_bias")
SSD_GAUGES = ("ssd.dt_mean", "ssd.chunk_log_decay_min", "ssd.state_abs_max")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B as published (``config.json``); what it
    does not fix is in ``benchmark/configs/nemotron3_nano_30b_a3b_s8192.json``
    under ``assumed``. (``expand``, ``rope_theta`` and
    ``partial_rotary_factor`` name nothing this model builds.)"""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = PATTERN
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 1
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    route_eps: float = 1e-20
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5  # ``layer_norm_epsilon`` / ``norm_eps``
    initializer_range: float = 0.02
    # ``rescale_prenorm_residual``: the out-projections' deviation over the
    # square root of this depth (the PUBLISHED one, whatever the cut)
    rescale_depth: int = 52
    bias_update_speed: float = 0.001  # as DeepseekV3Config's
    expert_shard: Tuple[int, int] = (0, 1)
    # (index, count): this chip holds 1 / count of every mixer's heads
    head_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of models/remat.py's table
    remat_policy: str = "whole_mixer"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.n_routed_experts)  # raises
        # a share is whole GROUPS (a group's B and C live with its heads)
        # and whole query heads
        for heads in (self.n_groups, self.num_attention_heads):
            held_heads(self.head_shard, heads)  # raises
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"{self.mamba_num_heads} Mamba heads in {self.n_groups} groups"
            )
        if self.held_heads % self.held_kv_heads:
            raise ValueError(
                f"head_shard {self.head_shard}: {self.held_heads} query "
                f"heads over {self.held_kv_heads} key heads"
            )
        pattern = self.hybrid_override_pattern
        if set(pattern) - {MAMBA, ATTENTION, EXPERTS}:
            raise ValueError(f"hybrid_override_pattern {pattern!r}: M, * or E")
        if not 1 <= self.num_hidden_layers <= len(pattern):
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers}: the published "
                f"pattern names {len(pattern)} layers"
            )
        if EXPERTS not in self.layer_kinds:
            raise ValueError(
                f"the cut {self.layer_kinds!r} holds no expert layer: the "
                "loss reads a routed layer's statistics"
            )

    @property
    def layer_kinds(self) -> str:
        """The letters of the layers run: the first ``num_hidden_layers``
        of the published pattern."""
        return self.hybrid_override_pattern[:self.num_hidden_layers]

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.n_routed_experts)

    @property
    def held_groups(self) -> int:
        return held_heads(self.head_shard, self.n_groups)

    @property
    def held_mamba_heads(self) -> int:
        return self.held_groups * (self.mamba_num_heads // self.n_groups)

    @property
    def held_heads(self) -> int:
        """Query heads of attention held."""
        return held_heads(self.head_shard, self.num_attention_heads)

    @property
    def held_kv_heads(self) -> int:
        """Key heads held: split while the count allows, shared beyond."""
        return max(self.num_key_value_heads // self.head_shard[1], 1)

    @property
    def out_init_scale(self) -> float:
        return 1.0 / math.sqrt(self.rescale_depth)

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "nemotron3_nano_30b_a3b": NemotronHConfig.nemotron3_nano_30b_a3b,
            "nemotron_h_tiny": NemotronHConfig.tiny,
        })

    @staticmethod
    def nemotron3_nano_30b_a3b(**overrides) -> "NemotronHConfig":
        return NemotronHConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "NemotronHConfig":
        """Test-sized: every mechanism (the period ``MEMEM*E`` and three
        layers over, eight Mamba heads in two groups and four query heads
        over two key heads so a head share exists, two chunks a row, 16
        experts top-3 beside a shared one twice their width, a chunked
        untied head), no published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=7,
            hybrid_override_pattern="MEMEM*EM*E", mamba_num_heads=8,
            mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            n_routed_experts=16, num_experts_per_tok=3,
            moe_intermediate_size=16, moe_shared_expert_intermediate_size=32,
            max_position_embeddings=128, moe_row_tile=8,
            attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return NemotronHConfig(**base)


def _a_log_init(first: int):
    """log(1 .. heads) by head (Mamba-2's ``A_init_range`` (1, heads) read
    as ``arange``), for the heads from ``first`` on."""
    def init(_key, shape, dtype=jnp.float32):
        return jnp.log(jnp.arange(first + 1, first + shape[0] + 1, dtype=dtype))

    return init


def _dt_bias_init(cfg: NemotronHConfig):
    """The inverse softplus of a step log-uniform in [``time_step_min``,
    ``time_step_max``], floored at ``time_step_floor`` (Mamba's)."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, dtype, math.log(cfg.time_step_min),
            math.log(cfg.time_step_max),
        )), cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


def _conv_init(taps: int):
    """A depthwise Conv1d's default, taps and bias: uniform in ±1 /
    sqrt(taps)."""
    def init(key, shape, dtype=jnp.float32):
        bound = taps ** -0.5
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class GroupRMSNorm(nn.Module):
    """RMSNorm over each of ``groups`` equal runs of the last axis's lanes,
    one ``weight`` a lane, statistics in float32; returns float32."""

    cfg: Any
    groups: int

    @nn.compact
    def __call__(self, x):
        weight = self.param(
            "weight", nn.initializers.ones, (x.shape[-1],), jnp.float32
        )
        grouped = x.astype(jnp.float32).reshape(
            *x.shape[:-1], self.groups, x.shape[-1] // self.groups
        )
        var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
        return (
            grouped * jax.lax.rsqrt(var + self.cfg.rms_norm_eps)
        ).reshape(x.shape) * weight


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer over the groups this chip holds. Returns (the
    mixer's output — a PARTIAL sum under a head share —, its three
    gauges)."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        B, S, _ = hidden.shape
        heads, groups = cfg.held_mamba_heads, cfg.held_groups
        P, N = cfg.mamba_head_dim, cfg.ssm_state_size
        inner, keys = heads * P, groups * N
        # named for the remat policies that keep what the prelude's
        # backward reads (``whole_mixer``): the replay runs no matmul
        proj = checkpoint_name(
            dense(2 * inner + 2 * keys + heads, cfg, "in_proj")(hidden),
            "ssd_in_proj",
        )
        z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * keys], axis=-1)
        conv_init = _conv_init(cfg.conv_kernel)
        xbc = causal_conv_silu(
            xbc,
            self.param("conv", conv_init, (inner + 2 * keys, cfg.conv_kernel),
                       jnp.float32),
            self.param("conv_bias", conv_init, (inner + 2 * keys,),
                       jnp.float32),
        )
        x, b, c = jnp.split(xbc, [inner, inner + keys], axis=-1)
        first = cfg.head_shard[0] * heads
        a_log = self.param("A_log", _a_log_init(first), (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        dt_bias = self.param(
            "dt_bias", _dt_bias_init(cfg), (heads,), jnp.float32
        )
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        a = -jnp.exp(a_log) * dt
        operands = (
            x.reshape(B, S, heads, P), dt, a, b.reshape(B, S, groups, N),
            c.reshape(B, S, groups, N), skip,
        )
        if cfg.attention_impl == "flash":
            y, state = ssd(*operands, chunk=cfg.chunk_size, return_state=True)
        elif cfg.attention_impl == "dense":
            y, state = ssd_recurrence(*operands, return_state=True)
        else:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r}: a decoder takes "
                "'flash' or 'dense'"
            )
        gated = y.reshape(B, S, inner).astype(jnp.float32) * nn.silu(
            z.astype(jnp.float32)
        )
        normed = GroupRMSNorm(cfg, groups, name="norm")(gated)
        out = dense(cfg.hidden_size, cfg, "out_proj", cfg.out_init_scale)(
            normed.astype(cfg.dtype)
        )
        ragged = -S % cfg.chunk_size  # a = 0 behind the row moves no sum
        chunked = jnp.pad(
            jax.lax.stop_gradient(a), ((0, 0), (0, ragged), (0, 0))
        ).reshape(B, (S + ragged) // cfg.chunk_size, cfg.chunk_size, heads)
        report = jnp.stack([
            jnp.mean(jax.lax.stop_gradient(dt)),
            # the most negative cumulative log-decay a chunk reaches
            jnp.min(jnp.sum(chunked, axis=2)),
            jnp.max(jnp.abs(state)),
        ])
        return out, report


class NemotronLayer(nn.Module):
    """x + f(RMSNorm(x)), f by ``kind``: a Mamba mixer (``M``), NoPE grouped
    attention (``*``) or the routed feed-forward (``E``). Returns (y, what
    the layer reports: ``ssd`` — a Mamba mixer's three gauges —, or a routed
    layer's routing, or nothing)."""

    cfg: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, hidden, rope=None):
        cfg = self.cfg
        x = RMSNorm(cfg, name="norm")(hidden)
        if self.kind == MAMBA:
            mixed, gauges = Mamba2Mixer(cfg, name="mixer")(x)
            return hidden + mixed, {"ssd": gauges}
        if self.kind == ATTENTION:
            return hidden + GroupedQueryAttention(
                cfg, Visibility(causal=True), rotated=False,
                heads=cfg.held_heads, kv_heads=cfg.held_kv_heads,
                out_init_scale=cfg.out_init_scale, name="mixer",
            )(x, None), {}
        # the router's input as a buffer of its own, KEPT by name: the
        # top-k is discrete, and the load statistic is the REPLAY's count
        # while ``moe.choice`` is the forward's. A replay that made the
        # input again could round a lane to the other bf16 neighbour; a
        # forward that read the norm's float32 value through a fused
        # convert pair (XLA may skip a rounding it fuses) would route by
        # other scores than a replay that reads the kept bf16 tensor — 8 of
        # 49,152 slots differed on the chip without the barrier (PR 57)
        x = checkpoint_name(jax.lax.optimization_barrier(x), "routed_input")
        y, routing = RoutedFFN(
            cfg,
            shared_width=(
                cfg.n_shared_experts * cfg.moe_shared_expert_intermediate_size
            ),
            activation="relu2", down_init_scale=cfg.out_init_scale,
            name="mixer",
        )(x)
        return hidden + y, routing


class NemotronHForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the ``E`` layers in
    order, but ``ssd`` [Mamba layers, 3]). The head's weight is the
    parameter ``lm_head`` [H, V], applied by ``nemotron_h_loss`` a chunk of
    tokens at a time."""

    cfg: NemotronHConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        hidden = embed_tokens(self, input_ids)
        routings, gauges = [], []
        for i, kind in enumerate(cfg.layer_kinds):
            hidden, report = remat_layer(
                NemotronLayer, cfg, kind, name=f"layer_{i}"
            )(hidden)
            if kind == MAMBA:
                gauges.append(report["ssd"])
            elif kind == EXPERTS:
                routings.append(report)
        routing = jax.tree.map(lambda *xs: jnp.stack(xs), *routings)
        routing["ssd"] = (
            jnp.stack(gauges) if gauges
            else jnp.zeros((0, len(SSD_GAUGES)), jnp.float32)
        )
        return RMSNorm(cfg, name="norm")(hidden), routing


def nemotron_h_loss(model: NemotronHForCausalLM, params,
                    batch: Dict[str, jnp.ndarray], grad_sinks=None,
                    compute_copies=None):
    """``decoder.expert_lm_loss`` under the untied head, with the largest
    bias magnitude of any layer and the Mamba layers' three gauges (a vector
    each, one entry a Mamba layer in order)."""
    cfg = model.cfg
    return expert_lm_loss(
        model, params, batch, grad_sinks, compute_copies=compute_copies,
        head=lambda p: p["lm_head"].astype(cfg.dtype),
        gauges={
            "moe.bias_abs_max": lambda p, _r: jnp.max(jnp.stack([
                jnp.max(jnp.abs(leaf))
                for path, leaf in jax.tree_util.tree_leaves_with_path(p)
                if path[-1].key == BIAS
            ])),
            **{name: lambda _p, r, column=column: r["ssd"][:, column]
               for column, name in enumerate(SSD_GAUGES)},
        },
    )


# decayed: every matrix; not the RMSNorm ``weight``s (the group norm's among
# them), the correction bias, nor a Mamba mixer's vectors (A_log, D,
# dt_bias, the taps and their bias)
nemotron_h_weight_decay_mask = functools.partial(
    weight_decay_mask, exempt=("weight", BIAS) + MAMBA_VECTORS
)


def nemotron_h_parts_flops_per_token(cfg: NemotronHConfig,
                                     seq: int) -> Dict[str, float]:
    """Forward FLOPs a token of ONE layer of each kind and of the head, at
    the heads and experts HELD: the Mamba mixer (its two projections and the
    chunked scan's own products: C Bᵀ, the intra-chunk product, the state's
    read and update), attention at its triangle, the routed feed-forward
    (two matrices an expert), the untied head over the held rows."""
    h, q = cfg.hidden_size, cfg.chunk_size
    heads, groups = cfg.held_mamba_heads, cfg.held_groups
    P, N = cfg.mamba_head_dim, cfg.ssm_state_size
    inner = heads * P
    scan = groups * 2 * q * q * N + heads * (2 * q * q * P + 2 * 2 * q * N * P)
    d, f = cfg.head_dim, cfg.moe_intermediate_size
    return {
        MAMBA: (
            2 * h * (2 * inner + 2 * groups * N + heads) + 2 * inner * h
            + scan / q
        ),
        ATTENTION: (
            2 * h * (cfg.held_heads + 2 * cfg.held_kv_heads) * d
            + 2 * cfg.held_heads * d * h
            + 2 * 2 * cfg.held_heads * d * (seq + 1) / 2
        ),
        EXPERTS: (
            2 * h * cfg.n_routed_experts
            + 2 * 2 * h * cfg.n_shared_experts
            * cfg.moe_shared_expert_intermediate_size
            + 2 * 2 * h * f * cfg.num_experts_per_tok
            * cfg.held_experts[1] / cfg.n_routed_experts
        ),
        "head": 2 * h * cfg.vocab_size,
    }


def nemotron_h_train_tflops_per_sample(cfg: NemotronHConfig,
                                       seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (backward = 2x forward, remat's replays and the element-wise
    prelude not counted)."""
    part = nemotron_h_parts_flops_per_token(cfg, seq)
    per_token = part["head"] + sum(part[kind] for kind in cfg.layer_kinds)
    return 3.0 * per_token * seq / 1e12
