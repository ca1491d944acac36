"""SmallThinker (``model_name: smallthinker_21b_instruct``; the published
model this file was written for is PowerInfer/SmallThinker-21BA3B-Instruct),
in Flax: a pre-norm decoder whose attention layers are of two KINDS — a
global layer without positions in one layer of four, a sliding-window layer
with RoPE in the other three — and whose every layer ends in a dropless
routed FFN of ReLU-gated experts with the router fed from BEFORE attention.
``benchmark/reference/smallthinker.py`` carries the same equations in plain
``jax.numpy``:

    x [S, H]; eps 1e-6:
    n  = RMSNorm_in(x)
    r  = W_r n                              [E] logits, float32: the router
                                            reads n, BEFORE attention
    q, k, v = W_q n, W_k n, W_v n           28 / 4 / 4 heads of 128
    layer kind ``band_rope``:   q, k = RoPE(q, k)  (rotate-half, whole head)
               ``global_nope``: no positions at all
    a  = softmax(q kᵀ / sqrt(128) + mask) v   kv head j serves query heads
         mask: key <= query; ``band_rope`` also query - key < window
    h  = x + W_o a
    m  = RMSNorm_post(h)
    C  = top_k(r);  w = softmax(r[C])       no bias, no scale, sums to 1
    y  = h + Σ_{e in C} w_e W_down,e(relu(W_gate,e m) ⊙ W_up,e m)
    after the stack a final RMSNorm; an untied head; loss: mean next-token
    cross-entropy; NO auxiliary or balancing term (``config.json`` names
    none), no leaf stepped by a sign

The program's shape is ``models/lfm2_moe.py``'s: ``rope_layout`` and
``sliding_window_layout`` (the published patterns) say what each layer is,
the layers are cut into whole PERIODS of the pattern (four: global, band,
band, band), one ``nn.scan`` over the periods with every layer of a period a
remat'd block of its own kind, and what is left over after the last whole
period is unrolled. RMSNorm, RoPE and the chunked head + cross-entropy are
Ouro's; the routed loop is ``parallel/moe.routed_experts`` with
``activation="relu"`` and its gradient sinks (``models/deepseek_v3.py``'s
collection); attention is the grouped-query mode of
``ops/flash_attention.py`` — a group of SEVEN, a whole group a program —
causal for the global layers and with ``band=`` for the others.

**A chip's share**, as for the other expert decoders: ``expert_shard`` (the
experts held of every layer), ``vocab_size`` (rows held of the embedding AND
of the head) and ``num_hidden_layers`` (the first layers of the pattern:
there is no leading dense layer).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dedloc_tpu.models.albert import remat_policy_object
from dedloc_tpu.models.deepseek_v3 import (
    EXPERT_LEAVES,
    GRAD_SINKS,
    apply_with_grad_sinks,
)
from dedloc_tpu.models.lfm2_moe import _period_of
from dedloc_tpu.models.ouro import (
    RMSNorm,
    _dense,
    apply_rope,
    chunked_cross_entropy,
    rope_tables,
)
from dedloc_tpu.ops.flash_attention import visited_tiles
from dedloc_tpu.parallel.moe import (
    expert_load,
    route_top_k_softmax,
    routed_experts,
)

GLOBAL_NOPE, BAND_ROPE = "global_nope", "band_rope"


def _published_layout(layers: int) -> Tuple[int, ...]:
    """SmallThinker-21BA3B's ``rope_layout`` = ``sliding_window_layout``:
    0 (global, no positions) in layers 0, 4, 8, ..., 1 in the others."""
    return tuple(int(i % 4 != 0) for i in range(layers))


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """SmallThinker-21BA3B-Instruct as published (``config.json``); what it
    does not fix is in ``benchmark/configs/smallthinker_21b_a3b_s16384.json``
    under ``assumed``."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    rope_layout: Tuple[int, ...] = _published_layout(52)
    sliding_window_layout: Tuple[int, ...] = _published_layout(52)
    sliding_window_size: int = 4096
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768  # moe_ffn_hidden_size
    num_experts: int = 64  # moe_num_primary_experts
    num_experts_per_tok: int = 6  # moe_num_active_primary_experts
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of albert.remat_policy_object's table. "kernel_operands": the
    # layer keeps q / k / v as the flash kernels read them beside out + lse,
    # so the backward's replay runs no q / k / v projection, RoPE or
    # relayout (RoPE's own backward is linear: it needs no stash): 16,384 x
    # (28 + 2·4) x 128 x 2 bytes = 151 MB a layer a micro-batch, 0.60 GB in
    # the benchmark's cell of four layers, where accumulate_step's scratch
    # reads 2.53 GB against 2.50 (the stash takes the place of the replay's
    # own q / k / v) beside 10.38 GB of state while a backup drains. A
    # smaller chip or a larger share: --training.remat_policy kernel_outputs
    remat_policy: str = "kernel_operands"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        index, count = self.expert_shard
        if not (0 <= index < count) or self.num_experts % count:
            raise ValueError(
                f"expert_shard {index}/{count}: the count must divide the "
                f"{self.num_experts} routed experts, 0 <= index < count"
            )
        if len(self.rope_layout) != len(self.sliding_window_layout):
            raise ValueError("the two layouts name the same layers")
        if not 1 <= self.num_hidden_layers <= len(self.rope_layout):
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers}: the layouts "
                f"have {len(self.rope_layout)} layers"
            )

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        index, count = self.expert_shard
        n = self.num_experts // count
        return index * n, n

    @property
    def layer_plan(self) -> List[Tuple[bool, bool]]:
        """(rotated?, banded?) of every layer run: the first
        ``num_hidden_layers`` of the published layouts."""
        return [
            (bool(rope), bool(band)) for rope, band in zip(
                self.rope_layout[:self.num_hidden_layers],
                self.sliding_window_layout,
            )
        ]

    @staticmethod
    def named(model_size: str):
        ctors = {
            "smallthinker_21b_a3b": SmallThinkerConfig.smallthinker_21b_a3b,
            "smallthinker_tiny": SmallThinkerConfig.tiny,
        }
        if model_size not in ctors:
            raise ValueError(
                f"unknown model_size {model_size!r} "
                f"(expected one of {sorted(ctors)})"
            )
        return ctors[model_size]

    @staticmethod
    def smallthinker_21b_a3b(**overrides) -> "SmallThinkerConfig":
        return SmallThinkerConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "SmallThinkerConfig":
        """Test-sized: every mechanism (two periods of a global layer
        without positions and three banded ones with RoPE, a group of SEVEN
        query heads on one kv head, a band of 8 at S=32, 8 ReLU-gated experts
        top-2 routed from before attention, a chunked untied head), no
        published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=8,
            rope_layout=_published_layout(8),
            sliding_window_layout=_published_layout(8),
            sliding_window_size=8, num_attention_heads=7,
            num_key_value_heads=1, head_dim=8, moe_intermediate_size=16,
            num_experts=8, num_experts_per_tok=2,
            max_position_embeddings=128, moe_row_tile=8,
            attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return SmallThinkerConfig(**base)


class BandAttention(nn.Module):
    """Grouped-query causal attention, rotated or not, banded or not."""

    cfg: SmallThinkerConfig
    rotated: bool
    banded: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        B, S, _ = hidden.shape
        H, KV, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        band = cfg.sliding_window_size if self.banded else None
        q = _dense(H * D, cfg, "q_proj")(hidden).reshape(B, S, H, D)
        k = _dense(KV * D, cfg, "k_proj")(hidden).reshape(B, S, KV, D)
        v = _dense(KV * D, cfg, "v_proj")(hidden).reshape(B, S, KV, D)
        if self.rotated:
            q, k = (apply_rope(x, *rope) for x in (q, k))
        if cfg.attention_impl == "flash":
            from dedloc_tpu.ops.flash_attention import flash_attention

            # the kernels' operands as buffers of their own. Without the
            # barrier XLA:TPU folds RoPE's last add + cast into each of
            # their consumers and relays the float32 pieces BEFORE that add
            # out around every one (and under "kernel_operands" keeps THEM
            # for the backward: 4x the bytes): accumulate_step 395.8 → 374.5
            # ms a micro-batch in the benchmark's cell with the barrier
            # alone, 361.8 with the operands kept too; scratch 4.00 GB
            # without it, 2.53 with it (PERF.md section 6, PR 41)
            q, k, v = jax.lax.optimization_barrier((q, k, v))
            ctx = flash_attention(
                q, k, v, causal=True, band=band,
                block_q=cfg.attention_block_size,
                block_k=cfg.attention_block_size, mesh=cfg.mesh,
            )
        elif cfg.attention_impl == "dense":
            q, k, v = (checkpoint_name(x, "flash_qkv") for x in (q, k, v))
            grouped = q.reshape(B, S, KV, H // KV, D)
            logits = jnp.einsum(
                "bqcgd,bkcd->bcgqk", grouped, k,
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(D))
            i = jnp.arange(S)
            visible = i[None, :] <= i[:, None]
            if band is not None:
                visible &= i[:, None] - i[None, :] < band
            logits = jnp.where(visible, logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum("bcgqk,bkcd->bqcgd", probs, v)
        else:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r}: this model takes "
                "'flash' or 'dense'"
            )
        return _dense(cfg.hidden_size, cfg, "o_proj")(
            ctx.reshape(B, S, H * D)
        )


class RoutedGLU(nn.Module):
    """Σ_{e in C} w_e GLU_e(x) over the HELD experts (``activation``: the
    gate's, "relu" here, "silu" in ``models/sdar_moe.py``), with C and w —
    the top k and a softmax over the chosen ones — from the router LOGITS of
    ``router_input`` (this model: the layer's normalised input, from before
    attention). ``cfg``: this model's, or any config with the routed
    layer's fields under the same names. Returns (y, routing) with
    ``routing`` = the logits [T, E] (as ``scores``), choice [T, k], load [E]
    and the counts of ``parallel/moe.routed_experts``. An apply that carries
    the collection ``GRAD_SINKS`` hands this layer's three buffers to the
    tile loop's backward."""

    cfg: Any
    activation: str = "relu"

    @nn.compact
    def __call__(self, x, router_input):
        cfg = self.cfg
        B, S, H = x.shape
        E, F = cfg.num_experts, cfg.moe_intermediate_size
        first, held = cfg.held_experts
        init = nn.initializers.normal(cfg.initializer_range)
        router = self.param("router", init, (H, E), jnp.float32)
        gate, up, down = (
            self.param(name, init, shape, jnp.float32)
            for name, shape in zip(
                EXPERT_LEAVES, ((held, H, F), (held, H, F), (held, F, H))
            )
        )
        # the router in float32 at full precision: the top-k is discrete
        logits = jnp.dot(
            router_input.reshape(B * S, H).astype(jnp.float32), router,
            precision=jax.lax.Precision.HIGHEST,
        )
        choice, weights = route_top_k_softmax(logits, cfg.num_experts_per_tok)
        sinks = tuple(
            self.get_variable(GRAD_SINKS, name) for name in EXPERT_LEAVES
        ) if self.has_variable(GRAD_SINKS, EXPERT_LEAVES[0]) else None
        routed, counts = routed_experts(
            x.reshape(B * S, H), choice, weights, gate.astype(cfg.dtype),
            up.astype(cfg.dtype), down.astype(cfg.dtype), (first, held),
            tile=cfg.moe_row_tile, grad_sinks=sinks,
            activation=self.activation,
        )
        return routed.reshape(B, S, H).astype(cfg.dtype), dict(
            counts, scores=logits, choice=choice, load=expert_load(choice, E)
        )


class DecoderLayer(nn.Module):
    """n = RMSNorm(x); h = x + Attn(n); y = h + Experts(RMSNorm(h)) routed
    by W_r n. Returns (y, routing)."""

    cfg: SmallThinkerConfig
    rotated: bool
    banded: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        x = RMSNorm(cfg, name="input_layernorm")(hidden)
        hidden = hidden + BandAttention(
            cfg, self.rotated, self.banded, name="self_attn"
        )(x, rope)
        y, routing = RoutedGLU(cfg, name="block_sparse_moe")(
            RMSNorm(cfg, name="post_attention_layernorm")(hidden), x
        )
        return hidden + y, routing


def _layer(cfg: SmallThinkerConfig, kind: Tuple[bool, bool], name: str):
    return nn.remat(
        DecoderLayer, policy=remat_policy_object(cfg.remat_policy)
    )(cfg, *kind, name=name)


class _Period(nn.Module):
    """Scan body: one period of the pattern, a remat'd layer per position.
    carry = hidden; rope broadcast; per-step out = the period's routing."""

    cfg: SmallThinkerConfig
    kinds: Tuple[Tuple[bool, bool], ...]

    @nn.compact
    def __call__(self, hidden, rope):
        routings = []
        for i, kind in enumerate(self.kinds):
            hidden, routing = _layer(self.cfg, kind, f"layer_{i}")(
                hidden, rope
            )
            routings.append(routing)
        return hidden, jax.tree.map(lambda *xs: jnp.stack(xs), *routings)


class SmallThinkerForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the layers in
    order). The head's weight is the parameter ``lm_head`` [H, V], applied
    by ``smallthinker_loss`` a chunk of tokens at a time."""

    cfg: SmallThinkerConfig

    @nn.compact
    def __call__(self, input_ids) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        init = nn.initializers.normal(cfg.initializer_range)
        embed = self.param(
            "embed_tokens", init, (cfg.vocab_size, cfg.hidden_size),
            jnp.float32,
        )
        self.param(
            "lm_head", init, (cfg.hidden_size, cfg.vocab_size), jnp.float32
        )
        hidden = jnp.take(embed, input_ids, axis=0).astype(cfg.dtype)
        rope = rope_tables(input_ids.shape[1], cfg.head_dim, cfg.rope_theta)
        kinds = cfg.layer_plan
        period = _period_of(kinds)
        periods = len(kinds) // period
        stack = nn.scan(
            _Period,
            variable_axes={"params": 0, GRAD_SINKS: 0},
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            length=periods,
        )
        hidden, routing = stack(
            cfg, tuple(kinds[:period]), name="layers"
        )(hidden, rope)
        # [periods, period, ...] -> [layers, ...]
        routings = [jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), routing
        )]
        for i, kind in enumerate(kinds[periods * period:]):
            hidden, routing = _layer(cfg, kind, f"tail_layer_{i}")(
                hidden, rope
            )
            routings.append(jax.tree.map(lambda x: x[None], routing))
        routing = jax.tree.map(lambda *xs: jnp.concatenate(xs), *routings)
        return RMSNorm(cfg, name="norm")(hidden), routing


def band_tile_share(cfg: SmallThinkerConfig, seq: int) -> float:
    """(query tile, key tile) pairs a band layer's kernels visit over the
    causal triangle's, from the shapes: 252 / 528 at S=16,384, a band of
    4,096 and 512 x 512 tiles; 1 where the band is the sequence."""
    block = cfg.attention_block_size
    return visited_tiles(
        seq, block, block, True, cfg.sliding_window_size
    ) / visited_tiles(seq, block, block, True)


def smallthinker_loss(model: SmallThinkerForCausalLM, params,
                      batch: Dict[str, jnp.ndarray], grad_sinks=None):
    """(loss, metrics) of one micro-batch: ``input_ids`` and next-token
    ``labels``, [B, S] each, no padding; the metrics and ``grad_sinks`` are
    ``models/deepseek_v3.deepseek_v3_loss``'s without a bias to report
    (``moe.scores`` holds the router's LOGITS), and ``attn.band_tile_share``
    beside them."""
    cfg = model.cfg
    hidden, routing = apply_with_grad_sinks(
        model, params, batch["input_ids"], grad_sinks
    )
    ce = chunked_cross_entropy(
        hidden.reshape(1, -1, cfg.hidden_size),
        params["lm_head"].astype(cfg.dtype),
        batch["labels"].reshape(-1), cfg.loss_chunk_tokens,
    )
    loss = jnp.mean(ce)
    load = routing["load"]  # [L, E]
    return loss, {
        "loss": loss,
        "moe.load_max_over_mean": jnp.max(load, axis=1) / jnp.mean(
            load, axis=1
        ),
        "moe.local_slot_share": jnp.mean(routing["local_slot_share"]),
        "moe.bulk_row_share": jnp.mean(routing["bulk_row_share"]),
        "moe.dropped_slots": jnp.sum(routing["dropped_slots"]),
        "moe.grad_sink_leaves": jnp.sum(routing["grad_sink_leaves"]),
        "attn.band_tile_share": jnp.float32(
            band_tile_share(cfg, batch["input_ids"].shape[1])
        ),
        "moe.choice": routing["choice"],
        "moe.scores": routing["scores"],
    }


def smallthinker_weight_decay_mask(params):
    """True where weight decay applies: every matrix; not the RMSNorm
    ``weight``s."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: path[-1].key != "weight", params
    )


def smallthinker_layer_flops_per_token(cfg: SmallThinkerConfig,
                                       seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs a token of one layer, by kind — a band layer
    counted at its band: min(i + 1, window) keys for query i — and of the
    untied head over the held rows; routed work for the HELD experts at the
    expected share of slots."""
    h, d = cfg.hidden_size, cfg.head_dim
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    band = min(cfg.sliding_window_size, seq)
    pairs = {
        GLOBAL_NOPE: seq * (seq + 1) / 2,
        BAND_ROPE: band * (band + 1) / 2 + (seq - band) * band,
    }
    shared = (
        2 * h * (heads + 2 * kv) * d + 2 * heads * d * h  # q k v, out
        + 2 * h * cfg.num_experts  # the router
        + 2 * 3 * h * cfg.moe_intermediate_size * cfg.num_experts_per_tok
        * cfg.held_experts[1] / cfg.num_experts
    )
    return {
        **{kind: shared + 2 * 2 * heads * d * n / seq  # QKᵀ, PV
           for kind, n in pairs.items()},
        "head": 2 * h * cfg.vocab_size,
    }


def smallthinker_train_tflops_per_sample(cfg: SmallThinkerConfig,
                                         seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (matmuls only, backward = 2x forward, remat's replays not
    counted)."""
    part = smallthinker_layer_flops_per_token(cfg, seq)
    per_token = part["head"] + sum(
        part[BAND_ROPE if banded else GLOBAL_NOPE]
        for _rotated, banded in cfg.layer_plan
    )
    return 3.0 * per_token * seq / 1e12
