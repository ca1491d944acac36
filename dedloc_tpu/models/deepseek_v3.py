"""DeepSeek-V3-style decoder (``model_type: deepseek_v3``), in Flax: latent
attention and a dropless, bias-balanced expert layer. The published model
this file was written for is kanana-2-30b-a3b (kakaocorp; ``q_lora_rank:
null``), whose equations — ``benchmark/reference/deepseek_v3.py`` carries the
same in plain ``jax.numpy`` — are:

    x [S, H]; pre-norm:  h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    Attn(u):  q = W_q u -> [S, heads, nope + rope]
              c = W_kva u -> (c_kv [S, rank] | k_rope [S, rope], ONE head)
              (k_nope | v) = W_kvb RMSNorm(c_kv) -> [S, heads, nope + v]
              RoPE on q_rope and k_rope, pairs (2i, 2i+1) (rope_interleave)
              q = (q_nope | q_rope), k = (k_nope | k_rope for every head)
              out = W_o softmax_causal(q kᵀ / sqrt(nope + rope)) v
    FFN, the first ``first_k_dense_replace`` layers: SwiGLU, dense width
    FFN, the others: s = sigmoid(W_r u) in float32 over ALL experts
              choice = top_k(s + b);  w = s[choice] / Σ s[choice] · scale
              FFN(u) = Σ_{e in choice} w_e SwiGLU_e(u) + SwiGLU_shared(u)
    loss: mean next-token cross-entropy (no auxiliary loss: noaux_tc)
    b: not trained by a gradient — after every GLOBAL step
              b_e <- b_e − γ · sign(load_e − mean load)

The program's shape: the leading dense layer(s) outside the scan, the expert
layers stacked under ``nn.scan`` + remat as Ouro's are; RMSNorm, RoPE
tables, SwiGLU and the chunked head + cross-entropy are Ouro's
(``models/ouro.py``). The kernels take q and k 192 wide beside v 128 wide as
they are (``ops/flash_attention.py``: two column-block widths, nothing
padded); the one rotary key head is broadcast into k's layout first.

**A chip's share.** ``expert_shard = (index, count)`` tells every expert
layer which ``n_routed_experts / count`` experts it holds: the parameters
exist for those alone, the router scores all ``n_routed_experts``, and slots
that chose an absent expert add nothing (``parallel/moe.routed_experts``).
``vocab_size`` is the rows of the vocabulary held: a sliced vocabulary is a
smaller vocabulary.

The load statistic of the bias rule leaves the backward as the bias leaf's
cotangent (``parallel/moe.with_load_cotangent``), so it is accumulated over
micro-batches and averaged over peers as a gradient is;
``sign_step_mask`` marks those leaves for ``optim``'s sign rule.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dedloc_tpu.models.albert import remat_policy_object
from dedloc_tpu.models.ouro import (
    RMSNorm,
    SwiGLU,
    _dense,
    chunked_cross_entropy,
    rope_tables,
)
from dedloc_tpu.parallel.moe import (
    expert_load,
    route_top_k,
    routed_experts,
    with_load_cotangent,
)

BIAS = "e_score_correction_bias"  # the leaf the sign rule steps
# a routed layer's held matrices: the leaves whose gradients the tile loop
# can leave in a float32 accumulator it is handed (``parallel/moe.py``) — as
# the collection GRAD_SINKS beside ``params``, the same names and stacking
EXPERT_LEAVES = ("experts_gate", "experts_up", "experts_down")
GRAD_SINKS = "grad_sinks"


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """kanana-2-30b-a3b as published (``config.json``); what it does not
    fix is in ``benchmark/configs/kanana2_30b_a3b_s4096.json`` under
    ``assumed``."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    route_eps: float = 1e-20  # added to the chosen scores' sum (HF's own)
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    initializer_range: float = 0.02
    # γ of the bias rule (DeepSeek-V3, arXiv 2412.19437, section 2.1.2)
    bias_update_speed: float = 0.001
    # (index, count): this chip holds experts [index·E/count, (index+1)·E/count)
    expert_shard: Tuple[int, int] = (0, 1)
    # rows of one expert's tile in the routed loop (parallel/moe.py)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    remat_policy: str = "kernel_outputs"  # as Ouro: layer input + out + lse
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    mesh: Any = None

    def __post_init__(self):
        index, count = self.expert_shard
        if not (0 <= index < count) or self.n_routed_experts % count:
            raise ValueError(
                f"expert_shard {index}/{count}: the count must divide the "
                f"{self.n_routed_experts} routed experts, 0 <= index < count"
            )

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        index, count = self.expert_shard
        n = self.n_routed_experts // count
        return index * n, n

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @staticmethod
    def named(model_size: str):
        ctors = {"kanana2_30b_a3b": DeepseekV3Config.kanana2_30b_a3b,
                 "kanana2_tiny": DeepseekV3Config.tiny}
        if model_size not in ctors:
            raise ValueError(
                f"unknown model_size {model_size!r} "
                f"(expected one of {sorted(ctors)})"
            )
        return ctors[model_size]

    @staticmethod
    def kanana2_30b_a3b(**overrides) -> "DeepseekV3Config":
        return DeepseekV3Config(**overrides)

    @staticmethod
    def tiny(**overrides) -> "DeepseekV3Config":
        """Test-sized: every mechanism (a dense layer and two expert
        layers, two q/k widths, a latent, 16 experts top-3, two shared
        experts, a chunked head), no published width."""
        base = dict(
            vocab_size=256, hidden_size=32, num_hidden_layers=3,
            num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=16, intermediate_size=48,
            moe_intermediate_size=16, n_routed_experts=16,
            num_experts_per_tok=3, max_position_embeddings=128,
            moe_row_tile=8, attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return DeepseekV3Config(**base)


def apply_rope_interleaved(x, cos, sin):
    """x [B, S, H, D], rotated in pairs (2i, 2i+1) by the i-th frequency
    (``rope_interleave``), in float32; cos, sin [S, D/2]."""
    x32 = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    even, odd = x32[..., 0], x32[..., 1]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.stack(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).reshape(x.shape).astype(x.dtype)


class LatentAttention(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        B, S, _ = hidden.shape
        H, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rot, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        cos, sin = rope
        q = _dense(H * (nope + rot), cfg, "q_proj")(hidden).reshape(
            B, S, H, nope + rot
        )
        latent = _dense(rank + rot, cfg, "kv_a_proj_with_mqa")(hidden)
        kv = _dense(H * (nope + dv), cfg, "kv_b_proj")(
            RMSNorm(cfg, name="kv_a_layernorm")(latent[..., :rank])
        ).reshape(B, S, H, nope + dv)
        q_rope = apply_rope_interleaved(q[..., nope:], cos, sin)
        k_rope = apply_rope_interleaved(
            latent[..., rank:].reshape(B, S, 1, rot), cos, sin
        )
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        # the ONE rotary key head, broadcast into k's 192-wide layout
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rot))],
            axis=-1,
        )
        v = kv[..., nope:]
        if cfg.attention_impl == "flash":
            from dedloc_tpu.ops.flash_attention import flash_attention

            ctx = flash_attention(
                q, k, v, causal=True, block_q=cfg.attention_block_size,
                block_k=cfg.attention_block_size, mesh=cfg.mesh,
            )
        elif cfg.attention_impl == "dense":
            q, k, v = (checkpoint_name(x, "flash_qkv") for x in (q, k, v))
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
            ) / jnp.sqrt(jnp.float32(nope + rot))
            visible = jnp.tril(jnp.ones((S, S), bool))
            logits = jnp.where(visible[None, None], logits, -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        else:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r}: this model takes "
                "'flash' or 'dense'"
            )
        return _dense(cfg.hidden_size, cfg, "o_proj")(
            ctx.reshape(B, S, H * dv)
        )


class RoutedFFN(nn.Module):
    """Σ over the chosen HELD experts + the shared experts (where the model
    has any); returns (y, routing) with ``routing`` = scores [T, E], choice
    [T, k], load [E] and the counts of ``parallel/moe.routed_experts``.
    ``cfg``: this model's, or any config with the routed layer's fields
    under the same names (``models/lfm2_moe.Lfm2MoeConfig``). An apply that
    carries the collection ``GRAD_SINKS`` hands this layer's three buffers
    to the tile loop's backward."""

    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        B, S, H = x.shape
        E, F = cfg.n_routed_experts, cfg.moe_intermediate_size
        first, held = cfg.held_experts
        init = nn.initializers.normal(cfg.initializer_range)
        router = self.param("router", init, (H, E), jnp.float32)
        bias = self.param(BIAS, nn.initializers.zeros, (E,), jnp.float32)
        gate = self.param("experts_gate", init, (held, H, F), jnp.float32)
        up = self.param("experts_up", init, (held, H, F), jnp.float32)
        down = self.param("experts_down", init, (held, F, H), jnp.float32)
        tokens = x.reshape(B * S, H)
        # the router in float32 at full precision: the top-k is discrete
        scores = jax.nn.sigmoid(jnp.dot(
            tokens.astype(jnp.float32), router,
            precision=jax.lax.Precision.HIGHEST,
        ))
        choice, weights = route_top_k(
            scores, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.route_eps,
        )
        sinks = tuple(
            self.get_variable(GRAD_SINKS, name) for name in EXPERT_LEAVES
        ) if self.has_variable(GRAD_SINKS, EXPERT_LEAVES[0]) else None
        routed, counts = routed_experts(
            tokens, choice, weights, gate.astype(cfg.dtype),
            up.astype(cfg.dtype), down.astype(cfg.dtype), (first, held),
            tile=cfg.moe_row_tile, grad_sinks=sinks,
        )
        routed = routed.reshape(B, S, H)
        if cfg.n_shared_experts:
            routed = routed + SwiGLU(
                cfg, cfg.n_shared_experts * F, name="shared_experts"
            )(x).astype(jnp.float32)
        load = expert_load(choice, E)
        y = routed.astype(cfg.dtype)
        y = with_load_cotangent(y, bias, load)
        return y, dict(counts, scores=scores, choice=choice, load=load)


class DecoderLayer(nn.Module):
    """Pre-norm: h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h)); the FFN
    is a dense SwiGLU (``sparse=False``) or the routed layer."""

    cfg: DeepseekV3Config
    sparse: bool

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        hidden = hidden + LatentAttention(cfg, name="self_attn")(
            RMSNorm(cfg, name="input_layernorm")(hidden), rope
        )
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        if not self.sparse:
            return hidden + SwiGLU(cfg, cfg.intermediate_size, name="mlp")(x)
        y, routing = RoutedFFN(cfg, name="mlp")(x)
        return hidden + y, routing


def _remat(cfg: DeepseekV3Config):
    return nn.remat(
        DecoderLayer, policy=remat_policy_object(cfg.remat_policy)
    )


class _ScannedLayer(nn.Module):
    """Scan body: carry = hidden; rope broadcast; per-step out = routing."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, hidden, rope):
        return _remat(self.cfg)(self.cfg, True, name="block")(hidden, rope)


class DeepseekV3ForCausalLM(nn.Module):
    """``__call__(input_ids)`` -> (hidden [B, S, H] after the final norm, in
    the compute dtype; routing, every entry stacked over the expert
    layers). The head's weight is the parameter ``lm_head`` [H, V], applied
    by ``deepseek_v3_loss`` a chunk of tokens at a time."""

    cfg: DeepseekV3Config

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
        cos, sin = rope_tables(
            input_ids.shape[1], cfg.qk_rope_head_dim, cfg.rope_theta
        )
        half = cfg.qk_rope_head_dim // 2
        rope = (cos[:, :half], sin[:, :half])  # one column per pair
        for i in range(cfg.first_k_dense_replace):
            hidden = _remat(cfg)(cfg, False, name=f"dense_layer_{i}")(
                hidden, rope
            )
        stack = nn.scan(
            _ScannedLayer,
            variable_axes={"params": 0, GRAD_SINKS: 0},
            split_rngs={"params": True},
            in_axes=nn.broadcast,
            length=cfg.num_expert_layers,
        )
        hidden, routing = stack(cfg, name="layers")(hidden, rope)
        return RMSNorm(cfg, name="norm")(hidden), routing


def apply_with_grad_sinks(model, params, input_ids, grad_sinks):
    """``model.apply`` on ``params``, with ``grad_sinks`` (None, or the
    subtree of a float32 gradient accumulator that ``routed_grad_sink_mask``
    marks) riding beside them as the collection ``GRAD_SINKS``."""
    variables = {"params": params}
    if grad_sinks is not None:
        variables[GRAD_SINKS] = grad_sinks
    return model.apply(variables, input_ids)


def deepseek_v3_loss(model: DeepseekV3ForCausalLM, params,
                     batch: Dict[str, jnp.ndarray], grad_sinks=None):
    """(loss, metrics) of one micro-batch: ``input_ids`` and next-token
    ``labels``, [B, S] each, no padding. Beside the loss, the routing
    gauges of ``docs/observability.md`` and this micro-batch's routing as
    the step itself computed it (``moe.choice`` [L, T, k], ``moe.scores``
    [L, T, E]: what a check routes its reference by and compares; 8 MB at
    the published sizes, summed by nothing). ``grad_sinks``:
    ``apply_with_grad_sinks``'s; differentiated with respect to them too,
    their cotangent is ``sink + gradient`` of the leaf of the same name,
    whose own gradient is then zero."""
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
        "moe.bias_abs_max": jnp.max(jnp.abs(
            params["layers"]["block"]["mlp"][BIAS]
        )),
        "moe.dropped_slots": jnp.sum(routing["dropped_slots"]),
        "moe.grad_sink_leaves": jnp.sum(routing["grad_sink_leaves"]),
        "moe.choice": routing["choice"],
        "moe.scores": routing["scores"],
    }


def _leaf_name(path) -> str:
    return path[-1].key


def deepseek_v3_weight_decay_mask(params):
    """True where weight decay applies: every matrix; not the RMSNorm
    ``weight``s nor the correction bias."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _leaf_name(path) not in ("weight", BIAS), params
    )


def routed_grad_sink_mask(params):
    """True for the leaves whose gradient the routed loop can add into an
    accumulator in place: the held experts' matrices."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _leaf_name(path) in EXPERT_LEAVES, params
    )


def deepseek_v3_sign_step_mask(params):
    """True for the leaves stepped by the sign of their (load) cotangent:
    the expert layers' correction biases."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: _leaf_name(path) == BIAS, params
    )


def deepseek_v3_train_tflops_per_sample(cfg: DeepseekV3Config,
                                        seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq``
    tokens (matmuls only, backward = 2x forward, remat's replays not
    counted, causal attention at its triangle with its two widths, routed
    work for the HELD experts only, at the expected share of slots)."""
    h, heads = cfg.hidden_size, cfg.num_attention_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    attention = (
        2 * h * heads * qk  # W_q
        + 2 * h * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)  # W_kva
        + 2 * cfg.kv_lora_rank * heads * (
            cfg.qk_nope_head_dim + cfg.v_head_dim
        )  # W_kvb
        + 2 * heads * cfg.v_head_dim * h  # W_o
        + 2 * heads * (qk + cfg.v_head_dim) * (seq + 1) / 2  # QKᵀ, PV
    )
    f = cfg.moe_intermediate_size
    held_share = cfg.held_experts[1] / cfg.n_routed_experts
    sparse = (
        2 * h * cfg.n_routed_experts  # router
        + 2 * 3 * h * f * cfg.n_shared_experts
        + 2 * 3 * h * f * cfg.num_experts_per_tok * held_share
    )
    per_token = (
        cfg.num_hidden_layers * attention
        + cfg.first_k_dense_replace * 2 * 3 * h * cfg.intermediate_size
        + cfg.num_expert_layers * sparse
        + 2 * h * cfg.vocab_size
    )
    return 3.0 * per_token * seq / 1e12
