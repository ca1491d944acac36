"""ALBERT in Flax, TPU-first.

Capability parity with the reference's ``AlbertForPreTraining`` workload
(reference: albert/run_trainer.py:56-70 builds transformers'
AlbertForPreTraining — MLM + sentence-order-prediction heads). This is NOT a
port of the torch module: the design exploits ALBERT's cross-layer parameter
sharing with ``nn.scan`` so the HLO contains ONE transformer layer body
iterated ``num_hidden_layers`` times — smaller programs, faster compiles, and
the natural shape for ``jax.checkpoint`` rematerialisation.

TPU notes:
- matmuls run in bf16 with fp32 accumulation (``preferred_element_type``);
  softmax and layernorm statistics in fp32.
- static shapes everywhere; attention mask is an additive bias, no gather.
- remat policy on the scanned layer trades HBM for MXU FLOPs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from dedloc_tpu.models.remat import remat_policy_object

Dtype = Any


@dataclasses.dataclass(frozen=True)
class AlbertConfig:
    """ALBERT-large defaults (the reference's canonical workload config)."""

    vocab_size: int = 30000
    embedding_size: int = 128
    hidden_size: int = 1024
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    pad_token_id: int = 0
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    remat: bool = True
    # rematerialization policy for the scanned layer: "nothing" saves no
    # activations (min HBM), "dots" saves matmul outputs (fewer recomputed
    # MXU ops when HBM allows), "fused_ln" pairs with fused_ln=True (saves
    # exactly the named matmuls + every Pallas kernel's outputs, so the
    # backward replays no elementwise chain at all)
    remat_policy: str = "nothing"
    # fuse each residual-add + LayerNorm into one Pallas pass (fp32 stats,
    # one-kernel backward); numerics match the unfused path to bf16
    # precision. Off TPU the kernel runs in interpreter mode.
    fused_ln: bool = False
    # "dense" (materialized S² scores), "blockwise" (online-softmax over KV
    # blocks via lax.scan, O(S·block) memory — the long-context path),
    # "flash" (the same math as ONE fused Pallas kernel with a custom-VJP
    # backward: scores never leave VMEM; interpret-mode off TPU), or "ring"
    # (sequence-parallel exact attention: KV shards rotate around the mesh's
    # ``ring_axis`` via ppermute — requires ``mesh``). All exact.
    attention_impl: str = "dense"
    attention_block_size: int = 512
    # the slice mesh the model's jit spans (set by the trainer whenever
    # --training.mesh_devices > 1). The ops that GSPMD cannot partition open
    # their own shard_map over it: the Pallas kernels (flash attention,
    # fused add+LN — batch over "data", heads over "model") and ring
    # attention (the sequence over ``ring_axis``).
    mesh: Any = None
    ring_axis: str = "seq"
    # pipeline parallelism (--training.mesh_pipe_devices): the mesh whose
    # ``pipe_axis`` the encoder's layer iterations are staged over — ALBERT's
    # shared block applied num_hidden_layers/n_stages times per stage, GPipe
    # microbatch schedule under shard_map (parallel/pipeline.py). The param
    # tree is IDENTICAL to the scanned path (encoder/layer/block/...), so
    # checkpoints and collaborative gradient schemas interchange freely
    # between pipelined and non-pipelined peers.
    pipe_mesh: Any = None
    pipe_axis: str = "pipe"
    pipe_microbatches: int = 0  # 0 = 2 x n_stages (bubble = (S-1)/(M+S-1))
    # Switch-MoE FFN variant (--training.moe_experts, parallel/moe.py): the
    # dense gelu FFN becomes a top-1-routed expert FFN; experts shard over
    # ``moe_axis`` when ``moe_mesh`` is set (--training.mesh_expert_devices),
    # the dispatch einsums lowering to XLA all-to-alls. The Switch
    # load-balancing aux loss is sowed into the "losses" collection.
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    moe_mesh: Any = None
    moe_axis: str = "expert"

    @staticmethod
    def named(model_size: str):
        """The one model_size -> config-constructor resolver (role CLIs and
        fine-tune CLIs must agree on names and fail the same way)."""
        ctors = {"tiny": AlbertConfig.tiny, "large": AlbertConfig.large}
        if model_size not in ctors:
            raise ValueError(
                f"unknown model_size {model_size!r} "
                f"(expected one of {sorted(ctors)})"
            )
        return ctors[model_size]

    @staticmethod
    def large(**overrides) -> "AlbertConfig":
        return AlbertConfig(**overrides)

    @staticmethod
    def tiny(**overrides) -> "AlbertConfig":
        """Test-sized config (CI smoke; SURVEY.md §4 fake-backend pattern)."""
        base = dict(
            vocab_size=512,
            embedding_size=16,
            hidden_size=32,
            num_hidden_layers=2,
            num_attention_heads=2,
            intermediate_size=64,
            max_position_embeddings=64,
        )
        base.update(overrides)
        return AlbertConfig(**base)


def _dense(features: int, cfg: AlbertConfig, name: str) -> nn.Dense:
    return nn.Dense(
        features,
        dtype=cfg.dtype,
        param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(cfg.initializer_range),
        name=name,
    )


class AddLayerNorm(nn.Module):
    """``LayerNorm(x + residual)`` with the same parameter tree as
    ``nn.LayerNorm`` (scale/bias), so checkpoints are interchangeable.

    With ``cfg.fused_ln`` the add→stats→normalize chain runs as ONE Pallas
    pass each way (ops/fused_ln.py) instead of several HBM passes in the
    remat replay. Both paths now perform the residual ADD in fp32 (the
    pre-round-4 code added in ``cfg.dtype`` before the fp32-stat LN, so
    bf16 configs differ from older runs at bf16-rounding level — a strict
    precision improvement, and fused/unfused match each other)."""

    cfg: AlbertConfig

    @nn.compact
    def __call__(self, x, residual):
        cfg = self.cfg
        h = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (h,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (h,), jnp.float32)
        from dedloc_tpu.ops.fused_ln import ln_residual, ln_residual_reference

        if cfg.fused_ln:
            return ln_residual(
                x, residual, scale, bias, eps=cfg.layer_norm_eps,
                mesh=cfg.mesh,
            ).astype(cfg.dtype)
        return ln_residual_reference(
            x.astype(jnp.float32), residual.astype(jnp.float32),
            scale, bias, eps=cfg.layer_norm_eps,
        ).astype(cfg.dtype)


class AlbertSelfAttention(nn.Module):
    cfg: AlbertConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, attn_bias):
        cfg = self.cfg
        deterministic = self.deterministic
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        B, S, H = hidden.shape

        def split_heads(x):
            return x.reshape(B, S, cfg.num_attention_heads, head_dim)

        q = split_heads(_dense(cfg.hidden_size, cfg, "query")(hidden))
        k = split_heads(_dense(cfg.hidden_size, cfg, "key")(hidden))
        v = split_heads(_dense(cfg.hidden_size, cfg, "value")(hidden))

        if (
            cfg.attention_impl in ("flash", "blockwise", "ring")
            and cfg.attention_dropout_prob > 0.0
            and not deterministic
        ):
            # in deterministic (eval/serving) mode dropout is inactive, so a
            # dense-trained model can still be served with the fused impls
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r} does not support "
                "attention dropout in training (the reference recipe uses "
                "0.0); use attention_impl='dense' or attention_dropout_prob=0"
            )
        if cfg.attention_impl == "flash":
            # fused Pallas kernel: scores stay in VMEM, flash backward
            # (attention dropout is 0.0 in the reference recipe, so the
            # fused path loses nothing). The kernels index the projections'
            # [B, S, H·D] directly — split_heads and the reshape back are
            # the same bytes, nothing is transposed — and tag q/k/v
            # "flash_qkv" for the fused_ln remat policy
            from dedloc_tpu.ops.flash_attention import flash_attention

            kv_bias = attn_bias[:, 0, 0, :]  # additive [B, S_kv]
            ctx = flash_attention(
                q, k, v, kv_bias,
                block_q=cfg.attention_block_size,
                block_k=cfg.attention_block_size,
                mesh=cfg.mesh,
            ).reshape(B, S, H)
        elif cfg.attention_impl == "ring":
            # sequence parallelism: S is sharded over the mesh's ring_axis;
            # each device keeps its resident queries and rotates KV shards
            # around the ring (ppermute over ICI) — exact, never materializes
            # the S×S score matrix on any one device
            from dedloc_tpu.parallel.ring_attention import ring_attention

            if cfg.mesh is None:
                raise ValueError(
                    "attention_impl='ring' needs mesh (a Mesh with a "
                    f"{cfg.ring_axis!r} axis); the trainer sets it when "
                    "--training.mesh_seq_devices > 1"
                )
            kv_bias = attn_bias[:, 0, 0, :]  # additive [B, S_kv]
            ctx = ring_attention(
                q, k, v, kv_bias, mesh=cfg.mesh, axis=cfg.ring_axis
            ).reshape(B, S, H)
        elif cfg.attention_impl == "blockwise":
            # long-context path: exact online-softmax over KV blocks — never
            # materializes the S×S score matrix
            from dedloc_tpu.parallel.ring_attention import blockwise_attention

            kv_bias = attn_bias[:, 0, 0, :]  # additive [B, S_kv]
            ctx = blockwise_attention(
                q, k, v, kv_bias, block_size=cfg.attention_block_size
            ).reshape(B, S, H)
        else:
            # fp32 logits + softmax for numerical stability; bf16 elsewhere.
            scale = 1.0 / jnp.sqrt(head_dim).astype(jnp.float32)
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
            ) * scale
            logits = logits + attn_bias  # additive mask: 0 keep / -inf drop
            probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
            if cfg.attention_dropout_prob > 0.0 and not deterministic:
                probs = nn.Dropout(cfg.attention_dropout_prob)(
                    probs, deterministic=deterministic
                )
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H)
        out = _dense(cfg.hidden_size, cfg, "dense")(ctx)
        if cfg.hidden_dropout_prob > 0.0 and not deterministic:
            out = nn.Dropout(cfg.hidden_dropout_prob)(out, deterministic=deterministic)
        return AddLayerNorm(cfg, name="layernorm")(out, hidden)


class AlbertLayer(nn.Module):
    """One shared transformer block (attention + FFN, post-LN).

    Returns ``(hidden, aux_loss)`` — aux_loss is the Switch load-balancing
    term when ``cfg.moe_experts`` routes the FFN through experts, else 0.
    """

    cfg: AlbertConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, attn_bias):
        cfg = self.cfg
        deterministic = self.deterministic
        hidden = AlbertSelfAttention(cfg, deterministic, name="attention")(
            hidden, attn_bias
        )
        aux = jnp.zeros([], jnp.float32)
        if cfg.moe_experts > 0:
            ffn, aux = self._moe_ffn(hidden)
        else:
            # named for the fused_ln remat policy: the FFN up-projection is
            # the one matmul output the backward cannot cheaply recompute
            # (gelu's input); everything downstream is covered by saved
            # Pallas outputs
            ffn = checkpoint_name(
                _dense(cfg.intermediate_size, cfg, "ffn")(hidden), "ffn_up"
            )
            # also named so fused_ln_gelu can save the activation output and
            # skip the gelu forward replay in the remat backward (naming is
            # free for policies that don't reference it)
            ffn = checkpoint_name(nn.gelu(ffn, approximate=True), "ffn_gelu")
            ffn = _dense(cfg.hidden_size, cfg, "ffn_output")(ffn)
        if cfg.hidden_dropout_prob > 0.0 and not deterministic:
            ffn = nn.Dropout(cfg.hidden_dropout_prob)(ffn, deterministic=deterministic)
        return AddLayerNorm(cfg, name="layernorm")(ffn, hidden), aux

    def _moe_ffn(self, hidden):
        """Switch-routed FFN (parallel/moe.py): one expert set shared across
        the layer iterations — ALBERT's cross-layer sharing extended to the
        experts. Router/expert weights live in this layer's param tree, so
        checkpoints and the collaborative gradient schema carry them like
        any other leaf."""
        from dedloc_tpu.parallel.moe import MoEConfig, moe_ffn

        cfg = self.cfg
        B, S, H = hidden.shape
        mcfg = MoEConfig(
            hidden_size=cfg.hidden_size,
            ffn_size=cfg.intermediate_size,
            num_experts=cfg.moe_experts,
            capacity_factor=cfg.moe_capacity_factor,
            dtype=cfg.dtype,
        )
        init = nn.initializers.normal(cfg.initializer_range)
        params = {
            "router": self.param(
                "moe_router", init, (H, cfg.moe_experts), jnp.float32
            ),
            "wi": self.param(
                "moe_wi", init,
                (cfg.moe_experts, H, cfg.intermediate_size), jnp.float32,
            ),
            "wo": self.param(
                "moe_wo", init,
                (cfg.moe_experts, cfg.intermediate_size, H), jnp.float32,
            ),
        }
        # bf16 expert compute like the dense FFN; router math is fp32 inside
        params = {
            "router": params["router"],
            "wi": params["wi"].astype(cfg.dtype),
            "wo": params["wo"].astype(cfg.dtype),
        }
        y, aux = moe_ffn(
            params, hidden.reshape(B * S, H), mcfg,
            mesh=cfg.moe_mesh, axis=cfg.moe_axis,
        )
        return y.reshape(B, S, H).astype(cfg.dtype), aux


#: The only policy names that engage the fused add+LN Pallas kernel; a
#: membership test (not a prefix match) so a typo like "fused_ln_geluu"
#: fails fast at the remat-policy table with "unknown remat_policy"
#: instead of enabling the kernel and dying later on a bare KeyError.
FUSED_LN_POLICIES = frozenset({"fused_ln", "fused_ln_gelu"})


def fused_ln_for_policy(remat_policy: str) -> bool:
    """Policy -> whether the fused add+LN Pallas kernel must be on: the
    fused_ln* saved sets only cover the backward when the kernel produces
    the (y, x̂, rstd) outputs they rely on. One source of truth for every
    builder (bench, roles, profiler)."""
    return remat_policy in FUSED_LN_POLICIES


class _ScannedAlbertLayer(nn.Module):
    """scan body: carry = hidden states; attn_bias broadcast; per-step out =
    the layer's aux (MoE load-balance) loss."""

    cfg: AlbertConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, attn_bias):
        layer_cls = AlbertLayer
        if self.cfg.remat:
            layer_cls = nn.remat(
                AlbertLayer, policy=remat_policy_object(self.cfg.remat_policy)
            )
        out, aux = layer_cls(self.cfg, self.deterministic, name="block")(
            hidden, attn_bias
        )
        return out, aux


class AlbertEncoder(nn.Module):
    """Shared-parameter layer stack: nn.scan (one layer body in the HLO) —
    or, with ``cfg.pipe_mesh``, the GPipe pipeline path staging the same
    shared block across the mesh's pipe axis (parallel/pipeline.py)."""

    cfg: AlbertConfig
    deterministic: bool = True

    @nn.compact
    def __call__(self, hidden, attn_bias):
        cfg = self.cfg
        if cfg.pipe_mesh is not None:
            hidden, moe_aux = self._pipelined(hidden, attn_bias)
        else:
            # variable_broadcast shares the single layer's params across all
            # iterations — exactly ALBERT's cross-layer weight sharing.
            scan_layer = nn.scan(
                _ScannedAlbertLayer,
                variable_broadcast="params",
                split_rngs={"params": False, "dropout": True},
                in_axes=nn.broadcast,
                length=cfg.num_hidden_layers,
            )
            hidden, aux_ys = scan_layer(cfg, self.deterministic, name="layer")(
                hidden, attn_bias
            )
            moe_aux = jnp.sum(aux_ys)
        if cfg.moe_experts > 0:
            # the trainer's loss_fn reads this via mutable=("losses",) and
            # adds cfg.moe_aux_weight * moe_aux (Switch load balancing)
            self.sow("losses", "moe_aux", moe_aux)
        return hidden

    def _pipelined(self, hidden, attn_bias):
        """Pipeline-parallel forward: num_hidden_layers/n_stages iterations
        of the ONE shared block per stage, microbatches hopping stage→stage
        (GPipe under shard_map). The param tree is created by the same
        AlbertLayer init as the scan path, under the same names
        (layer/block/...), so both paths share checkpoints and gradient
        schemas. Composes with a "data" mesh axis (microbatch rows sharded
        over it); "seq"/"model" axes and MoE need their own collectives
        inside the stage and are rejected with the reason."""
        from dedloc_tpu.parallel.pipeline import pipeline_apply, shared_stage_fn

        cfg = self.cfg
        mesh, axis = cfg.pipe_mesh, cfg.pipe_axis
        n_stages = int(mesh.shape[axis])
        if cfg.num_hidden_layers % n_stages:
            raise ValueError(
                f"num_hidden_layers ({cfg.num_hidden_layers}) must divide "
                f"evenly into {n_stages} pipeline stages"
            )
        if cfg.moe_experts > 0:
            raise ValueError(
                "pipe_mesh + moe_experts unsupported: the expert all-to-all "
                "would need its own axis inside the pipeline's shard_map"
            )
        if cfg.attention_impl == "ring":
            raise ValueError(
                "pipe_mesh + attention_impl='ring' unsupported: ring "
                "attention opens its own shard_map over the seq axis"
            )
        if not self.deterministic and (
            cfg.hidden_dropout_prob > 0.0 or cfg.attention_dropout_prob > 0.0
        ):
            raise ValueError(
                "the pipeline path does not thread dropout rngs through "
                "shard_map stages; use dropout 0 (the reference recipe)"
            )
        iters = cfg.num_hidden_layers // n_stages
        B, S, H = hidden.shape
        M = cfg.pipe_microbatches or 2 * n_stages
        # the stage body already runs per device inside the pipeline's
        # shard_map: its kernels must not open a second one
        layer = AlbertLayer(
            dataclasses.replace(cfg, mesh=None), self.deterministic
        )
        proto_x = jnp.zeros((max(1, B // M), S, H), hidden.dtype)
        proto_b = jnp.zeros(
            (max(1, B // M),) + attn_bias.shape[1:], attn_bias.dtype
        )
        params = self.param(
            "layer",
            lambda rng: {"block": layer.init(rng, proto_x, proto_b)["params"]},
        )
        if self.is_initializing():
            # init runs with the PER-DEVICE batch (roles init that way so
            # param shapes come cheap) — the pipeline schedule is
            # irrelevant to parameter creation, so apply the block
            # sequentially for the init-time forward value
            h = hidden
            for _ in range(cfg.num_hidden_layers):
                h, _aux = layer.apply({"params": params["block"]}, h, attn_bias)
            return h, jnp.zeros([], jnp.float32)
        if B % M:
            raise ValueError(
                f"batch ({B}) must divide into pipe_microbatches ({M})"
            )

        def block_fn(p, xb):
            h, b = xb
            h2, _aux = layer.apply({"params": p["block"]}, h, b)
            return (h2, b)

        if cfg.remat:
            block_fn = jax.checkpoint(
                block_fn, policy=remat_policy_object(cfg.remat_policy)
            )
        stage = shared_stage_fn(block_fn, iters)
        micro = (
            hidden.reshape(M, B // M, S, H),
            jnp.broadcast_to(
                attn_bias, (B,) + attn_bias.shape[1:]
            ).reshape((M, B // M) + attn_bias.shape[1:]),
        )
        spec = P(None, "data") if "data" in mesh.axis_names else P()
        out_h, _ = pipeline_apply(
            stage, params, micro, mesh, axis=axis,
            stacked_params=False, micro_spec=spec,
        )
        return out_h.reshape(B, S, H), jnp.zeros([], jnp.float32)


class AlbertModel(nn.Module):
    cfg: AlbertConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        deterministic: bool = True,
    ):
        cfg = self.cfg
        B, S = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, S), dtype=jnp.int32)
        if token_type_ids is None:
            token_type_ids = jnp.zeros((B, S), dtype=jnp.int32)

        word_emb = nn.Embed(
            cfg.vocab_size,
            cfg.embedding_size,
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            param_dtype=jnp.float32,
            name="word_embeddings",
        )
        pos_emb = nn.Embed(
            cfg.max_position_embeddings,
            cfg.embedding_size,
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            param_dtype=jnp.float32,
            name="position_embeddings",
        )
        type_emb = nn.Embed(
            cfg.type_vocab_size,
            cfg.embedding_size,
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            param_dtype=jnp.float32,
            name="token_type_embeddings",
        )
        positions = jnp.arange(S)[None, :]
        emb = word_emb(input_ids) + pos_emb(positions) + type_emb(token_type_ids)
        emb = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                           name="embeddings_layernorm")(emb)
        if cfg.hidden_dropout_prob > 0.0 and not deterministic:
            emb = nn.Dropout(cfg.hidden_dropout_prob)(emb, deterministic=deterministic)

        # Factorized embedding: project emb_size -> hidden_size.
        hidden = _dense(cfg.hidden_size, cfg, "embedding_projection")(
            emb.astype(cfg.dtype)
        )

        attn_bias = jnp.where(attention_mask[:, None, None, :] > 0, 0.0, -1e9).astype(
            jnp.float32
        )
        hidden = AlbertEncoder(cfg, deterministic, name="encoder")(hidden, attn_bias)

        pooled = _dense(cfg.hidden_size, cfg, "pooler")(hidden[:, 0])
        pooled = jnp.tanh(pooled)
        return hidden, pooled


class AlbertForPreTraining(nn.Module):
    """ALBERT with MLM + sentence-order-prediction heads.

    The MLM decoder is tied to the word-embedding table (same capability as
    transformers' AlbertForPreTraining used at albert/run_trainer.py:64-67).
    """

    cfg: AlbertConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        deterministic: bool = True,
        mlm_positions=None,
    ):
        """``mlm_positions`` [B, P]: when given, the MLM head runs only on
        those gathered positions (returns [B, P, vocab]) — the TPU-native
        masked-position path that skips ~85% of the vocab-projection FLOPs.
        When None, logits cover every position (reference-equivalent)."""
        cfg = self.cfg
        backbone = AlbertModel(cfg, name="albert")
        hidden, pooled = backbone(
            input_ids, attention_mask, token_type_ids, deterministic
        )

        if mlm_positions is not None:
            # gather [B, P, H] prediction positions before the vocab matmul
            hidden = jnp.take_along_axis(
                hidden, mlm_positions[..., None].astype(jnp.int32), axis=1
            )

        # MLM head: hidden -> embedding_size -> vocab (tied decoder).
        x = _dense(cfg.embedding_size, cfg, "mlm_dense")(hidden)
        x = nn.gelu(x, approximate=True)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                         name="mlm_layernorm")(x).astype(cfg.dtype)
        embedding_table = backbone.variables["params"]["word_embeddings"]["embedding"]
        mlm_logits = jnp.einsum(
            "bsh,vh->bsv",
            x,
            embedding_table.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
        mlm_bias = self.param(
            "mlm_bias", nn.initializers.zeros, (cfg.vocab_size,), jnp.float32
        )
        mlm_logits = mlm_logits + mlm_bias

        sop_logits = _dense(2, cfg, "sop_classifier")(pooled).astype(jnp.float32)
        return mlm_logits, sop_logits


class AlbertForTokenClassification(nn.Module):
    """ALBERT with a per-token classifier head.

    Capability of ``AutoModelForTokenClassification`` as used by the
    reference's NER fine-tune driver (sahajbert/train_ner.py:160-168):
    backbone hidden states -> dropout -> Dense(num_labels) in fp32.
    """

    cfg: AlbertConfig
    num_labels: int
    classifier_dropout: float = 0.1

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        deterministic: bool = True,
    ):
        hidden, _ = AlbertModel(self.cfg, name="albert")(
            input_ids, attention_mask, token_type_ids, deterministic
        )
        if self.classifier_dropout > 0.0 and not deterministic:
            hidden = nn.Dropout(self.classifier_dropout)(
                hidden, deterministic=deterministic
            )
        return _dense(self.num_labels, self.cfg, "classifier")(hidden).astype(
            jnp.float32
        )


class AlbertForSequenceClassification(nn.Module):
    """ALBERT with a pooled-output classifier head.

    Capability of ``AutoModelForSequenceClassification`` as used by the
    reference's news-category fine-tune driver (sahajbert/train_ncc.py:25,159):
    pooled [CLS] -> dropout -> Dense(num_labels) in fp32.
    """

    cfg: AlbertConfig
    num_labels: int
    classifier_dropout: float = 0.1

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        deterministic: bool = True,
    ):
        _, pooled = AlbertModel(self.cfg, name="albert")(
            input_ids, attention_mask, token_type_ids, deterministic
        )
        if self.classifier_dropout > 0.0 and not deterministic:
            pooled = nn.Dropout(self.classifier_dropout)(
                pooled, deterministic=deterministic
            )
        return _dense(self.num_labels, self.cfg, "classifier")(pooled).astype(
            jnp.float32
        )


def _masked_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray, mask: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Masked-mean CE + accuracy over positions where ``mask`` is 1.

    ``labels`` must already be clamped into [0, num_classes). Returns
    (loss, accuracy, denom) with denom = max(mask.sum(), 1).
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    acc = ((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32) * mask).sum() / (
        denom
    )
    return loss, acc, denom


def classification_loss(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    ignore_index: int = -100,
) -> Tuple[jnp.ndarray, dict]:
    """Cross-entropy over any leading shape, masked-mean over labels != -100.

    Serves both fine-tune heads: token classification ([B, S, L] logits with
    -100 on special/continuation tokens, train_ner.py:199-209) and sequence
    classification ([B, L] logits, all labelled).
    """
    mask = (labels != ignore_index).astype(jnp.float32)
    safe = jnp.where(labels == ignore_index, 0, labels)
    loss, acc, _ = _masked_cross_entropy(logits, safe, mask)
    return loss, {"loss": loss, "accuracy": acc, "n_labels": mask.sum()}


def albert_pretraining_loss(
    mlm_logits: jnp.ndarray,
    sop_logits: jnp.ndarray,
    mlm_labels: jnp.ndarray,
    sop_labels: jnp.ndarray,
    ignore_index: int = -100,
) -> Tuple[jnp.ndarray, dict]:
    """MLM + SOP cross-entropy, masked-mean over labelled positions.

    Matches the loss AlbertForPreTraining computes (MLM CE over positions with
    label != -100 plus SOP CE over the pooled output).
    """
    mask = (mlm_labels != ignore_index).astype(jnp.float32)
    safe_labels = jnp.where(mlm_labels == ignore_index, 0, mlm_labels)
    mlm_loss, mlm_acc, _ = _masked_cross_entropy(mlm_logits, safe_labels, mask)

    sop_logp = jax.nn.log_softmax(sop_logits.astype(jnp.float32), axis=-1)
    sop_nll = -jnp.take_along_axis(sop_logp, sop_labels[:, None], axis=-1)[:, 0]
    sop_loss = sop_nll.mean()

    loss = mlm_loss + sop_loss
    metrics = {
        "loss": loss,
        "mlm_loss": mlm_loss,
        "sop_loss": sop_loss,
        "mlm_acc": mlm_acc,
    }
    return loss, metrics


def albert_pretraining_loss_gathered(
    mlm_logits: jnp.ndarray,  # [B, P, vocab] — logits at gathered positions
    sop_logits: jnp.ndarray,
    mlm_label_ids: jnp.ndarray,  # [B, P]
    mlm_weights: jnp.ndarray,  # [B, P] 1.0 real prediction / 0.0 padding
    sop_labels: jnp.ndarray,
) -> Tuple[jnp.ndarray, dict]:
    """Masked-position variant of the MLM+SOP loss (same value as the dense
    loss for equal label sets; see the gathered-head path above)."""
    w = mlm_weights.astype(jnp.float32)
    mlm_loss, mlm_acc, _ = _masked_cross_entropy(mlm_logits, mlm_label_ids, w)

    sop_logp = jax.nn.log_softmax(sop_logits.astype(jnp.float32), axis=-1)
    sop_nll = -jnp.take_along_axis(sop_logp, sop_labels[:, None], axis=-1)[:, 0]
    sop_loss = sop_nll.mean()

    loss = mlm_loss + sop_loss
    metrics = {
        "loss": loss,
        "mlm_loss": mlm_loss,
        "sop_loss": sop_loss,
        "mlm_acc": mlm_acc,
    }
    return loss, metrics
