"""Keye-VL-2.0's LANGUAGE MODEL (``model_type: KeyeVL2``; the published model
this file was written for is Kwai-Keye/Keye-VL-2.0-30B-A3B), in Flax: the
Qwen3-MoE decoder — pre-norm, grouped-query attention with an RMSNorm over
each head of q and k, every layer a dropless routed FFN of SwiGLU experts —
in which each query attends only to the keys a learned INDEXER selects for it
(DeepSeek Sparse Attention's lightning indexer, ``sa_config``: 16 heads of
64, top-2,048), the indexer trained by a loss of its own, and positions are
three streams a token (M-RoPE). The vision tower is not here: what it leaves
in the language model — three position streams and image positions that
carry no loss — arrives in the batch (``data/causal_lm.py``).
``benchmark/reference/keye_vl2.py`` carries the same equations in plain
``jax.numpy``:

    x [S, 2048], no bias in any projection, RMSNorm eps 1e-6, every layer
    alike, positions p = (p_t, p_h, p_w) [3, S]:
    n = RMSNorm_in(x)
    q = W_q n [32 x 128];  k = W_k n, v = W_v n [4 x 128];  kv head j serves
    the 8 ADJACENT query heads
    q_h <- RMSNorm_q(q_h), k_j <- RMSNorm_k(k_j)    over a head's 128 lanes
    M-RoPE, rotate-half over the whole head: 64 inverse frequencies f_i =
        10,000,000^(-2i/128); pair i turns by f_i x p_t[s] for i in 0..15,
        f_i x p_h[s] for i in 16..39, f_i x p_w[s] for i in 40..63
        (mrope_section [16, 24, 24], contiguous); cos / sin repeated over both
        halves. A text token has p_t = p_h = p_w: plain RoPE there.
    indexer, from nd = stop_gradient(n):
        qI = W_qI nd [16 x 64];  kI = LayerNorm(W_kI nd) [64] (ONE key head;
        weight and bias, eps 1e-6);  w = W_wI nd [16];  qI and kI rotate-half
        over their 64 lanes, 32 frequencies 10,000,000^(-2i/64), by p_t
        I[t, s] = sum_j w[t, j] relu(qI[t, j] · kI[s]) 64^-0.5 16^-0.5
                                                       for s <= t, float32
        S_t = the 2,048 keys s <= t with the largest I[t, s] (all of them
        where t < 2,048); ties to the lower s
    a_h[t] = softmax over s in S_t of (q_h[t] · k_j[s] / sqrt(128)) v_j[s]
                                               (no gradient through S_t)
    h = x + W_o concat_h(a_h);   m = RMSNorm_post(h)
    r = softmax(W_r m) [128], float32;  C = top-8(r);  w_e = r_e / sum_C r_c
    y = h + sum_{e in C} w_e Expert_e(m);  Expert_e: SwiGLU 2048 -> 768 ->
    2048, dropless, no shared expert
    after the stack a final RMSNorm and an untied head.
    L_LM = sum_t u_t CE(logits_t, label_t) / sum_t u_t,  u_t = 0 where the
           LABEL is an image position, else 1
    L_I  = mean over layers and queries t of KL(pbar_t || softmax_{S_t} I[t]),
           pbar_t[s] = stop_gradient(sum_h P_h[t, s]) / 32 over s in S_t,
           P_h the main attention's probabilities
    L = L_LM + L_I: the main model's leaves get dL_LM only, the indexer's
    (W_qI, W_kI, W_wI, the LayerNorm) dL_I only.

The program's shape. The selection is exact and it is DATA: each layer makes
its own, once (``select_keys``: the index scores and the k-th largest of
each row by bisection — no sort), as an int8 [B, S, S] mask that the three
``flash_sel_*`` kernels read tile by tile beside q / k / v
(``ops/flash_attention.py``, "selected tiles"; named ``attn_selection`` for
the remat policies: kept from ``kernel_operands`` up, so the backward
kernels read the forward's own array). Which path runs where is
``attention_impl``'s to say, for the selection as for the loss: behind the
flash kernels (``"flash"``: the published widths, the benchmark's cell) the
selection is ONE Pallas call a layer (``ops/index_select.index_select``: a
block of query rows' scores and their exact top-k in VMEM, over the causal
triangle) and the indexer's loss a kernel pair of its own
(``ops/index_loss.py``: pbar rebuilt from the kernels' log-sum-exp a (query
tile, key tile) pair of the triangle at a time); behind ``"dense"``
attention (tests, tiny models, widths that are no whole lane tiles) each is
a ``lax.map`` over blocks of query rows in XLA — ``index_scores`` +
``ops/index_select.top_k_mask`` for the selection, the loss's blocks under
``jax.checkpoint`` — which is also the kernels' oracle. Nothing of size
[heads, S, S] exists on either path. Scopes in a trace: ``dsa_index`` (the
score passes in XLA), ``dsa_select``, ``dsa_loss``, ``mrope``,
``moe_routed``.

**A chip's share**, as for the other expert decoders: ``expert_shard``,
``vocab_size`` (rows held of the embedding and of the head) and
``num_hidden_layers``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from dedloc_tpu.models.decoder import (
    GroupedQueryAttention,
    RMSNorm,
    RoutedGLU,
    Visibility,
    apply_rope,
    apply_with_grad_sinks,
    chunked_cross_entropy,
    dense,
    embed_tokens,
    held_range,
    mixer_residual,
    mrope_tables,
    named_config,
    routed_metrics,
    scan_periods,
    weight_decay_mask,
)
from dedloc_tpu.models.remat import remat_layer
from dedloc_tpu.ops.flash_attention import selection_tile_flags, visited_tiles
from dedloc_tpu.ops.index_loss import index_loss_rows
from dedloc_tpu.ops.index_select import index_select, top_k_mask_and_ties

# layers a scan step runs, unrolled: ``models/sdar_moe.py``'s reason (the
# routed loop's gradient sinks are the accumulator's own leaves)
SCAN_PERIOD = 4
# query rows a step of the index-score + top-k pass / of the indexer's loss
# (their block loops, the ``"dense"`` path: behind the flash kernels they are
# ``ops/index_select.py``'s and ``ops/index_loss.py``'s kernels and no loop)
# takes: what bounds their transients ([rows, 16, S] and [32, rows, S] float32). They DIFFER, and a
# device trace tells the two passes' loops apart by that (the selection cut
# into their blocks, ``s8[blocks, rows, S]``:
# ``benchmark/reducers/keye_block_loop_time.py`` reads both from here and
# reports neither where they coincide)
INDEX_BLOCK_ROWS = 256
INDEX_LOSS_BLOCK_ROWS = 128


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    """Keye-VL-2.0-30B-A3B's language model as published (``config.json``);
    what it does not fix is in
    ``benchmark/configs/keye_vl2_30b_a3b_s16384.json`` under ``assumed``."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: Tuple[int, int, int] = (16, 24, 24)
    # ``sa_config``: the indexer's heads, their width and the keys a query
    # keeps
    index_n_heads: int = 16
    index_head_dim: int = 64
    index_topk: int = 2048
    initializer_range: float = 0.02
    expert_shard: Tuple[int, int] = (0, 1)
    moe_row_tile: int = 256
    dtype: Any = jnp.bfloat16  # compute dtype; params stay fp32
    # a name of models/remat.py's table. "kernel_operands": what the layer's
    # Pallas backward kernels read is kept beside what the forward ones
    # wrote — q / k / v as the kernels read them (168 MB a layer a row of
    # 16,384), out + lse (136 MB) AND the selection (``attn_selection``,
    # int8 [B, S, S]: 268 MB) — so the backward's replay runs no index-score
    # pass and no top-k for the kernels' sake, and the dq / dkv kernels read
    # the very array the forward read. Not "whole_mixer", the other expert
    # decoders' default: the stream after attention and the q / k norm's
    # input are 218 MB a layer more (0.87 GB in the benchmark's cell, where
    # accumulate_step's scratch reads 5.34 GB beside 5.3 GB of state and
    # accumulator), and what they save — q_proj, k_proj and
    # o_proj in the replay, 0.2 TFLOP a layer — is a hundredth of this
    # layer's replay. Below "kernel_operands" the selection is REPLAYED from
    # the replayed indexer (the same ops on the same values)
    remat_policy: str = "kernel_operands"
    attention_impl: str = "flash"  # or "dense" (tests, tiny models)
    attention_block_size: int = 512
    loss_chunk_tokens: int = 512
    # a check's: the loss's metrics also carry every layer's selection
    # ([L, B, S, S] int8: 1 GB at the cell's shape — never on the normal path)
    emit_selection: bool = False
    mesh: Any = None

    def __post_init__(self):
        held_range(self.expert_shard, self.num_experts)  # raises

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert held, how many)."""
        return held_range(self.expert_shard, self.num_experts)

    @staticmethod
    def named(model_size: str):
        return named_config(model_size, {
            "keye_vl2_30b_a3b": KeyeVL2Config.keye_vl2_30b_a3b,
            "keye_vl2_tiny": KeyeVL2Config.tiny,
        })

    @staticmethod
    def keye_vl2_30b_a3b(**overrides) -> "KeyeVL2Config":
        return KeyeVL2Config(**overrides)

    @staticmethod
    def tiny(**overrides) -> "KeyeVL2Config":
        """Test-sized: every mechanism (4 query heads on 2 kv heads with
        their q / k norm, three position streams, 2 indexer heads of 8
        keeping 8 keys of up to 32, 8 SwiGLU experts top-2, a chunked untied
        head), no published width."""
        base = dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            mrope_section=(2, 3, 3), index_n_heads=2, index_head_dim=8,
            index_topk=8, moe_intermediate_size=16, num_experts=8,
            num_experts_per_tok=2, max_position_embeddings=128,
            moe_row_tile=8, attention_impl="dense", loss_chunk_tokens=32,
        )
        base.update(overrides)
        return KeyeVL2Config(**base)


class LightningIndexer(nn.Module):
    """qI [B, S, 16, 64], kI [B, S, 64] (ONE key head, LayerNorm'd) and the
    head weights w [B, S, 16] float32 of the layer's DETACHED normalised
    input, qI and kI rotated by the temporal position (``rope``: tables
    [B, S, 64])."""

    cfg: KeyeVL2Config

    @nn.compact
    def __call__(self, normed, rope):
        cfg = self.cfg
        B, S, _ = normed.shape
        J, D = cfg.index_n_heads, cfg.index_head_dim
        q = dense(J * D, cfg, "wq")(normed).reshape(B, S, J, D)
        k = dense(D, cfg, "wk")(normed).astype(jnp.float32)
        weight = self.param("k_norm_weight", nn.initializers.ones, (D,),
                            jnp.float32)
        bias = self.param("k_norm_bias", nn.initializers.zeros, (D,),
                          jnp.float32)
        mean = jnp.mean(k, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
        k = ((k - mean) * jax.lax.rsqrt(var + cfg.rms_norm_eps) * weight
             + bias).astype(cfg.dtype)
        w = dense(J, cfg, "weights_proj")(normed).astype(jnp.float32)
        return (apply_rope(q, *rope),
                apply_rope(k[:, :, None, :], *rope)[:, :, 0], w)


def index_scores(cfg, q_rows, keys, w_rows):
    """I [R, S] float32 of a block of query rows: q_rows [R, 16, 64], keys
    [S, 64], w_rows [R, 16] — sum_j w_j relu(q_j · k) 64^-0.5 16^-0.5, the
    dots in the compute dtype with float32 sums."""
    with jax.named_scope("dsa_index"):
        dots = jnp.einsum("rjd,sd->rjs", q_rows, keys,
                          preferred_element_type=jnp.float32)
        scale = (cfg.index_head_dim * cfg.index_n_heads) ** -0.5
        return jnp.sum(nn.relu(dots) * w_rows[:, :, None], axis=1) * scale


def _row_blocks(x, rows: int):
    """[B, S, ...] -> [B · S / rows, rows, ...]."""
    return x.reshape((-1, rows) + x.shape[2:])


def _blocks_of(seq: int, batch: int, rows: int):
    """(rows a block, each block's batch row, each block's first query)."""
    rows = min(rows, seq)
    if seq % rows:
        raise ValueError(f"blocks of {rows} query rows do not divide {seq}")
    per_row = seq // rows
    block = jnp.arange(batch * per_row)
    return rows, block // per_row, (block % per_row) * rows


def select_keys(cfg, q_index, k_index, weights):
    """(the layer's selection, int8 [B, S, S] (rows queries): 1 at the
    ``cfg.index_topk`` keys s <= t with the largest index score of each
    query t — all of them where t < top-k, ties to the lower s —, the share
    of its blocks of query rows in which a row had more keys EQUAL to its
    threshold than it may take, so that the bisection over the position
    ran: 0 for an indexer whose scores are distinct). Exactly the top-k of
    the program's own scores; no gradient. Behind the flash kernels
    (``cfg.attention_impl == "flash"``) ONE Pallas call over the causal
    triangle (``ops/index_select.index_select``); behind ``"dense"``
    attention this loop over blocks of ``INDEX_BLOCK_ROWS`` query rows — the
    kernel's oracle — whose every step scores the block against ALL keys."""
    B, S = weights.shape[:2]
    q_index, k_index, weights = jax.lax.stop_gradient(
        (q_index, k_index, weights)
    )
    if cfg.attention_impl == "flash":
        with jax.named_scope("dsa_select"):
            selection, tied = index_select(
                q_index, k_index, weights, cfg.index_topk,
                block_rows=INDEX_BLOCK_ROWS,
            )
        return selection, jnp.mean(tied.astype(jnp.float32))
    rows, batch_row, first = _blocks_of(S, B, INDEX_BLOCK_ROWS)
    key_at = jnp.arange(S)[None, :]

    def block(args):
        q_rows, w_rows, b, t0 = args
        scores = index_scores(cfg, q_rows, k_index[b], w_rows)
        with jax.named_scope("dsa_select"):
            valid = key_at <= t0 + jnp.arange(rows)[:, None]
            chosen, tied = top_k_mask_and_ties(scores, valid, cfg.index_topk)
            return chosen.astype(jnp.int8), tied

    selection, tied = jax.lax.map(block, (
        _row_blocks(q_index, rows), _row_blocks(weights, rows), batch_row,
        first,
    ))
    return selection.reshape(B, S, S), jnp.mean(tied.astype(jnp.float32))


def index_loss(cfg, q_index, k_index, weights, selection, q, k, lse):
    """(L_I of the layer — the mean over its B · S queries of KL(pbar_t ||
    softmax over S_t of I[t]) —, the index-peak gauge: the mean over queries
    of |S_t| x the largest softmax_{S_t}(I)[t, s]; 1 for a flat indexer).
    pbar is the main attention's probabilities summed over the heads / H,
    recomputed a block of query rows at a time from q, k [B, S, heads, D]
    (as the kernels read them) and the kernels' ``lse`` [B, H, S], all
    DETACHED; the gradient reaches ``q_index``, ``k_index`` and ``weights``
    alone. Behind the flash kernels (``cfg.attention_impl == "flash"``) the
    loss is a kernel pair of its own over the tiles of the causal triangle
    (``ops/index_loss.py``); behind ``"dense"`` attention it is this loop
    over blocks of ``INDEX_LOSS_BLOCK_ROWS`` query rows — the kernels'
    oracle — each block under ``jax.checkpoint``: the backward recomputes it
    from its inputs, nothing [H, rows, S] is kept."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    if cfg.attention_impl == "flash":
        # the same loss as Pallas kernels over the causal triangle's tiles
        # (``ops/index_loss.py``): nothing [rows, J, S] or [H, rows, S]
        # leaves VMEM, and the backward recomputes a tile, not a block
        with jax.named_scope("dsa_loss"):
            kl, peak = index_loss_rows(
                q_index, k_index, weights, selection, q, k, lse,
                block_q=cfg.attention_block_size,
                block_k=cfg.attention_block_size,
            )
        return jnp.sum(kl) / (B * S), jnp.sum(peak) / (B * S)
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    rows, batch_row, _first = _blocks_of(S, B, INDEX_LOSS_BLOCK_ROWS)
    # [B, H, S] -> a block's [H, rows]
    lse_rows = lse.reshape(B, H, S // rows, rows).transpose(0, 2, 1, 3)

    @jax.checkpoint
    def block(args):
        qi_rows, w_rows, chosen, q_rows, lse_block, b = args
        chosen = chosen != 0
        scores = jnp.where(
            chosen, index_scores(cfg, qi_rows, k_index[b], w_rows), -1e30
        )
        with jax.named_scope("dsa_loss"):
            log_norm = jax.nn.logsumexp(scores, axis=-1, keepdims=True)
            dots = jnp.einsum(
                "rcgd,scd->cgrs", q_rows.reshape(rows, KV, H // KV, D), k[b],
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(jnp.float32(D))
            probs = jnp.exp(dots - lse_block.reshape(KV, H // KV, rows, 1))
            target = jnp.where(chosen, jnp.sum(probs, axis=(0, 1)) / H, 0.0)
            kl = jnp.where(
                target > 0,
                target * (jnp.log(jnp.maximum(target, 1e-38))
                          - (scores - log_norm)),
                0.0,
            )
            peak = jnp.sum(chosen, axis=-1) * jnp.exp(
                jnp.max(scores, axis=-1) - log_norm[:, 0]
            )
            return jnp.sum(kl), jnp.sum(peak)

    kl, peak = jax.lax.map(block, (
        _row_blocks(q_index, rows), _row_blocks(weights, rows),
        _row_blocks(selection, rows), _row_blocks(q, rows),
        lse_rows.reshape(-1, H, rows), batch_row,
    ))
    return jnp.sum(kl) / (B * S), jax.lax.stop_gradient(
        jnp.sum(peak) / (B * S)
    )


class DecoderLayer(nn.Module):
    """h = x + Attn_S(n), n = RMSNorm(x), S the indexer's selection from the
    detached n; y = h + Experts(RMSNorm(h)). Returns (y, routing): the
    routed layer's, and this layer's ``index_kl`` (its L_I), ``index_peak``,
    ``select_tiles`` (tiles of the kernels' triangle that hold a selected
    pair) and ``select_tie_blocks`` (``select_keys``' share), with
    ``cfg.emit_selection`` the ``selection`` itself."""

    cfg: KeyeVL2Config

    @nn.compact
    def __call__(self, hidden, rope):
        cfg = self.cfg
        cos, sin, index_cos, index_sin = rope
        normed = RMSNorm(cfg, name="input_layernorm")(hidden)
        q_index, k_index, weights = LightningIndexer(cfg, name="indexer")(
            jax.lax.stop_gradient(normed), (index_cos, index_sin)
        )
        # named HERE, so that every reader below — the kernels, the loss's
        # blocks, the tile count — reads the one array a policy keeps: a
        # reader of the un-named value would make the backward's replay run
        # the index pass and the top-k again for it (78.9 ms an execution at
        # the benchmark's cell: PERF.md section 5)
        selection, tie_blocks = select_keys(cfg, q_index, k_index, weights)
        selection = checkpoint_name(selection, "attn_selection")
        mixed, (q, k, lse) = GroupedQueryAttention(
            cfg, Visibility(selected=True), qk_norms=("q_norm", "k_norm"),
            name="self_attn",
        )(normed, (cos, sin), selection=selection)
        index_kl, index_peak = index_loss(
            cfg, q_index, k_index, weights, selection, q, k, lse
        )
        hidden = mixer_residual(hidden, mixed)
        x = RMSNorm(cfg, name="post_attention_layernorm")(hidden)
        with jax.named_scope("moe_routed"):
            y, routing = RoutedGLU(cfg, activation="silu", name="mlp")(x, x)
        block = cfg.attention_block_size
        routing = dict(
            routing, index_kl=index_kl, index_peak=index_peak,
            select_tie_blocks=tie_blocks,
            select_tiles=jnp.sum(
                selection_tile_flags(selection, block, block)
            ).astype(jnp.float32),
        )
        if cfg.emit_selection:
            routing["selection"] = selection
        return hidden + y, routing


class KeyeVL2ForCausalLM(nn.Module):
    """``__call__(input_ids, position_ids=None)``: ``position_ids`` [3, B, S]
    the tokens' (temporal, height, width) positions — None: text, three
    ``arange``s — -> (hidden [B, S, H] after the final norm, in the compute
    dtype; routing, every entry stacked over the layers in order). The
    head's weight is the parameter ``lm_head`` [H, V], applied by
    ``keye_vl2_loss`` a chunk of tokens at a time."""

    cfg: KeyeVL2Config

    @nn.compact
    def __call__(self, input_ids,
                 position_ids=None) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        cfg = self.cfg
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(input_ids.shape[1], dtype=jnp.int32),
                (3,) + input_ids.shape,
            )
        hidden = embed_tokens(self, input_ids)
        rope = (
            *mrope_tables(position_ids, cfg.head_dim, cfg.rope_theta,
                          cfg.mrope_section),
            # the indexer's: all its 32 pairs by the temporal stream
            *mrope_tables(position_ids, cfg.index_head_dim, cfg.rope_theta,
                          (cfg.index_head_dim // 2, 0, 0)),
        )
        hidden, routing = scan_periods(
            functools.partial(remat_layer, DecoderLayer, cfg),
            ((),) * cfg.num_hidden_layers,
            min(SCAN_PERIOD, cfg.num_hidden_layers), hidden, rope,
        )
        return RMSNorm(cfg, name="norm")(hidden), routing


def selected_pairs(cfg: KeyeVL2Config, seq: int) -> int:
    """(query, key) pairs a layer attends over a row of ``seq``: sum_t
    min(t + 1, top-k) — 31,458,304 of the triangle's 134,225,920 at 16,384."""
    k = min(cfg.index_topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def keye_vl2_loss(model: KeyeVL2ForCausalLM, params,
                  batch: Dict[str, jnp.ndarray], grad_sinks=None,
                  compute_copies=None):
    """(L_LM + L_I, metrics) of one micro-batch of ``data/causal_lm.py``:
    ``input_ids`` and next-token ``labels`` [B, S] and, where the source
    built them, ``position_ids`` [3, B, S] and ``loss_weights`` [B, S] (else
    text positions, every weight 1). ``loss`` is the sum that is minimised,
    ``loss.lm`` and ``loss.index_kl`` its two terms; beside
    ``decoder.routed_metrics``: ``attn.select_kept_share`` (selected pairs
    over the triangle's, from the shapes), ``attn.select_tile_share`` (tiles
    of the kernels' triangle holding a selected pair, from the selection),
    ``attn.index_loss_tile_share`` ((query tile, key tile) pairs the
    indexer's loss walks over the square's, from the shapes: the causal
    sweep of ``ops/index_loss.py``'s kernels — 528 / 1,024 at 16,384 — or
    1.0, the block loop's whole rows), ``attn.index_peak`` [L],
    ``attn.select_tie_block_share`` [L] (``select_keys``' second result: the
    share of a layer's blocks of query rows that resolved ties by position)
    and ``data.image_token_share`` (labels that carry no loss). ``grad_sinks`` and ``compute_copies``:
    ``decoder.expert_lm_loss``'s."""
    cfg = model.cfg
    labels = batch["labels"]
    B, S = labels.shape
    hidden, routing = apply_with_grad_sinks(
        model, params, batch["input_ids"], grad_sinks, compute_copies,
        position_ids=batch.get("position_ids"),
    )
    ce = chunked_cross_entropy(
        hidden.reshape(1, -1, cfg.hidden_size),
        params["lm_head"].astype(cfg.dtype), labels.reshape(-1),
        cfg.loss_chunk_tokens,
    )
    weights = batch.get("loss_weights")
    if weights is None:
        weights = jnp.ones(labels.shape, jnp.float32)
    lm = jnp.sum(ce * weights.reshape(1, -1)) / jnp.maximum(
        jnp.sum(weights), 1.0
    )
    index_kl = jnp.mean(routing["index_kl"])
    loss = lm + index_kl
    block = cfg.attention_block_size
    tiles = B * visited_tiles(S, block, block, True)
    kept = selected_pairs(cfg, S) / (S * (S + 1) // 2)
    # the loss's kernels walk the kernels' triangle; the block loop the square
    loss_tiles = visited_tiles(S, block, block, cfg.attention_impl == "flash")
    loss_share = loss_tiles / visited_tiles(S, block, block, False)
    metrics = {
        "loss": loss, "loss.lm": lm, "loss.index_kl": index_kl,
        "data.image_token_share": 1.0 - jnp.mean(weights),
        **routed_metrics(routing, params, {
            "attn.select_kept_share": lambda _p, _r: jnp.float32(kept),
            "attn.select_tile_share": lambda _p, r: jnp.mean(
                r["select_tiles"]
            ) / tiles,
            "attn.index_peak": lambda _p, r: r["index_peak"],
            "attn.select_tie_block_share": lambda _p, r: r[
                "select_tie_blocks"
            ],
            "attn.index_loss_tile_share": lambda _p, _r: jnp.float32(
                loss_share
            ),
        }),
    }
    if cfg.emit_selection:
        metrics["attn.selection"] = routing["selection"]
    return loss, metrics


# decayed: every matrix; not the RMSNorm ``weight``s nor the indexer's
# LayerNorm
keye_vl2_weight_decay_mask = functools.partial(
    weight_decay_mask, exempt=("weight", "k_norm_weight", "k_norm_bias")
)


def keye_vl2_flops_per_row(cfg: KeyeVL2Config, seq: int) -> Dict[str, float]:
    """Forward matmul FLOPs of one row of ``seq`` tokens, by part — MODEL
    FLOPs: attention over the SELECTED pairs alone (QKᵀ and PV), the index
    scores over the whole triangle (every pair s <= t is scored: 2 x 16 x 64
    a pair); routed work for the HELD experts at the expected share of
    slots. The indexer's loss re-scores (its second index pass, the main
    scores of the selected pairs again for pbar): not counted."""
    h, d = cfg.hidden_size, cfg.head_dim
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    j, di = cfg.index_n_heads, cfg.index_head_dim
    layers = cfg.num_hidden_layers
    return {
        "projections": layers * seq * (
            2 * h * (heads + 2 * kv) * d + 2 * heads * d * h
        ),
        "indexer_projections": layers * seq * 2 * h * (j * di + di + j),
        "attention": layers * 2 * 2 * heads * d * selected_pairs(cfg, seq),
        "index_scores": layers * 2 * j * di * (seq * (seq + 1) // 2),
        "router": layers * seq * 2 * h * cfg.num_experts,
        "routed": layers * seq * (
            2 * 3 * h * cfg.moe_intermediate_size * cfg.num_experts_per_tok
            * cfg.held_experts[1] / cfg.num_experts
        ),
        "head": seq * 2 * h * cfg.vocab_size,
    }


def keye_vl2_train_tflops_per_sample(cfg: KeyeVL2Config, seq: int) -> float:
    """Analytic MODEL TFLOPs of one forward + backward row of ``seq`` tokens
    (matmuls only, backward = 2x forward, remat's replays and the indexer's
    loss pass not counted)."""
    return 3.0 * sum(keye_vl2_flops_per_row(cfg, seq).values()) / 1e12
