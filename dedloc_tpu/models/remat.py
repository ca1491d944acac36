"""The layer remat policies by name: one table for every model of the tree."""
from __future__ import annotations

import flax.linen as nn
import jax


# Pallas kernels whose output is NOT kept: one cheap pass away from values
# that are. ``head_gate_fwd`` (ops/head_gate.py) makes the gated context from
# the flash kernel's ``out`` and the gate: keeping it is 134 MB a layer at
# 8,192 x 64 x 128 for a 0.37 ms pass, and on the v5e the step takes the same
# time either way (remat's reduce-precision pass over a kept value costs what
# the replayed call does: PERF.md section 5, PR 48). ``index_select``
# (ops/index_select.py) writes a selected attention's int8 [B, S, S]
# selection, 268 MB a layer at S = 16,384: it is kept BY NAME
# ("attn_selection") from "kernel_operands" up and replayed below, as it was
# when XLA wrote it
REPLAYED_KERNELS = ("head_gate_fwd", "index_select")


def _pallas_outputs_saveable(prim, *_, **params) -> bool:
    """Remat-policy predicate: save the outputs of Pallas kernels (here the
    flash-attention out/lse residuals) instead of re-running them backward,
    but those of ``REPLAYED_KERNELS``."""
    return getattr(prim, "name", "") == "pallas_call" and (
        params.get("name") not in REPLAYED_KERNELS
    )


def remat_policy_object(name: str):
    """Resolve a remat-policy NAME to the jax.checkpoint policy object — the
    one table both the scanned encoder and the pipeline-parallel stage wrap
    their layer body with (so --training.remat_policy means the same thing
    on every parallelism path). Raises on unknown names."""
    table = {
        "nothing": jax.checkpoint_policies.nothing_saveable,
        # ONLY the Pallas kernels' outputs (flash: out in the model's own
        # layout + lse): the custom-VJP backward reads them as they were,
        # so the replay re-runs no kernel; every matmul output is still
        # recomputed. For a model whose STATE fills the chip (the looped
        # decoder, models/ouro.py: 17 MB a layer iteration)
        "kernel_outputs": _pallas_outputs_saveable,
        # what a layer's Pallas BACKWARD kernels read as well as what the
        # forward ones wrote: the flash operands q / k / v ("flash_qkv":
        # after the q / k norm and RoPE, [B, S, H·D], the layout kernels
        # and stash share) and the short convolution's B | C | u
        # ("short_conv_bcu": ``in_proj``'s output as it wrote it) beside
        # out + lse / y. The replay of a layer loses RoPE, the relayouts
        # around it and every projection whose output the kernel reads AS
        # IT IS — q / k / v where nothing but RoPE (a linear map) lies
        # between, every v, ``in_proj``; it still runs the input norm (the
        # projections' weight gradients read its output), the
        # out-projection, behind a per-head q / k RMSNorm (SDAR, LFM2) the
        # q and k matmuls (the norm's backward reads the norm's INPUT), the
        # post-attention norm, the router and the routed loop. Over
        # "kernel_outputs", a layer a micro-batch: B·S·(H + 2·H_kv)·D·2
        # bytes for an attention (151 MB at SmallThinker's 16,384 x
        # (28 + 2·4) x 128), B·S·3·hidden·2 for a convolution (50 MB at
        # LFM2's 4,096 x 3 x 2,048). A SELECTED attention's kernels also
        # read the selection ("attn_selection": int8 [B, S, S], the
        # visibility a learned indexer chose, models/keye_vl2.py — 268 MB a
        # layer at S = 16,384): KEPT here and above, so the dq / dkv
        # kernels read the very array the forward kernel read and the
        # replay runs neither the index-score pass nor the top-k for their
        # sake; under the rungs below it is REPLAYED — the same ops on the
        # replayed indexer's values, in the same program: bit-equal
        # (tests/test_remat_operands.py holds every rung to that). A KDA
        # mixer's kernels (ops/kda.py, models/kimi_linear.py) read q / k / v
        # after convolution, SiLU and the L2 norm, the float32 log-decays g
        # and the write strengths ("kda_operands": B·S·heads·(3·2 + 4)·128
        # bytes + beta, 84 MB a layer at 8,192 x 8 heads) beside their
        # outputs — o and the float32 state entering every chunk of 64
        # tokens (67 MB a layer there): kept here and above, so the backward
        # kernel reads what the forward one read and the replay runs the
        # prelude for the prelude's own backward alone. A Mamba-2 mixer's
        # (ops/ssd.py, models/nemotron_h.py) read x | B | C after the
        # convolution and SiLU, the float32 time steps and log-decays
        # ("ssd_operands": B·S·(heads·64 + 2·groups·128)·2 bytes + 2 float32
        # a token-head, 52 MB a layer at 8,192 x 32 heads in 4 groups)
        # beside their outputs — y and the float32 state entering every
        # chunk of 128 tokens (34 + 67 MB a layer there). The rung
        # under "whole_mixer"
        "kernel_operands": (
            jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.save_only_these_names(
                    "flash_qkv", "short_conv_bcu", "attn_selection",
                    "kda_operands", "ssd_operands",
                ),
                _pallas_outputs_saveable,
            )
        ),
        # "kernel_operands" + the two values of a mixer that its matmuls
        # write and something other than a kernel reads: the stream after
        # the mixer ("mixer_residual": hidden + Mixer(norm(hidden)), which
        # the post-attention norm, the router and the experts' backward
        # read) and the input of a per-head q / k RMSNorm ("qk_norm_input":
        # ``q_proj``'s and ``k_proj``'s outputs where the model has such a
        # norm). The replay of a layer then runs NO matmul of the mixer —
        # the out-projection and, in SDAR and LFM2's attention layer, q_proj
        # and k_proj leave it as v_proj did — and the backward's router
        # reads the sum the forward routed by. It still runs the input
        # norm, the q / k norm's multiply, the post-attention norm, the
        # router and the routed loop. Over "kernel_operands", a layer a
        # micro-batch: B·S·hidden·2 bytes, + B·S·(H + H_kv)·D·2 behind a
        # q / k norm (109 MB at SDAR's 8,192 x (2,048 + 4,096 + 512), 84
        # at SmallThinker's 16,384 x 2,560, 17 a convolution layer of
        # LFM2's). For the decoders whose state leaves that room
        # (models/smallthinker.py, sdar_moe.py, lfm2_moe.py). A per-head
        # output gate (``decoder.head_gate``, models/laguna.py) multiplies
        # the flash kernel's OUTPUT before the out-projection, behind the
        # flash kernels in a kernel of its own (``head_gate_fwd``,
        # ops/head_gate.py): every rung from "kernel_outputs" up keeps
        # ``out`` itself, and the GATED context (the out-projection's
        # operand, a second [B, S, H·D]) is kept by none although a Pallas
        # kernel writes it (``REPLAYED_KERNELS``) — the backward's replay
        # makes it again from ``out`` with one more call of that kernel
        # (0.37 ms a layer at Laguna's cell). The gate's logits
        # ("attn_gate": [B, S, H], 1 MB at 8,192 x 64 against the context's
        # 134 MB) are kept HERE, so this rung's replay still runs no matmul
        # of the mixer; under the rungs below the replay runs ``g_proj``
        # (2·B·S·hidden·H FLOPs, a 1/128th of q_proj's) behind the input
        # norm it runs anyway. A Mamba-2 mixer keeps, IN PLACE of the
        # kernels' operands, the fused in-projection's output
        # ("ssd_in_proj": z | x | B | C | dt as the matmul wrote it, 84 MB
        # a layer at 8,192 x 5,152): the convolution's and the gate's
        # backward read it, the operands are one element-wise pass behind
        # it, and the replay then runs the prelude — convolution, SiLU,
        # softplus, the gate, the group norm — and no matmul and no kernel
        # (the scan's y and chunk states are kept as every kernel's
        # outputs are). A layer that is a routed feed-forward ALONE
        # (models/nemotron_h.py) has no stream after a mixer to keep: it
        # names the normed input its router reads ("routed_input",
        # B·S·hidden·2 bytes), so the replay routes by the very tensor the
        # forward routed by
        "whole_mixer": (
            jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.save_only_these_names(
                    "flash_qkv", "short_conv_bcu", "mixer_residual",
                    "qk_norm_input", "attn_gate", "attn_selection",
                    "kda_operands", "ssd_in_proj", "routed_input",
                ),
                _pallas_outputs_saveable,
            )
        ),
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": (
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        ),
        # dots_no_batch + flash-attention outputs (out, lse): the
        # custom-VJP backward then runs straight from saved residuals
        # instead of re-running the forward kernel during remat
        # (~30 MB/layer extra HBM at B=32, measured step win on v5e)
        "dots_no_batch_attn": (
            jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                _pallas_outputs_saveable,
            )
        ),
        # fused-LN recipe (pairs with cfg.fused_ln): save ONLY the
        # named matmul outputs ("flash_qkv": the q/k/v projections as
        # the dense layers write them, [B, S, H·D], which is the layout
        # the flash kernels read; "ffn_up") plus every Pallas kernel's
        # outputs — flash (out in that same layout, lse) and the
        # fused add+LN's (y, x̂, rstd). The backward then replays no
        # elementwise chain; dropping the two out-projection dot
        # saves pays for the x̂ residuals, so HBM is ~neutral vs
        # dots_no_batch_attn.
        "fused_ln": (
            jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.save_only_these_names(
                    "flash_qkv", "ffn_up"
                ),
                _pallas_outputs_saveable,
            )
        ),
        # fused_ln + the gelu output: the backward's one remaining
        # forward replay (gelu of the FFN up-projection) runs from a
        # saved residual instead — costs [B,S,intermediate] bf16 per
        # layer iteration of extra HBM (ffn_up stays saved: gelu's
        # VJP still needs its primal input)
        "fused_ln_gelu": (
            jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.save_only_these_names(
                    "flash_qkv", "ffn_up", "ffn_gelu"
                ),
                _pallas_outputs_saveable,
            )
        ),
    }
    if name not in table:
        raise ValueError(
            f"unknown remat_policy {name!r}; expected one of {sorted(table)}"
        )
    return table[name]


def remat_layer(layer_cls, cfg, *args, name: str) -> nn.Module:
    """``layer_cls(cfg, *args, name=name)`` under the policy
    ``cfg.remat_policy`` names: a decoder's layer always runs under one. A
    remat'd class a CALL: layers of one class lower to shared inner
    functions — another program text, if no other program."""
    return nn.remat(layer_cls, policy=remat_policy_object(cfg.remat_policy))(
        cfg, *args, name=name
    )
