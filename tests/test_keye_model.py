"""Keye-VL-2.0's language model (models/keye_vl2.py) against the plain
reference (benchmark/reference/keye_vl2.py) at tiny sizes, float32, seeded
weights away from the initialiser: L_LM, L_I, every gradient leaf, router
scores and choices and the SELECTION, on text rows and on rows with image
spans; the two gradient paths are disjoint; at S <= top-k the layer is the
same layer under ``causal`` bit for bit; M-RoPE against plain RoPE and a
hand-written table; the weights zero the image labels; a bf16 reference
fails; THE SHARE TEST; the cut's parameter count."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import keye_vl2 as reference
from dedloc_tpu.data.causal_lm import synthetic_causal_lm_batches
from dedloc_tpu.models import decoder, keye_vl2
from dedloc_tpu.models.decoder import (
    GroupedQueryAttention,
    RoutedGLU,
    Visibility,
    apply_rope,
    mrope_tables,
    rope_tables,
)
from dedloc_tpu.models.keye_vl2 import (
    KeyeVL2Config,
    KeyeVL2ForCausalLM,
    keye_vl2_flops_per_row,
    keye_vl2_loss,
    keye_vl2_train_tflops_per_sample,
    select_keys,
    selected_pairs,
)
from dedloc_tpu.roles.common import KEYE_VL2, drop_collator_keys

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, LEAF_TOL = 1e-5, 2e-4


@pytest.fixture(autouse=True)
def blocks_of_query_rows(monkeypatch):
    """Both passes over blocks of query rows take several steps at the
    tests' 32 and 64 positions."""
    monkeypatch.setattr(keye_vl2, "INDEX_BLOCK_ROWS", 16)
    monkeypatch.setattr(keye_vl2, "INDEX_LOSS_BLOCK_ROWS", 8)


def _rows(share):
    """The family's rows, with image spans that fit a row of 32."""
    def batch(cfg, seq):
        return drop_collator_keys(next(synthetic_causal_lm_batches(
            cfg.vocab_size, 2, seq, 3, image_token_share=share,
            image_grids=((2, 2), (2, 3)), positions=True,
        )))

    return batch


def _reference_kwargs(cfg, **changes):
    return dict(
        num_heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads,
        eps=cfg.rms_norm_eps, theta=cfg.rope_theta,
        sections=cfg.mrope_section, index_heads=cfg.index_n_heads,
        index_top_k=cfg.index_topk, top_k=cfg.num_experts_per_tok,
        held=cfg.held_experts, **changes,
    )


def _term(term):
    """The loss whose gradient is taken: one term of the model's metrics."""
    def loss(model, params, batch):
        _total, metrics = keye_vl2_loss(model, params, batch)
        return metrics[term], metrics

    return loss


# a quarter of every row image spans; and text rows alone
KEYE = cases.Family(
    tiny=KeyeVL2Config.tiny, module=KeyeVL2ForCausalLM, loss=_term("loss"),
    reference=reference, reference_kwargs=_reference_kwargs, seq=32,
    batch=_rows(0.25), loss_tol=LOSS_TOL, leaf_tol=LEAF_TOL,
)
ROWS = {0.25: KEYE, 0.0: dataclasses.replace(KEYE, batch=_rows(0.0))}


@pytest.mark.parametrize(
    "seq,share,overrides",
    [(32, 0.0, dict()), (32, 0.25, dict()),
     (32, 0.25, dict(expert_shard=(1, 4))),
     (64, 0.25, dict(index_topk=16))],
    # a depth of a period and a tail layer: tests/test_keye_role.py trains
    # one (five layers' model AND reference gradients compile for 100 s)
    ids=["text_rows", "image_spans", "share_1_of_4", "top_16_of_64"],
)
def test_model_matches_reference(seq, share, overrides):
    overrides = dict(overrides, emit_selection=True)
    cfg, _model, _params, batch = cases.case(ROWS[share], seq, **overrides)
    (loss, metrics), grads = cases.own(ROWS[share], seq, **overrides)
    (ref_loss, ref), ref_grads = cases.reference_own(
        ROWS[share], seq, **overrides
    )
    # float32 on both sides: the choices and the selection agree exactly
    np.testing.assert_array_equal(metrics["moe.choice"], ref["choice"])
    np.testing.assert_array_equal(
        np.asarray(metrics["attn.selection"]) != 0, ref["selection"]
    )
    np.testing.assert_allclose(metrics["moe.scores"], ref["scores"], atol=1e-5)
    assert abs(float(metrics["loss.lm"]) - float(ref["lm"])) <= (
        LOSS_TOL * float(ref["lm"])
    )
    assert abs(float(metrics["loss.index_kl"]) - float(ref["index_kl"])) <= (
        1e-4 * float(ref["index_kl"])
    )
    assert float(loss) == pytest.approx(float(ref_loss), rel=LOSS_TOL)
    assert cases.worst_leaf(grads, ref_grads) <= LEAF_TOL
    np.testing.assert_allclose(
        metrics["attn.index_peak"], ref["index_peak"], rtol=1e-4
    )
    assert float(metrics["moe.dropped_slots"]) == 0.0
    weights = np.asarray(batch["loss_weights"])
    assert float(metrics["data.image_token_share"]) == pytest.approx(
        1.0 - weights.mean()
    )
    assert (share == 0.0) == bool((weights == 1).all())
    assert float(metrics["attn.select_kept_share"]) == pytest.approx(
        selected_pairs(cfg, seq) / (seq * (seq + 1) / 2)
    )
    # every query keeps min(t + 1, top-k) keys, none after it
    kept = np.asarray(metrics["attn.selection"]).sum(-1)
    np.testing.assert_array_equal(
        kept, np.broadcast_to(
            np.minimum(np.arange(seq) + 1, cfg.index_topk), kept.shape
        ),
    )
    assert not np.triu(np.asarray(metrics["attn.selection"]), 1).any()


def test_the_two_gradient_paths_are_disjoint():
    """dL_I reaches the indexer's leaves alone, dL_LM every other leaf
    alone: exact zeros, not small numbers."""
    _cfg, model, params, batch = cases.case(KEYE)
    (_l, _m), of_kl = cases.model_grads(
        _term("loss.index_kl"), model, params, batch
    )
    (_l, _m), of_lm = cases.model_grads(_term("loss.lm"), model, params, batch)

    def norms(tree, indexer):
        return [
            float(jnp.max(jnp.abs(leaf)))
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
            if any(getattr(k, "key", None) == "indexer" for k in path)
            == indexer
        ]

    assert all(n == 0.0 for n in norms(of_kl, indexer=False))
    assert all(n == 0.0 for n in norms(of_lm, indexer=True))
    assert all(n > 0.0 for n in norms(of_kl, indexer=True))
    assert sum(n > 0.0 for n in norms(of_lm, indexer=False)) >= 10


def test_reference_given_the_programs_choices_and_selection():
    """Given the program's router choices and selection the reference
    reproduces its own result (the chip check gives it both), and a
    DIFFERENT selection moves it."""
    cfg, _model, params, batch = cases.case(KEYE)
    (loss, own), _ = cases.reference_own(KEYE)
    (again, _), _ = cases.reference_grads(
        reference, _reference_kwargs(cfg, selections=own["selection"]),
        params, batch, choices=own["choice"],
    )
    assert float(again) == pytest.approx(float(loss), rel=1e-6)
    causal = jnp.broadcast_to(
        jnp.tril(jnp.ones((32, 32), bool)), own["selection"].shape
    )
    (other, out), _ = cases.reference_grads(
        reference, _reference_kwargs(cfg, selections=causal), params, batch,
        choices=own["choice"],
    )
    assert abs(float(other) - float(loss)) > 1e-3
    # ... and what it would have selected itself is still reported
    np.testing.assert_array_equal(out["selection"][0], own["selection"][0])


def test_a_bf16_reference_fails():
    """The reading below the cell's precision is far off at least one of
    the tolerances the float32 comparison meets."""
    cfg, _model, params, batch = cases.case(KEYE)
    (_loss, metrics), grads = cases.own(KEYE)
    (_r, ref), ref_grads = cases.reference_grads(
        reference, _reference_kwargs(cfg, dtype=jnp.bfloat16), params, batch,
        choices=metrics["moe.choice"],
    )
    ref_grads = jax.tree.map(lambda x: x.astype(jnp.float32), ref_grads)
    assert (
        cases.worst_leaf(grads, ref_grads) > 10 * LEAF_TOL
        or abs(float(metrics["loss.lm"]) - float(ref["lm"]))
        > 10 * LOSS_TOL * float(metrics["loss.lm"])
    )


class _CausalLayer(decoder.nn.Module):
    """The same attention module under ``causal``."""

    cfg: KeyeVL2Config
    visible: Visibility

    @decoder.nn.compact
    def __call__(self, x, rope, selection=None):
        return GroupedQueryAttention(
            self.cfg, self.visible, qk_norms=("q_norm", "k_norm"),
            name="self_attn",
        )(x, rope, selection=selection)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_at_most_top_k_keys_is_the_causal_layer(impl):
    """Where no query has more than top-k keys before it the selection
    keeps everything, and the selected layer IS the causal one: the same
    bits, output and gradients."""
    sizes = dict(
        hidden_size=64, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, mrope_section=(16, 24, 24), attention_block_size=64,
    ) if impl == "flash" else {}
    seq = 128 if impl == "flash" else 32
    cfg = KeyeVL2Config.tiny(
        dtype=jnp.float32, attention_impl=impl, index_topk=seq, **sizes
    )
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, cfg.hidden_size))
    positions = jnp.broadcast_to(jnp.arange(seq), (3, 2, seq))
    rope = mrope_tables(positions, cfg.head_dim, cfg.rope_theta,
                        cfg.mrope_section)
    q_index = jax.random.normal(jax.random.PRNGKey(1), (2, seq, 2, 8))
    k_index = jax.random.normal(jax.random.PRNGKey(2), (2, seq, 8))
    w = jax.random.normal(jax.random.PRNGKey(3), (2, seq, 2))
    # behind "flash" the kernel (``ops/index_select.py``: every block
    # keeps every valid key and computes nothing), behind "dense" the loop
    selection, tie_blocks = select_keys(cfg, q_index, k_index, w)
    np.testing.assert_array_equal(
        selection, np.broadcast_to(np.tril(np.ones((seq, seq))), (2, seq, seq))
    )
    assert float(tie_blocks) == 0.0
    selected = _CausalLayer(cfg, Visibility(selected=True))
    causal = _CausalLayer(cfg, Visibility(causal=True))
    params = causal.init(jax.random.PRNGKey(4), x, rope)["params"]

    def through(layer, **beside):
        def loss(p, x):
            out = layer.apply({"params": p}, x, rope, **beside)
            out = out[0] if beside else out
            return jnp.sum(out * jnp.cos(out)), out

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            params, x
        )

    (_, got), got_grads = through(selected, selection=selection)
    (_, want), want_grads = through(causal)
    np.testing.assert_array_equal(got, want)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(a, b)


def test_the_selection_is_the_kernels_behind_flash_and_the_loops_behind_dense():
    """``attention_impl`` decides the selection's path as it decides the
    loss's: ONE ``index_select`` call a layer behind the flash kernels, the
    block loop (``index_scores`` + ``top_k_mask``, no kernel) behind
    ``"dense"`` — and at the tiny model, on the same parameters and rows,
    the two paths select the same keys (float32 scores summed in another
    order: a key within an ulp of a row's threshold may fall the other way)
    and give the same losses."""
    sizes = dict(head_dim=128, mrope_section=(16, 24, 24),
                 attention_block_size=16, emit_selection=True)
    out = {}
    for impl in ("dense", "flash"):
        _cfg, model, params, batch = cases.case(
            KEYE, 64, attention_impl=impl, **sizes
        )
        fn = jax.jit(lambda p: keye_vl2_loss(model, p, batch))
        text = str(jax.make_jaxpr(lambda p: keye_vl2_loss(model, p, batch))(
            params
        ))
        assert ("name=index_select" in text) == (impl == "flash")
        out[impl] = fn(params)
    (dense_loss, dense), (flash_loss, flash) = out["dense"], out["flash"]
    differ = np.asarray(dense["attn.selection"] != flash["attn.selection"])
    assert differ.mean() <= 1e-4
    for term in ("loss.lm", "loss.index_kl"):
        assert float(flash[term]) == pytest.approx(float(dense[term]),
                                                   rel=1e-4)
    assert float(flash_loss) == pytest.approx(float(dense_loss), rel=1e-4)
    np.testing.assert_array_equal(flash["attn.select_tie_block_share"],
                                  dense["attn.select_tie_block_share"])
    assert flash["attn.select_tie_block_share"].shape == (2,)


def test_mrope_is_plain_rope_on_text_and_the_table_elsewhere():
    seq, dim, theta, sections = 24, 16, 1e7, (2, 3, 3)
    text = jnp.broadcast_to(jnp.arange(seq), (3, 2, seq))
    for got, want in zip(mrope_tables(text, dim, theta, sections),
                         rope_tables(seq, dim, theta)):
        np.testing.assert_array_equal(got, np.broadcast_to(want, got.shape))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, seq, 3, dim))
    np.testing.assert_array_equal(
        apply_rope(x, *mrope_tables(text, dim, theta, sections)),
        apply_rope(x, *rope_tables(seq, dim, theta)),
    )
    # three (t, h, w) positions, by hand: pair i of 8 turns by f_i x t for
    # i < 2, f_i x h for 2 <= i < 5, f_i x w for i >= 5, both halves alike
    where = np.array([[5, 5, 5], [7, 9, 8], [7, 10, 12]], np.int32)
    cos, sin = mrope_tables(
        jnp.asarray(where.T[:, None, :]), dim, theta, sections
    )
    freq = np.float32(theta) ** -(np.arange(0, dim, 2, dtype=np.float32) / dim)
    for n, (t, h, w) in enumerate(where):
        angle = np.array(
            [t, t, h, h, h, w, w, w], np.float32
        ) * freq.astype(np.float32)
        angle = np.concatenate([angle, angle])
        np.testing.assert_allclose(cos[0, n], np.cos(angle), rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(sin[0, n], np.sin(angle), rtol=1e-6,
                                   atol=1e-6)
    with pytest.raises(ValueError, match="sections"):
        mrope_tables(text, dim, theta, (2, 3, 4))


def test_loss_weights_zero_the_image_labels():
    """A label inside an image span carries no loss: changing it changes
    nothing; a text label's change does."""
    cfg, model, params, batch = cases.case(KEYE)
    weights = np.asarray(batch["loss_weights"])
    assert (weights == 0).any() and (weights == 1).any()
    loss = jax.jit(lambda b: keye_vl2_loss(model, params, b)[1]["loss.lm"])
    base = float(loss(batch))
    image = np.argwhere(weights == 0)[0]
    text = np.argwhere(weights == 1)[0]

    def with_label(at):
        labels = np.asarray(batch["labels"]).copy()
        labels[tuple(at)] = (labels[tuple(at)] + 7) % cfg.vocab_size
        return dict(batch, labels=jnp.asarray(labels))

    assert float(loss(with_label(image))) == base
    assert float(loss(with_label(text))) != base
    # and the mean is over the weighted labels alone
    _, metrics = keye_vl2_loss(model, params, batch)
    hidden, _ = model.apply(
        {"params": params}, batch["input_ids"],
        position_ids=batch["position_ids"],
    )
    logp = jax.nn.log_softmax(hidden @ params["lm_head"], axis=-1)
    ce = -jnp.take_along_axis(logp, batch["labels"][..., None], -1)[..., 0]
    assert float(metrics["loss.lm"]) == pytest.approx(
        float(jnp.sum(ce * weights) / weights.sum()), rel=1e-5
    )


def test_the_shares_add_up_to_the_uncut_layer():
    """One layer's FFN: the routed parts that the 4 shares compute (each
    told its share, holding 2 of the 8 experts) are the uncut reference's
    layer output — no shared expert, so nothing is computed alike on every
    chip but the router, whose choices agree; the mixer and the indexer are
    data-parallel: every chip computes them whole."""
    cfg, _model, params, _batch = cases.case(KEYE)
    layer = jax.tree.map(lambda x: x[0], params["layers"]["layer_1"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.hidden_size))
    with jax.default_matmul_precision("highest"):
        whole, _logits, choice = reference.moe_ffn(
            x.reshape(-1, cfg.hidden_size), layer, held=(0, 8),
            top_k=cfg.num_experts_per_tok,
        )
    cases.check_the_routed_shares_add_up(
        KEYE, layer, lambda share: RoutedGLU(share, activation="silu"),
        (x, x), dict(routed=whole, choice=choice), reference.EXPERTS,
        shares=4,
    )


def test_masks_flops_and_the_cut():
    params = cases.case(KEYE).params
    decay = KEYE_VL2.weight_decay_mask(params)
    assert decay["norm"]["weight"] is False and decay["lm_head"] is True
    layer = decay["layers"]["layer_0"]
    assert layer["self_attn"]["q_norm"]["weight"] is False
    assert layer["indexer"]["k_norm_weight"] is False
    assert layer["indexer"]["k_norm_bias"] is False
    assert layer["indexer"]["wq"]["kernel"] is True
    sinks = KEYE_VL2.grad_sink_mask(params)["layers"]["layer_1"]["mlp"]
    assert sinks["experts_down"] is True and sinks["router"] is False
    assert KEYE_VL2.sign_step_mask is None
    # the cell's cut: 314.4 M parameters (ISSUE 51's count, by part)
    cut = KeyeVL2Config(
        num_hidden_layers=4, vocab_size=18992, expert_shard=(0, 16)
    )
    shapes = jax.eval_shape(
        lambda: KeyeVL2ForCausalLM(cut).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32)
        )["params"]
    )

    def size(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    one = shapes["layers"]["layer_0"]
    assert size(one["self_attn"]) == 18_874_368 + 256
    assert size(one["indexer"]) == 2_261_120
    assert size(one["mlp"]) == 262_144 + 37_748_736
    assert size(one) == 59_150_720
    assert size(shapes) == 314_396_160
    assert one["mlp"]["experts_gate"].shape == (1, 8, 2048, 768)
    assert one["indexer"]["wq"]["kernel"].shape == (1, 2048, 1024)
    # the whole published model: 30.64 B
    whole = 48 * (59_150_720 + 120 * 3 * 2048 * 768) + 2 * 151936 * 2048 + 2048
    assert whole == 48 * 625_381_760 + 622_331_904
    # FLOPs: attention at its SELECTED pairs, index scores over the triangle
    assert selected_pairs(cut, 16384) == 31_458_304
    assert selected_pairs(cut, 1024) == 1024 * 1025 // 2
    part = keye_vl2_flops_per_row(cut, 16384)
    assert part["attention"] == 4 * 2 * 2 * 32 * 128 * 31_458_304
    assert part["index_scores"] == 4 * 2 * 16 * 64 * 134_225_920
    assert part["head"] == 16384 * 2 * 2048 * 18992
    assert part["routed"] == 4 * 16384 * 2 * 3 * 2048 * 768 * 8 * 8 / 128
    total = keye_vl2_train_tflops_per_sample(cut, 16384)
    assert total == pytest.approx(3 * sum(part.values()) / 1e12)
    assert 3 * part["attention"] / 1e12 / total < 0.35
