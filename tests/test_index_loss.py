"""The indexer's loss kernels (``ops/index_loss.py``) in interpreter mode
against the XLA block loop they replace behind the flash kernels
(``models/keye_vl2.index_loss`` under ``attention_impl="dense"``): the
loss, the peak gauge and the gradients of ``q_index``, ``k_index`` and
``weights`` over selections of every shape the causal sweep meets — in
float32, so what is compared is the arithmetic, not a rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.models import keye_vl2
from dedloc_tpu.ops.flash_attention import selection_tile_flags
from dedloc_tpu.ops import index_loss
from dedloc_tpu.ops.index_loss import index_loss_rows

SEQ, TILE, TOPK = 64, 16, 8


@pytest.fixture(autouse=True)
def blocks_of_query_rows(monkeypatch):
    """The oracle's loop takes several blocks at 64 positions."""
    monkeypatch.setattr(keye_vl2, "INDEX_BLOCK_ROWS", 32)
    monkeypatch.setattr(keye_vl2, "INDEX_LOSS_BLOCK_ROWS", 16)


def _cfg(impl):
    """4 query heads over 2 kv heads of 16, 2 index heads of 8."""
    return keye_vl2.KeyeVL2Config.tiny(
        index_topk=TOPK, attention_impl=impl, attention_block_size=TILE,
        dtype=jnp.float32,
    )


def _operands(batch, seed=0):
    cfg = _cfg("dense")
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shapes = (
        (batch, SEQ, cfg.index_n_heads, cfg.index_head_dim),
        (batch, SEQ, cfg.index_head_dim),
        (batch, SEQ, cfg.index_n_heads),
        (batch, SEQ, cfg.num_attention_heads, cfg.head_dim),
        (batch, SEQ, cfg.num_key_value_heads, cfg.head_dim),
    )
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _selection(kind, batch, q_index, k_index, weights):
    """int8 [B, S, S], nothing marked above the diagonal."""
    t, s = jnp.arange(SEQ)[:, None], jnp.arange(SEQ)[None, :]
    causal = jnp.broadcast_to(s <= t, (batch, SEQ, SEQ))
    if kind == "causal":  # S <= top-k: every key a query may see
        return causal.astype(jnp.int8)
    if kind == "tie":  # the layer's own top-8, keys in equal PAIRS: the
        # 8th and 9th largest of a row tie, the lower key is kept
        k_index = k_index.at[:, 1::2].set(k_index[:, 0::2])
        return keye_vl2.select_keys(
            _cfg("dense"), q_index, k_index, weights
        )[0]
    if kind == "window":  # the last 16 keys: tiles off the band hold nothing
        return (causal & (t - s < TILE)).astype(jnp.int8)
    assert kind == "one_key"  # ... and one row that keeps its own key alone
    return (causal & ((t != 37) | (s == 37))).astype(jnp.int8)


def _lse(q, k, selection):
    """The main attention's log-sum-exp over the selected keys, [B, H, S]."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    logits = jnp.einsum(
        "bqcgd,bkcd->bcgqk", q.reshape(B, S, KV, H // KV, D), k,
    ) / jnp.sqrt(jnp.float32(D))
    logits = jnp.where((selection != 0)[:, None, None], logits, -1e30)
    return jax.nn.logsumexp(logits, axis=-1).reshape(B, H, S)


def _loss_and_grads(impl, operands, selection, lse, scale=1.0):
    q_index, k_index, weights, q, k = operands

    def loss(q_index, k_index, weights, q, k, lse):
        value, peak = keye_vl2.index_loss(
            _cfg(impl), q_index, k_index, weights, selection, q, k, lse
        )
        return scale * value, peak

    return jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True
    ))(q_index, k_index, weights, q, k, lse)


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("kind", ["causal", "tie", "window", "one_key"])
def test_the_kernels_agree_with_the_block_loop(kind, batch):
    """Value, peak gauge and the three gradients, over: everything causal, a
    top-k with a tie, a window whose off-band tiles are skipped by their
    flags, a row with one selected key; one and two batch rows."""
    operands = _operands(batch)
    selection = _selection(kind, batch, *operands[:3])
    assert not np.any(np.triu(np.asarray(selection), 1))
    flags = np.asarray(selection_tile_flags(selection, TILE, TILE))
    causal_tiles = np.tril(np.ones(flags.shape[1:], bool))
    assert (flags[:, causal_tiles].min() == 0) == (kind == "window")
    lse = _lse(operands[3], operands[4], selection)
    (value, peak), grads = _loss_and_grads("flash", operands, selection, lse)
    (ref_value, ref_peak), ref_grads = _loss_and_grads(
        "dense", operands, selection, lse
    )
    np.testing.assert_allclose(value, ref_value, rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(peak, ref_peak, rtol=2e-6)
    for got, ref, name in zip(grads, ref_grads,
                              ("q_index", "k_index", "weights")):
        assert np.isfinite(np.asarray(got)).all(), name
        np.testing.assert_allclose(
            got, ref, rtol=1e-4, atol=1e-6 * float(jnp.abs(ref).max()),
            err_msg=name,
        )


def test_the_cotangent_scales_the_three_gradients_and_reaches_nothing_else():
    """3 x the loss: 3 x the gradient of ``q_index``, ``k_index`` and
    ``weights``; q, k and ``lse`` are read detached."""
    operands = _operands(1, seed=1)
    selection = _selection("tie", 1, *operands[:3])
    lse = _lse(operands[3], operands[4], selection)
    (_, _), once = _loss_and_grads("flash", operands, selection, lse)
    (_, _), thrice = _loss_and_grads("flash", operands, selection, lse, 3.0)
    for one, three in zip(once[:3], thrice[:3]):
        assert float(jnp.abs(one).max()) > 0
        np.testing.assert_allclose(three, 3.0 * one, rtol=1e-5, atol=1e-9)
    for detached in thrice[3:]:
        assert not np.any(np.asarray(detached))


def test_a_selected_pair_whose_target_underflowed_adds_nothing():
    """Rows whose main probabilities underflow to 0 at every selected key
    (a log-sum-exp far above their scores): pbar == 0 there, KL_t is 0 x
    nothing = 0, not 0 x log 0 — in the loss, in the rows' own output and in
    every gradient."""
    operands = _operands(2, seed=2)
    selection = _selection("tie", 2, *operands[:3])
    lse = _lse(operands[3], operands[4], selection)
    lse = lse.at[:, :, 16:32].add(300.0)  # one query tile of both rows
    kl, _peak = index_loss_rows(
        *operands[:3], selection, operands[3], operands[4], lse,
        block_q=TILE, block_k=TILE,
    )
    assert not np.any(np.asarray(kl[:, 16:32]))
    assert float(kl[:, 1:16].min()) > 0  # query 0 sees one key: KL 0
    (value, _), grads = _loss_and_grads("flash", operands, selection, lse)
    (ref_value, _), ref_grads = _loss_and_grads(
        "dense", operands, selection, lse
    )
    np.testing.assert_allclose(value, ref_value, rtol=2e-6)
    for got, ref in zip(grads[:3], ref_grads[:3]):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(
            got, ref, rtol=1e-4, atol=1e-6 * float(jnp.abs(ref).max())
        )


def test_a_tile_cut_into_strips_sums_to_the_whole_tile(monkeypatch):
    """A tile is worked out a strip of (rows, keys) at a time — the online
    log-sum-exp and the gradients' sums run over the strips as over the
    tiles: 2 x 2 strips of 8 x 8 a tile give what one strip of 16 x 16
    gives."""
    operands = _operands(2, seed=3)
    selection = _selection("tie", 2, *operands[:3])
    lse = _lse(operands[3], operands[4], selection)
    whole = _loss_and_grads("flash", operands, selection, lse)
    monkeypatch.setattr(index_loss, "STRIP_ROWS", 8)
    monkeypatch.setattr(index_loss, "STRIP_KEYS", 8)
    (value, peak), grads = _loss_and_grads("flash", operands, selection, lse)
    np.testing.assert_allclose(value, whole[0][0], rtol=2e-6)
    np.testing.assert_allclose(peak, whole[0][1], rtol=2e-6)
    for got, ref in zip(grads[:3], whole[1][:3]):
        np.testing.assert_allclose(
            got, ref, rtol=1e-4, atol=1e-6 * float(jnp.abs(ref).max())
        )
