"""``selected`` inside the flash kernels (a visibility that is an OPERAND:
query t sees the keys s <= t an int8 [B, S, S] selection marks), in
interpreter mode against a dense masked float32 attention: forward, the
log-sum-exp it hands out and all three gradients, at one head count and
grouped (a group of eight on one kv head, a group of two at D=64), under a
random selection, one with EMPTY tiles (skipped: no body runs), the causal
prefix (window-shaped), and the whole triangle — which is the causal call
bit for bit; the backward kernels read the forward's selection."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.ops.flash_attention import (
    flash_attention,
    selection_tile_flags,
)

fa = importlib.import_module("dedloc_tpu.ops.flash_attention")

S, BLOCK = 96, 32
SHAPES = [(4, 4, 128), (8, 1, 128), (4, 2, 64)]


def _selections(seq=S, batch=1, seed=0):
    rng = np.random.default_rng(seed)
    tri = np.tril(np.ones((seq, seq), bool))
    random = (rng.random((batch, seq, seq)) < 0.3) & tri
    random[:, np.arange(seq), np.arange(seq)] = True
    empty = random.copy()
    empty[:, 2 * BLOCK:, :BLOCK] = False  # the bottom-left tile: nothing
    i = np.arange(seq)
    prefix = np.broadcast_to(
        tri & (i[None, :] < 24) | np.eye(seq, dtype=bool), (batch, seq, seq)
    )
    return {"random": random, "empty_tiles": empty, "causal_prefix": prefix,
            "the_triangle": np.broadcast_to(tri, (batch, seq, seq))}


SELECTIONS = _selections()


def _dense(q, k, v, chosen):
    h, kv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(x, h // kv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision="highest") / np.sqrt(q.shape[-1])
    logits = jnp.where(chosen[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return (jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest"),
            jax.nn.logsumexp(logits, axis=-1))


def _operands(h, kv, d, seed=0, seq=S):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((1, seq, n, d)), jnp.float32)
        for n in (h, kv, kv)
    ) + (jnp.asarray(rng.standard_normal((1, seq, h, d)), jnp.float32),)


@pytest.mark.parametrize("name", SELECTIONS.keys())
@pytest.mark.parametrize(
    "h,kv,d", SHAPES, ids=[f"{h}_{kv}x{d}" for h, kv, d in SHAPES]
)
def test_selected_against_dense(h, kv, d, name):
    q, k, v, do = _operands(h, kv, d)
    chosen = jnp.asarray(SELECTIONS[name])
    selection = chosen.astype(jnp.int8)

    def flash(q, k, v):
        return flash_attention(q, k, v, selection=selection,
                               block_q=BLOCK, block_k=BLOCK)

    (out, lse), vjp = jax.vjp(flash, q, k, v)
    (want, want_lse), want_vjp = jax.vjp(
        lambda *x: _dense(*x, chosen), q, k, v
    )
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5, rtol=2e-5)
    # the log-sum-exp is handed out detached: its cotangent is dropped
    grads = vjp((do, jnp.ones_like(lse)))
    wants = want_vjp((do, jnp.zeros_like(want_lse)))
    for got, ref, which in zip(grads, wants, ("dq", "dk", "dv")):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4,
                                   err_msg=which)


def test_the_triangle_is_the_causal_call():
    """With every key before it selected a query sees what ``causal``
    shows it: the same bits, output and gradients, grouped."""
    q, k, v, do = _operands(8, 1, 128)
    selection = jnp.asarray(SELECTIONS["the_triangle"], jnp.int8)
    (out, _lse), vjp = jax.vjp(
        lambda *x: flash_attention(*x, selection=selection,
                                   block_q=BLOCK, block_k=BLOCK), q, k, v,
    )
    want, want_vjp = jax.vjp(
        lambda *x: flash_attention(*x, causal=True, block_q=BLOCK,
                                   block_k=BLOCK), q, k, v,
    )
    np.testing.assert_array_equal(out, want)
    for got, ref in zip(vjp((do, jnp.zeros((1, 8, S)))), want_vjp(do)):
        np.testing.assert_array_equal(got, ref)


def test_tile_flags_names_and_the_backwards_selection(monkeypatch):
    empty = jnp.asarray(SELECTIONS["empty_tiles"], jnp.int8)
    flags = np.asarray(selection_tile_flags(empty, BLOCK, BLOCK))[0]
    np.testing.assert_array_equal(flags, [[1, 0, 0], [1, 1, 0], [0, 1, 1]])
    # the kernels' names, and what each call was handed behind its other
    # operands: the backward kernels read the SAME two arrays the forward
    # read (residuals of the custom VJP: not a second top-k, not a replay)
    calls = []
    call = fa.pl.pallas_call

    def noting(kernel, **kw):
        run = call(kernel, **kw)

        def noted(*operands):
            calls.append((kw["name"], operands[-2:]))
            return run(*operands)

        return noted

    monkeypatch.setattr(fa.pl, "pallas_call", noting)
    q, k, v, do = _operands(8, 1, 128)
    _out, vjp = jax.vjp(
        lambda *x: flash_attention(*x, selection=empty,
                                   block_q=BLOCK, block_k=BLOCK), q, k, v,
    )
    vjp((do, jnp.zeros((1, 8, S))))
    assert [name for name, _ in calls] == [
        "flash_sel_fwd", "flash_sel_bwd_tiled"
    ]
    (selection, tile_flags) = calls[0][1]
    assert selection.dtype == jnp.int8 and tile_flags.shape == (9,)
    for _name, (again, flags_again) in calls[1:]:
        assert again is selection and flags_again is tile_flags
    # what a selection marks ABOVE the diagonal is never read
    above = np.asarray(empty).copy()
    above[:, :BLOCK, 2 * BLOCK:] = 1
    monkeypatch.undo()
    a = flash_attention(q, k, v, selection=empty,
                        block_q=BLOCK, block_k=BLOCK)[0]
    b = flash_attention(q, k, v, selection=jnp.asarray(above),
                        block_q=BLOCK, block_k=BLOCK)[0]
    np.testing.assert_array_equal(a, b)


def test_what_a_selected_call_refuses():
    q, k, v, _do = _operands(4, 4, 128)
    selection = jnp.asarray(SELECTIONS["random"], jnp.int8)
    with pytest.raises(ValueError, match="one device"):
        flash_attention(q, k, v, selection=selection, mesh=object())
    with pytest.raises(ValueError, match="mask of its own"):
        flash_attention(q, k, v, selection=selection,
                        causal=True, band=8)
    with pytest.raises(ValueError, match="mask of its own"):
        flash_attention(q, k, v, selection=selection,
                        block_diffusion=4)
