"""``data/causal_lm.py``: rows of S+1 ids cut from a document stream, and
what the trainer's loss reads from them."""
import numpy as np

from dedloc_tpu.data.causal_lm import (
    causal_lm_batches,
    draw_image_spans,
    mrope_position_ids,
    pack_rows,
    synthetic_causal_lm_batches,
    with_image_spans,
)


def test_pack_rows_concatenates_documents_with_eos_and_no_padding():
    docs = [np.arange(1, 6), np.arange(10, 13), np.arange(20, 40)]
    rows = list(pack_rows(iter(docs), batch_size=2, seq_length=4))
    stream = np.concatenate([np.append(d, 0) for d in docs])
    # two rows of 5 ids per block, straight off the stream; the tail that
    # does not fill a block waits for more documents
    assert len(rows) == len(stream) // 10
    for i, block in enumerate(rows):
        assert block.shape == (2, 5) and block.dtype == np.int32
        np.testing.assert_array_equal(
            block.reshape(-1), stream[i * 10:(i + 1) * 10]
        )
    # a document may span rows: the third one (20 ids) crosses a row's end
    assert rows[1][0, -1] == 24 and rows[1][1, 0] == 25


def test_causal_lm_batches_shift_labels_by_one():
    block = np.arange(12, dtype=np.int32).reshape(2, 6)
    (batch,) = list(causal_lm_batches([block]))
    np.testing.assert_array_equal(batch["input_ids"], block[:, :-1])
    np.testing.assert_array_equal(batch["labels"], block[:, 1:])
    np.testing.assert_array_equal(
        batch["input_ids"][:, 1:], batch["labels"][:, :-1]
    )


def test_synthetic_source_is_seeded_and_full():
    a = synthetic_causal_lm_batches(512, 3, 64, seed=7)
    b = synthetic_causal_lm_batches(512, 3, 64, seed=7)
    c = synthetic_causal_lm_batches(512, 3, 64, seed=8)
    first, again, other = next(a), next(b), next(c)
    assert first["input_ids"].shape == first["labels"].shape == (3, 64)
    np.testing.assert_array_equal(first["input_ids"], again["input_ids"])
    assert not np.array_equal(first["input_ids"], other["input_ids"])
    ids = np.concatenate([next(a)["input_ids"].reshape(-1) for _ in range(20)])
    assert ids.min() >= 0 and ids.max() < 512
    # documents end (eos id 0 appears) but rows are never padded out
    assert 0 < (ids == 0).mean() < 0.05
    # a seed larger than 32 signed bits is a seed like any other
    big = next(synthetic_causal_lm_batches(512, 1, 16, seed=3_000_000_011))
    assert big["input_ids"].shape == (1, 16)


def test_rows_without_spans_are_the_rows_they_were():
    """The six cells that use this source today pass no share: the same
    keys, the same ids, draw for draw — and a source that builds positions
    draws its spans from a generator of its own, so its ids are those too."""
    plain = synthetic_causal_lm_batches(512, 2, 64, seed=7)
    default = synthetic_causal_lm_batches(512, 2, 64, seed=7,
                                          image_token_share=0.0)
    spans = synthetic_causal_lm_batches(
        512, 2, 64, seed=7, image_token_share=0.25,
        image_grids=((2, 2), (2, 3)),
    )
    for _ in range(3):
        a, b, c = next(plain), next(default), next(spans)
        assert sorted(a) == sorted(b) == ["input_ids", "labels"]
        assert sorted(c) == ["input_ids", "labels", "loss_weights",
                             "position_ids"]
        for key in a:
            np.testing.assert_array_equal(a[key], b[key])
            np.testing.assert_array_equal(a[key], c[key])
    # text rows WITH positions (a family that asks for them, share 0):
    # three aranges and every weight 1
    text = next(synthetic_causal_lm_batches(512, 2, 64, seed=7,
                                            positions=True))
    np.testing.assert_array_equal(
        text["position_ids"], np.broadcast_to(np.arange(64), (3, 2, 64))
    )
    assert (text["loss_weights"] == 1).all()


def test_positions_and_weights_of_an_image_span():
    # a row of 12 with ONE span of 2 x 3 at token 4: text 0..3, the span's
    # six tokens at t = 4, h = 4 + row, w = 4 + column, text resumes at
    # 4 + max(2, 3) = 7
    ids = mrope_position_ids([(4, 2, 3)], 12)
    np.testing.assert_array_equal(ids, [
        [0, 1, 2, 3, 4, 4, 4, 4, 4, 4, 7, 8],
        [0, 1, 2, 3, 4, 4, 4, 5, 5, 5, 7, 8],
        [0, 1, 2, 3, 4, 5, 6, 4, 5, 6, 7, 8],
    ])
    # two spans back to back, the second at the row's end
    ids = mrope_position_ids([(1, 2, 2), (5, 2, 2)], 9)
    np.testing.assert_array_equal(ids[0], [0, 1, 1, 1, 1, 3, 3, 3, 3])
    np.testing.assert_array_equal(ids[2], [0, 1, 2, 1, 2, 3, 4, 3, 4])
    # the batch: a label (the NEXT token) inside a span carries no loss
    block = np.arange(1, 27, dtype=np.int32).reshape(2, 13)
    (batch,) = list(with_image_spans(
        causal_lm_batches([block]), 0.5, seed=1, grids=((2, 3),)
    ))
    assert batch["position_ids"].shape == (3, 2, 12)
    rng = np.random.default_rng(1)
    for row in range(2):
        spans = draw_image_spans(rng, 12, 0.5, ((2, 3),))
        assert len(spans) == 1  # 6 of 12 tokens: one span fits
        start = spans[0][0]
        inside = np.zeros(13, bool)
        inside[start:start + 6] = True
        np.testing.assert_array_equal(
            batch["loss_weights"][row], (~inside[1:]).astype(np.float32)
        )
        np.testing.assert_array_equal(
            batch["position_ids"][:, row], mrope_position_ids(spans, 12)
        )
    # spans never overlap, stay inside the row and fill the share
    rng = np.random.default_rng(0)
    for _ in range(20):
        spans = draw_image_spans(rng, 16384, 0.25)
        ends = [s + h * w for s, h, w in spans]
        assert all(a <= b for a, (b, _h, _w) in zip(ends, spans[1:]))
        assert ends[-1] <= 16384
        covered = sum(h * w for _s, h, w in spans)
        assert 16384 // 4 - 256 < covered <= 16384 // 4
