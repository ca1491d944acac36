"""``data/causal_lm.py``: rows of S+1 ids cut from a document stream, and
what the trainer's loss reads from them."""
import numpy as np

from dedloc_tpu.data.causal_lm import (
    causal_lm_batches,
    pack_rows,
    synthetic_causal_lm_batches,
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
