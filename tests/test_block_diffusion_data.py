"""``data/block_diffusion.py``: the noise of a block-diffusion row — one
level per block, masked ids, 1 / t weights — drawn on the host from the
seed."""
import numpy as np
import pytest

from dedloc_tpu.data.block_diffusion import (
    block_diffusion_batches,
    synthetic_block_diffusion_batches,
)

VOCAB, MASK = 512, 511


def _take(n, seed=7, batch=4, seq=256, block=4):
    source = synthetic_block_diffusion_batches(VOCAB, batch, seq, block, seed)
    return [next(source) for _ in range(n)]


def test_a_batch_is_a_row_its_noisy_copy_and_the_weights():
    (batch,) = _take(1)
    assert sorted(batch) == ["input_ids", "labels", "loss_weights"]
    assert all(x.shape == (4, 256) for x in batch.values())
    assert batch["input_ids"].dtype == batch["labels"].dtype == np.int32
    assert batch["loss_weights"].dtype == np.float32
    masked = batch["input_ids"] == MASK
    # the noisy copy is the row wherever it is not the mask id
    np.testing.assert_array_equal(
        batch["input_ids"][~masked], batch["labels"][~masked]
    )
    # a weight exactly where masked, 1 / t >= 1, one level a block
    np.testing.assert_array_equal(batch["loss_weights"] > 0, masked)
    assert batch["loss_weights"][masked].min() >= 1.0
    blocks = batch["loss_weights"].reshape(4, 64, 4)
    for block in blocks.reshape(-1, 4):
        assert len(set(block[block > 0])) <= 1


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_deterministic_from_the_seed(seed):
    first, again = _take(3, seed), _take(3, seed)
    for a, b in zip(first, again):
        for key in a:
            np.testing.assert_array_equal(a[key], b[key])
    other = _take(1, seed + 1)[0]
    assert (other["labels"] != first[0]["labels"]).mean() > 0.9
    assert (other["loss_weights"] != first[0]["loss_weights"]).mean() > 0.2


def test_the_mask_id_is_never_drawn_as_data():
    for batch in _take(8, batch=8):
        assert batch["labels"].max() < MASK
        assert batch["labels"].min() >= 0


def test_the_masked_share_and_the_weights_expectation():
    """t ~ U(0, 1]: half the positions are masked, and a row's weights sum
    to its length in expectation (E[1/t · 1{masked}] = 1)."""
    batches = _take(40, batch=8, seq=512)
    masked = np.mean([(b["loss_weights"] > 0).mean() for b in batches])
    assert masked == pytest.approx(0.5, abs=0.01)
    total = np.mean([b["loss_weights"].mean() for b in batches])
    assert total == pytest.approx(1.0, abs=0.05)


def test_a_level_of_one_masks_its_whole_block():
    """1 / t = 1 only at t = 1, where every id of the block is masked."""
    for batch in _take(20, batch=8):
        blocks = batch["loss_weights"].reshape(-1, 4)
        ones = blocks[(blocks == 1.0).any(axis=1)]
        assert (ones == 1.0).all()


def test_rows_are_whole_blocks():
    rows = [np.zeros((2, 30), np.int32)]
    with pytest.raises(ValueError, match="whole blocks"):
        next(block_diffusion_batches(rows, 4, MASK, 0))
