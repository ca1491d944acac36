"""Block-diffusion batches: a packed row, its noisy copy and the weight of
every position's loss.

Block Diffusion (arXiv 2503.09573; the objective SDAR, arXiv 2510.06303,
adapts an autoregressive model with) cuts a row x of L ids into blocks of B
and draws, PER BLOCK, a noise level t ~ U(0, 1]; every id of the block is
replaced by the mask id M independently with probability t (absorbing noise,
the linear schedule), giving x~. The model reads [x~ ; x] and predicts x_p AT
every masked position p of x~; the bound weighs that position's
cross-entropy by 1 / t of its block, an unmasked position by 0. All of it is
drawn here, on the host, from the seed: a fixed seed fixes the noise too.

The rows are ``data/causal_lm.py``'s (documents closed by an end-of-document
id, concatenated and cut with no padding); the mask id is never drawn as
data.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator

import numpy as np

from dedloc_tpu.data.causal_lm import MEAN_DOCUMENT_TOKENS, pack_rows


def block_diffusion_batches(
    rows: Iterable[np.ndarray], block_length: int, mask_id: int, seed: int,
) -> Iterator[Dict[str, np.ndarray]]:
    """Rows [B, L] of clean ids -> {"input_ids": x~ [B, L], "labels": x
    [B, L], "loss_weights": [B, L] float32, 1 / t where x~ holds the mask
    id and 0 elsewhere}. L is whole blocks."""
    rng = np.random.default_rng(seed)
    for clean in rows:
        batch, length = clean.shape
        if length % block_length:
            raise ValueError(
                f"rows of {length} ids are not whole blocks of "
                f"{block_length}"
            )
        # t in (0, 1]: ``random`` draws from [0, 1)
        level = 1.0 - rng.random((batch, length // block_length))
        level = np.repeat(level, block_length, axis=1)
        masked = rng.random((batch, length)) < level
        yield {
            "input_ids": np.where(masked, np.int32(mask_id), clean),
            "labels": clean,
            "loss_weights": np.where(masked, 1.0 / level, 0.0).astype(
                np.float32
            ),
        }


def synthetic_block_diffusion_batches(
    vocab_size: int, batch_size: int, seq_length: int, block_length: int,
    seed: int,
) -> Iterator[Dict[str, np.ndarray]]:
    """Random-token documents of geometric length through the real packer,
    then the noise; the LAST id of the vocabulary is the mask id and no
    document holds it. Deterministic per seed."""
    rng = np.random.default_rng(seed)

    def documents():
        while True:
            length = int(rng.geometric(1.0 / MEAN_DOCUMENT_TOKENS))
            yield rng.integers(1, vocab_size - 1, (length,), dtype=np.int32)

    # the packer cuts rows of its ``seq_length`` + 1 ids (inputs and the
    # next-token labels): here a row IS its ids, nothing is shifted
    return block_diffusion_batches(
        pack_rows(documents(), batch_size, seq_length - 1), block_length,
        vocab_size - 1, seed + 1,
    )
