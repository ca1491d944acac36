"""Causal-LM batches: rows of S+1 token ids, cut from a stream of documents.

The path a tokenized corpus takes to a decoder's step: documents (lists of
token ids, each closed by an end-of-document id) are concatenated into one
stream and cut into rows of ``seq_length + 1`` ids with no padding — a row's
first S ids are the inputs, its last S the next-token labels, so every
position of every row is trained on (``data/mlm.py`` builds ALBERT's segment
pairs + SOP instead). The synthetic source feeds random documents through
the same packer.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator

import numpy as np

EOS_ID = 0  # closes every document of the stream
# the synthetic documents' mean length (geometric), a quarter of a 4k row
MEAN_DOCUMENT_TOKENS = 1024


def pack_rows(
    documents: Iterable[np.ndarray], batch_size: int, seq_length: int,
) -> Iterator[np.ndarray]:
    """[batch_size, seq_length + 1] int32 rows off the concatenated stream
    ``doc, eos, doc, eos, ...``; a document may span rows."""
    need = batch_size * (seq_length + 1)
    parts, have = [], 0
    for doc in documents:
        parts += [np.asarray(doc, np.int32), np.array([EOS_ID], np.int32)]
        have += len(doc) + 1
        while have >= need:
            stream = np.concatenate(parts)
            yield stream[:need].reshape(batch_size, seq_length + 1)
            parts, have = [stream[need:]], have - need


def causal_lm_batches(
    rows: Iterable[np.ndarray],
) -> Iterator[Dict[str, np.ndarray]]:
    """Rows of S+1 ids -> {"input_ids": [B, S], "labels": [B, S]} (the
    next token at every position)."""
    for block in rows:
        yield {"input_ids": block[:, :-1], "labels": block[:, 1:]}


def synthetic_causal_lm_batches(
    vocab_size: int, batch_size: int, seq_length: int, seed: int,
) -> Iterator[Dict[str, np.ndarray]]:
    """Random-token documents of geometric length through the real packer.
    Deterministic per seed."""
    rng = np.random.default_rng(seed)

    def documents():
        while True:
            length = int(rng.geometric(1.0 / MEAN_DOCUMENT_TOKENS))
            yield rng.integers(1, vocab_size, (length,), dtype=np.int32)

    return causal_lm_batches(pack_rows(documents(), batch_size, seq_length))
