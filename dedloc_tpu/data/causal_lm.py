"""Causal-LM batches: rows of S+1 token ids, cut from a stream of documents.

The path a tokenized corpus takes to a decoder's step: documents (lists of
token ids, each closed by an end-of-document id) are concatenated into one
stream and cut into rows of ``seq_length + 1`` ids with no padding — a row's
first S ids are the inputs, its last S the next-token labels, so every
position of every row is trained on (``data/mlm.py`` builds ALBERT's segment
pairs + SOP instead). The synthetic source feeds random documents through
the same packer.

Interleaved image-text rows (``image_token_share`` > 0; a vision-language
decoder whose positions are three streams, ``models/keye_vl2.py``): a share
of each row's positions lies in IMAGE SPANS, runs of g_h x g_w ids that
stand for a vision tower's features of one image, row-major. Such a batch
also carries ``position_ids`` [3, B, S] — a token's temporal, height and
width position (M-RoPE, Qwen2-VL's rule: text counts all three up together;
a span that begins where the text position is p0 has t = p0, h = p0 + its
grid row, w = p0 + its grid column, and text resumes at p0 + max(g_h, g_w))
— and ``loss_weights`` [B, S], 0 where the LABEL is an image position (an
image token is not predicted), else 1. At a share of 0 — the default, and
every family that does not ask for positions — none of this is built: the
rows, the keys and the random draws are the ones they were.
"""
from __future__ import annotations

from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

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


# the synthetic image spans' token grids (a tower's patch grid after its
# merge): drawn uniformly
IMAGE_GRIDS = ((16, 16), (24, 24), (32, 32))


def draw_image_spans(
    rng: np.random.Generator, length: int, share: float,
    grids: Optional[Sequence[Tuple[int, int]]] = None,
) -> List[Tuple[int, int, int]]:
    """(start, g_h, g_w) of a row's image spans, in order, not overlapping:
    grids drawn uniformly among those that still fit under ``share`` of the
    row's ``length`` positions until none does, the text between them cut
    at uniform places. ``grids`` None: ``IMAGE_GRIDS``."""
    grids = IMAGE_GRIDS if grids is None else grids
    budget, spans = int(share * length), []
    while True:
        fit = [g for g in grids if g[0] * g[1] <= budget]
        if not fit:
            break
        g_h, g_w = fit[int(rng.integers(len(fit)))]
        budget -= g_h * g_w
        spans.append((g_h, g_w))
    text = length - sum(g_h * g_w for g_h, g_w in spans)
    # the text before each span: sorted uniform cuts of the row's text
    cuts = np.sort(rng.integers(0, text + 1, len(spans)))
    placed, shift = [], 0
    for cut, (g_h, g_w) in zip(cuts, spans):
        placed.append((int(cut) + shift, g_h, g_w))
        shift += g_h * g_w
    return placed


def mrope_position_ids(
    spans: Sequence[Tuple[int, int, int]], length: int,
) -> np.ndarray:
    """[3, length] int32: the (temporal, height, width) position of every
    token of a row with image ``spans`` (see the module docstring); three
    equal ``arange``s where there is none."""
    ids = np.empty((3, length), np.int32)
    at, position = 0, 0  # the next token, the next text position
    for start, g_h, g_w in spans:
        run = start - at
        ids[:, at:start] = position + np.arange(run, dtype=np.int32)
        position += run
        cell = np.arange(g_h * g_w, dtype=np.int32)
        ids[0, start:start + g_h * g_w] = position
        ids[1, start:start + g_h * g_w] = position + cell // g_w
        ids[2, start:start + g_h * g_w] = position + cell % g_w
        position += max(g_h, g_w)
        at = start + g_h * g_w
    ids[:, at:] = position + np.arange(length - at, dtype=np.int32)
    return ids


def with_image_spans(
    batches: Iterable[Dict[str, np.ndarray]], share: float, seed: int,
    grids: Optional[Sequence[Tuple[int, int]]] = None,
) -> Iterator[Dict[str, np.ndarray]]:
    """Causal-LM batches with ``position_ids`` [3, B, S] and ``loss_weights``
    [B, S] (0 where the label — the NEXT token — lies in an image span) for
    image spans drawn per row from ``seed``; the ids are left as they are
    (an image position's id stands for a feature that arrives from the
    tower's stage)."""
    rng = np.random.default_rng(seed)
    for batch in batches:
        rows, length = batch["input_ids"].shape
        positions = np.empty((3, rows, length), np.int32)
        weights = np.ones((rows, length), np.float32)
        for row in range(rows):
            spans = draw_image_spans(rng, length, share, grids)
            positions[:, row] = mrope_position_ids(spans, length)
            for start, g_h, g_w in spans:
                # labels are the inputs shifted by one: label s is token s+1
                weights[row, max(start - 1, 0):start + g_h * g_w - 1] = 0.0
        yield dict(batch, position_ids=positions, loss_weights=weights)


def synthetic_causal_lm_batches(
    vocab_size: int, batch_size: int, seq_length: int, seed: int,
    image_token_share: float = 0.0,
    image_grids: Optional[Sequence[Tuple[int, int]]] = None,
    positions: bool = False,
) -> Iterator[Dict[str, np.ndarray]]:
    """Random-token documents of geometric length through the real packer.
    Deterministic per seed. ``positions`` (a model whose positions come from
    the batch) or ``image_token_share`` > 0: the batches of
    ``with_image_spans``, spans drawn from ``seed + 1``."""
    rng = np.random.default_rng(seed)

    def documents():
        while True:
            length = int(rng.geometric(1.0 / MEAN_DOCUMENT_TOKENS))
            yield rng.integers(1, vocab_size, (length,), dtype=np.int32)

    batches = causal_lm_batches(pack_rows(documents(), batch_size, seq_length))
    if not positions and image_token_share <= 0:
        return batches
    return with_image_spans(batches, image_token_share, seed + 1, image_grids)
