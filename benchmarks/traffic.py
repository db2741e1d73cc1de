"""The one general generator of traffic. A mix is a data file of parameters
(a cell's ``traffic`` object); everything below is drawn from ``--seed`` by
numpy's ``default_rng``, which takes whole numbers of any size. Arrival
processes for serving come with the first serve cell (PERF.md, open
questions)."""

from __future__ import annotations

import numpy as np


def _rng(seed: int, *stream: int):
    return np.random.default_rng([int(seed), *stream])


def caption_rows(rng, rows: int, text_seq_len: int, num_text_tokens: int,
                 text_tokens) -> np.ndarray:
    """(rows, text_seq_len) int32 token ids: each row a caption of a length
    drawn uniformly from ``text_tokens`` = [least, most], ids in
    [1, num_text_tokens), padded with 0 as the tokenizer pads."""
    lo, hi = int(text_tokens[0]), int(text_tokens[1])
    lengths = rng.integers(lo, hi + 1, rows)
    ids = rng.integers(1, num_text_tokens, (rows, text_seq_len))
    keep = np.arange(text_seq_len)[None, :] < lengths[:, None]
    return np.where(keep, ids, 0).astype(np.int32)


def train_batch(seed: int, step: int, batch: int, model: dict,
                traffic: dict) -> tuple:
    """Batch ``step`` of a training run: (text, image ids), every row
    different, the same for the same seed and step."""
    rng = _rng(seed, 1, step)
    text = caption_rows(rng, batch, model["text_seq_len"],
                        model["num_text_tokens"], traffic["text_tokens"])
    ids = rng.integers(0, model["image_vocab_size"],
                       (batch, model["image_fmap_size"] ** 2))
    return text, ids.astype(np.int32)
