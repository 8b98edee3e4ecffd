"""The one traffic generator: a mix is a data file of parameters.

`kind: "train_batches"` — a training job's feed: a pool of `pool` host
batches of `batch_per_chip * chips` rows by `seq` tokens, every row
different, drawn from the seed: token ids, two segments per row with
sentence types, `masked_share` of the positions masked for MLM with their
labels, an NSP label.  The loop feeds them round-robin.  Every seed gives
the same sizes; only the contents change.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np


def load(path) -> dict:
    mix = json.loads(pathlib.Path(path).read_text())
    if mix.get("kind") != "train_batches":
        raise ValueError(f"{path}: unknown traffic kind {mix.get('kind')!r}")
    return mix


def n_masked(mix: dict) -> int:
    return max(1, int(mix["seq"] * mix["masked_share"]))


def global_batch(mix: dict) -> int:
    return mix["batch_per_chip"] * mix["chips"]


def make_pool(mix: dict, model: dict, seed: int) -> list:
    rng = np.random.default_rng([int(seed), 1])
    rows, seq, n_mask = global_batch(mix), mix["seq"], n_masked(mix)
    vocab, types = model["vocab_size"], model["type_vocab_size"]
    pool = []
    for _ in range(mix["pool"]):
        split = rng.integers(seq // 4, 3 * seq // 4, (rows, 1))
        kinds = rng.integers(0, types, (rows, 2))
        second = np.arange(seq)[None, :] >= split
        pool.append({
            "input_ids": rng.integers(1, vocab, (rows, seq)).astype(np.int32),
            "token_type_ids": np.where(second, kinds[:, 1:], kinds[:, :1])
            .astype(np.int32),
            "masked_positions": np.argsort(rng.random((rows, seq)), axis=1)
            [:, :n_mask].astype(np.int32),
            "mlm_labels": rng.integers(0, vocab, (rows, n_mask))
            .astype(np.int32),
            "nsp_labels": rng.integers(0, 2, (rows,)).astype(np.int32),
        })
    return pool
