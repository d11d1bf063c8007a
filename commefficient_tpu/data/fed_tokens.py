"""Per-client token streams for causal-LM fine-tuning
(``--dataset_name TOKENS``).

The prepared layout of ``--dataset_dir``:

    tokens_train.npy   (num_clients, stream_len) uint16 | int32
    tokens_val.npy     (num_val_streams, stream_len)
    stats.json         {"seq_len": T, "vocab_size": V, ...}

A client's stream is its documents, already tokenised, concatenated
with a separator id and never masked across documents (GPT-2-style
packing); it is cut here into ``stream_len // seq_len`` sequences of
``seq_len`` tokens, the records the sampler deals out. No tokenizer is
involved: whoever prepares the directory owns the vocabulary.
``--test`` with no prepared directory writes a tiny synthetic one.
"""

from __future__ import annotations

import json
import os

import numpy as np

__all__ = ["FedTokens", "generate_synthetic_tokens"]


class FedTokens:
    """What ``FedSampler`` deals from (``data_per_client``,
    ``num_clients``, ``len``) and the loaders read (``sequences``);
    record ``i`` is sequence ``i % per_client`` of stream
    ``i // per_client``."""

    def __init__(self, dataset_dir, train=True, num_clients=None):
        with open(os.path.join(dataset_dir, "stats.json")) as f:
            self.stats = json.load(f)
        self.seq_len = int(self.stats["seq_len"])
        self.vocab_size = int(self.stats["vocab_size"])
        self.type = "train" if train else "val"
        streams = np.load(os.path.join(
            dataset_dir, f"tokens_{self.type}.npy"), mmap_mode="r")
        if train and num_clients is not None:
            if num_clients > len(streams):
                raise ValueError(
                    f"--num_clients {num_clients} exceeds the "
                    f"{len(streams)} prepared streams")
            streams = streams[:num_clients]
        self.per_client = streams.shape[1] // self.seq_len
        if self.per_client < 1:
            raise ValueError("streams are shorter than one sequence")
        self.streams = streams

    @property
    def num_clients(self):
        return len(self.streams)

    @property
    def data_per_client(self):
        return np.full(self.num_clients, self.per_client, dtype=int)

    def __len__(self):
        return self.num_clients * self.per_client

    def sequences(self, idxs, client_id=None):
        """(n,) record indices -> (n, seq_len) int32 ids."""
        idxs = np.asarray(idxs, np.int64)
        cid, j = np.divmod(idxs, self.per_client)
        if client_id is not None:
            assert (cid == client_id).all(), (cid, client_id)
        T = self.seq_len
        cols = j[:, None] * T + np.arange(T)[None]
        return np.asarray(self.streams[cid[:, None], cols], np.int32)


def generate_synthetic_tokens(dataset_dir, num_clients=16, stream_len=256,
                              seq_len=32, vocab_size=96, num_val=4,
                              seed=0):
    """A tiny learnable corpus in the prepared layout: each client
    repeats its own short random phrase with a little noise."""
    rng = np.random.RandomState(seed)
    os.makedirs(dataset_dir, exist_ok=True)

    def streams(n):
        out = np.zeros((n, stream_len), np.uint16)
        for c in range(n):
            phrase = rng.randint(1, vocab_size, size=rng.randint(3, 9))
            s = np.tile(phrase, stream_len // len(phrase) + 1)[:stream_len]
            noise = rng.rand(stream_len) < 0.05
            s = np.where(noise, rng.randint(1, vocab_size, stream_len), s)
            out[c] = s
        return out

    np.save(os.path.join(dataset_dir, "tokens_train.npy"),
            streams(num_clients))
    np.save(os.path.join(dataset_dir, "tokens_val.npy"), streams(num_val))
    with open(os.path.join(dataset_dir, "stats.json"), "w") as f:
        json.dump({"seq_len": seq_len, "vocab_size": vocab_size,
                   "num_clients": num_clients, "stream_len": stream_len,
                   "synthetic": True}, f)
