"""Per-client token streams from the seed, in the prepared layout
``data/fed_tokens.py`` reads (``tokens_train.npy``, ``tokens_val.npy``,
``stats.json``), so that a causal-LM cell goes through the trainer's
own dataset class, sampler and loader.

``token_streams``: every client is a stream of ``stream_len`` ids over a
vocabulary slice of ``vocab_size`` rows. Ids follow Zipf's law (s = 1)
over the ranks 1 .. vocab_size - 1; id 0 is the document separator. A
client's own usage differs from the next one's where it matters most:
each client permutes the ``head`` most frequent ranks among themselves
(its favourite words are its own), the tail is shared. Documents have
geometric lengths of mean ``doc_mean``: each position is a separator
with probability 1 / doc_mean. Streams are packed: no padding, no mask
across documents.
"""

from __future__ import annotations

import json
import os

import numpy as np


def token_streams(dataset_dir: str, seed: int, num_clients: int = 2048,
                  stream_len: int = 8192, seq_len: int = 1024,
                  vocab_size: int = 16160, head: int = 256,
                  doc_mean: int = 512, num_val: int = 16) -> None:
    os.makedirs(dataset_dir, exist_ok=True)
    rng = np.random.default_rng([int(seed), 0x70CE])
    head = min(head, vocab_size - 1)
    ranks = np.arange(1, vocab_size, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks)
    cdf /= cdf[-1]

    def streams(n):
        # rank of each token, 0-based, by inverse CDF
        r = np.searchsorted(cdf, rng.random((n, stream_len)),
                            side="right").astype(np.int64)
        r = np.minimum(r, vocab_size - 2)
        # the client's own order of the head ranks
        own = np.argsort(rng.random((n, head)), axis=1)
        in_head = r < head
        rows = np.broadcast_to(np.arange(n)[:, None], r.shape)
        r = np.where(in_head, own[rows, np.minimum(r, head - 1)], r)
        ids = (r + 1).astype(np.uint16 if vocab_size <= 65536
                             else np.int32)
        ids[rng.random((n, stream_len)) < 1.0 / doc_mean] = 0
        return ids

    np.save(os.path.join(dataset_dir, "tokens_train.npy"),
            streams(num_clients))
    np.save(os.path.join(dataset_dir, "tokens_val.npy"), streams(num_val))
    with open(os.path.join(dataset_dir, "stats.json"), "w") as f:
        json.dump({"seq_len": seq_len, "vocab_size": vocab_size,
                   "num_clients": num_clients, "stream_len": stream_len,
                   "head": head, "doc_mean": doc_mean}, f)
