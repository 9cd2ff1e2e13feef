"""Traffic generator `memory_tokens`: documents of `int32` token ids already in
memory, for a token encoder. A mix names it in its `generator` key; the harness
finds this file by that name and calls `build(params, config, data_dir)`."""

from __future__ import annotations

import numpy as np


class MemoryTokens:
    """`distinct` documents of `length` ids in memory behind `entries` indices:
    entry `e` is document `e % distinct` rotated by `e // distinct` positions,
    so all entries differ. Ids are Zipf(`zipf`) over the vocabulary's first
    `vocab - 1` ids (the last is the mask id of the program's views). The batch
    protocol is the feed's own: rows, labels, and the rows' lengths as extents."""

    def __init__(self, distinct: int, entries: int, length: int, vocab: int, zipf: float, seed: int):
        rng = np.random.default_rng(seed)
        p = np.arange(1, vocab, dtype=np.float64) ** -zipf
        self.docs = rng.choice(vocab - 1, size=(distinct, length), p=p / p.sum()).astype(np.int32)
        self.entries, self.length, self.num_classes = entries, length, 1

    def __len__(self):
        return self.entries

    def get_batch(self, indices):
        idx = np.asarray(indices)
        doc, shift = idx % len(self.docs), idx // len(self.docs)
        cols = (np.arange(self.length)[None, :] - shift[:, None]) % self.length
        rows = self.docs[doc[:, None], cols]
        return rows, np.zeros(len(idx), np.int32), np.full((len(idx), 1), self.length, np.int32)


def build(params: dict, config, data_dir: str):
    if config.vocab_size and config.vocab_size != params["vocab"]:
        raise ValueError(f"the mix draws from a vocabulary of {params['vocab']}, "
                         f"the configuration holds {config.vocab_size}")
    if params["length"] < config.seq_len:
        raise ValueError("the mix's documents are shorter than a view")
    return MemoryTokens(params["distinct"], params["entries"], params["length"], params["vocab"],
                        params.get("zipf", 1.0), params["data_seed"])
