"""Traffic generator `memory_u8`: uint8 images already in memory, the feed
bypassed. A mix names it in its `generator` key (`traffic/<mix>.json`); the
harness finds this file by that name and calls `build(params, config, data_dir)`."""

from __future__ import annotations

import numpy as np


class MemoryImages:
    """`distinct` uint8 images in memory behind `entries` indices: entry `e` is
    image `e % distinct` rolled `e // distinct` pixels sideways, so all entries
    differ. Pictures are blocks of `block` pixels (8 unless the mix says) under
    fine noise. No decode, no canvas: the feed is a host gather and the transfer."""

    def __init__(self, distinct: int, entries: int, size: int, seed: int, block: int = 8):
        rng = np.random.default_rng(seed)
        coarse = rng.integers(0, 256, (distinct, size // block, size // block, 3), np.uint8)
        fine = rng.integers(0, 64, (distinct, size, size, 3), np.uint8)
        self.images = (coarse.repeat(block, 1).repeat(block, 2) // 4) * 3 + fine
        self.entries, self.size, self.num_classes = entries, size, 1
        self.extent = np.asarray([size, size, 0], np.int32)

    def __len__(self):
        return self.entries

    def get_batch(self, indices):
        idx = np.asarray(indices)
        out = self.images[idx % len(self.images)]
        for row, shift in enumerate(idx // len(self.images)):
            if shift:
                out[row] = np.roll(out[row], int(shift), axis=1)
        return out, np.zeros(len(idx), np.int32), np.tile(self.extent, (len(idx), 1))


def build(params: dict, config, data_dir: str):
    return MemoryImages(params["distinct"], params["entries"], config.image_size,
                        params["data_seed"], params.get("block", 8))
