"""Traffic generator `jpeg_tree`: a class-per-subdirectory tree of JPEG files,
read by the program's own `ImageFolder`. A mix names it in its `generator` key
(`traffic/<mix>.json`); the harness finds this file by that name and calls
`build(params, config, data_dir)`. The pixels come from the mix's `data_seed`,
so a cell's later runs find the tree already there."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np

def _write_jpegs(directory: str, n: int, width: int, height: int, quality: int, seed: int):
    """Low-frequency content plus noise: realistic JPEG entropy (about 40 KB at
    500x375, quality 85). The benchmark's copy of `datasets.write_jpeg_tree`'s
    picture, encoded on a few threads."""
    from PIL import Image

    rng = np.random.RandomState(seed)
    bases = rng.randint(0, 256, (n, 6, 8, 3)).astype(np.uint8)
    noise_seeds = rng.randint(0, 2 ** 31 - 1, n)

    def one(i: int):
        img = np.asarray(Image.fromarray(bases[i]).resize((width, height), Image.BILINEAR), np.int16)
        noise = np.random.RandomState(noise_seeds[i]).randint(-25, 25, (height, width, 1))
        Image.fromarray(np.clip(img + noise, 0, 255).astype(np.uint8)).save(
            os.path.join(directory, f"{i:06d}.jpg"), quality=quality)

    with ThreadPoolExecutor(4) as pool:
        list(pool.map(one, range(n)))


def _root(params: dict, data_dir: str) -> str:
    key = hashlib.sha1(json.dumps(params, sort_keys=True).encode()).hexdigest()[:12]
    return os.path.join(data_dir, f"jpeg-{key}")


def build(params: dict, config, data_dir: str):
    """A class-per-subdirectory tree of `entries` names over `distinct_files`
    JPEGs (hard links), read by the program's own `ImageFolder`."""
    from moco_tpu.data.datasets import ImageFolder

    root = _root(params, data_dir)
    if not os.path.exists(os.path.join(root, "done")):
        shutil.rmtree(root, ignore_errors=True)
        files = os.path.join(root, "files")
        os.makedirs(files)
        _write_jpegs(files, params["distinct_files"], params["width"], params["height"],
                     params["quality"], params["data_seed"])
        per_class = params["entries"] // params["classes"]
        for c in range(params["classes"]):
            d = os.path.join(root, "train", f"class{c:03d}")
            os.makedirs(d)
            for j in range(per_class):
                e = c * per_class + j
                os.link(os.path.join(files, f"{e % params['distinct_files']:06d}.jpg"),
                        os.path.join(d, f"{e:07d}.jpg"))
        open(os.path.join(root, "done"), "w").close()
    kw = {"stage_size": config.stage_size} if config.stage_size else {}
    if config.num_workers:
        kw["num_workers"] = config.num_workers
    return ImageFolder(os.path.join(root, "train"), **kw)


def check_inputs(params: dict, data_dir: str, inputs: list, seed: int, rows_per_batch: int = 8):
    """What the feed staged against this generator's own reading of its files: a
    sample of rows, drawn from the seed, of every batch kept for `correct`; the
    valid region of each staged canvas has to equal PIL's decode of one of the
    tree's files, level for level (the limit is 0). Covers the program's decoder
    and its staging: placement on the canvas, extents, rotation."""
    from PIL import Image

    files = sorted(os.path.join(_root(params, data_dir), "files", f)
                   for f in os.listdir(os.path.join(_root(params, data_dir), "files")))
    ys = np.linspace(0, params["height"] - 1, 5).astype(int)
    xs = np.linspace(0, params["width"] - 1, 5).astype(int)

    def decode(path):
        return np.asarray(Image.open(path).convert("RGB"))

    with ThreadPoolExecutor(4) as pool:
        marks = np.stack(list(pool.map(lambda f: decode(f)[np.ix_(ys, xs)].astype(np.int16), files)))
    rng = np.random.default_rng(seed)
    worst, where = 0, ""
    for b, (imgs, extents) in enumerate(inputs):
        for r in rng.choice(len(imgs), min(rows_per_batch, len(imgs)), replace=False):
            h, w, rot = (int(v) for v in extents[r])
            got = imgs[r, :h, :w]
            got = got.transpose(1, 0, 2) if rot else got
            if got.shape != (params["height"], params["width"], 3):
                gap, f = 255, -1
            else:
                f = int(np.argmin(np.abs(marks - got[np.ix_(ys, xs)].astype(np.int16)).sum((1, 2, 3))))
                gap = int(np.abs(got.astype(np.int16) - decode(files[f]).astype(np.int16)).max())
            if gap >= worst:
                worst, where = gap, f"batch {b} row {int(r)} file {f}"
    return {"canvas_max": (float(worst), where)}
