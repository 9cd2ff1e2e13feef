"""Operations and bytes of the Mosaic `_blur_kernel` (`ops/pallas_blur.py`),
from shapes alone: one call blurs one `[3, S, S]` image with `2R+1` taps along
each axis, reading the edge-padded image once and writing the result once.
"""

NAME = "_blur_kernel"
MOSAIC = 'custom_call_target="tpu_custom_call"'


def radius(size: int) -> int:
    return max(1, int(0.05 * size))


def work(size: int, itemsize: int) -> dict:
    """One call (one image of one view): multiply-adds counted as two."""
    r = radius(size)
    taps = 2 * r + 1
    padded = size + 2 * r
    flops = 2 * taps * 3 * size * padded + 2 * taps * 3 * size * size
    bytes_moved = 3 * padded * padded * itemsize + 3 * size * size * itemsize
    return {"flops": flops, "bytes": bytes_moved}


def is_call(event: str, size: int) -> bool:
    """Whether a device event of the trace is this kernel. XLA names the event
    by the whole HLO line, in which the kernel's own name does not appear (the
    vmapped call is `%vmap__.N`): a Mosaic call over `[B, 3, S+2R, S+2R]`."""
    padded = size + 2 * radius(size)
    return NAME in event or (MOSAIC in event and f",3,{padded},{padded}]" in event)


def calls_per_step(config) -> int:
    """Views that take the lifted kernel: the v2 recipe blurs both views with
    it; v3's solarizing view keeps the in-pipeline blur."""
    return config.batch_size * (1 if config.variant == "v3" else 2)
