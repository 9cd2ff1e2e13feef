"""The Mosaic blur kernel's share of its roofline: the least time the chip could
take for one call (operations over peak, or bytes over peak, whichever is
larger; bytes bound it) over the call's median device time."""

import statistics

from perfbench import peaks
from perfbench.kernels import blur


def read(run):
    size = run["config"].image_size
    ds = [d for event, durations in run["trace"]["ops"].items() if blur.is_call(event, size)
          for d in durations]
    if not ds:
        return None
    itemsize = 2 if run["config"].compute_dtype == "bfloat16" else 4
    work = blur.work(size, itemsize)
    # one event may cover a whole view's batch of calls or a single call
    per_step = len(ds) / max(run["traced_steps"], 1)
    calls = blur.calls_per_step(run["config"]) / run["chips"] / max(per_step, 1e-9)
    least = max(work["flops"] / peaks.for_kind(run["device_kind"])["flops_bf16"],
                work["bytes"] / peaks.for_kind(run["device_kind"])["hbm_bytes_per_s"]) * max(calls, 1.0)
    return 100.0 * least / statistics.median(ds)
