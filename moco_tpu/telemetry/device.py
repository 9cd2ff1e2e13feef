"""HBM + host-memory sampling (ISSUE 2 tentpole part 3).

`jax.Device.memory_stats()` returns allocator statistics on TPU/GPU
backends (`bytes_in_use`, `peak_bytes_in_use`, `bytes_limit`) and None on
the CPU backend, which has no allocator API — so the absence of HBM keys in
a record means "CPU", not zero. An accelerator that fails the call raises:
a run whose HBM peak cannot be read should say so, not record nothing.

Host RSS comes from /proc/self/statm (Linux; current resident set), with
`resource.getrusage` ru_maxrss (peak, kB) as the portable fallback — both
are cheap enough to sample at the device stride.
"""

from __future__ import annotations

import os
import resource
import sys

_PAGE_SIZE = os.sysconf("SC_PAGE_SIZE") if hasattr(os, "sysconf") else 4096

# memory_stats keys → our schema names
_HBM_KEYS = (
    ("bytes_in_use", "hbm_bytes_in_use"),
    ("peak_bytes_in_use", "hbm_peak_bytes"),
    ("bytes_limit", "hbm_bytes_limit"),
)


def host_rss_bytes() -> int:
    """Current resident set size (Linux /proc); off-Linux the fallback is
    ru_maxrss — the PEAK, not current, so the off-Linux curve is monotone
    — in the platform's native unit (bytes on macOS, kilobytes elsewhere:
    a blind *1024 would report terabytes on a Mac dev box)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE_SIZE
    except (OSError, ValueError, IndexError):
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(rss if sys.platform == "darwin" else rss * 1024)


class DeviceMonitor:
    """Samples one device's allocator stats + this host's RSS."""

    def __init__(self, device=None):
        if device is None:
            import jax

            device = jax.local_devices()[0]
        self.device = device

    def sample(self) -> dict:
        out = {"host_rss_bytes": host_rss_bytes()}
        stats = self.device.memory_stats() or {}
        for src, dst in _HBM_KEYS:
            if src in stats:
                out[dst] = int(stats[src])
        return out
