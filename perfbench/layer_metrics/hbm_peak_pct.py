"""Peak bytes in use on the fullest chip, as the runtime reports them after the
window, over the chip's memory."""

from perfbench import peaks


def read(run):
    if not run["memory_peak_bytes"]:
        return None
    return 100.0 * run["memory_peak_bytes"] / peaks.for_kind(run["device_kind"])["hbm_bytes"]
