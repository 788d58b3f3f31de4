"""Kernel: a residual network's roofline time for the window's calls over
the device time of the Pallas ``com_matmul`` calls, in percent.

The roofline time of a call sums ``max(FLOPs / peak, bytes / peak
bandwidth)`` over the configuration's layers (``bench/residual_net.py``:
``work.py``'s counts plus each join's shortcut read), from the unpadded
layer shapes. The kernels do every multiply and every join of the
network, so their time can undercut that only if the count or the time
is wrong."""
from bench import residual_net


def read(trace, record):
    if trace.kernel_s <= 0 or record["calls"] == 0:
        return None
    per_call = residual_net.roofline_seconds(
        record["cfg"], record["mix"]["batch"], record["peaks"])
    return 100.0 * per_call * record["calls"] / trace.kernel_s
