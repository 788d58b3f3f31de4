"""Kernel: the network's roofline time for the window's calls over the
device time of the Pallas ``com_matmul`` calls, in percent.

The roofline time of a call is the sum over the configuration's layers of
``max(FLOPs / peak, bytes / peak bandwidth)`` (``bench/work.py``), from
the unpadded layer shapes. The kernels do every multiply of the network,
so their time can undercut that only if the count or the time is wrong.
"""
from bench import work


def read(trace, record):
    if trace.kernel_s <= 0 or record["calls"] == 0:
        return None
    per_call = work.roofline_seconds(record["cfg"], record["mix"]["batch"],
                                     record["peaks"])
    return 100.0 * per_call * record["calls"] / trace.kernel_s
