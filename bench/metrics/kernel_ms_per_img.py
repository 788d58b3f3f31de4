"""Kernel: device time of the Pallas ``com_matmul`` calls
(``tpu_custom_call``) in the window, in milliseconds per image."""


def read(trace, record):
    if trace.kernel_s <= 0 or record["images"] == 0:
        return None
    return 1e3 * trace.kernel_s / record["images"]
