"""Executor glue: device time of every operation in the window that is not
a Pallas kernel (padding, im2col, pooling, reshapes, copies), in
milliseconds per image."""


def read(trace, record):
    if trace.kernel_s + trace.glue_s <= 0 or record["images"] == 0:
        return None
    return 1e3 * trace.glue_s / record["images"]
