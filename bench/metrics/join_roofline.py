"""Kernel: the roofline time of a residual network's join layers for the
window's calls over the device time of their kernels, in percent.

A join layer's kernel (``com_matmul_<layer>``, the Pallas
``tpu_custom_call`` in the layer's scope) adds the shortcut to its
accumulator before the ReLU. Its roofline is that of
``bench/residual_net.py``, which counts the shortcut's read. Kernels map
to layers through the cell's compiled chain (``bench/scopes.py``); None
where the chain has no join kernel or the trace holds an op the chain
does not."""
from bench import residual_net, scopes


def read(trace, record):
    if record["calls"] == 0 or not trace.ops:
        return None
    cfg, batch = record["cfg"], record["mix"]["batch"]
    joins = {l.name for l in residual_net.layers(cfg) if l.joins}
    ops = scopes.cell_scopes(cfg["network"], batch)
    if any(label not in ops for label in trace.ops):
        return None
    seconds = 0.0
    for label, secs in trace.ops.items():
        role = scopes.op_role(label, ops)
        if role is not None and role[1] == "kernel" and role[0] in joins:
            seconds += secs
    if seconds <= 0:
        return None
    per_call = residual_net.roofline_seconds(cfg, batch, record["peaks"],
                                             only_joins=True)
    return 100.0 * per_call * record["calls"] / seconds
