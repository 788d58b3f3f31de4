"""Shortcut: device time of the ops under a layer's ``shortcut`` scope,
the projection layers of a residual network (their strided slice and
their kernel), in milliseconds per image. Ops map to scopes through the
cell's compiled chain (``bench/scopes.py``); None where the chain has no
such scope or the trace holds an op the chain does not."""
from bench import scopes


def read(trace, record):
    if record["images"] == 0 or not trace.ops:
        return None
    ops = scopes.cell_scopes(record["cfg"]["network"], record["mix"]["batch"])
    if any(label not in ops for label in trace.ops):
        return None
    seconds = sum(secs for label, secs in trace.ops.items()
                  if "shortcut" in ops[label].split("/")[:-1])
    if seconds <= 0:
        return None
    return 1e3 * seconds / record["images"]
