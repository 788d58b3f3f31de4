"""Executor glue: device time of the ops under a layer's ``pad`` and
``unpad`` scopes (``com_matmul_padded`` padding the operands to block
multiples and slicing the result back), in milliseconds per image. Ops map
to scopes through the cell's compiled chain (``bench/scopes.py``); None
where the chain has no such scope."""
from bench import scopes


def read(trace, record):
    return scopes.read_role_ms_per_img(trace, record, ("pad", "unpad"))
