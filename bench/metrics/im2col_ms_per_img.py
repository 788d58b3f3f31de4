"""Executor glue: device time of the ops under a layer's ``im2col`` scope
(the input's padding, its K*K shifted slices and their concatenation), in
milliseconds per image. Ops map to scopes through the cell's compiled
chain (``bench/scopes.py``); None where the chain has no such scope."""
from bench import scopes


def read(trace, record):
    return scopes.read_role_ms_per_img(trace, record, ("im2col",))
