"""Which layer of the network, and which step of it, each device op serves.

The program wraps each layer of its jitted chain in ``jax.named_scope(<layer
name>)``, with sub-scopes ``im2col``, ``matmul`` and ``pool``, and the block
padding around the kernel in ``pad`` and ``unpad``. The scopes reach the
compiled HLO as each instruction's ``metadata={op_name="jit(forward)/<layer>
/<step>/.../<primitive>"}``, not the profiler's op names, so the map from a
trace's ops to layers is read from the compiled chain itself: the cell's
chain, compiled for its batch on the default device, whose op labels
(``trace.op_label``) are the trace's, and beside it the one other module
that runs in each call, the upload's ``jit_convert_element_type``. A fusion
carries no metadata of its own and takes the scope of what it writes (its
fused computation's root); an op the compiler made from none of the
program's (a concatenation's pieces, a copy between memories) takes the
scope of the op it feeds (``hlo_scopes``).

An op's role (``op_role``) is ``kernel`` for the Pallas kernel, else the
innermost of those step scopes, ``layer`` where an op is in a layer's
scope and none of its steps; an op in no layer's scope (the upload's) is
chain-level. A trace that holds an op the map does not is not the chain
the map was compiled from: the readers then report nothing.
"""
from __future__ import annotations

import functools
import re
import sys
from typing import Dict, Optional, Tuple

from bench import trace

ROLES = ("im2col", "matmul", "pad", "unpad", "pool")

_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_NAME = re.compile(r"^(?:ROOT )?%([\w.\-]+) = ")
_OPCODE = re.compile(r" [a-z][\w\-]*\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def _operands(line: str) -> list:
    m = _OPCODE.search(line, line.index(" = "))
    if m is None:
        return []
    depth, i = 1, m.end()
    while depth and i < len(line):
        depth += {"(": 1, ")": -1}.get(line[i], 0)
        i += 1
    return _REF.findall(line[m.end():i])


def hlo_scopes(text: str) -> Dict[str, str]:
    """``{op label: op_name}`` for every instruction of a compiled HLO
    module's text. An instruction in no layer's scope by its own metadata
    takes, in this order, the scope of what it writes (a fusion's root, or
    each element of a tuple root), that of the first op that consumes its
    result, and that of any op it calls (a fusion's computation, root
    first). So the pieces of a concatenation the compiler rewrote take the
    concatenation's scope, even where one of them also slices the layer
    before; and its copies between memories, which carry no source op,
    that of the op they feed. What finds no layer keeps its own
    ``op_name``, or ``""``."""
    comps: Dict[str, list] = {}
    roots: Dict[str, str] = {}
    own: Dict[str, str] = {}
    calls: Dict[str, list] = {}
    operands: Dict[str, list] = {}
    users: Dict[str, list] = {}
    labels: Dict[str, str] = {}
    current = None
    for raw in text.splitlines():
        line = raw.strip()
        m = _COMP.match(line)
        if m and " = " not in line.split("(")[0]:
            current = m.group(1)
            comps[current] = []
            continue
        m = _NAME.match(line)
        if m is None or current is None:
            continue
        name = m.group(1)
        comps[current].append(name)
        if line.startswith("ROOT "):
            roots[current] = name
            line = line[5:]
        labels[name] = trace.op_label(line)
        found = _OP_NAME.search(line)
        if found:
            own[name] = found.group(1)
        calls[name] = _CALLS.findall(line)
        operands[name] = _operands(line)
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)

    def layered(name: str) -> str:
        op = own.get(name, "")
        return op if layer_role(op) is not None else ""

    def written(name: str) -> str:
        for comp in calls.get(name, []):
            root = roots.get(comp)
            if root is None:
                continue
            out = [root]
            if labels[root].split(" ")[1:2] == ["tuple"]:
                out = operands[root]
            for n in out:
                if layered(n):
                    return layered(n)
        return ""

    @functools.lru_cache(maxsize=None)
    def of_computation(comp: str) -> str:
        names = comps.get(comp, [])
        order = [roots[comp]] if comp in roots else []
        order += [n for n in reversed(names) if n not in order]
        for n in order:
            scope = layered(n) or inside(n)
            if scope:
                return scope
        return ""

    def inside(name: str) -> str:
        for comp in calls.get(name, []):
            scope = of_computation(comp)
            if scope:
                return scope
        return ""

    @functools.lru_cache(maxsize=None)
    def of_instruction(name: str) -> str:
        scope = layered(name) or written(name)
        for user in users.get(name, []):
            if scope:
                break
            scope = of_instruction(user)
        return scope or inside(name)

    return {labels[n]: of_instruction(n) or own.get(n, "") for n in labels}


def layer_role(op_name: str) -> Optional[Tuple[str, str]]:
    """``(layer, role)`` of an op's ``op_name``, or None where it lies in
    no layer's scope."""
    scopes = [c for c in op_name.split("/")[:-1] if not c.startswith("jit(")]
    if not scopes:
        return None
    steps = [c for c in scopes[1:] if c in ROLES]
    return scopes[0], steps[-1] if steps else "layer"


def chain_args(network: str, batch: int):
    """``(x, ws)``: shapes of the jitted chain's float32 arguments for
    ``network`` at ``batch``, the images and the layers' weights."""
    import jax
    import jax.numpy as jnp

    from repro.core import compile_program
    from repro.core.mapping import ConvSpec
    from repro.sweep.registry import resolve_network

    layers = compile_program(resolve_network(network)).workload.layers
    first = layers[0]
    x = jax.ShapeDtypeStruct((batch, first.h_in, first.w_in, first.c_in),
                             jnp.float32)
    ws = [jax.ShapeDtypeStruct((l.k, l.k, l.c_in, l.c_out)
                               if isinstance(l, ConvSpec)
                               else (l.c_in, l.c_out), jnp.float32)
          for l in layers]
    return x, ws


def compiled_text(network: str, batch: int) -> str:
    """The HLO text of the program's jitted chain for ``network`` at
    ``batch``, compiled for the default device as the executor compiles
    it (its float32 weights, the backend's own interpret default)."""
    import jax

    from repro.core import compile_program
    from repro.core.executor import default_interpret, jax_forward
    from repro.sweep.registry import resolve_network

    program = compile_program(resolve_network(network))
    forward = jax_forward(program, interpret=default_interpret())
    return jax.jit(forward).lower(*chain_args(network, batch)) \
        .compile().as_text()


def upload_text(shape) -> str:
    """The HLO text of the module the executor's upload runs on the
    device: ``jnp.asarray`` of the float64 batch as float32 dispatches
    ``convert_element_type`` as a jitted primitive of its own."""
    import jax
    import numpy as np
    from jax._src import dispatch

    convert = dispatch.xla_primitive_callable(
        jax.lax.convert_element_type_p, new_dtype=np.dtype(np.float32),
        weak_type=False, sharding=None)
    return convert.lower(jax.ShapeDtypeStruct(shape, np.float32)) \
        .compile().as_text()


@functools.lru_cache(maxsize=4)
def cell_scopes(network: str, batch: int) -> Dict[str, str]:
    """``hlo_scopes`` of the cell's chain and of its upload; compiled once
    a process."""
    x, _ = chain_args(network, batch)
    return {**hlo_scopes(upload_text(x.shape)),
            **hlo_scopes(compiled_text(network, batch))}


def op_role(label: str, scopes: Dict[str, str]
            ) -> Optional[Tuple[str, str]]:
    """``(layer, role)`` of the device op ``label`` (``trace.op_label``)
    by the scope map: role ``kernel`` for the Pallas kernel, else its
    step's; None where the op lies in no layer's scope. KeyError where
    the map does not hold the op."""
    lr = layer_role(scopes[label])
    if lr is not None and label.split(" ")[1:2] == ["tpu_custom_call"]:
        return lr[0], "kernel"
    return lr


def seconds_by_role(ops: Dict[str, float],
                    scopes: Dict[str, str]) -> Optional[Dict[str, float]]:
    """Device seconds of ``ops`` (label → seconds, as ``trace.Reduced``
    keeps them) by the role of each op; ops in no layer's scope are left
    out. None where ``scopes`` lacks an op of ``ops``."""
    missing = [label for label in ops if label not in scopes]
    if missing:
        print(f"scopes: {len(missing)} device ops of the trace are not in "
              f"the compiled chain's scope map, e.g. {missing[:3]}",
              file=sys.stderr)
        return None
    out: Dict[str, float] = {}
    for label, secs in ops.items():
        lr = op_role(label, scopes)
        if lr is not None:
            out[lr[1]] = out.get(lr[1], 0.0) + secs
    return out


def read_role_ms_per_img(trace_reduced, record, roles) -> Optional[float]:
    """Device milliseconds per image of the ops whose role is one of
    ``roles``; None where the chain has no such scope (a program without
    layer scopes), where the trace holds an op the cell's compiled chain
    does not (the executor compiled another chain), or where the window
    holds no image."""
    if record["images"] == 0 or not trace_reduced.ops:
        return None
    scopes = cell_scopes(record["cfg"]["network"], record["mix"]["batch"])
    if not any((layer_role(s) or ("", ""))[1] in roles
               for s in scopes.values()):
        return None
    by_role = seconds_by_role(trace_reduced.ops, scopes)
    if by_role is None:
        return None
    return 1e3 * sum(by_role.get(r, 0.0) for r in roles) / record["images"]
