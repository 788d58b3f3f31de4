"""Compile the executor's main path for a described TPU v5e chip.

Nothing here runs on a chip: the TPU compiler that ships with JAX compiles
for a ``v5e:2x2`` topology that is described, not attached. That checks
what Pallas interpret mode never does (tile alignment, VMEM limits, and
whether the program fits the chip's 16 GB of HBM) at the real widths of
``vgg16-imagenet`` at batch 8: one test per distinct ``com_matmul_padded``
shape class of the chain, and one for the whole chain; and the whole chain
of each benchmark cell, whose blocks ``choose_blocks`` picks per layer
within the kernel's default VMEM limit, a residual join's block included;
and, in ``resnet50-imagenet``'s chain, that every join is inside its
layer's kernel.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import compile_program
from repro.core.executor import jax_forward
from repro.core.mapping import ConvSpec
from repro.sweep.registry import resolve_network
from repro.kernels.com_matmul import com_matmul_padded

V5E_HBM_BYTES = 16 * 2**30
BATCH = 8
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU library otherwise logs to /tmp/tpu_logs whatever TMPDIR says
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("m, k, n", [
    (BATCH * 224 * 224, 27, 64),      # conv1_1: 3x3x3 im2col rows
    (BATCH * 224 * 224, 576, 64),     # conv1_2: the 224x224 64->64 conv
    (BATCH, 25088, 4096),             # fc6: flattened 7x7x512 -> 4096
], ids=["conv1_1", "conv1_2", "fc6"])
def test_com_matmul_compiles_for_v5e(one_chip, m, k, n):
    fn = jax.jit(lambda x, w: com_matmul_padded(x, w, activation="relu"))
    compiled = fn.lower(_spec((m, k), one_chip),
                        _spec((k, n), one_chip)).compile()
    assert compiled.as_text().count(KERNEL_CALL) == 1


def test_vgg16_chain_compiles_for_v5e(one_chip):
    program = compile_program(resolve_network("vgg16-imagenet"))
    layers = program.workload.layers
    first = layers[0]
    x = _spec((BATCH, first.h_in, first.w_in, first.c_in), one_chip)
    ws = [_spec((l.k, l.k, l.c_in, l.c_out) if isinstance(l, ConvSpec)
                else (l.c_in, l.c_out), one_chip) for l in layers]
    compiled = jax.jit(jax_forward(program, interpret=False)).lower(
        x, ws).compile()
    assert compiled.as_text().count(KERNEL_CALL) == len(layers) == 16
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem


@pytest.mark.parametrize("network, batch", [
    ("vgg16-imagenet", 1), ("vgg16-imagenet", 32), ("vgg11-cifar", 256),
    ("resnet50-imagenet", 32), ("vgg11-cifar", 1),
], ids=["vgg16-b1", "vgg16-b32", "vgg11-b256", "resnet50-b32", "vgg11-b1"])
def test_cell_chain_compiles_for_v5e_unpadded(one_chip, network, batch):
    program = compile_program(resolve_network(network))
    layers = program.workload.layers
    first = layers[0]
    x = _spec((batch, first.h_in, first.w_in, first.c_in), one_chip)
    ws = [_spec((l.k, l.k, l.c_in, l.c_out) if isinstance(l, ConvSpec)
                else (l.c_in, l.c_out), one_chip) for l in layers]
    text = jax.jit(jax_forward(program, interpret=False)).lower(
        x, ws).compile().as_text()
    assert text.count(KERNEL_CALL) == len(layers)
    # the chosen blocks divide every matmul: no block padding, no slice
    assert "/matmul/pad/" not in text and "/matmul/unpad/" not in text


def test_resnet50_joins_are_inside_their_kernels(one_chip):
    """The lowered ResNet-50 chain adds no tensors outside the Pallas
    kernels: each of its 16 joins is the epilogue of its layer's kernel,
    which takes the shortcut as its third operand. The one add left is
    the global average pool's reduction."""
    import re

    program = compile_program(resolve_network("resnet50-imagenet"))
    layers = program.workload.layers
    first = layers[0]
    x = _spec((BATCH, first.h_in, first.w_in, first.c_in), one_chip)
    ws = [_spec((l.k, l.k, l.c_in, l.c_out) if isinstance(l, ConvSpec)
                else (l.c_in, l.c_out), one_chip) for l in layers]
    text = jax.jit(jax_forward(program, interpret=False)).lower(
        x, ws).as_text()
    assert re.findall(r"= stablehlo\.add ", text) == []
    assert text.count("applies stablehlo.add") == 1
    calls = [line for line in text.splitlines()
             if "@tpu_custom_call" in line]
    assert len(calls) == len(layers) == 54
    operands = [line.split("@tpu_custom_call(")[1].split(")")[0].count("%")
                for line in calls]
    joins = [l for l in layers if getattr(l, "residual_from", None)]
    assert operands.count(3) == len(joins) == 16
    assert operands.count(2) == len(layers) - 16
