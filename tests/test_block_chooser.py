"""com_matmul's block chooser (``repro.kernels.com_matmul.choose_blocks``)
on every layer matmul of the benchmark's cells, as the executor's chain
runs them (``matmul_plans``): legal Mosaic block shapes, no padding, a
working set within the VMEM budget, and fewer grid steps than 128³
blocks. Pure shape arithmetic; ``tests/test_tpu_compile.py`` compiles the
same chains for a described v5e."""
import pytest

from repro.core import compile_program
from repro.core.executor import matmul_plans
from repro.kernels.com_matmul import (
    VMEM_BUDGET_BYTES,
    choose_blocks,
    matmul_plan,
    vmem_bytes,
)
from repro.sweep.registry import resolve_network

# (network, images on one device): the cells, and one shard of
# vgg16-imagenet's batch of 8 over four devices (chip_smoke --four-chips)
CHAINS = [("vgg16-imagenet", 1), ("vgg16-imagenet", 32),
          ("vgg11-cifar", 256), ("vgg16-imagenet", 2),
          ("resnet50-imagenet", 32), ("vgg11-cifar", 1)]


def _steps_128(p):
    def blocks(d):
        return -(-d // 128)
    return blocks(p.m) * blocks(p.k) * blocks(p.n)


def _plans(network, images):
    return matmul_plans(compile_program(resolve_network(network)), images)


CASES = [(network, images, name, plan)
         for network, images in CHAINS
         for name, plan in _plans(network, images)]


@pytest.mark.parametrize(
    "network, images, name, plan", CASES,
    ids=[f"{n}-b{i}-{name.split('.')[-1]}" for n, i, name, _ in CASES])
def test_chosen_blocks_are_legal_dividing_and_fit(network, images, name,
                                                  plan):
    p = plan
    # each block is its whole dimension or a multiple of its tile
    assert p.bm == p.m or p.bm % 8 == 0, p
    assert p.bk == p.k or p.bk % 128 == 0, p
    assert p.bn == p.n or p.bn % 128 == 0, p
    # and divides it, so that nothing is padded
    assert (p.m % p.bm, p.k % p.bk, p.n % p.bn) == (0, 0, 0), p
    assert p.pad_bytes == 0
    assert p.steps == (p.m // p.bm) * (p.k // p.bk) * (p.n // p.bn)
    # a join's double-buffered residual block counts too
    assert vmem_bytes(p.bm, p.bk, p.bn, 4, p.joins) <= VMEM_BUDGET_BYTES
    assert p.steps <= _steps_128(p)


@pytest.mark.parametrize("network, images, steps_128, at_most", [
    ("vgg16-imagenet", 32, 289_008, 10_000),
    ("vgg16-imagenet", 1, 16_910, 550),
    ("vgg11-cifar", 256, 23_104, 850),
])
def test_a_calls_grid_steps_fall_from_the_128_cube_count(
        network, images, steps_128, at_most):
    plans = [p for _, p in _plans(network, images)]
    assert sum(_steps_128(p) for p in plans) == steps_128
    assert 0 < sum(p.steps for p in plans) <= at_most


@pytest.mark.parametrize("m, k, n", [
    (1, 1, 1), (7, 27, 10), (10_007, 300, 70), (2, 25_088, 4_096),
])
def test_any_shape_gets_legal_fitting_blocks(m, k, n):
    p = matmul_plan(m, k, n, 4)
    for b, d, tile in ((p.bm, m, 8), (p.bk, k, 128), (p.bn, n, 128)):
        assert b == d or b % tile == 0, p
    assert vmem_bytes(p.bm, p.bk, p.bn, 4) <= VMEM_BUDGET_BYTES
    # a prime M too tall for one block is padded to a multiple of 8 rows
    padded = (m, k, n) == (10_007, 300, 70)
    assert (p.pad_bytes > 0) == padded, p


def test_a_block_given_is_kept_and_the_others_are_chosen():
    free = choose_blocks(25_088, 4_608, 512, 4)
    bm, bn, bk = choose_blocks(25_088, 4_608, 512, 4, block_m=8)
    assert bm == 8 != free[0]
    assert vmem_bytes(bm, bk, bn, 4) <= VMEM_BUDGET_BYTES
    # a block given that does not divide pads, as before the chooser
    p = matmul_plan(64, 576, 64, 4, block_m=48, block_n=128, block_k=128)
    assert (p.bm, p.bn, p.bk) == (48, 128, 128)
    assert p.steps == 2 * 1 * 5
    assert p.pad_bytes == 4 * (96 * 640 + 640 * 128)


# (bm, bn, bk) of each layer, as chosen before residual joins existed;
# the joins' VMEM term leaves every chain without a join as it was
VGG_BLOCKS = {
    ("vgg16-imagenet", 32): [
        (2048, 64, 27), (1024, 64, 576), (1024, 128, 576), (784, 128, 1152),
        (784, 128, 1152), (448, 128, 2304), (448, 128, 2304),
        (448, 128, 2304), (512, 512, 512), (512, 512, 512), (448, 128, 2304),
        (448, 128, 2304), (448, 128, 2304), (32, 4096, 256), (32, 4096, 256),
        (32, 1000, 1024)],
    ("vgg16-imagenet", 1): [
        (1792, 64, 27), (1024, 64, 576), (896, 128, 576), (784, 128, 1152),
        (784, 128, 1152), (448, 128, 2304), (448, 128, 2304),
        (784, 128, 1152), (784, 128, 1152), (784, 128, 1152),
        (196, 512, 1152), (196, 512, 1152), (196, 512, 1152),
        (1, 4096, 256), (1, 4096, 256), (1, 1000, 1024)],
    ("vgg11-cifar", 256): [
        (2048, 64, 27), (1024, 128, 576), (256, 256, 1152), (1024, 128, 768),
        (512, 512, 384), (512, 512, 512), (512, 512, 512), (512, 512, 512),
        (256, 2048, 256), (256, 2048, 256), (256, 10, 2048)],
}


@pytest.mark.parametrize("network, images", sorted(VGG_BLOCKS))
def test_vgg_plans_are_unchanged(network, images):
    program = compile_program(resolve_network(network))
    from repro.core.executor import ProgramExecutor, random_weights

    plan = ProgramExecutor(program, random_weights(program),
                           backend="jax").plan(images)
    assert [(p.bm, p.bn, p.bk) for _, p in plan] == \
        VGG_BLOCKS[network, images]
    assert not any(p.joins or p.residual_bytes or p.pad_bytes
                   for _, p in plan)


def test_a_join_counts_its_residual_block_in_vmem():
    """The residual's ``(bm, bn)`` block, double-buffered, is one more
    output block twice over."""
    plain = vmem_bytes(1024, 64, 256, 4)
    assert vmem_bytes(1024, 64, 256, 4, True) - plain == 2 * 1024 * 256 * 4
    # ResNet-50's stage-2 join: its fastest blocks without a residual
    # (896 rows) would not fit with one, so the chooser takes 784
    m, k, n = 25_088, 128, 512
    bm, bn, bk = choose_blocks(m, k, n, 4)
    rm, rn, rk = choose_blocks(m, k, n, 4, residual=True)
    assert vmem_bytes(rm, rk, rn, 4, True) <= VMEM_BUDGET_BYTES
    assert vmem_bytes(bm, bk, bn, 4, True) > VMEM_BUDGET_BYTES
    assert (bm, rm) == (896, 784)
