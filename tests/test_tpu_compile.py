"""Every Pallas kernel compiles for a TPU v5e chip at real widths.

The other kernel tests run on the CPU, where the kernels execute in
interpret mode or take the jnp fallback, so Mosaic never sees them.
Here each kernel module is compiled with ``interpret=False`` for a
described (not attached) ``v5e:2x2`` topology, one chip of it, which
runs the TPU compiler's tiling, VMEM and memory-space checks without a
chip.  Each test asserts that the compiled program holds the Mosaic
kernel (``tpu_custom_call``).

The topology is described only inside the ``topo`` fixture, never while
a module is imported: only one process at a time may load the TPU
library, and with several test workers an import-time call would make
the workers collect different tests.  Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.fl.compression import CompressionPolicy
from repro.kernels import broadcast, fused_update, policy_update, quantize, tree_aggregate

ROWS = 85_248  # a ResNet-34-sized delta on the (rows, 256) grid: 21.8 M f32
COMMITS = 8
CHAIN_CAP = CompressionPolicy().chain_cap


def _tiles(n: int, tile: int) -> int:
    return -(-n // tile) * tile


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described topology, with the persistent compile
    cache off: entries written for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "G,C,L",
    [
        (ROWS, COMMITS, tree_aggregate.TILE),  # buffered_aggregate_quantized apply
        (2, 4, _tiles(ROWS * 256, tree_aggregate.TILE)),  # a tree level at payload width
        (5, 9, 2 * tree_aggregate.TILE),  # a tree level of a small app model
    ],
)
def test_tree_aggregate_groups_compiles(one_chip, G, C, L):
    _compile(
        lambda g, w: tree_aggregate.tree_aggregate_groups(g, w), one_chip,
        ((G, C, L), jnp.float32), ((G, C), jnp.float32),
    )


@pytest.mark.parametrize(
    "rows,levels",
    [
        pytest.param(ROWS, 127, id="127"),
        pytest.param(ROWS, CompressionPolicy().downlink_levels,
                     id=str(CompressionPolicy().downlink_levels)),
        # the MNIST 2NN's delta: a ragged last row block
        pytest.param(779, 127, id="rows779"),
    ],
)
def test_qsgd_quantize_compiles(one_chip, rows, levels):
    _compile(
        lambda x, r: quantize.qsgd_quantize(x, r, levels=levels), one_chip,
        ((rows, quantize.ROW), jnp.float32), ((rows, quantize.ROW), jnp.float32),
    )


def test_qsgd_dequantize_compiles(one_chip):
    _compile(
        quantize.qsgd_dequantize, one_chip,
        ((ROWS, quantize.ROW), jnp.int8), ((ROWS, 1), jnp.float32),
    )


@pytest.mark.parametrize("depth", [1, CHAIN_CAP])
def test_apply_quantized_broadcast_compiles(one_chip, depth):
    _compile(
        broadcast.apply_quantized_broadcast, one_chip,
        ((ROWS, broadcast.ROW), jnp.float32),
        ((depth, ROWS, broadcast.ROW), jnp.int8),
        ((depth, ROWS, 1), jnp.float32),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_compiles(one_chip, dtype):
    L = _tiles(ROWS * 256, fused_update.TILE)
    _compile(
        lambda w, g, w0: fused_update.fused_update(w, g, w0, lr=0.1, mu=0.01, wd=1e-4),
        one_chip, ((L,), dtype), ((L,), dtype), ((L,), dtype),
    )


@pytest.mark.parametrize("N,K", [(1024, 16), (4096, 32)])
def test_policy_update_compiles(one_chip, N, K):
    M = 1 + K + 8  # pathplan.candidate_policy_set: uniform + K corners + 8 draws
    _compile(
        lambda pi, mask, cand, r: policy_update.policy_update(
            pi, mask, cand, r, tau=4, alpha=0.8, beta=0.4),
        one_chip,
        ((N, K), jnp.float32), ((N, K), jnp.bool_), ((M, K), jnp.float32),
        ((N, K), jnp.float32),
    )
