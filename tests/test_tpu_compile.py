"""Ahead-of-time compiles of the five Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: lowering and
compiling against a *described* ``v5e:2x2`` topology raises what the
chip's Mosaic compiler would raise (unsupported primitives, illegal block
shapes, VMEM overruns), which interpret-mode parity tests cannot see. Each
kernel compiles at the f32 widths the factorization gives it: tile
b = 512, ARA block s = 16, ladder widths 1 ... r_max = 128, one and
sixteen right-hand sides.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import set_tile_mesh
from repro.kernels import ops
from repro.kernels.batched_gemm import batched_gemm_pallas
from repro.kernels.batched_qr import batched_qr_pallas
from repro.kernels.lr_sample import lr_sample_pallas
from repro.kernels.small_svd import small_svd_pallas
from repro.kernels.tlr_matvec import tile_chain_pallas

B, S, T = 512, 16, 64
WIDTHS = [1, 16, 128]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    # x64 off, as on the chip (the suite's conftest turns it on)
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("r", WIDTHS)
def test_batched_qr_compiles(one_chip, r):
    _compile(lambda y: batched_qr_pallas(y, interpret=False),
             _spec(one_chip, (T, B, r)))


@pytest.mark.parametrize("n", [16, 128])
def test_small_svd_compiles(one_chip, n):
    _compile(lambda m: small_svd_pallas(m, interpret=False),
             _spec(one_chip, (T, n, n)))


@pytest.mark.parametrize("r", WIDTHS)
def test_batched_gemm_compiles(one_chip, r):
    _compile(lambda a, b, k: batched_gemm_pallas(a, b, k, interpret=False),
             _spec(one_chip, (T, B, r)), _spec(one_chip, (T, r, S)),
             _spec(one_chip, (T,), jnp.int32))


@pytest.mark.parametrize("r", WIDTHS)
def test_lr_sample_compiles(one_chip, r):
    _compile(lambda u, v, w: lr_sample_pallas(u, v, w, interpret=False),
             _spec(one_chip, (T, 8, B, r)), _spec(one_chip, (T, 8, B, r)),
             _spec(one_chip, (8, B, S)))


@pytest.mark.parametrize("nrhs", [1, 16])
@pytest.mark.parametrize("r", WIDTHS)
def test_tile_chain_compiles(one_chip, r, nrhs):
    _compile(lambda u, v, x: tile_chain_pallas(u, v, x, interpret=False),
             _spec(one_chip, (T, B, r)), _spec(one_chip, (T, B, r)),
             _spec(one_chip, (T, B, nrhs)))


def test_kernel_on_tile_mesh_compiles(topo, monkeypatch):
    """Under a tile mesh (the sharded factorization) the kernels run in
    ``shard_map`` over the data axis: XLA cannot partition a Mosaic call,
    and compiling it for four chips without the wrapper fails."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    # an odd batch (replicated) exercises the pad-to-the-quantum path
    tiles = NamedSharding(mesh, PartitionSpec())
    prev = set_tile_mesh(mesh)
    try:
        compiled = _compile(
            lambda a, b, k: ops.batched_gemm(a, b, k, impl="pallas"),
            _spec(tiles, (T + 1, B, 128)), _spec(tiles, (T + 1, 128, S)),
            _spec(tiles, (T + 1,), jnp.int32))
    finally:
        set_tile_mesh(prev)
    assert compiled.memory_analysis() is not None
