"""The control: the program with its contractions one precision step
below what the configuration states (``high``, three bf16 passes, for f32
at ``highest``), set through ``repro.precision.MATMUL_PRECISION`` before
anything is traced. The check must refuse it.

At ``high`` the left driver does not end a factorization within a run's
time (``run.py`` then ends by its watchdog with ``correct`` false), so
this test reads the number that separates the two before that: the
set-up's compressed operator against the dense matrix, ``compress_err``
as the check compares it, at the cell's own size on three seeds. A CPU
runs f32 contractions at full precision whatever they name, so the test
needs a TPU (run it alone:
``python -m pytest benchmarks/chip/tests/test_control.py``).
"""

import jax
import jax.numpy as jnp
import pytest

import bench
import problem
from drivers import factor

SEEDS = (88001, 88002, 88003)


@pytest.fixture(scope="module")
def tpu():
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the lower matmul precision has no effect on a CPU")
    bench.enable_compile_cache()
    bench.set_matmul_precision("high")
    yield
    bench.set_matmul_precision("highest")


@pytest.mark.parametrize("cell", ["cov3d-factor-left", "cov2d-factor-left"])
def test_control_is_refused(tpu, cell):
    _, cfg, _, limits = bench.cell_files(cell, bench.spec())
    for seed in SEEDS:
        ctx = type("Ctx", (), {"cfg": cfg, "seed": seed})()
        K = problem.dense_covariance(problem.points(cfg, seed), cfg)
        A = factor.compress(K, ctx).A
        z = jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(bench.seed32(seed, 3)), 0),
            (cfg["n"], 4), jnp.float32)
        az = problem.matmul_dense(K, z)
        err = problem.tlr_apply(A.D, A.U, A.V, A.ranks, z) - az
        value = float(jnp.linalg.norm(err) / jnp.linalg.norm(az))
        assert value > limits["compress_err"], (seed, value)
