import jax
import pytest

# TLR numerical validation runs in f64 (the paper's precision). LM-side code
# passes explicit dtypes everywhere, so enabling x64 globally is safe.
jax.config.update("jax_enable_x64", True)


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_executables_between_modules():
    """Release jit executables when a test module finishes.

    The CPU XLA backend in this toolchain segfaults once a single process
    accumulates enough compiled executables (the full suite compiles a few
    thousand: every module warms its own shapes and options). No single
    module comes anywhere near the limit, so dropping the caches at module
    boundaries keeps the whole run bounded; tests that pin compile counts
    warm up and measure within one module, so they are unaffected.
    """
    yield
    jax.clear_caches()


@pytest.fixture
def fresh_column_steps():
    """Start from an empty process-wide cache of the left driver's jitted
    column steps, so a test that pins which columns trace sees the first
    factorization of its configuration whatever ran before it."""
    from repro.core.cholesky import _column_steps

    _column_steps.cache_clear()
