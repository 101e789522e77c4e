"""Jitted dispatch wrappers for the Pallas kernels.

``impl`` selects the execution path:
  * "pallas"    -- compiled Pallas TPU kernel (real hardware),
  * "interpret" -- Pallas interpreter (CPU validation; kernel body runs in
                   python/XLA with identical semantics),
  * "ref"       -- pure-jnp oracle (also what XLA fuses best on CPU).

The default (``impl=None``) is resolved per op: "pallas" on a TPU backend,
except for the ops named in ``TPU_XLA_DEFAULT``, and "ref" on any other
backend. "interpret" is only ever chosen explicitly (the kernel tests and
the CPU rehearsal of ``chip_smoke.py``). An explicit ``impl="pallas"``
always runs the kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..launch.mesh import dp_axes
from ..launch.sharding import tile_dp_size, tile_mesh
from . import ref as _ref
from .batched_gemm import batched_gemm_pallas
from .batched_qr import batched_qr_pallas
from .lr_sample import lr_sample_pallas
from .small_svd import small_svd_pallas
from .tlr_matvec import tile_chain_pallas


IMPLS = ("ref", "interpret", "pallas")

# Ops whose default on a TPU is the XLA path rather than their Pallas
# kernel, and why. The kernel compiles for v5e (tests/test_tpu_compile.py);
# it is only too slow to be the default.
TPU_XLA_DEFAULT = {
    "small_svd": "the one-sided Jacobi kernel runs one tile per grid step "
                 "through (n - 1) * sweeps sequential rounds of n x n "
                 "matmuls; on a v5e it takes 1.15 s for 64 cores at n=128 "
                 "against 48 ms for XLA's batched SVD",
}


def _on_tpu() -> bool:
    # A backend that fails to initialize raises here: it is not "not a TPU",
    # and picking the CPU path for it would hide the device.
    return jax.default_backend() == "tpu"


def default_impl(op: str | None = None) -> str:
    """The path ``impl=None`` takes for ``op`` on this backend."""
    if not _on_tpu():
        return "ref"
    return "ref" if op in TPU_XLA_DEFAULT else "pallas"


def resolve_impl(impl: str | None, op: str | None = None) -> str:
    """Resolve an impl knob (e.g. ``CholOptions.impl``) to a concrete path
    for ``op`` (one of the dispatch functions below; ``None`` names the
    kernel path as a whole, as ``stats["impl"]`` reports it).

    ``impl="pallas"`` compiles the kernels for real TPU hardware; off-TPU
    that request used to die deep inside ``pallas_call`` with an opaque
    backend message, so it is rejected up front here instead.
    """
    impl = impl or default_impl(op)
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "pallas" and not _on_tpu():
        raise RuntimeError(
            "impl='pallas' compiles the Pallas TPU kernels and requires a "
            f"TPU backend, but jax.default_backend() is "
            f"{jax.default_backend()!r}; use impl='interpret' to validate "
            "the kernel bodies on CPU, or impl='ref' for the pure-jnp "
            "oracles (DESIGN.md section 3)")
    return impl


def flop_estimate(fn, *args, **kwargs) -> float:
    """XLA ``cost_analysis`` FLOPs for one jitted call at these operand
    shapes (compile only, nothing executes).

    The padded-vs-useful accounting the rank-bucketed dispatch layer
    (``core/batching.py``) is judged by: lower the flat r_max-wide core and
    the per-bucket cores at their real shapes, and the FLOP ratio is the
    arithmetic the flat path wastes on zero padding. Static/keyword
    arguments must already be bound (``functools.partial``).
    """
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    ca = compiled.cost_analysis() or {}  # backends may report no cost model
    return float(ca.get("flops", 0.0))


def _over_tile_mesh(kernel, batched, *args):
    """Call a Pallas kernel, split over the installed tile mesh.

    Mosaic kernels cannot be partitioned by XLA's SPMD pass, so when a tile
    mesh is installed (``launch.sharding.set_tile_mesh``) the kernel runs
    under ``shard_map``: the leading tile-batch axis of every argument
    flagged in ``batched`` is split over the mesh's data-parallel axes
    (zero-padded to a multiple of their size; zero tiles are inert in every
    kernel), the others are replicated. Without a mesh it is a plain call.
    """
    mesh = tile_mesh()
    if mesh is None:
        return kernel(*args)
    axes = dp_axes(mesh)
    dp = tile_dp_size()
    n = args[batched.index(True)].shape[0]
    pad = -n % dp
    args = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)) if bt and pad
            else x for x, bt in zip(args, batched)]
    out = jax.shard_map(
        kernel, mesh=mesh,
        in_specs=tuple(P(axes) if bt else P() for bt in batched),
        out_specs=P(axes), check_vma=False)(*args)
    return jax.tree.map(lambda y: y[:n], out)


def lr_sample(Ui, Vi, W2, impl: str | None = None,
              width: int | None = None):
    """``width``: optional TilePlan bucket width -- the factor operands run
    at the bucket's ladder width instead of their padded r_max (sliced
    before the einsum on the ref path, before the ``pallas_call`` on the
    kernel paths so the BlockSpecs shrink with it)."""
    impl = resolve_impl(impl, "lr_sample")
    if width is not None and width < Ui.shape[-1]:
        if impl == "ref":
            Ui, Vi = Ui[..., :width], Vi[..., :width]
    if impl == "ref":
        return _ref.lr_sample_ref(Ui, Vi, W2)
    kernel = partial(lr_sample_pallas, interpret=(impl == "interpret"),
                     width=width)
    return _over_tile_mesh(kernel, (True, True, False), Ui, Vi, W2)


def batched_gemm(A, B, ranks, impl: str | None = None):
    impl = resolve_impl(impl, "batched_gemm")
    if impl == "ref":
        return _ref.batched_gemm_ref(A, B, ranks)
    kernel = partial(batched_gemm_pallas, interpret=(impl == "interpret"))
    return _over_tile_mesh(kernel, (True, True, True), A, B, ranks)


def tile_chain(U, V, X, impl: str | None = None,
               width: int | None = None):
    """``width``: optional TilePlan bucket width, same contract as
    :func:`lr_sample` (exact slice of the zero-padded factors)."""
    impl = resolve_impl(impl, "tile_chain")
    if width is not None and width < U.shape[-1]:
        if impl == "ref":
            U, V = U[..., :width], V[..., :width]
    if impl == "ref":
        return _ref.tile_chain_ref(U, V, X)
    kernel = partial(tile_chain_pallas, interpret=(impl == "interpret"),
                     width=width)
    return _over_tile_mesh(kernel, (True, True, True), U, V, X)


def batched_qr(Y, impl: str | None = None):
    """Batched economy QR (T, b, r) -> (Q, R); rank-deficient columns inert."""
    impl = resolve_impl(impl, "batched_qr")
    if impl == "ref":
        return _ref.batched_qr_ref(Y)
    kernel = partial(batched_qr_pallas, interpret=(impl == "interpret"))
    return _over_tile_mesh(kernel, (True,), Y)


def small_svd(M, impl: str | None = None):
    """Batched small-core SVD (T, m, n) -> (U, s, V), M ~= U diag(s) V^T,
    singular values sorted descending (the rounding pass truncates on that
    order)."""
    impl = resolve_impl(impl, "small_svd")
    if impl == "ref":
        return _ref.small_svd_ref(M)
    kernel = partial(small_svd_pallas, interpret=(impl == "interpret"))
    U, s, V = _over_tile_mesh(kernel, (True,), M)
    # Jacobi leaves values unsorted; sort here so every impl agrees.
    order = jnp.argsort(-s, axis=-1)
    s = jnp.take_along_axis(s, order, axis=-1)
    U = jnp.take_along_axis(U, order[:, None, :], axis=-1)
    V = jnp.take_along_axis(V, order[:, None, :], axis=-1)
    return U, s, V
