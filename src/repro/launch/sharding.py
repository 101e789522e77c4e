"""Sharding rules: DP/FSDP over (pod, data), Megatron TP + EP over model.

Parameter specs are derived from the pytree path:
  * attention wq/wk/wv: head (output) dim on "model"; wo: input dim on "model"
  * MLP wg/wu/wi: F on "model"; wd/wo: F on "model"
  * MoE experts (E, D, F): E on "model" when divisible (expert parallelism),
    else F on "model" (tensor parallelism inside experts) -- granite's 40
    experts do not divide 16-way, so it takes the TP path
  * embeddings: vocab on "model" (parallel CE loss)
  * SSD: in/out projections sharded on d_inner over "model"
  * FSDP: the largest remaining dim additionally sharded over (pod, data)
    when enabled and divisible (ZeRO-3; all-gather per scanned block)

Every rule degrades gracefully: a dim is sharded only when divisible by the
axis size, so reduced smoke configs fall back to replication.
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import dp_axes


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(path_s: str, shape: tuple[int, ...], mesh: Mesh,
               fsdp: bool = True) -> P:
    """PartitionSpec for one parameter leaf."""
    model = "model" if "model" in mesh.axis_names else None
    dp = dp_axes(mesh)
    msize = _axis_size(mesh, model)
    dsize = _axis_size(mesh, dp)
    nd = len(shape)
    spec: list = [None] * nd

    def try_shard(dim: int, axes) -> bool:
        size = _axis_size(mesh, axes)
        if axes and spec[dim] is None and shape[dim] % size == 0 and size > 1:
            spec[dim] = axes
            return True
        return False

    # Block-stacked params carry a leading repeats axis -> never shard dim 0
    # for block params; detect via path containing "blocks".
    offset = 1 if ("blocks/" in path_s and nd >= 2) else 0

    leaf = path_s.rsplit("/", 1)[-1]
    parent = path_s.rsplit("/", 2)[-2] if path_s.count("/") >= 1 else ""

    if leaf == "tok":                       # (V, D) embedding
        try_shard(0, model)
        if fsdp:
            try_shard(1, dp)
    elif leaf == "head":                    # (D, V) unembedding
        try_shard(1, model)
        if fsdp:
            try_shard(0, dp)
    elif leaf in ("wq", "wk", "wv"):        # (D, H*hd): heads on model
        try_shard(offset + 1, model)
        if fsdp:
            try_shard(offset + 0, dp)
    elif leaf == "wo" and parent in ("mixer", "cross"):  # (H*hd, D)
        try_shard(offset + 0, model)
        if fsdp:
            try_shard(offset + 1, dp)
    elif leaf in ("wg", "wu", "wi") and nd - offset == 3:   # MoE (E, D, F)
        if not try_shard(offset + 0, model):     # EP preferred
            try_shard(offset + 2, model)         # else TP on F
        if fsdp:
            try_shard(offset + 1, dp)
    elif leaf in ("wd", "wo") and nd - offset == 3:         # MoE (E, F, D)
        if not try_shard(offset + 0, model):
            try_shard(offset + 1, model)
        if fsdp:
            try_shard(offset + 2, dp)
    elif leaf in ("wg", "wu", "wi"):        # dense MLP (D, F)
        try_shard(offset + 1, model)
        if fsdp:
            try_shard(offset + 0, dp)
    elif leaf in ("wd",):                   # dense MLP (F, D)
        try_shard(offset + 0, model)
        if fsdp:
            try_shard(offset + 1, dp)
    elif leaf == "wo":                      # gelu MLP out (F, D)
        try_shard(offset + 0, model)
        if fsdp:
            try_shard(offset + 1, dp)
    elif leaf == "router":                  # (D, E)
        if fsdp:
            try_shard(offset + 0, dp)
    elif leaf == "w_in":                    # SSD (D, 2*din+2N+nh)
        try_shard(offset + 1, model)
        if fsdp:
            try_shard(offset + 0, dp)
    elif leaf == "w_out":                   # SSD (din, D)
        try_shard(offset + 0, model)
        if fsdp:
            try_shard(offset + 1, dp)
    elif nd - offset >= 2 and fsdp:
        # generic matrices: fsdp the largest dim
        dims = sorted(range(offset, nd), key=lambda d: -shape[d])
        try_shard(dims[0], dp)
    # vectors (norm scales, biases, A_log, ...) stay replicated
    return P(*spec)


def params_shardings(params_abstract, mesh: Mesh, fsdp: bool = True):
    """NamedSharding pytree matching an abstract parameter tree."""

    def one(path, leaf):
        spec = param_spec(_path_str(path), leaf.shape, mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_abstract)


# -- TLR tile-algebra batches (ROADMAP: sharded tile algebra) -------------------


def tile_batch_spec(n: int, ndim: int, mesh: Mesh) -> P:
    """PartitionSpec for a TLR tile-algebra batch: shard the leading
    (output-tile) axis over the DP axes when divisible, else replicate.

    The accumulation batches of ``tlr_gemm`` / ``tlr_syrk_column`` are
    embarrassingly parallel over output tiles -- one batched call per
    column with no cross-tile dependencies -- so the batch axis is the
    natural multi-device split (core/batching.py installs a mesh via
    ``set_tile_mesh``; without one the tile algebra stays single-device).
    """
    spec: list = [None] * ndim
    dp = dp_axes(mesh)
    if ndim and dp and n > 0 and n % _axis_size(mesh, dp) == 0:
        spec[0] = dp
    return P(*spec)


def tile_batch_sharding(mesh: Mesh, n: int, ndim: int) -> NamedSharding:
    """NamedSharding for one tile-batch array (see ``tile_batch_spec``)."""
    return NamedSharding(mesh, tile_batch_spec(n, ndim, mesh))


# The installed tile mesh. It lives here, below both the tile algebra
# (core/batching.py, which places its batches with it) and the kernel
# dispatch (kernels/ops.py, which splits Pallas calls over it).

TILE_MESH_MODES = ("pad", "error")

_TILE_MESH = {"mesh": None, "on_indivisible": "pad"}


def set_tile_mesh(mesh, *, on_indivisible: str = "pad"):
    """Install (or clear, with ``None``) the mesh that the tile-algebra
    batches shard their leading output-tile axis over. Returns the
    previously installed mesh so callers can restore it.

    ``on_indivisible`` decides what ``core.batching.shard_tile_batch`` does
    when a batch axis does not divide the mesh's DP axis size -- there is
    no silent identity fallback:

    * ``"pad"`` (default): zero-pad the leading axis up to the next
      multiple and shard the padded array. Zero tiles are numerically
      inert in every accumulation path, and the index-driven gathers /
      scatters of the tile algebra never reference the trailing pad
      slots, so results are unchanged. Call sites that must keep the
      caller-visible shape (``preserve_shape=True``) replicate instead.
    * ``"error"``: raise ``ValueError`` with the offending sizes, so a
      topology mismatch fails at the first sharded dispatch instead of
      silently running replicated.
    """
    if on_indivisible not in TILE_MESH_MODES:
        raise ValueError(f"on_indivisible must be one of {TILE_MESH_MODES}, "
                         f"got {on_indivisible!r}")
    prev = _TILE_MESH["mesh"]
    _TILE_MESH["mesh"] = mesh
    _TILE_MESH["on_indivisible"] = on_indivisible
    return prev


def tile_mesh():
    return _TILE_MESH["mesh"]


def tile_mesh_mode() -> str:
    """The installed ``on_indivisible`` mode (see :func:`set_tile_mesh`)."""
    return _TILE_MESH["on_indivisible"]


def tile_dp_size() -> int:
    """Size of the installed mesh's data-parallel axes (1 when no mesh)."""
    mesh = _TILE_MESH["mesh"]
    return 1 if mesh is None else _axis_size(mesh, dp_axes(mesh))


# -- inputs ---------------------------------------------------------------------


def batch_spec(shape: tuple[int, ...], mesh: Mesh) -> P:
    """Shard dim0 (global batch) over as many DP axes as divide it; for
    batch-1 decode, shard the sequence dim (dim with the largest extent)."""
    dp = dp_axes(mesh)
    spec: list = [None] * len(shape)
    if shape and shape[0] % _axis_size(mesh, dp) == 0 and len(dp) > 0:
        spec[0] = dp
    elif shape and len(dp) > 0 and shape[0] % mesh.shape[dp[-1]] == 0 \
            and mesh.shape[dp[-1]] > 1 and shape[0] > 1:
        spec[0] = dp[-1]
    else:
        # batch not shardable (e.g. long_500k batch=1): shard longest dim
        if len(shape) >= 2:
            d = int(np.argmax(shape[1:])) + 1
            if shape[d] % _axis_size(mesh, dp) == 0:
                spec[d] = dp
    return P(*spec)


def cache_spec(shape: tuple[int, ...], mesh: Mesh) -> P:
    """KV / SSM caches: stacked (R, B, S, KV, hd) or (R, B, ...). Shard batch
    over DP when divisible, else sequence; shard heads over model when
    divisible."""
    dp = dp_axes(mesh)
    spec: list = [None] * len(shape)
    if len(shape) < 2:
        return P(*spec)
    if shape[1] % _axis_size(mesh, dp) == 0 and shape[1] > 1:
        spec[1] = dp
    elif len(shape) >= 3 and shape[2] % _axis_size(mesh, dp) == 0:
        spec[2] = dp   # sequence-sharded cache (long-context decode)
    if len(shape) >= 4:
        msize = dict(mesh.shape).get("model", 1)
        if spec[3] is None and shape[3] % msize == 0 and shape[3] > 1:
            spec[3] = "model"       # KV heads over model
        elif len(shape) >= 5 and spec[2] is None and msize > 1 and \
                shape[2] % msize == 0:
            spec[2] = "model"       # else: cache sequence over model
    return P(*spec)


def inputs_shardings(specs: Any, mesh: Mesh):
    """NamedSharding pytree for input_specs structures (train/prefill/decode)."""

    def one(path, leaf):
        ps = _path_str(path)
        if "caches" in ps:
            return NamedSharding(mesh, cache_spec(leaf.shape, mesh))
        if leaf.shape == ():
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, batch_spec(leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(
        one, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def caches_shardings(caches: Any, mesh: Mesh):
    """NamedSharding pytree for decode-cache structures.

    Must be used whenever a cache subtree is passed on its own (the path no
    longer contains "caches", so ``inputs_shardings`` would misroute it to
    ``batch_spec`` -- which shards the leading layer-stack axis over data and
    forces a full cache all-gather inside the layer scan)."""

    def one(leaf):
        return NamedSharding(mesh, cache_spec(leaf.shape, mesh))

    return jax.tree.map(
        one, caches,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
