"""repro.core -- the paper's contribution: TLR symmetric factorizations.

Public API (operator-first since PR 2; DESIGN.md section 5):

  TLROperator                      construction + algebra facade
    .compress / .from_dense / .from_kernel   batched tile compression
    .matvec / @ / .to_dense / .memory_stats  operator algebra
    .cholesky(opts) / .ldlt(opts)            -> TLRFactorization
  TLRFactorization                 active factorization handle
    .solve(y) / .tri_solve / .tri_matvec     jitted bucketed TRSM solves
    .logdet() / .sample(key, num)            determinant / MVN sampling
    .matvec                                  preconditioner action (A^{-1})
  CholOptions, tlr_cholesky, tlr_ldlt        factorizations (CholOptions.algo
                                             picks left- vs right-looking)
  TLRMatrix                                  tile low rank representation
  TLRTiles                                   general (nonsymmetric) tile grid
  ARAParams, ara_compress_dense              adaptive randomized approx.
  tlr_matvec, tlr_trsv, pcg                  free-function operator algebra
                                             (pcg accepts (n, k) RHS with
                                             per-column masks since PR 7)
  BatchedPCG                                 incremental batched-RHS PCG
                                             engine (the serve-path core)
  tlr_round, tlr_axpy, tlr_scale, tlr_gemm, tlr_syrk   batched tile algebra
  TilePlan, tile_plan, plan_rank_buckets     rank-aware execution plans
                                             (memoized per ranks array;
                                             DESIGN.md section 9)
  choose_batching, resolve_policy            the batching="auto" policy
                                             (rank histogram + cost model)
  trace_count, trace_counts,                 unified compile-count registry
  trace_counts_diff
                                             ("trsm"/"algebra"/"batching"/
                                             "plan" keys)
  batching_trace_count, set_tile_mesh        rank-bucketed dynamic batching
                                             + tile-mesh sharding (DESIGN.md
                                             section 8; pad_tile_batch /
                                             tile_dp_size size buffers to
                                             the sharding quantum)
  Stage, SequentialSchedule,                 column-stage graph + schedules
  LookaheadSchedule, run_graph               both drivers execute (DESIGN.md
                                             section 12; CholOptions.lookahead
                                             picks the overlap schedule)
  RetryPolicy, HealthMonitor,                breakdown detection + bounded
  HealthEvent, BreakdownReport,              recovery (CholOptions.check /
  FactorizationBreakdown, column_flags       .retry; DESIGN.md section 13)
  tlr_newton_schulz                          Newton-Schulz TLR inverse / PCG
  covariance_problem, fractional_diffusion_problem   paper's test matrices
  covariance_points, exp_covariance_device   the same points / covariance
                                             built on the device
  fractional_diffusion_device                the section 6.2 operator built
                                             on the device, SPD in f32

Deprecated shims (kept for one release; each warns and delegates):
  from_dense          -> TLROperator.compress
(the PR-2 ``tlr_factor_solve`` / ``tlr_logdet`` / ``mvn_sample`` shims were
removed in PR 6 -- use the TLRFactorization handle methods)
"""

from .tlr import (  # noqa: F401
    TLRMatrix, from_dense, tlr_to_dense, zeros_like_structure,
    tril_index, tril_pairs, num_tiles, rank_heatmap,
)
from .ara import ARAParams, ara_compress_dense, run_ara_fused  # noqa: F401
from .operator import TLROperator, TLRFactorization  # noqa: F401
from .cholesky import (  # noqa: F401
    CholOptions, tlr_cholesky, tlr_ldlt,
    robust_cholesky, dense_ldlt_tile,
)
from .buckets import (trace_count, trace_counts,  # noqa: F401
                      trace_counts_diff)
from .solve import (  # noqa: F401
    BatchedPCG, PCGHistory, tlr_matvec, tlr_tri_matvec, tlr_trsv,
    tlr_trsv_reference, trsm_trace_count, pcg, tile_perm_to_element_perm,
)
from .generators import (  # noqa: F401
    grid_points, ball_points, exp_covariance, matern32_covariance,
    fractional_diffusion, covariance_problem, fractional_diffusion_problem,
    covariance_points, exp_covariance_device, fractional_diffusion_device,
)
from .algebra import (  # noqa: F401
    TLRTiles, algebra_trace_count, generalize, offd_index, offd_pairs,
    symmetrize, tlr_add_diag, tlr_axpy, tlr_gemm, tlr_round,
    tlr_round_tiles, tlr_scale, tlr_syrk, tlr_syrk_column, tlr_transpose,
)
from .batching import (  # noqa: F401
    BatchPlan, RankBucket, TilePlan, batching_trace_count, bucket_width,
    bucketed_round_tiles, choose_batching, pad_tile_batch,
    plan_rank_buckets, rank_ladder, resolve_batching, resolve_policy,
    set_tile_mesh, shard_tile_batch, tile_dp_size, tile_mesh, tile_plan,
)
from .stages import (  # noqa: F401
    LookaheadSchedule, Schedule, SequentialSchedule, Stage, build_deps,
    run_graph,
)
from .health import (  # noqa: F401
    BreakdownReport, FactorizationBreakdown, HealthEvent, HealthMonitor,
    RetryPolicy, column_flags,
)
from .precond import NewtonSchulzInfo, tlr_newton_schulz  # noqa: F401
from .ordering import kd_tree_ordering, morton_ordering  # noqa: F401
from .dense_ref import (  # noqa: F401
    dense_cholesky, dense_ldlt, blocked_cholesky_left, spectral_norm_est,
    spectral_norm_est_op,
)
