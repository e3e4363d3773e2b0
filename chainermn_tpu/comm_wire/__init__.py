"""Gradient wire layer: bucketed fused allreduce with compressed wire
formats and error feedback.

Two pieces:

* :mod:`.planner` — a deterministic, size-targeted bucket plan (a pure
  function of the gradient pytree's shapes/dtypes) that groups leaves
  into contiguous dtype-homogeneous wire buffers, each reduced with ONE
  collective (vs one per leaf before this layer: 267 collectives for
  ResNet-50 — pinned by the HLO census tests).
* :mod:`.codecs` — what the bucket looks like on the wire (``none`` /
  ``f32`` / ``bf16`` / ``f16`` / ``int8`` with per-bucket absmax
  scale) and the optional error-feedback residual that re-injects
  compressed rounding error into the next step.
* :mod:`.overlap` — the bucket-granularity comm/compute overlap engine
  (ISSUE 8): a jaxpr scheduling pass that re-emits the compiled step so
  each bucket's fused psum is dispatched the moment its bucket's leaves
  are produced, hiding sync under the remaining backward segments.
  Bit-identical to the synchronous wire (pure reordering); selected via
  ``create_multi_node_optimizer(..., overlap="bucket")``.
* :mod:`.schedules` — topology-aware multi-hop collective schedules
  (ISSUE 11): a cost-model-driven per-bucket choice between the flat
  psum and the DynamiQ-style ``hier_rs_ag`` triple (full-precision
  intra-slice reduce-scatter → codec-compressed inter-slice all-reduce
  → intra all-gather), plus the ``bcast_tree`` multicast spelling of
  the eager bcast.  The chosen schedule lands in the :class:`WirePlan`
  whose hash ``plan_agreement`` exchanges, so ranks cannot schedule
  apart.
* :mod:`.autotune` — the measured-feedback autotuner (ISSUE 12): a
  :class:`BandwidthProfile` artifact (per hop/class achieved-bandwidth
  curves + launch latencies, from ``profile_from_attribution`` over
  any telemetry export or a short ``calibrate`` sweep) that replaces
  the fixed 4 MiB/6-slot constants and the analytic flat-vs-hier byte
  rule with measured predictions; the profile's content hash is folded
  into ``WirePlan.plan_hash()`` so ``plan_agreement`` keeps ranks from
  tuning apart, and a rank missing the profile file raises
  :class:`ProfileMissingError` before the first collective.

Threaded through ``optimizers._sync_grads`` (compiled tier), the
double-buffering and ZeRO optimizers, and the eager
``allreduce_grad`` of the XLA and host-staged communicators.

"ONE collective a bucket, not one a leaf" is the layer's contract for
the leaves it packs, and the count every CPU-traced budget pins.  It is
not a speed claim for a large leaf on a TPU: on four v5e chips the
packed wire's step was 333 ms beside 250 for autodiff's all-reduce a
leaf (ledger, PR 50), so on a multi-chip TPU mesh
``optimizers._split_wire`` hands this layer only the leaves under
``bucket_bytes`` and ships the rest in their own shape (my chip run,
PR 51: 262.5 ms).
"""

from .planner import (  # noqa: F401
    DEFAULT_BUCKET_BYTES,
    DEFAULT_MAX_BUCKETS,
    Bucket,
    BucketPlan,
    LeafSlot,
    flatten_to_buckets,
    make_plan,
    pack_stacked,
    plan_for_trace,
    plan_of_tree,
    tune_wire_for_trace,
    unflatten_from_buckets,
    unpack_stacked,
)
from .codecs import (  # noqa: F401
    CODECS,
    WireConfig,
    codec_of_dtype,
    reduce_buckets,
    resolve_wire,
    storage_dtype,
    zero_residuals,
)
from .schedules import (  # noqa: F401
    GRAD_SCHEDULES,
    MIN_HIER_INTER_SAVINGS,
    SCHEDULES,
    AxisSplit,
    WirePlan,
    axis_split,
    bcast_tree_stages,
    hier_inter_savings,
    mesh_axis_sizes,
    plan_wire,
    reduce_wire,
    schedule_for_bucket,
    zero_residuals_wire,
)
from .autotune import (  # noqa: F401
    DEFAULT_CALIBRATION_SIZES,
    PROFILE_ENV,
    BandwidthProfile,
    ProfileMissingError,
    calibrate,
    is_wire_record,
    predict_bucket_sync,
    predict_collective,
    predict_cost,
    predict_hier_triple,
    predict_sync_time,
    profile_from_attribution,
    resolve_profile,
)
from .overlap import (  # noqa: F401
    OVERLAP_MODES,
    IssueRecord,
    OverlappedStep,
    assert_overlap_order,
    bucket_issue_report,
    issue_report,
    order_violations,
    resolve_overlap,
    schedule_jaxpr,
)


class WirePlanMismatchError(ValueError):
    """Processes disagree on the bucket plan — training would deadlock
    or silently mix wire layouts at the first bucketed collective."""


def plan_agreement(comm, plan, *, max_attempts: int = 4):
    """Verify every process computed the same bucket plan.

    Exchanges the plan hash over the communicator's object store.  The
    exchange is retried on transient faults AND on
    :class:`~chainermn_tpu.resilience.errors.PayloadCorruptionError`:
    a truncated payload is observed by EVERY process (each one unpickles
    each rank's payload), so all ranks fail — and re-exchange — in
    lockstep, which keeps the collective stream aligned (the one-sided
    failure that forbids retrying ordinary host collectives cannot
    happen here).  Returns the agreed hash; raises
    :class:`WirePlanMismatchError` on divergence.
    """
    from ..resilience.retry import lockstep_allgather

    mine = plan.plan_hash()

    hashes = lockstep_allgather(comm, mine,
                                site="comm_wire.plan_agreement",
                                max_attempts=max_attempts)
    if any(h != mine for h in hashes):
        raise WirePlanMismatchError(
            f"wire-plan hash mismatch across processes: {hashes} "
            "(the hash covers bucket layout, per-bucket schedule, mesh "
            "signature, and — when measured tuning is active — the "
            "BandwidthProfile content hash: a mismatch means the "
            "processes built different models, see different meshes, "
            "or loaded different wire profiles)"
        )
    return mine
