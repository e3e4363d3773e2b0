"""Pinned collective budgets for the repo's compiled paths.

Collective *count* is a performance contract: the flat-wire layer exists
precisely to turn ResNet-50's 267-leaf psum storm into <= 6 bucket
reductions + 1 loss pmean, and regressions re-introduce themselves
silently (one refactor that defeats bucketing costs nothing at trace
time and everything on the wire).  Each entry here is a ceiling, not a
target — enforced by :func:`~chainermn_tpu.analysis.checks.
assert_within_budget` on the walker census in the tier-1 tests, where a
string-grep of HLO used to live.

Ceilings derive from the wire plan: ``DEFAULT_MAX_BUCKETS`` (6) grad
buckets + 1 loss pmean (+1 int8 scale pmax where applicable).  ZeRO
replaces the bucket all-reduces with one reduce-scatter down and one
all-gather up per bucket.  The MoE expert path adds exactly 2 all_to_all
per MoE layer (dispatch + return) and the pipeline path 1 ppermute per
stage edge per direction.

What the contract covers (PR 51): these ceilings are of steps traced on
a CPU mesh, where the wire packs every leaf, and they hold there as they
did.  A count is not a time: on four v5e chips four bucket all-reduces
of 321-784 MB made a 333 ms step where 74 all-reduces of a leaf each
made a 250 ms one (ledger, PR 50), and on such a mesh the wire now ships
a leaf of ``bucket_bytes`` or more in its own shape, one asynchronous
all-reduce each, beside <= 6 buckets of the smaller ones
(``optimizers._split_wire``; my chip run, PR 51: 262.5 ms).  A TPU-mesh
step is therefore not held to these numbers; its form is read from the
compiled program (``step.collective_schedule``,
``benchmarks/collective_schedule_aot.py --grad-wire``).
"""

from __future__ import annotations

from .trace import CollectiveTrace
from .checks import assert_within_budget

# The data-parallel all_reduce ceiling is the wire-plan contract:
# comm_wire.DEFAULT_MAX_BUCKETS (6) grad buckets + 1 loss pmean, with
# one ceiling notch of slack (= 8) so a bucket-count change inside the
# promised <= 6 never trips the pin.  Numbers are literal (not imported)
# so a planner default drift FAILS the pin instead of moving it.
#
# Measured-feedback tuning (ISSUE 12) does not get its own pins: these
# ceilings are CONTRACTS a tuned plan must still satisfy, and the tuner
# guarantees it structurally — candidate slot budgets never exceed
# max_buckets, so tuning may only REDUCE collective counts (pinned by
# tests/test_autotune.py enforcing mlp_train_step on a profile-tuned
# compiled step).
BUDGETS = {
    # ISSUE 5 acceptance: the ResNet-50 train step stays <= 8 all-reduce
    # (267 leaves -> 4 default buckets + 1 loss pmean measured; 8 is the
    # contract ceiling the wire layer promised in ISSUE 4).
    "resnet50_train_step": {"all_reduce": 8},
    # transformer LM data-parallel step: same wire plan contract.
    "transformer_train_step": {"all_reduce": 8},
    # MLP/MNIST tier: small trees still bucket (never leaf-storm).
    "mlp_train_step": {"all_reduce": 8},
    # ZeRO-1: one reduce-scatter down + one all-gather up per bucket,
    # loss pmean stays the only all-reduce.
    "zero_train_step": {
        "reduce_scatter": 6,
        "all_gather": 6,
        "all_reduce": 1,
    },
    # Expert-parallel MoE layer: dispatch + return = exactly 2
    # all_to_all per call (``parallel.expert_parallel``).
    "ep_moe_layer": {"all_to_all": 2},
    # Pipeline forward chain: one ppermute edge per stage boundary and
    # one loss-broadcast psum (``parallel.pipeline``).
    "pipeline_forward": {"collective_permute": 1, "all_reduce": 1},
    # ISSUE 6 satellite: the seq2seq pipeline BACKWARD was unguarded —
    # only the forward ppermute was pinned.  Differentiating the gpipe
    # scan yields exactly ONE transposed ppermute (the reverse ring
    # edge, in the backward scan body) and one transposed loss psum:
    # the full train step is fwd + bwd = 2 ppermute + 2 psum, and a
    # schedule regression that unrolls the reverse ring (one permute
    # per microbatch) trips this pin.
    "pipeline_train_step": {"collective_permute": 2, "all_reduce": 2},
    # ISSUE 11: per-schedule collective counts for the multi-hop wire.
    # hier_rs_ag costs exactly 1 reduce_scatter + 1 all_reduce + 1
    # all_gather per bucket (vs flat's 1 all_reduce/bucket): <= 6
    # buckets -> rs <= 6, ag <= 6, ar <= 6 bucket inter-hops + 1 loss
    # pmean = 7.
    "hier_train_step": {
        "all_reduce": 7,
        "reduce_scatter": 6,
        "all_gather": 6,
    },
    # int8 inter hop adds exactly ONE batched scale pmax over the hier
    # buckets (the flat tier's one-extra-collective contract, applied
    # per schedule class): ar ceiling 8.
    "hier_int8_train_step": {
        "all_reduce": 8,
        "reduce_scatter": 6,
        "all_gather": 6,
    },
    # ZeRO's staged blocked path: the single full-mesh rs/ag pair per
    # bucket becomes 2 rs down (intra full-precision + inter on the
    # wire) and 2 ag up; the loss pmean stays the only all_reduce.
    "zero_hier_train_step": {
        "reduce_scatter": 12,
        "all_gather": 12,
        "all_reduce": 1,
    },
    # the eager bcast_tree multicast: exactly 2 masked psums (inter
    # root->leaders, intra leaders->slices) — vs 1 for the flat
    # spelling; a regression to per-stage-per-rank storms trips this.
    "bcast_tree": {"all_reduce": 2},
    # ISSUE 13: the serving tier's tensor-parallel single-token decode
    # step (serving.decode, 2-layer pinned fixture).  Decode is
    # collective-LATENCY-bound ("Understanding and Improving
    # Communication Performance in Multi-node LLM Inference",
    # PAPERS.md), so the count per token IS the latency floor: exactly
    # 2 row-parallel psums per layer (attention out-proj + MLP
    # down-proj) and nothing else — the replicated embedding, paged
    # cache write, and tied head cost zero collectives.  The ceiling
    # is EXACT (no slack notch): any extra collective per token is a
    # regression the latency budget cannot absorb.  The prefill
    # program has the identical census (the pin is enforced on both
    # traces in tests/test_serving.py).
    "decode_step": {"all_reduce": 4},
    # ISSUE 17: the speculative verify program (serving.decode
    # verify_step, same 2-layer fixture) scores k draft tokens per
    # slot in ONE batched step — the s=k program runs the SAME two
    # row-parallel psums per layer as the s=1 decode step, so the k
    # tokens amortize an unchanged collective count.  That amortization
    # is speculative decode's entire value on a latency-bound
    # interconnect, so the ceiling is EXACT like decode_step's: a
    # verify program that added even one collective would scale its
    # cost with k and erase the win.
    "spec_verify_step": {"all_reduce": 4},
    # ISSUE 18: the prefill program under disaggregation (serving.
    # decode prefill phase, same 2-layer fixture).  TP prefill is the
    # same 2-row-parallel-psums-per-layer family as decode_step — the
    # prompt bucket rides the batch/seq dims, never the collective
    # count — so a PREFILL pool's cost per request is bucket-shaped
    # compute over a fixed collective floor.  EXACT like decode_step;
    # the KV handoff path itself (export -> codec pack -> import) is
    # separately pinned to ZERO collectives in tests/test_serving.py.
    "prefill_step": {"all_reduce": 4},
}

# ----------------------------------------------------------------------
# per-rank HBM ceilings (ISSUE 6): bytes a rank may hold at the live-
# range peak of the pinned train-step FIXTURES (the tier-1 test
# configs — tiny models on the 8-way CPU mesh; the estimator scales
# with the real model when you pin your own).  Ceilings carry one
# notch of slack over the measured estimate, and — like the collective
# ceilings — are literal numbers so an estimator or model drift FAILS
# the pin instead of silently moving it.  Enforced by
# :func:`enforce_memory` from ``analysis.memory.train_step_memory``.
MiB = 1024 * 1024
HBM_BUDGETS = {
    # ResNet-50 fixture (b=8 global, 64x64 imgs): 97.7 MiB params
    # resident + ~131 MiB transient (grads + conv activation chain +
    # fresh output params) = 229 MiB measured; 320 is the ceiling.
    "resnet50_train_step": 320 * MiB,
    # tiny transformer LM fixture (d=32, L=2, seq 16): 0.34 MiB
    # measured.
    "transformer_train_step": 1 * MiB,
    # ZeRO fixture (6144 params, adam): 0.10 MiB measured — per-rank
    # opt state is 1/8 of the replicated wrapper's; the pin is what
    # keeps the state_partition_spec annotation honest.
    "zero_train_step": 1 * MiB,
    # MoE transformer fixture (4 experts over the (2,2,2) mesh, top-2,
    # capacity 2x): 1.2 MiB measured.
    "moe_train_step": 4 * MiB,
}


class MemoryBudgetError(AssertionError):
    """A traced program exceeds its pinned per-rank HBM ceiling."""


def budget_for(name: str) -> dict:
    if name not in BUDGETS:
        raise KeyError(
            f"no pinned budget named {name!r}; known: {sorted(BUDGETS)}"
        )
    return dict(BUDGETS[name])


def enforce(name: str, trace: CollectiveTrace) -> dict:
    """Assert ``trace`` stays within the named pin; returns the census."""
    return assert_within_budget(trace, budget_for(name), name=name)


def memory_budget_for(name: str) -> int:
    if name not in HBM_BUDGETS:
        raise KeyError(
            f"no pinned HBM budget named {name!r}; "
            f"known: {sorted(HBM_BUDGETS)}"
        )
    return int(HBM_BUDGETS[name])


def enforce_memory(name: str, estimate) -> int:
    """Assert a :class:`~chainermn_tpu.analysis.memory.MemoryEstimate`'s
    per-rank peak stays under the named ceiling; returns the peak bytes.
    Raises :class:`MemoryBudgetError` with the estimate's breakdown
    otherwise — the memory analogue of :func:`enforce`."""
    ceiling = memory_budget_for(name)
    peak = int(estimate.peak_bytes)
    if peak > ceiling:
        raise MemoryBudgetError(
            f"per-rank HBM budget exceeded for {name}: peak "
            f"{peak / MiB:.1f} MiB > ceiling {ceiling / MiB:.1f} MiB "
            f"({estimate})"
        )
    return peak
