#!/usr/bin/env python
"""TransformerLM step attribution at the CURRENT bench config.

Round 3 built this ladder at the 16-head/dh-64 era; round 4 moved the
bench to 8 heads (dh=128, the MXU lane width) + 1024x1024 flash blocks
and reached MFU 0.65 — making the old table stale (VERDICT r4 #3).
This version anchors every rung at the shipping config and reports
attention-INCLUSIVE MFU (same accounting as bench.py: analytic flash
FLOPs added to XLA's count, which can't see inside pallas_call), so
rows are directly comparable to the bench table.

Rungs (all deltas vs `full` = the bench config: b8, heads8/dh128,
flash 1024x1024, adamw, fused lm_loss):

  no_attn     attention_fn returns q — the attention share
  no_head     vocab-8 twin — the 32k logits matmul + fp32 (b,s,V)
              CE traffic share
  sgd         adamw -> sgd — optimizer-state traffic share
  ln_bf16     LayerNorm in bf16 instead of fp32 — the LN/residual share
  chunked     fused chunked linear+CE — logits never materialize
  b16_remat   batch 16 + remat — is the MXU under-fed at b8?
  blocks256x512  the r03 flash block geometry — the tuning delta
  xla_attn    XLA's fused attention instead of the Pallas kernel
  legacy_heads16 the r03 16-head/dh64 config — cross-round anchor
  anatomy_*   SEGMENT-ANATOMY mode (round 6): the same step timed
              under taxonomy=legacy/split/interior at fixed geometry —
              the A/B deltas divide by the printed block census into
              per-block-type costs (see the VARIANTS comment and
              docs/performance.md "Diagonal-split kernel")

Usage: python benchmarks/transformer_mfu.py [rung ...]   (TPU)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from bench import _flash_attn_tflops, _peak_flops
from chainermn_tpu.models.transformer import TransformerLM, lm_loss
from chainermn_tpu.utils.benchmarking import protocol_fields
from chainermn_tpu.ops.pallas_attention import flash_attention_fn

K = int(os.environ.get("HUNT_K", "10"))
VOCAB, D, LAYERS, SEQ = 32768, 1024, 8, 2048


def _peak():
    """Device-kind peak lookup (same as bench.py) so the ladder's MFU
    rows stay comparable to the bench table on any chip generation; an
    unknown device kind is an error, exactly as in bench.py.  LAZY on
    purpose: a chip belongs to one process at a time, so the multi-rung
    parent must touch no backend — jax.devices() at module scope would
    take the chip from its per-rung subprocesses."""
    return _peak_flops(jax.devices()[0])


def _readback(x):
    return float(np.asarray(x).ravel()[0])


def time_variant(name, *, batch=8, loss="lm", attention="flash",
                 opt="adamw", n_heads=None, remat=False,
                 block_q=None, block_k=None, bwd_block_q=None,
                 bwd_block_k=None, ln_dtype=jnp.float32,
                 taxonomy=None):
    heads = n_heads or D // 128  # dh=128: the shipping config
    attn = {
        "flash": flash_attention_fn(block_q=block_q, block_k=block_k,
                                    bwd_block_q=bwd_block_q,
                                    bwd_block_k=bwd_block_k,
                                    taxonomy=taxonomy),
        "none": lambda q, k, v, causal, scale: q,
        "xla": None,
    }[attention]
    model = TransformerLM(
        vocab_size=VOCAB, d_model=D, n_heads=heads, n_layers=LAYERS,
        max_len=SEQ, attention_fn=attn, ln_dtype=ln_dtype,
    )
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, VOCAB, (batch, SEQ)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks[:1])
    tx = (optax.adamw(3e-4, weight_decay=0.01) if opt == "adamw"
          else optax.sgd(0.1, momentum=0.9))
    opt_state = tx.init(params)

    if loss == "lm":
        def loss_fn(p):
            return lm_loss(model.apply(p, toks), toks)
    elif loss == "chunked":
        from chainermn_tpu.ops import chunked_lm_loss

        def loss_fn(p):
            return chunked_lm_loss(model, p, toks, n_chunks=16)
    elif loss == "no_head":
        # vocab-8 twin: the transformer blocks are identical, the 32k
        # head matmul and the fp32 (b, s, 32k) logits/CE traffic vanish
        small = TransformerLM(
            vocab_size=8, d_model=D, n_heads=heads, n_layers=LAYERS,
            max_len=SEQ, attention_fn=attn, ln_dtype=ln_dtype,
        )
        stoks = toks % 8
        params = small.init(jax.random.PRNGKey(0), stoks[:1])
        opt_state = tx.init(params)

        def loss_fn(p):
            return lm_loss(small.apply(p, stoks), stoks)
    else:
        raise ValueError(loss)

    if remat:
        loss_fn = jax.checkpoint(loss_fn)

    def one_step(p, o):
        l, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = tx.update(grads, o, p)
        p = optax.apply_updates(p, updates)
        return p, o, l

    @jax.jit
    def ksteps(p, o, n):
        def body(i, carry):
            p, o, _ = carry
            return one_step(p, o)

        return lax.fori_loop(0, n, body, (p, o, jnp.float32(0)))

    flops = None
    try:
        an = jax.jit(one_step).lower(
            params, opt_state
        ).compile().cost_analysis()
        if isinstance(an, (list, tuple)):
            an = an[0]
        flops = float(an.get("flops", 0.0)) or None
    except Exception:
        pass
    attn_tf = (
        _flash_attn_tflops(batch, heads, SEQ, D // heads, LAYERS)
        if attention == "flash" else 0.0
    )

    p, o, l = ksteps(params, opt_state, 2)
    _readback(l)

    def timed(n):
        t0 = time.perf_counter()
        _, _, l = ksteps(params, opt_state, n)
        _readback(l)
        return time.perf_counter() - t0

    dts = []
    for _ in range(2):
        t1, t2 = timed(K), timed(2 * K)
        dts.append((t2 - t1) / K)
    dt = min(d for d in dts if d > 0) if any(d > 0 for d in dts) else dts[-1]
    out = {
        "variant": name,
        "batch": batch,
        "step_time_ms": round(dt * 1e3, 2),
        "tokens_per_sec": round(batch * SEQ / dt, 1),
        "samples": [round(d * 1e3, 2) for d in dts],
        **protocol_fields(dts),
    }
    if attention == "flash":
        # segment anatomy: the static block census this launch executes
        # per (batch*head) program — what turns the taxonomy-rung A/B
        # times into per-block-type costs (docs/performance.md
        # "Diagonal-split kernel").  launch_census applies the same
        # clamps the kernel does, so the printed census is the geometry
        # that RAN, not the one requested — UNLESS the backward's
        # scoped-VMEM retry warned and shrank mid-run (it prints a
        # UserWarning naming both geometries); a capture that saw that
        # warning must rerun with the shrunk blocks requested
        # explicitly before dividing times by this census.
        from chainermn_tpu.ops.pallas_attention import launch_census

        census = launch_census(SEQ, SEQ, D // heads, block_q, block_k,
                               bwd_block_q, bwd_block_k)
        out["taxonomy"] = taxonomy or "split"
        out["block_census_fwd"] = census["fwd"]
        out["block_census_bwd"] = census["bwd"]
    if flops:
        total = flops + attn_tf * 1e12
        out["tflops_per_step"] = round(total / 1e12, 3)
        peak = _peak()
        if peak:
            out["mfu"] = round(total / dt / peak, 4)
            if attn_tf:
                out["mfu_xla_counted"] = round(flops / dt / peak, 4)
    print(json.dumps(out), flush=True)
    return out


VARIANTS = {
    "full": lambda: time_variant("full"),
    "no_attn": lambda: time_variant("no_attn", attention="none"),
    "no_head": lambda: time_variant("no_head", loss="no_head"),
    "sgd": lambda: time_variant("sgd", opt="sgd"),
    "ln_bf16": lambda: time_variant("ln_bf16", ln_dtype=jnp.bfloat16),
    "chunked": lambda: time_variant("chunked", loss="chunked"),
    "b16_remat": lambda: time_variant("b16_remat", batch=16, remat=True),
    # can the chunked loss (no (b,s,32k) fp32 logits) buy batch 16 at
    # the current config where the dense loss OOMs even with remat?
    "chunked_b16": lambda: time_variant("chunked_b16", batch=16,
                                        loss="chunked"),
    "chunked_b16_remat": lambda: time_variant(
        "chunked_b16_remat", batch=16, loss="chunked", remat=True),
    "blocks256x512": lambda: time_variant(
        "blocks256x512", block_q=256, block_k=512),
    # causal diagonal-waste geometry at seq 2048: with bq=bk=1024 the
    # kernel computes 3/4 of the full score grid (2x2 blocks, 3 live);
    # bq=512 cuts that to 5/8 at finer-grid cost — never swept at 2048
    "blocks512x512": lambda: time_variant(
        "blocks512x512", block_q=512, block_k=512),
    "blocks512x1024": lambda: time_variant(
        "blocks512x1024", block_q=512, block_k=1024),
    "blocks1024x2048_fwd_only": lambda: time_variant(
        "blocks1024x2048_fwd_only", block_q=1024, block_k=2048,
        bwd_block_q=1024, bwd_block_k=1024),
    "xla_attn": lambda: time_variant("xla_attn", attention="xla"),
    "legacy_heads16": lambda: time_variant("legacy_heads16", n_heads=16),
    # ---- segment anatomy (round 6): per-block-type timing ----
    # Three rungs at the SAME 1024^2 geometry (census fwd: 1 interior /
    # 2 masked / 1 dead; bwd identical), differing only in taxonomy:
    #   anatomy_legacy    every live block pays the masked path (the
    #                     pre-split kernel — the r5 shipping cost)
    #   anatomy_split     interior blocks take the fast branch (the
    #                     shipping r6 kernel; == `full` but explicit)
    #   anatomy_interior  ALL live blocks take the fast branch — a
    #                     TIMING-ONLY floor (numerics wrong under the
    #                     causal mask; never a training path)
    # Per-block-type costs: with n_live live blocks and n_int interior,
    #   masked-block overhead = (legacy - interior) / n_live
    #   split win             =  legacy - split  (= overhead * n_int)
    #   irreducible diagonal  =  split - interior (= overhead * n_diag)
    # If split ~= interior, the remaining attention-segment gap to the
    # dense program's MFU is the unmasked online-softmax VPU work
    # itself — the measured kernel floor, not the diagonal handling.
    "anatomy_legacy": lambda: time_variant(
        "anatomy_legacy", block_q=1024, block_k=1024, taxonomy="legacy"),
    "anatomy_split": lambda: time_variant(
        "anatomy_split", block_q=1024, block_k=1024, taxonomy="split"),
    "anatomy_interior": lambda: time_variant(
        "anatomy_interior", block_q=1024, block_k=1024,
        taxonomy="interior"),
    # the shipping fwd geometry under the split kernel: at seq 2048,
    # fwd 1024x2048 has ZERO interior blocks (both live blocks straddle
    # the diagonal) while 1024^2 has 1 of 3 — whether the wider K
    # stream still beats the fast branch is this A/B vs anatomy_split
    "anatomy_ship_geometry": lambda: time_variant(
        "anatomy_ship_geometry", block_q=1024, block_k=2048,
        bwd_block_q=1024, bwd_block_k=1024, taxonomy="split"),
}


def main():
    names = sys.argv[1:] or list(VARIANTS)
    if len(names) > 1:
        # One subprocess per rung: compiled executables + params of
        # earlier rungs otherwise stay live in jax's caches and HBM
        # fragments — the tail of a full sweep used to die
        # RESOURCE_EXHAUSTED (observed r5: 4 of 10 rungs lost).
        import subprocess

        for name in names:
            try:
                r = subprocess.run(
                    [sys.executable, os.path.abspath(__file__), name],
                    capture_output=True, text=True, timeout=1800,
                )
            except subprocess.TimeoutExpired:
                # one hung rung must not abort the rest of the sweep
                print(json.dumps({"variant": name,
                                  "error": "timeout after 1800s"}),
                      flush=True)
                continue
            out = [l for l in r.stdout.splitlines()
                   if l.startswith("{")]
            print("\n".join(out) if out else json.dumps(
                {"variant": name,
                 "error": f"exit {r.returncode}: {r.stderr[-300:]}"}
            ), flush=True)
        return
    for name in names:
        try:
            VARIANTS[name]()
        except Exception as e:
            print(json.dumps({"variant": name,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)


if __name__ == "__main__":
    main()
