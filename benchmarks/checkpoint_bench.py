#!/usr/bin/env python
"""Checkpoint save/restore performance (VERDICT r4 #5).

The distributed checkpointer was correctness-complete (newest-common-
step agreement, mp-tested) but had zero perf presence.  This script
measures, for the bench LM's FULL train state (params + adamw moments,
~1.6 GB at vocab 32768 / d 1024 / L 8):

  * sync orbax save: wall time + effective GB/s
  * restore (sharded, via the template): wall time + GB/s
  * async save (ocp.AsyncCheckpointer): the training STALL (time until
    save() returns) vs the background commit time — the stall is the
    number training cares about
  * the ZeRO-1 tier: 1/N-sharded adam state over the 8-mesh
  * resume equality through BOTH paths (allclose over the whole tree)

Runs on a CPU virtual mesh (storage + serialization are host-side;
the measurement is orbax/tensorstore + local-disk, which is what a
real pod's per-host shard writes look like — NOT the chip's D2H
link).

Usage:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/checkpoint_bench.py [--small] [--out out.json]
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
)


def tree_bytes(tree):
    import jax

    return sum(
        l.nbytes for l in jax.tree_util.tree_leaves(tree)
        if hasattr(l, "nbytes")
    )


def tree_allclose(a, b, rtol=0, atol=0):
    import jax
    import numpy as np

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if hasattr(x, "shape"):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=rtol, atol=atol
            )


def du_bytes(path):
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                pass
    return total


def build_state(small, zero):
    import jax
    import jax.numpy as jnp
    import optax

    import chainermn_tpu as cmn
    from chainermn_tpu.models.transformer import TransformerLM

    comm = cmn.create_communicator("tpu", devices=jax.devices("cpu"))
    vocab, d_model, n_layers = (2048, 128, 2) if small else (32768, 1024, 8)
    seq = 128
    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, n_heads=max(d_model // 128, 1),
        n_layers=n_layers, max_len=seq,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32)
    )
    opt = cmn.create_multi_node_optimizer(
        optax.adamw(3e-4, weight_decay=0.01), comm,
        zero_redundancy=zero,
    )

    def loss_fn(p, b):
        from chainermn_tpu.models.transformer import lm_loss

        return lm_loss(model.apply(p, b), b)

    step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
    params, opt_state = step.place(params, opt.init(params))
    # Freshly-initialized adam moments are all-zero and tensorstore
    # compresses them to ~nothing, flattering GB/s; fill them with
    # random bytes so the measurement writes what a mid-training
    # snapshot writes.  (Cheaper than running real train steps on the
    # 1-core host; the byte statistics are what matter for I/O.)
    import numpy as np

    rng = np.random.RandomState(0)

    def fill(leaf):
        if hasattr(leaf, "shape") and leaf.size > 1:
            return jax.device_put(
                jnp.asarray(
                    rng.standard_normal(leaf.shape).astype(leaf.dtype)
                ),
                leaf.sharding,
            )
        return leaf

    opt_state = jax.tree_util.tree_map(fill, opt_state)
    return comm, step, params, opt_state


def measure_tier(comm, params, opt_state, *, label, workdir):
    """One tier's full measurement set; returns a dict."""
    from chainermn_tpu.extensions.checkpoint import (
        create_multi_node_checkpointer,
    )

    state = {"params": params, "opt_state": opt_state}
    logical = tree_bytes(state)
    rec = {"tier": label, "state_GiB": round(logical / 2**30, 3)}

    # -- sync save -----------------------------------------------------
    sync = create_multi_node_checkpointer(
        f"{label}_sync", comm, path=workdir, keep=2
    )
    t0 = time.perf_counter()
    sync.save(1, state)
    t_save = time.perf_counter() - t0
    on_disk = du_bytes(os.path.join(workdir, f"{label}_sync"))
    rec["sync_save_s"] = round(t_save, 2)
    rec["sync_save_GBps"] = round(logical / t_save / 1e9, 2)
    rec["on_disk_GiB"] = round(on_disk / 2**30, 3)

    # -- restore (sharded via template) --------------------------------
    t0 = time.perf_counter()
    got_step, got = sync.resume(like=state)
    t_rest = time.perf_counter() - t0
    assert got_step == 1
    tree_allclose(got, state)
    rec["restore_s"] = round(t_rest, 2)
    rec["restore_GBps"] = round(logical / t_rest / 1e9, 2)

    # -- async save: stall vs commit -----------------------------------
    asy = create_multi_node_checkpointer(
        f"{label}_async", comm, path=workdir, keep=2, use_async=True
    )
    t0 = time.perf_counter()
    asy.save(2, state)
    t_stall = time.perf_counter() - t0
    asy.wait_until_finished()
    t_commit = time.perf_counter() - t0
    rec["async_save_stall_s"] = round(t_stall, 2)
    rec["async_save_commit_s"] = round(t_commit, 2)
    rec["async_stall_fraction"] = round(t_stall / max(t_commit, 1e-9), 3)

    # -- resume equality through the async path ------------------------
    got_step, got = asy.resume(like=state)
    assert got_step == 2
    tree_allclose(got, state)
    rec["async_resume_equal"] = True
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny model (CI-sized smoke)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    # host-side measurement; never touch a (possibly busy) TPU
    jax.config.update("jax_platforms", "cpu")

    results = []
    for zero, label in [(False, "dense_replicated"), (True, "zero1_sharded")]:
        comm, _step, params, opt_state = build_state(args.small, zero)
        workdir = tempfile.mkdtemp(prefix=f"ckpt_bench_{label}_")
        try:
            results.append(measure_tier(
                comm, params, opt_state, label=label, workdir=workdir,
            ))
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        print(json.dumps(results[-1]), flush=True)

    out = {
        "n_devices": len(jax.devices("cpu")),
        "host_cores": os.cpu_count(),
        "tiers": results,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"summary": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
