#!/usr/bin/env python
"""Data-parallel ImageNet-style training.

Parity target: the reference's ``examples/imagenet/train_imagenet.py`` —
the flagship data-parallel workload (``--arch`` selects resnet50 / alex /
googlenet / googlenetbn / nin; scatter_dataset + hierarchical communicator
+ MultiprocessIterator + optional MNBN).

TPU-native shape: one jitted SPMD train step over the communicator's mesh;
BN running statistics are carried as model state (``has_aux`` path of
``build_train_step``) and mean-reduced across shards so the carried state
stays replicated.  Training-time normalization is still per-shard with
plain BN; ``--mnbn`` switches to MultiNodeBatchNormalization, which
computes *global* batch statistics inside the forward pass (reference
``create_mnbn_model`` — true sync-BN).

Without a real ImageNet tree this script trains on an in-memory synthetic
classification set (same shapes, same step program); point ``--npz`` at a
directory of ``train.npz``/``val.npz`` (arrays ``x``, ``y``) to use real
data.

Run (defaults work anywhere, incl. CPU):
    python examples/imagenet/train_imagenet.py --arch resnet50 --epoch 1
"""

import argparse
import os
import sys

try:  # installed package (pip install -e .)
    import chainermn_tpu  # noqa: F401
except ImportError:  # source checkout without installation
    sys.path.insert(
        0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
    )

import numpy as np

import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.iterators.serial_iterator import EpochIterator
from chainermn_tpu.training import Trainer, Updater
from chainermn_tpu.training import extensions as T
from chainermn_tpu.extensions.evaluator import Evaluator
from chainermn_tpu.utils import SyntheticImageDataset


def make_model(arch: str, num_classes: int, train: bool):
    from chainermn_tpu import models

    factory = {
        "alex": models.AlexNet,
        "googlenet": models.GoogLeNet,
        "googlenetbn": models.GoogLeNetBN,
        "nin": models.NIN,
        "resnet18": models.ResNet18,
        "resnet50": models.ResNet50,
        "resnet101": models.ResNet101,
        "vgg16": models.VGG16,
    }[arch]
    return factory(num_classes=num_classes, train=train)


def make_loss_fn(model, prep_x):
    """``loss_fn(params, (x, y, seeds)) -> (loss, new batch_stats)`` for
    ``build_train_step(has_aux=True)``."""

    def loss_fn(p, batch):
        x, y, seeds = batch
        out, mut = model.apply(
            {"params": p["params"], "batch_stats": p["batch_stats"]},
            prep_x(x),
            mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(seeds[0])},
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            out, y
        ).mean()
        return loss, mut.get("batch_stats", {})

    return loss_fn


class _RngBatchIterator:
    """Wraps an iterator, appending per-shard dropout seeds to each batch.

    Each mesh shard receives its own int32 seed row, so dropout masks are
    decorrelated across chips (sharded along the same leading axis as the
    data).
    """

    def __init__(self, it, n_local_shards: int, shard_base: int,
                 n_global_shards: int, base_seed: int = 0):
        self._it = it
        self._n = n_local_shards
        self._base = shard_base
        self._global = n_global_shards
        self._seed = base_seed
        self._count = 0

    def __getattr__(self, name):
        return getattr(self._it, name)

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self._it)
        # Offset by this process's global shard base so no two shards in
        # the job ever share a seed, and stride by the *global* shard count
        # per iteration so seeds never repeat across iterations either.
        seeds = (np.arange(self._n, dtype=np.int32) + self._base
                 + self._count * self._global + self._seed)
        self._count += 1
        return (*batch, seeds)

    # Checkpoint protocol: include the seed counter, else a resumed run
    # would replay the first iterations' dropout seeds.
    def serialize(self):
        return {"inner": self._it.serialize(), "count": self._count}

    def restore(self, state):
        self._it.restore(state["inner"])
        self._count = int(state["count"])


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: ImageNet")
    p.add_argument("--arch", default="resnet50",
                   choices=["alex", "googlenet", "googlenetbn", "nin",
                            "resnet18", "resnet50", "resnet101", "vgg16"])
    p.add_argument("--communicator", default="tpu")
    p.add_argument("--batchsize", type=int, default=64,
                   help="global batch size (split over chips)")
    p.add_argument("--epoch", type=int, default=1)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--n-train", type=int, default=512,
                   help="synthetic train set size")
    p.add_argument("--n-val", type=int, default=128)
    p.add_argument("--npz", default=None,
                   help="directory with train.npz/val.npz (x, y arrays)")
    p.add_argument("--mnbn", action="store_true",
                   help="use MultiNodeBatchNormalization (sync-BN)")
    p.add_argument("--native-loader", action="store_true",
                   help="use the C++ threaded loader (csrc/loader.cpp): "
                        "crop/flip/normalize in worker threads off the GIL")
    p.add_argument("--native-wire", choices=["float32", "uint8"],
                   default="uint8",
                   help="loader wire format: uint8 ships raw crops (1/4 "
                        "of float32's bytes; the standard TPU input "
                        "design) and normalizes inside the jitted step; "
                        "float32 normalizes on the host")
    p.add_argument("--prefetch", type=int, default=2,
                   help="device-side input double-buffering depth: batch "
                        "i+1's host->device transfer is dispatched while "
                        "step i computes (0 disables; checkpoint resume "
                        "rewinds to the oldest unconsumed buffered batch, "
                        "so no data is skipped)")
    p.add_argument("--cpu-mesh", action="store_true")
    p.add_argument("--checkpoint", default=None)
    args = p.parse_args(argv)

    cmn.global_except_hook.add_hook()

    if args.cpu_mesh:
        jax.config.update("jax_platforms", "cpu")
        devices = jax.devices("cpu")
    else:
        devices = jax.devices()
        from chainermn_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    comm = cmn.create_communicator(args.communicator, devices=devices)
    chief = comm.process_index == 0
    if chief:
        print(f"arch={args.arch}  communicator={args.communicator}  {comm!r}")

    # -- data ----------------------------------------------------------
    if args.npz:
        tr = np.load(os.path.join(args.npz, "train.npz"))
        va = np.load(os.path.join(args.npz, "val.npz"))
        train = list(zip(tr["x"], tr["y"]))
        val = list(zip(va["x"], va["y"]))
    else:
        shape = (args.image_size, args.image_size, 3)
        train = SyntheticImageDataset(
            args.n_train, shape=shape,
            n_classes=min(args.num_classes, 64), seed=0)
        val = SyntheticImageDataset(
            args.n_val, shape=shape,
            n_classes=min(args.num_classes, 64), seed=1)
    train = cmn.scatter_dataset(train, comm, shuffle=True, seed=0)
    val = cmn.scatter_dataset(val, comm, shuffle=False, seed=0)

    # Per-process batch must be a multiple of the *local* shard count (the
    # chips this process feeds), floored at one row per local chip.
    local_shards = max(comm.size // comm.process_count, 1)
    batch_per_process = max(
        args.batchsize // comm.process_count // local_shards * local_shards,
        local_shards,
    )
    effective_global = batch_per_process * comm.process_count
    if effective_global != args.batchsize and comm.process_index == 0:
        print(
            f"note: global batch adjusted {args.batchsize} -> "
            f"{effective_global} ({batch_per_process}/process x "
            f"{comm.process_count} processes, multiple of "
            f"{local_shards} local chips)"
        )
    def prep_x(x):  # default input prep; uint8 wire overrides below
        return x.astype(jnp.bfloat16)

    if args.native_loader:
        from chainermn_tpu.utils.native_loader import NativeImageLoader

        # Materialize this process's shard as a uint8 array (the native
        # loader's array-backed input): pad by 8px so the train-time
        # random crop has room to augment.
        pad = 8
        raw = np.stack([np.asarray(x) for x, _ in train])
        if args.npz:
            if raw.dtype != np.uint8:
                raise ValueError(
                    "--native-loader with --npz requires uint8 pixel "
                    f"arrays (got {raw.dtype}); the loader normalizes "
                    "raw pixels itself — store images unnormalized"
                )
            xs8 = raw
            mean, std = (123.7, 116.3, 103.5), (58.4, 57.1, 57.4)
        else:
            # Synthetic floats are ~N(0,1): quantize to uint8 around 128
            # and undo inside the loader with the matching mean/std.
            xs8 = np.clip(raw * 64 + 128, 0, 255).astype(np.uint8)
            mean, std = (128.0,), (64.0,)
        xs8 = np.pad(xs8, ((0, 0), (pad, pad), (pad, pad), (0, 0)),
                     mode="edge")
        ys = np.asarray([y for _, y in train], np.int32)
        inner_it = NativeImageLoader(
            xs8, ys, batch_per_process,
            crop=(args.image_size, args.image_size),
            n_threads=4, seed=1, shuffle=True, train=True,
            mean=mean, std=std, wire=args.native_wire,
        )
        if args.native_wire == "uint8":
            # normalize ON DEVICE inside the jitted step (fuses into the
            # first conv); the wire ships raw uint8 crops
            from chainermn_tpu.utils.native_loader import device_normalize

            def prep_x(x):
                return device_normalize(x, mean, std, dtype=jnp.bfloat16)
    else:
        inner_it = SerialIterator(train, batch_per_process, shuffle=True,
                                  seed=1)
    train_it = _RngBatchIterator(
        inner_it,
        n_local_shards=local_shards,
        shard_base=comm.process_index * local_shards,
        n_global_shards=comm.size,
    )

    # -- model ---------------------------------------------------------
    model = make_model(args.arch, args.num_classes, train=True)
    eval_model = make_model(args.arch, args.num_classes, train=False)
    if args.mnbn:
        from chainermn_tpu.links import create_mnbn_model

        model = create_mnbn_model(model, comm)
        # Same module tree for eval (param/state names must match); in eval
        # mode MNBN reads running averages and performs no cross-rank sync.
        eval_model = create_mnbn_model(eval_model, comm)

    sample = jnp.zeros((1, args.image_size, args.image_size, 3),
                       jnp.bfloat16)
    # the set-up phase parallel.sharded_init records for the LM examples
    with cmn.observability.phase("setup.init_params"):
        variables = model.init(
            {"params": jax.random.PRNGKey(0),
             "dropout": jax.random.PRNGKey(1)},
            sample,
        )
        params = {"params": variables["params"],
                  "batch_stats": variables.get("batch_stats", {})}
        params = comm.bcast_data(params)

    opt = cmn.create_multi_node_optimizer(
        optax.sgd(args.lr, momentum=args.momentum), comm
    )
    opt_state = opt.init(params)

    step = cmn.build_train_step(
        comm, make_loss_fn(model, prep_x), opt, has_aux=True,
        merge_aux=lambda p, aux: {**p, "batch_stats": aux},
    )
    params, opt_state = step.place(params, opt_state)

    feed_it = train_it
    if args.prefetch > 0:
        from chainermn_tpu.iterators import prefetch_to_device

        # batches arrive on device `prefetch` deep: H2D overlaps compute
        feed_it = prefetch_to_device(
            train_it, step.place_batch, depth=args.prefetch
        )
    updater = Updater(feed_it, step, params, opt_state)
    trainer = Trainer(updater, stop_trigger=(args.epoch, "epoch"))

    def eval_metric(p, batch):
        x, y = batch
        logits = eval_model.apply(
            {"params": p["params"], "batch_stats": p["batch_stats"]},
            x.astype(jnp.bfloat16),
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()
        acc = (jnp.argmax(logits, -1) == y).mean()
        return {"loss": loss, "accuracy": acc}

    evaluator = Evaluator(
        lambda: EpochIterator(val, batch_per_process, pad_to=comm.size),
        eval_metric, comm,
    )
    trainer.extend(cmn.create_multi_node_evaluator(evaluator, comm))

    # every step's loss, kept as device scalars and read after the run
    # so that no step waits on the host for it
    step_losses = []
    trainer.extend(lambda t: step_losses.append(t.observation["loss"]),
                   trigger=(1, "iteration"), name="step_losses")

    log = T.LogReport(comm=comm)
    trainer.extend(T.Throughput(args.batchsize, comm=comm),
                   trigger=(1, "iteration"))
    trainer.extend(log, trigger=(1, "epoch"))
    trainer.extend(
        T.PrintReport(
            ["epoch", "iteration", "loss", "val/loss", "val/accuracy",
             "samples_per_sec"],
            log, comm=comm,
        ),
        trigger=(1, "epoch"),
    )
    if args.checkpoint:
        ckpt = cmn.create_multi_node_checkpointer(args.checkpoint, comm)
        trainer.extend(ckpt, trigger=(1, "epoch"))
        resumed = ckpt.restore_trainer(trainer)
        if resumed is not None and chief:
            print(f"resumed from iteration {resumed}")

    trainer.run()

    final = log.log[-1] if log.log else {}
    if chief:
        print("final:", {k: round(v, 4) for k, v in final.items()
                         if isinstance(v, float)})
        print(cmn.observability.setup_line())
    return {"final": final, "losses": [float(l) for l in step_losses],
            "comm": comm, "step": step, "trainer": trainer}


if __name__ == "__main__":
    main()
