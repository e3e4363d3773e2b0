"""Runner: ``examples/imagenet/train_imagenet.py`` as a training cell.

``main(argv)`` builds the communicator, the model, the multi-node
optimizer, the compiled step, the ``Updater`` and the ``Trainer`` and
runs one epoch, which warms the step's one shape.  The runner then lays
the benchmark's seeded weights into the updater's tree, zeroes the
optimizer state, and replaces the feed with the example's own chain
(``SerialIterator`` > per-shard seeds > ``prefetch_to_device``) over an
in-memory data set made from ``--seed``.  A step of the window is
``trainer.updater.update()``: iterator, collate, host-to-device copy
and dispatch are all inside it.
"""

from __future__ import annotations

import numpy as np

from .common import TrainCell, batch_rng, find_state, load_example, \
    load_reference, reuse_large_host_buffers


class _Memory:
    """A data set held in host memory: ``(image, label)`` by index."""

    def __init__(self, x, y):
        self.x, self.y = x, y

    def __len__(self):
        return len(self.y)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


class _Tap:
    """Passes batches through and keeps the first few for the reference."""

    def __init__(self, it, keep: int):
        self._it, self._keep, self.kept = it, keep, []

    def __getattr__(self, name):
        return getattr(self._it, name)

    def __iter__(self):
        return self

    def __next__(self):
        batch = next(self._it)
        if len(self.kept) < self._keep:
            self.kept.append(batch)
        return batch


def _nest(flat: dict) -> dict:
    out = {}
    for key, value in flat.items():
        node = out
        *parents, last = key.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[last] = value
    return out


def _flat(tree: dict, prefix="") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


class ImageCell(TrainCell):
    def __init__(self, spec):
        import jax
        import jax.numpy as jnp

        reuse_large_host_buffers()  # 77 MB a batch, collated afresh
        cfg, tr = spec.sizes, spec.traffic
        self.ref = ref = load_reference(spec.config)
        self.cfg, self.traffic = cfg, tr
        self.opt_cfg = spec.config["optimizer"]
        size, batch = int(cfg["image_size"]), int(tr["batch"])
        self.samples_per_step = batch
        argv = [
            "--image-size", str(size), "--num-classes",
            str(cfg["num_classes"]), "--batchsize", str(batch),
            "--n-train", str(tr["n_train"]), "--n-val", str(tr["n_val"]),
            "--prefetch", str(tr["prefetch"]), "--lr",
            str(self.opt_cfg["lr"]), "--momentum",
            str(self.opt_cfg["momentum"]), "--epoch", "1",
        ] + list(spec.config.get("argv", [])) + list(tr.get("argv", []))
        if spec.rehearse:
            argv.append("--cpu-mesh")
        example = load_example("imagenet/train_imagenet.py")
        out = example.main(argv)
        self.step, self.comm, self.example = out["step"], out["comm"], \
            example
        comm = self.comm
        self.updater = out["trainer"].updater
        if comm.size != spec.chips:
            raise RuntimeError(
                f"cell asks for {spec.chips} chips, the example's "
                f"communicator spans {comm.size}")

        # the benchmark's weights, from --seed, in the step's layout
        old = self.updater.params
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, old)
        fresh_stats = jax.tree_util.tree_map_with_path(
            lambda p, x: (jnp.ones if p[-1].key == "var" else jnp.zeros)(
                x.shape, x.dtype), old["batch_stats"])
        self.updater.params = old = None
        self._make_params = jax.jit(
            lambda key: {"params": _nest(ref.init_weights(key, cfg)),
                         "batch_stats": fresh_stats},
            out_shardings=shardings)
        self._zero = jax.jit(
            lambda s: jax.tree_util.tree_map(jnp.zeros_like, s),
            donate_argnums=0)

        def norms(tree):
            return {k: jnp.linalg.norm(x.astype(jnp.float32))
                    for k, x in _flat(tree["params"]).items()}

        self._norms = jax.jit(norms)
        self._deltas = jax.jit(lambda p, key: norms(
            {"params": jax.tree_util.tree_map(
                jnp.subtract, p["params"],
                _nest(ref.init_weights(key, cfg)))}))
        self._telemetry = None
        self._trace = spec.trace
        self.reseed(spec.seed)

    def reseed(self, seed: int):
        """Seeded weights, a zeroed optimizer, and the example's feed
        chain anew over seeded images held in host memory."""
        import chainermn_tpu as cmn
        from chainermn_tpu.iterators import SerialIterator, \
            prefetch_to_device

        self.seed = seed
        up, tr, cfg = self.updater, self.traffic, self.cfg
        up.params = None
        up.params = self._make_params(self.ref.seed_key(seed))
        up.opt_state = self._zero(up.opt_state)
        rng = batch_rng(seed, 0)
        n, size = int(tr["n_train"]), int(cfg["image_size"])
        data = _Memory(
            rng.standard_normal((n, size, size, 3), dtype=np.float32),
            rng.integers(0, cfg["num_classes"], n).astype(np.int32))
        data = cmn.scatter_dataset(data, self.comm, shuffle=True,
                                   seed=seed % 2**31)
        self._tap = _Tap(SerialIterator(data, int(tr["batch"]),
                                        shuffle=True, seed=seed % 2**31),
                         self.first_n)
        feed = self.example._RngBatchIterator(
            self._tap, n_local_shards=self.comm.size, shard_base=0,
            n_global_shards=self.comm.size)
        if int(tr["prefetch"]) > 0:
            feed = prefetch_to_device(feed, self.step.place_batch,
                                      depth=int(tr["prefetch"]))
        up.iterator = feed

    # -- the window's call and feed ------------------------------------
    def dispatch(self):
        self.updater.update()
        return self.updater.last_metrics["loss"]

    def start_window(self):
        if self._trace:  # the program's own spans, in the traced run only
            from chainermn_tpu.observability import timeline

            self._telemetry = timeline.Telemetry("cellbench")
            timeline.install(self._telemetry)

    def telemetry(self):
        if self._telemetry is None:
            return None
        from chainermn_tpu.observability import timeline

        timeline.install(None)
        return {name: [s["dur"] for s in
                       self._telemetry.timeline.spans(name)]
                for name in ("update", "data.wait", "compute.dispatch")}

    # -- what correct reads --------------------------------------------
    def _first_gradient(self):
        """Per-leaf norms of the first gradient as the optimizer got it,
        and its small leaves whole: momentum's trace after one step is
        g."""
        trace = find_state(self.updater.opt_state, "trace")
        small = {k: np.asarray(v, np.float32)
                 for k, v in _flat(trace["params"]).items()
                 if v.size <= self.ref.SMALL}
        return {k: float(v) for k, v in self._norms(trace).items()}, small

    def _delta_norms(self) -> dict:
        return {k: float(v) for k, v in self._deltas(
            self.updater.params, self.ref.seed_key(self.seed)).items()}

    def free(self):
        self.updater.params = self.updater.opt_state = None
        self.updater.iterator = self.updater.step_fn = None

    def first_inputs(self) -> dict:
        return {"seed": self.seed, "batches": list(self._tap.kept)}

    def reference(self, inputs: dict, lowp: bool = False) -> dict:
        return self.ref.train_readings(inputs["seed"], self.cfg,
                                  inputs["batches"], self.opt_cfg,
                                  lowp=lowp)


def build(spec) -> ImageCell:
    return ImageCell(spec)
