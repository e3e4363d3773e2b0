"""Distributed checkpoint / resume with newest-common-step agreement.

Reference parity: ``chainermn/extensions/checkpoint.py`` —
``create_multi_node_checkpointer(name, comm, ...)``: every rank snapshots
its local state at an interval; ranks allgather their snapshot inventories
and agree on the newest iteration present on *all* ranks; stale files are
garbage-collected; resume loads the newest common snapshot — fault-tolerant
restart under a batch scheduler (and, on TPU, under preemption).

TPU-native redesign: arrays are *global* (sharded over the mesh), so the
storage layer is orbax/tensorstore — each process writes exactly its
addressable shards of one logical checkpoint instead of one npz per rank.
The agreement protocol survives unchanged, but it agrees on complete
*global* checkpoints (a step counts only if every process finished its
shards — orbax's commit semantics make partial writes invisible, which is
strictly stronger than the reference's per-rank npz inventory).

Elastic restart (``resilience.elastic``): every snapshot carries a world
manifest (world size, process count, mesh axes; the npz tier adds
per-file integrity digests the inventory verifies).  ``resume()`` in a
world whose size differs from the manifest routes the state through the
checkpoint resharder instead of failing — see :meth:`resume`.
"""

from __future__ import annotations

import os
import pickle
import re
import shutil
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..observability import timeline as _obs
from ..resilience import elastic as _elastic
from ..resilience import fault_injection as _fi
from ..resilience.log import emit as _emit


class _MultiNodeCheckpointer:
    """Trainer extension; also usable standalone via save()/resume()."""

    priority = 200
    name = "checkpointer"

    def __init__(self, name: str, comm, path: str = "checkpoints",
                 trigger=(1, "epoch"), keep: int = 3,
                 use_orbax: bool = True, use_async: bool = False):
        """``use_async``: snapshot through ``ocp.AsyncCheckpointer`` —
        ``save()`` returns once the arrays are copied to host and the
        serialization/write continues on a background thread, so a
        snapshot does not stall training (the stall is not measured on
        a chip: ``PERF.md`` section 7, rows 6-7).  Commit stays atomic
        (tmp dir + rename), so the agreement protocol is unaffected: an
        in-flight save is simply not visible yet.  Call :meth:`wait_until_finished` (or
        ``finalize``) before reading the snapshot back or exiting."""
        if use_async and not use_orbax:
            raise ValueError(
                "use_async=True requires the orbax tier: the npz "
                "backend writes synchronously, which would silently "
                "break the non-stalling-save contract async promises"
            )
        self._name = name
        self._comm = comm
        self._root = os.path.join(path, name)
        if comm.process_count > 1 and not use_orbax:
            # Per-rank local-npz tier: every process writes its OWN
            # snapshots (the reference's per-rank storage model).  The
            # root is namespaced by process index so a path that happens
            # to be on a shared filesystem can never make two ranks race
            # on the same state.npz; on genuinely rank-local disks the
            # extra directory level is harmless.
            self._root = os.path.join(
                self._root, f"rank_{comm.process_index}"
            )
        self.trigger = trigger
        self._keep = keep
        self._use_orbax = use_orbax
        self._use_async = use_async
        self._ckptr = None
        # (old_world, new_world) of the last resume that routed through
        # the elastic resharder; None when the worlds matched.
        # last_manifest: the elected snapshot's world manifest.
        self.last_resize = None
        self.last_manifest = None
        # integrity-verification memo: path -> (stat signature, ok).
        # Committed snapshots never change, so one full-content hash per
        # directory state is enough; the inventory scan re-stats only.
        self._verified: dict = {}
        os.makedirs(self._root, exist_ok=True)

    # -- storage backends ----------------------------------------------
    def _orbax(self):
        if self._ckptr is None:
            import orbax.checkpoint as ocp

            if self._use_async:
                self._ckptr = ocp.AsyncCheckpointer(
                    ocp.PyTreeCheckpointHandler()
                )
            else:
                self._ckptr = ocp.PyTreeCheckpointer()
        return self._ckptr

    def wait_until_finished(self) -> None:
        """Block until any in-flight async save has committed (no-op for
        the sync checkpointer or before the first save)."""
        if self._ckptr is not None and hasattr(
            self._ckptr, "wait_until_finished"
        ):
            self._ckptr.wait_until_finished()

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._root, f"step_{step:012d}")

    def _available_steps(self) -> list:
        steps = []
        if os.path.isdir(self._root):
            # sorted: the step inventory feeds newest_common_step's
            # cross-rank agreement; listdir order must not differ per
            # host (spmd-unsorted-scan)
            for d in sorted(os.listdir(self._root)):
                m = re.fullmatch(r"step_(\d+)", d)
                if m and self._is_complete(os.path.join(self._root, d)):
                    steps.append(int(m.group(1)))
        return sorted(steps)

    def _is_complete(self, path: str) -> bool:
        # orbax writes atomically (tmp dir + rename); presence of the final
        # dir (with no orbax tmp marker) means commit finished.  On top of
        # presence, the integrity manifest (written at save by the npz
        # tier) is verified: a torn/corrupt snapshot (truncated npz,
        # flipped byte) is EXCLUDED from the inventory, so the agreement
        # protocol degrades to the previous step instead of electing a
        # snapshot that raises at load.
        if not os.path.isdir(path) or path.endswith(".tmp"):
            return False
        sig = _elastic.snapshot_signature(path)
        cached = self._verified.get(path)
        if cached is not None and cached[0] == sig:
            return cached[1]
        ok = _elastic.verify_snapshot(path)
        self._verified[path] = (sig, ok)
        if not ok:
            _emit("snapshot_corrupt", "checkpoint.inventory", path=path)
        return ok

    @property
    def _is_chief(self) -> bool:
        return self._comm.process_index == 0

    @property
    def _multiproc(self) -> bool:
        return self._comm.process_count > 1

    # -- save ----------------------------------------------------------
    def save(self, step: int, state: Dict[str, Any]) -> None:
        """Snapshot ``state`` (a pytree of global arrays + metadata).

        Under multi-process this is a collective: every process must call
        it (orbax writes each process's addressable shards); filesystem
        mutations of shared directories are chief-only with barriers.
        """
        with _obs.span("checkpoint.save", step=int(step)):
            self._save(step, state)

    def _save(self, step: int, state: Dict[str, Any]) -> None:
        # resilience site: rank-death / slice-loss rehearsal point for
        # the elastic mp tier (a `die` spec targeted at one process is a
        # spot reclaim mid-snapshot); no-op when no injector is active
        _fi.fire("checkpoint.save")
        target = self._step_dir(step)
        # Back-to-back saves serialize here (an in-flight async write of
        # an older step must commit before we mutate the directory
        # listing); save-vs-TRAINING overlap is unaffected.
        self.wait_until_finished()
        if self._multiproc and not self._use_orbax:
            # The reference's own storage model: each rank snapshots
            # PROCESS-LOCAL state to LOCAL disk (per-rank npz), and the
            # agreement protocol reconciles divergent inventories at
            # resume.  Valid only for fully-addressable leaves — a
            # cross-process global array cannot materialize here.
            for leaf in jax.tree_util.tree_leaves(state):
                if hasattr(leaf, "is_fully_addressable") and \
                        not leaf.is_fully_addressable:
                    raise ValueError(
                        "use_orbax=False under multi-process requires "
                        "process-local (fully addressable) state; leaf "
                        f"with sharding {leaf.sharding} spans processes "
                        "— use the orbax tier for global arrays"
                    )
            # no pre-delete: _save_np writes to a tmp dir and atomically
            # renames over target, so the PREVIOUS snapshot stays
            # electable until the instant of the swap — a crash during
            # the write must not leave the step with no snapshot at all
            self._save_np(target, state)
            self._gc_local()
            return
        if self._multiproc:
            if self._is_chief and os.path.exists(target):
                shutil.rmtree(target)
            self._comm.barrier()
            self._orbax().save(os.path.abspath(target), state)
            # world manifest (elastic restart contract): chief-written
            # SIBLING file — the orbax dir's contents belong to orbax.
            # Atomic (tmp + rename); for async saves it may precede the
            # data commit, which is safe: the step is only electable
            # once the directory itself exists.
            if self._is_chief:
                _elastic.write_manifest(
                    _elastic.world_manifest(self._comm),
                    _elastic.manifest_sibling(target),
                )
            if not self._use_async:
                self._comm.barrier()
        else:
            if os.path.exists(target):
                shutil.rmtree(target)
            if self._use_orbax:
                try:
                    self._orbax().save(os.path.abspath(target), state)
                    _elastic.write_manifest(
                        _elastic.world_manifest(self._comm),
                        _elastic.manifest_sibling(target),
                    )
                except Exception:
                    if self._use_async:
                        raise  # async failures must not silently degrade
                    # Degraded single-controller path; see _save_np.
                    self._save_np(target, state)
            else:
                self._save_np(target, state)
        self._gc()

    def _save_np(self, target: str, state) -> None:
        """Degraded (orbax-less) backend.

        Must satisfy the same contract as the orbax path: ``resume`` returns
        the *original pytree structure* so ``restore_trainer`` can index
        ``state["params"]`` etc.  Leaves are stored as indexed npz entries
        and the treedef is pickled alongside (treedefs of standard
        containers and NamedTuples pickle fine).  Leaves are materialized
        via ``np.asarray`` (process-local state only).

        Commit is ATOMIC (tmp dir + rename), matching what
        ``_is_complete`` assumes: a rank killed mid-write leaves only a
        tmp dir the step scan ignores, so the agreement protocol can
        never elect a torn snapshot.
        """
        import glob as _glob

        tmp = f"{target}.tmp{os.getpid()}"
        # glob.escape: a checkpoint path containing [ ? * is legal and
        # must not silently skip the stale-dir sweep
        for stale in sorted(_glob.glob(f"{_glob.escape(target)}.tmp*")):
            shutil.rmtree(stale, ignore_errors=True)  # crashed saves
        os.makedirs(tmp)
        leaves, treedef = jax.tree_util.tree_flatten(state)
        np.savez(
            os.path.join(tmp, "state.npz"),
            **{f"leaf_{i}": np.asarray(l) for i, l in enumerate(leaves)},
        )
        with open(os.path.join(tmp, "treedef.pkl"), "wb") as f:
            pickle.dump(treedef, f)
        # world manifest + per-file integrity digests, written INSIDE the
        # tmp dir so manifest and payload commit in the same rename: a
        # snapshot that later fails digest verification (torn write,
        # bit rot) is excluded from the inventory by _is_complete and
        # the agreement degrades to the previous step.
        _elastic.write_manifest(
            _elastic.world_manifest(
                self._comm, files=_elastic.file_digests(tmp)
            ),
            os.path.join(tmp, _elastic.MANIFEST_NAME),
        )
        # os.rename cannot replace a non-empty dir, so an existing
        # target (a re-save, or a failed orbax attempt's droppings) is
        # renamed ASIDE first.  The old snapshot survives until the new
        # one is fully written; the residual risk is a kill in the
        # instants BETWEEN the two renames, which loses only this
        # step's snapshot — the agreement protocol then resumes one
        # step earlier, which is safe.  Stale .old/.tmp dirs from
        # crashed saves are invisible to the step scan (the regex
        # matches step_<digits> exactly) and are swept here on the next
        # save of the same step, so they cannot accumulate or make the
        # rename-aside fail with ENOTEMPTY.
        old = f"{target}.old{os.getpid()}"
        for stale in sorted(_glob.glob(f"{_glob.escape(target)}.old*")):
            shutil.rmtree(stale, ignore_errors=True)
        if os.path.exists(target):
            os.rename(target, old)
        os.rename(tmp, target)
        shutil.rmtree(old, ignore_errors=True)

    # -- agreement + resume --------------------------------------------
    def newest_common_step(self) -> Optional[int]:
        """The newest step every process has on disk (parity: the allgather
        of snapshot inventories + max-common computation).

        The inventory exchange rides the SAME lockstep retry as
        ``comm_wire.plan_agreement`` / ``analysis.trace_agreement``: a
        transient obj-store fault or a torn payload during resume is
        observed by every process (each one unpickles each rank's
        payload), so all ranks fail — and re-exchange — together instead
        of desynchronizing the agreement.
        """
        from ..resilience.retry import lockstep_allgather

        with _obs.span("checkpoint.agreement"):
            local = self._available_steps()
            inventories = lockstep_allgather(
                self._comm, local,
                site="checkpoint.newest_common_step",
            )
            common = set(inventories[0])
            for inv in inventories[1:]:
                common &= set(inv)
            return max(common) if common else None

    def resume(self, like: Optional[Dict[str, Any]] = None):
        """Load the newest common snapshot; returns (step, state) or
        (None, None) when no checkpoint exists.

        Elastic restart: when the elected snapshot's world manifest
        names a DIFFERENT world size than this communicator spans, the
        load routes through the checkpoint resharder
        (``resilience.elastic.reshard_state``, template-driven by
        ``like``) instead of failing — ZeRO blocks re-partition, per-rank
        residuals drop, world-size-independent leaves survive.  A
        mismatch with no ``like`` template raises
        ``WorldResizeRequiredError`` (resharding needs the new world's
        freshly initialized state to re-partition onto).
        ``self.last_resize`` records ``(old_world, new_world)`` when the
        route was taken.
        """
        with _obs.span("checkpoint.resume"):
            return self._resume(like)

    def _resume(self, like: Optional[Dict[str, Any]] = None):
        self.wait_until_finished()  # async: the in-flight save counts
        self.last_resize = None
        self.last_manifest = None
        step = self.newest_common_step()
        if step is None:
            return None, None
        target = self._step_dir(step)
        manifest = _elastic.read_world_manifest(target)
        self.last_manifest = manifest
        old_world = (manifest or {}).get("world_size")
        resize = old_world is not None and int(old_world) != int(
            self._comm.size
        )
        npz = os.path.join(target, "state.npz")
        if os.path.exists(npz):
            treedef_path = os.path.join(target, "treedef.pkl")
            if not os.path.exists(treedef_path):
                raise RuntimeError(
                    f"checkpoint {target} uses the pre-0.2 flattened npz "
                    "format (no treedef.pkl); its tree structure cannot "
                    "be reconstructed — re-save with the current version"
                )
            data = np.load(npz, allow_pickle=True)
            with open(treedef_path, "rb") as f:
                treedef = pickle.load(f)
            leaves = [data[f"leaf_{i}"] for i in range(treedef.num_leaves)]
            leaves = [l[()] if l.ndim == 0 and l.dtype == object else l
                      for l in leaves]
            state = jax.tree_util.tree_unflatten(treedef, leaves)
            if resize:
                state = self._reshard(state, like, old_world, step)
            return step, state
        if resize:
            # World mismatch: the template's shapes (and shardings)
            # belong to the NEW world, so orbax must restore the SAVED
            # shapes as host arrays; the resharder's walk tolerates the
            # raw string-keyed-dict spelling of the saved structure.
            state = self._restore_raw_host(target)
            state = self._reshard(state, like, old_world, step)
            return step, state
        restore_kwargs = {}
        if like is not None:
            try:
                # Restore each leaf directly onto its devices with the
                # template's sharding (mesh-sharded TP kernels / expert
                # blocks / ZeRO state land sharded, no host round-trip).
                import orbax.checkpoint as ocp

                restore_kwargs["restore_args"] = (
                    ocp.checkpoint_utils.construct_restore_args(like)
                )
            except Exception as e:
                # Non-array template leaves (or an orbax API change):
                # restore still works via orbax defaults, but sharded
                # leaves then land replicated — say so rather than
                # silently degrading a large-model restore.
                import warnings

                warnings.warn(
                    "could not build sharded restore args from the "
                    f"template ({type(e).__name__}: {e}); restoring "
                    "with orbax defaults (leaves may come back "
                    "host-replicated — re-place with step.place)"
                )
        state = self._orbax().restore(
            os.path.abspath(target), item=like, **restore_kwargs
        )
        return step, state

    def _restore_raw_host(self, target: str):
        """Restore an orbax snapshot in its SAVED shapes as host numpy
        arrays (no template): the elastic path's loader — a
        world-mismatched snapshot cannot restore onto the new world's
        template shapes/shardings, and arrays saved from sharded
        ``jax.Array`` leaves need an explicit numpy restore type."""
        import orbax.checkpoint as ocp

        path = os.path.abspath(target)
        ckptr = self._orbax()
        # the per-leaf metadata tree (orbax wraps it in a StepMetadata)
        meta = ckptr.metadata(path).item_metadata.tree

        def arg_of(m):
            # true zarr-backed arrays must restore as numpy (the
            # default would rebuild jax.Arrays and demand the dead
            # world's shardings); scalar/string leaves live in the
            # aggregate file and must keep the default restore —
            # forcing np.ndarray makes orbax look for a zarr entry
            # that does not exist
            if type(m).__name__ == "ArrayMetadata":
                return ocp.RestoreArgs(restore_type=np.ndarray)
            return ocp.RestoreArgs()

        restore_args = jax.tree_util.tree_map(arg_of, meta)
        return ckptr.restore(path, restore_args=restore_args)

    def _reshard(self, state, like, old_world, step: int):
        """Route a world-mismatched snapshot through the elastic
        resharder (see :meth:`resume`)."""
        from ..resilience.errors import WorldResizeRequiredError

        new_world = int(self._comm.size)
        old_world = int(old_world)
        if like is None:
            raise WorldResizeRequiredError(
                f"checkpoint step {step} was written at world size "
                f"{old_world} but this world spans {new_world} chips; "
                "resharding needs a template of the new world's state — "
                "call resume(like=...) with freshly initialized "
                "params/opt_state (restore_trainer passes the trainer's "
                "own), or restart via Trainer.run_elastic",
                site="checkpoint.resume",
            )
        with _obs.span("checkpoint.reshard", step=int(step),
                       old_world=old_world, new_world=new_world):
            state = _elastic.reshard_state(
                state, like, old_world, new_world, label=f"step_{step}"
            )
        self.last_resize = (old_world, new_world)
        _emit(
            "elastic_resume", "checkpoint.resume",
            step=step, old_world=old_world, new_world=new_world,
        )
        return state

    def _rm_step(self, step: int) -> None:
        """Delete one snapshot AND its sibling world manifest (the npz
        tier's manifest lives inside the dir and goes with it)."""
        target = self._step_dir(step)
        shutil.rmtree(target, ignore_errors=True)
        try:
            os.remove(_elastic.manifest_sibling(target))
        except OSError:
            pass
        self._verified.pop(target, None)

    def _gc_local(self) -> None:
        """GC for the per-rank local-disk tier: every process owns its
        own directory, so deletion is local and barrier-free (a barrier
        here would turn one dead rank into a hang for all)."""
        steps = self._available_steps()
        for s in steps[: -self._keep] if self._keep else []:
            self._rm_step(s)

    def _gc(self) -> None:
        if self._multiproc and not self._use_orbax:
            self._gc_local()
            return
        if self._multiproc:
            # shared-FS deletes are chief-only; peers wait so a stale dir
            # never reappears in a subsequent scan
            if self._is_chief:
                steps = self._available_steps()
                for s in steps[: -self._keep] if self._keep else []:
                    self._rm_step(s)
            self._comm.barrier()
            return
        steps = self._available_steps()
        for s in steps[: -self._keep] if self._keep else []:
            self._rm_step(s)

    def finalize(self, trainer=None) -> None:
        """Parity: the reference's finalize/GC of stale snapshots (plus,
        async tier: drain the in-flight save so process exit cannot
        truncate a snapshot)."""
        self.wait_until_finished()
        self._gc()

    # -- trainer-extension protocol ------------------------------------
    def __call__(self, trainer) -> None:
        state = {
            "params": trainer.updater.params,
            "opt_state": trainer.updater.opt_state,
            "trainer": trainer.state_dict(),
        }
        self.save(trainer.iteration, state)

    def restore_trainer(self, trainer) -> Optional[int]:
        step, state = self.resume(
            like={
                "params": trainer.updater.params,
                "opt_state": trainer.updater.opt_state,
                "trainer": trainer.state_dict(),
            }
        )
        if step is None:
            return None
        if self.last_resize:
            # Iterator cursors are per-PROCESS state (each controller
            # feeds its own dataset shard), so they re-map by PROCESS
            # count: pos rescaled onto the new shard width, order
            # redrawn from the restored RNG.  An UNCHANGED process
            # count (chips-per-process resize, or single-controller
            # global batches) leaves the shard width — and therefore
            # the cursor and the in-flight permutation — exactly valid,
            # so they survive untouched.
            old_pc = int((self.last_manifest or {}).get(
                "process_count"
            ) or 1)
            new_pc = int(self._comm.process_count)
            tr = state.get("trainer")
            if old_pc != new_pc and isinstance(
                tr, dict
            ) and isinstance(tr.get("iterator"), dict):
                tr["iterator"] = _elastic.reshard_iterator_state(
                    tr["iterator"], old_pc, new_pc
                )
            # the resharded leaves are host arrays; lay them out onto
            # the NEW world's mesh per the step's own placement rules
            # (ZeRO state shards land sharded, params replicate)
            place = getattr(trainer.updater.step_fn, "place", None)
            if place is not None:
                state["params"], state["opt_state"] = place(
                    state["params"], state["opt_state"]
                )
        trainer.updater.params = state["params"]
        trainer.updater.opt_state = state["opt_state"]
        trainer.load_state_dict(state["trainer"])
        return step


def create_multi_node_checkpointer(name: str, comm, path: str = "checkpoints",
                                   trigger=(1, "epoch"), keep: int = 3,
                                   **kw) -> _MultiNodeCheckpointer:
    """Parity: ``chainermn.create_multi_node_checkpointer(name, comm)``."""
    return _MultiNodeCheckpointer(name, comm, path=path, trigger=trigger,
                                  keep=keep, **kw)
