"""Extensions + trainer-loop tests.

Parity: ``extensions_tests/test_checkpoint.py`` (snapshot/resume
round-trip), evaluator test, ``test_allreduce_persistent.py``; plus the
trainer loop this framework provides in place of Chainer's.
"""

import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

import chainermn_tpu as cmn
from chainermn_tpu.extensions.evaluator import Evaluator
from chainermn_tpu.extensions.allreduce_persistent import AllreducePersistent
from chainermn_tpu.iterators import SerialIterator
from chainermn_tpu.iterators.serial_iterator import EpochIterator
from chainermn_tpu.training import Trainer, Updater
from chainermn_tpu.training import extensions as T
from chainermn_tpu.models import MLP
from chainermn_tpu.utils import SyntheticImageDataset


@pytest.fixture(scope="module")
def comm(devices8):
    return cmn.create_communicator("tpu", devices=devices8)


def _make_training(comm, n=256, batch=64):
    ds = SyntheticImageDataset(n, shape=(8, 8), n_classes=4, seed=0)
    it = SerialIterator(ds, batch, shuffle=True, seed=1)
    model = MLP(n_units=32, n_out=4, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8)))
    params = comm.bcast_data(params)
    opt = cmn.create_multi_node_optimizer(optax.sgd(0.1), comm)

    def loss_fn(p, b):
        x, y = b
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y
        ).mean()

    step = cmn.build_train_step(comm, loss_fn, opt, donate=False)
    params, opt_state = step.place(params, opt.init(params))
    return model, it, step, params, opt_state


class TestTrainerLoop:
    def test_loss_decreases(self, comm):
        model, it, step, params, opt_state = _make_training(comm)
        updater = Updater(it, step, params, opt_state)
        trainer = Trainer(updater, stop_trigger=(3, "epoch"))
        log = T.LogReport(comm=comm, filename=None)
        trainer.extend(log, trigger=(1, "epoch"))
        trainer.run()
        losses = [e["loss"] for e in log.log if "loss" in e]
        assert len(losses) >= 2
        assert losses[-1] < losses[0]

    def test_stop_by_iteration(self, comm):
        model, it, step, params, opt_state = _make_training(comm)
        trainer = Trainer(
            Updater(it, step, params, opt_state),
            stop_trigger=(5, "iteration"),
        )
        trainer.run()
        assert trainer.iteration == 5

    def test_prefetched_batches_not_replaced(self, comm):
        """Feeding the Updater prefetch_to_device output (already-placed
        global jax.Arrays) must NOT go through place_batch again — in
        multi-process runs re-placing a non-fully-addressable global
        array crashes.  The guard: placed batches pass straight through."""
        from chainermn_tpu.iterators import prefetch_to_device

        model, it, step, params, opt_state = _make_training(comm)
        calls = {"n": 0}
        real_place = step.place_batch

        def counting_place(batch):
            calls["n"] += 1
            return real_place(batch)

        step.place_batch = counting_place
        feed = prefetch_to_device(it, real_place, depth=2)
        trainer = Trainer(
            Updater(feed, step, params, opt_state),
            stop_trigger=(3, "iteration"),
        )
        trainer.run()
        assert trainer.iteration == 3
        # the prefetcher placed them; the Updater must not re-place
        assert calls["n"] == 0


class TestEvaluator:
    def test_global_metrics(self, comm):
        model, it, step, params, opt_state = _make_training(comm)
        ds = SyntheticImageDataset(128, shape=(8, 8), n_classes=4, seed=9)

        def metric_fn(p, b):
            x, y = b
            logits = model.apply(p, x)
            return {
                "accuracy": (jnp.argmax(logits, -1) == y).mean(),
            }

        ev = Evaluator(lambda: EpochIterator(ds, 64), metric_fn, comm)
        out = ev.evaluate(params)
        assert "val/accuracy" in out
        assert 0.0 <= out["val/accuracy"] <= 1.0

    def test_create_multi_node_evaluator_passthrough(self, comm):
        model, it, step, params, opt_state = _make_training(comm)
        ev = Evaluator(lambda: iter(()), lambda p, b: {}, comm)
        assert cmn.create_multi_node_evaluator(ev, comm) is ev

    def test_wrap_foreign_evaluator(self, comm):
        class Plain:
            def evaluate(self):
                return {"loss": 2.0}

        wrapped = cmn.create_multi_node_evaluator(Plain(), comm)
        assert wrapped.evaluate() == {"loss": 2.0}


class TestCheckpointer:
    def test_save_resume_roundtrip(self, comm, tmp_path):
        ckpt = cmn.create_multi_node_checkpointer(
            "t1", comm, path=str(tmp_path)
        )
        state = {
            "params": {"w": jnp.arange(4.0)},
            "step_meta": {"iteration": 7},
        }
        ckpt.save(7, state)
        step, restored = ckpt.resume(like=state)
        assert step == 7
        np.testing.assert_allclose(
            np.asarray(restored["params"]["w"]), np.arange(4.0)
        )

    def test_newest_common_step_and_gc(self, comm, tmp_path):
        ckpt = cmn.create_multi_node_checkpointer(
            "t2", comm, path=str(tmp_path), keep=2
        )
        for s in (1, 2, 3):
            ckpt.save(s, {"x": jnp.zeros(2)})
        assert ckpt.newest_common_step() == 3
        assert len(ckpt._available_steps()) == 2  # GC kept last 2

    def test_resume_empty_returns_none(self, comm, tmp_path):
        ckpt = cmn.create_multi_node_checkpointer(
            "t3", comm, path=str(tmp_path)
        )
        assert ckpt.resume() == (None, None)

    def test_npz_fallback_roundtrips_tree_structure(self, comm, tmp_path,
                                                    monkeypatch):
        # Force the degraded (orbax-less) backend and verify resume()
        # returns the original nested structure — the restore_trainer
        # contract — not a flattened dict.
        ckpt = cmn.create_multi_node_checkpointer(
            "t4", comm, path=str(tmp_path)
        )

        class BrokenOrbax:
            def save(self, *a, **kw):
                raise OSError("orbax unavailable")

        monkeypatch.setattr(ckpt, "_orbax", lambda: BrokenOrbax())
        state = {
            "params": {"w": jnp.arange(4.0), "b": jnp.ones((2,))},
            "opt_state": (jnp.zeros((3,)), {"count": jnp.asarray(5)}),
            "trainer": {"iteration": 7, "epoch": 1},
        }
        ckpt.save(7, state)
        step, restored = ckpt.resume(like=state)
        assert step == 7
        np.testing.assert_allclose(
            np.asarray(restored["params"]["w"]), np.arange(4.0)
        )
        np.testing.assert_allclose(
            np.asarray(restored["opt_state"][0]), np.zeros((3,))
        )
        assert int(restored["opt_state"][1]["count"]) == 5
        assert int(restored["trainer"]["iteration"]) == 7

    def test_async_save_resume_equality(self, comm, tmp_path):
        """The async tier (VERDICT r4 #5): save() returns before the
        write commits; wait_until_finished/resume must still observe a
        complete, byte-equal snapshot — including SHARDED leaves (a
        ZeRO-style 1/N layout restored via the template)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        ckpt = cmn.create_multi_node_checkpointer(
            "t_async", comm, path=str(tmp_path), use_async=True
        )
        sharded = jax.device_put(
            jnp.arange(comm.size * 4.0).reshape(comm.size, 4),
            NamedSharding(comm.mesh, P(comm.axis_names)),
        )
        state = {
            "params": {"w": jnp.arange(4.0), "shard": sharded},
            "opt_state": (jnp.ones((3,)), {"count": jnp.asarray(5)}),
        }
        ckpt.save(3, state)
        # an in-flight save is not yet visible to the directory scan...
        ckpt.wait_until_finished()
        # ...but counts after the drain; resume() drains internally too
        assert ckpt.newest_common_step() == 3
        step, restored = ckpt.resume(like=state)
        assert step == 3
        np.testing.assert_allclose(
            np.asarray(restored["params"]["w"]), np.arange(4.0)
        )
        np.testing.assert_allclose(
            np.asarray(restored["params"]["shard"]), np.asarray(sharded)
        )
        # the sharded leaf must come back SHARDED (template layout),
        # not host-replicated
        assert restored["params"]["shard"].sharding.is_equivalent_to(
            sharded.sharding, sharded.ndim
        )
        assert int(restored["opt_state"][1]["count"]) == 5

    def test_async_requires_orbax(self, comm, tmp_path):
        """use_async with the synchronous npz backend would silently
        break the non-stalling-save contract — rejected loudly."""
        with pytest.raises(ValueError, match="use_async"):
            cmn.create_multi_node_checkpointer(
                "t_bad", comm, path=str(tmp_path),
                use_orbax=False, use_async=True,
            )

    def test_async_back_to_back_saves_serialize(self, comm, tmp_path):
        """Two async saves in a row: the second must wait for the
        first's commit (directory mutations would otherwise race), and
        both snapshots must be resumable."""
        ckpt = cmn.create_multi_node_checkpointer(
            "t_async2", comm, path=str(tmp_path), use_async=True, keep=3
        )
        for s in (1, 2):
            ckpt.save(s, {"x": jnp.full((2,), float(s))})
        step, restored = ckpt.resume()
        assert step == 2
        np.testing.assert_allclose(np.asarray(restored["x"]), 2.0)

    def test_npz_fallback_explicit(self, comm, tmp_path):
        ckpt = cmn.create_multi_node_checkpointer(
            "t5", comm, path=str(tmp_path), use_orbax=False
        )
        state = {"params": {"w": jnp.full((2, 2), 3.0)}, "meta": [1, 2]}
        ckpt.save(1, state)
        step, restored = ckpt.resume()
        assert step == 1
        np.testing.assert_allclose(np.asarray(restored["params"]["w"]), 3.0)
        assert list(restored["meta"]) == [1, 2]


class TestAllreducePersistent:
    def test_single_controller_identity(self, comm):
        arp = AllreducePersistent(comm)
        stats = {"mean": jnp.arange(3.0)}
        out = arp.reduce(stats)
        np.testing.assert_allclose(np.asarray(out["mean"]), np.arange(3.0))

    def test_stacked_per_rank_stats_averaged_in_mesh(self, comm):
        # Eager tier: BN running stats are stacked per-rank; reduce must
        # make every rank's slice the mean over ranks (the reference's
        # allreduce of persistent arrays), via the XLA allreduce.
        arp = AllreducePersistent(comm, stacked=True)
        per_rank = jnp.stack(
            [jnp.full((3,), float(r)) for r in range(comm.size)]
        )
        out = arp.reduce({"running_mean": per_rank})["running_mean"]
        want = np.full((comm.size, 3), np.mean(np.arange(comm.size)))
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


class TestGlobalExceptHook:
    def test_install_remove(self):
        import sys

        from chainermn_tpu import global_except_hook as geh

        # an example's main() run earlier in this process (which files a
        # worker gets is the scheduler's choice) leaves its hook in
        geh.remove_hook()
        old = sys.excepthook
        geh.add_hook()
        assert sys.excepthook is not old
        geh.remove_hook()
        assert sys.excepthook is sys.__excepthook__


class TestProfileExtension:
    def test_trace_window_produces_profile(self, comm, tmp_path):
        model, it, step, params, opt_state = _make_training(comm)
        trainer = Trainer(
            Updater(it, step, params, opt_state),
            stop_trigger=(6, "iteration"),
        )
        logdir = str(tmp_path / "prof")
        prof = T.Profile(start=2, stop=4, logdir=logdir, comm=comm)
        trainer.extend(prof, trigger=(1, "iteration"))
        trainer.run()
        assert prof.done
        # TensorBoard profile-plugin layout: plugins/profile/<run>/...
        plugin_dir = os.path.join(logdir, "plugins", "profile")
        assert os.path.isdir(plugin_dir)
        runs = os.listdir(plugin_dir)
        assert runs, "no profile run captured"
        files = os.listdir(os.path.join(plugin_dir, runs[0]))
        assert any("trace" in f for f in files), files

    def test_finalize_closes_open_trace(self, comm, tmp_path):
        model, it, step, params, opt_state = _make_training(comm)
        trainer = Trainer(
            Updater(it, step, params, opt_state),
            stop_trigger=(3, "iteration"),  # stops inside the window
        )
        prof = T.Profile(start=1, stop=10, logdir=str(tmp_path / "p2"),
                         comm=comm)
        trainer.extend(prof, trigger=(1, "iteration"))
        trainer.run()
        prof.finalize()
        assert prof.done

    def test_invalid_window_rejected(self):
        with pytest.raises(ValueError):
            T.Profile(start=5, stop=5)


class TestThroughputExtension:
    def test_reports_after_warmup(self, comm):
        model, it, step, params, opt_state = _make_training(comm)
        trainer = Trainer(
            Updater(it, step, params, opt_state),
            stop_trigger=(6, "iteration"),
        )
        trainer.extend(T.Throughput(64, comm=comm), trigger=(1, "iteration"))
        trainer.run()
        assert "samples_per_sec" in trainer.observation
        assert trainer.observation["samples_per_sec_per_chip"] > 0
