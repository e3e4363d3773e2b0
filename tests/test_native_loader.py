"""Native (C++) input-pipeline tests.

SURVEY.md section 2, native-code obligations: csrc/loader.cpp replaces the
reference's MultiprocessIterator + pinned staging path.  The contract
pinned here: batch order and augmentation are deterministic in the seed
for ANY worker-thread count, normalization matches the numpy oracle, and
the epoch bookkeeping mirrors SerialIterator.
"""

import numpy as np
import pytest

from chainermn_tpu.utils.native_loader import (
    NativeImageLoader,
    native_available,
    NativeTokenLoader,
)

pytestmark = pytest.mark.skipif(
    not native_available(), reason="no C++ toolchain for the native loader"
)

N, H, W, C = 64, 12, 10, 3
BATCH = 8


def _data(seed=0):
    rng = np.random.RandomState(seed)
    images = rng.randint(0, 256, size=(N, H, W, C), dtype=np.uint8)
    labels = rng.randint(0, 10, size=(N,)).astype(np.int32)
    return images, labels


def _take(loader, k):
    return [next(loader) for _ in range(k)]


class TestEvalModeOracle:
    def test_matches_numpy_center_crop_normalize(self):
        images, labels = _data()
        mean, std = (10.0, 20.0, 30.0), (50.0, 60.0, 70.0)
        crop = (8, 6)
        loader = NativeImageLoader(
            images, labels, BATCH, crop=crop, n_threads=2, seed=7,
            shuffle=False, train=False, mean=mean, std=std,
        )
        x, y = next(loader)
        assert x.shape == (BATCH, 8, 6, C) and x.dtype == np.float32
        off_h, off_w = (H - 8) // 2, (W - 6) // 2
        want = (images[:BATCH, off_h:off_h + 8, off_w:off_w + 6].astype(
            np.float32
        ) - np.asarray(mean, np.float32)) / np.asarray(std, np.float32)
        np.testing.assert_allclose(x, want, rtol=1e-6)
        np.testing.assert_array_equal(y, labels[:BATCH])
        loader.close()


class TestDeterminism:
    def _seq(self, n_threads, seed=3, train=True, k=16):
        images, labels = _data()
        loader = NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=n_threads,
            seed=seed, shuffle=True, train=train,
        )
        out = _take(loader, k)
        loader.close()
        return out

    def test_thread_count_does_not_change_results(self):
        a = self._seq(n_threads=1)
        b = self._seq(n_threads=4)
        for (xa, ya), (xb, yb) in zip(a, b):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_seed_changes_shuffle_and_augmentation(self):
        a = self._seq(n_threads=2, seed=3)
        b = self._seq(n_threads=2, seed=4)
        assert any(
            not np.array_equal(ya, yb) for (_, ya), (_, yb) in zip(a, b)
        )

    def test_epochs_reshuffle(self):
        images, labels = _data()
        loader = NativeImageLoader(
            images, labels, BATCH, n_threads=2, seed=1, shuffle=True,
            train=False,
        )
        bpe = loader.batches_per_epoch
        epoch0 = [y.copy() for _, y in _take(loader, bpe)]
        epoch1 = [y.copy() for _, y in _take(loader, bpe)]
        loader.close()
        # Same multiset of labels each epoch, different order.
        np.testing.assert_array_equal(
            np.sort(np.concatenate(epoch0)), np.sort(np.concatenate(epoch1))
        )
        assert any(
            not np.array_equal(a, b) for a, b in zip(epoch0, epoch1)
        )


class TestBookkeepingAndLifecycle:
    def test_epoch_counters(self):
        images, labels = _data()
        loader = NativeImageLoader(images, labels, BATCH, n_threads=2)
        bpe = loader.batches_per_epoch
        assert bpe == N // BATCH
        assert loader.epoch == 0
        _take(loader, bpe)
        assert loader.epoch == 1
        assert loader.epoch_detail == pytest.approx(1.0)
        loader.close()

    def test_zero_copy_acquire_release(self):
        images, labels = _data()
        loader = NativeImageLoader(
            images, labels, BATCH, n_threads=2, ring=2,
            shuffle=False, train=False,
        )
        slot, x, y = loader.acquire()
        first = x.copy()
        loader.release(slot)
        # After release+reuse the *contents* advance batch by batch.
        for _ in range(loader.batches_per_epoch - 1):
            s2, x2, _ = loader.acquire()
            loader.release(s2)
        np.testing.assert_array_equal(first[0], next(loader)[0][0])
        loader.close()

    def test_bad_config_rejected(self):
        images, labels = _data()
        with pytest.raises(ValueError):
            NativeImageLoader(images, labels, N + 1)  # batch > n
        with pytest.raises(ValueError):
            NativeImageLoader(images, labels, BATCH, crop=(H + 1, W))

    def test_tiny_epoch_ring_spans_stay_deterministic(self):
        # Regression: with batches_per_epoch (2) far below the requested
        # ring (8), tickets from 3+ epochs could race the epoch-parity
        # permutation cache (duplicated/corrupt samples).  The ring is now
        # clamped to one epoch; many epochs must match the 1-thread run.
        rng = np.random.RandomState(0)
        images = rng.randint(0, 256, size=(6, 4, 4, 1), dtype=np.uint8)
        labels = np.arange(6, dtype=np.int32)

        def run(n_threads):
            loader = NativeImageLoader(
                images, labels, 3, n_threads=n_threads, ring=8, seed=5,
                shuffle=True, train=True,
            )
            out = [(x.copy(), y.copy()) for x, y in
                   (next(loader) for _ in range(40))]
            loader.close()
            return out

        ref, par = run(1), run(4)
        for (xa, ya), (xb, yb) in zip(ref, par):
            np.testing.assert_array_equal(ya, yb)
            np.testing.assert_array_equal(xa, xb)
        # No duplicate samples within any epoch (2 batches x 3 = all 6)
        for e in range(20):
            ys = np.concatenate([par[2 * e][1], par[2 * e + 1][1]])
            assert len(set(ys.tolist())) == 6

    def test_serialize_restore_repositions_stream(self):
        images, labels = _data()
        mk = lambda: NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=2, seed=9,
            shuffle=True, train=True,
        )
        a = mk()
        _take(a, 5)
        state = a.serialize()
        want = _take(a, 3)
        # Fresh loader, restore, stream must continue identically.
        b = mk()
        _take(b, 11)  # past the snapshot: forces the rewind path
        b.restore(state)
        got = _take(b, 3)
        for (xa, ya), (xb, yb) in zip(want, got):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)
        a.close(), b.close()

    def test_seek_deep_is_constant_time(self):
        # The native seek repositions worker tickets directly: restoring
        # deep into training must NOT produce/discard the skipped batches.
        import time

        images, labels = _data()
        loader = NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=2, seed=9,
            shuffle=True, train=True,
        )
        deep = 200_000  # ~25k epochs of 8 batches; replay would take minutes
        t0 = time.monotonic()
        loader.restore({"iteration": deep})
        dt = time.monotonic() - t0
        assert dt < 5.0, f"seek took {dt:.1f}s — looks like a replay"
        assert loader.serialize()["iteration"] == deep
        got = next(loader)
        # Oracle: a fresh loader seeked (not replayed) to the same ticket
        # must produce the identical batch; also check epoch bookkeeping.
        other = NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=4, seed=9,
            shuffle=True, train=True,
        )
        other.restore({"iteration": deep})
        want = next(other)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        assert loader.epoch == deep // loader.batches_per_epoch
        loader.close(), other.close()

    def test_restore_refuses_while_slot_held(self):
        # restore's native seek restarts workers, which would overwrite a
        # still-held zero-copy view — it must raise until release()
        images, labels = _data()
        loader = NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=2, seed=3,
        )
        state = loader.serialize()
        slot, _x, _y = loader.acquire()
        with pytest.raises(RuntimeError, match="acquired slot"):
            loader.restore(state)
        loader.release(slot)
        loader.restore(state)  # released: seek proceeds
        loader.close()

    def test_train_augmentation_in_range(self):
        images, labels = _data()
        loader = NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=3, train=True,
        )
        x, _ = next(loader)
        assert np.isfinite(x).all()
        assert x.min() >= 0.0 and x.max() <= 1.0  # default mean 0, std 255
        loader.close()


class TestUint8Wire:
    """The uint8 wire mode (VERDICT r4 #2): crop/flip in C++, normalize
    on device — half of bf16's bytes over the link.  The contract pinned
    here: identical augmentation geometry to the float32 wire for the
    same seed, and device_normalize(uint8 batch) equals the float32
    wire's host-normalized output exactly (both are fp32 (px-mean)/std,
    one computed in C++, one in XLA)."""

    def test_u8_view_dtype_and_bytes(self):
        images, labels = _data()
        loader = NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=2, seed=3,
            wire="uint8",
        )
        slot, x, y = loader.acquire()
        assert x.dtype == np.uint8 and x.shape == (BATCH, 8, 8, C)
        assert x.nbytes == BATCH * 8 * 8 * C  # one byte per pixel-channel
        assert loader.wire == "uint8"
        loader.release(slot)
        loader.close()
        # the 1/4-of-float32 wire claim, against a real float32 batch
        f = NativeImageLoader(
            images, labels, BATCH, crop=(8, 8), n_threads=2, seed=3,
        )
        slot_f, x_f, _y_f = f.acquire()
        assert x_f.dtype == np.float32
        assert x_f.nbytes == 4 * x.nbytes
        f.release(slot_f)
        f.close()

    @pytest.mark.parametrize("train", [False, True])
    def test_matches_float_wire_after_device_normalize(self, train):
        from chainermn_tpu.utils.native_loader import device_normalize

        images, labels = _data()
        mean, std = (10.0, 20.0, 30.0), (50.0, 60.0, 70.0)
        kw = dict(crop=(8, 6), n_threads=2, seed=11, shuffle=True,
                  train=train, mean=mean, std=std)
        f = NativeImageLoader(images, labels, BATCH, **kw)
        u = NativeImageLoader(images, labels, BATCH, wire="uint8", **kw)
        try:
            for _ in range(6):
                xf, yf = next(f)
                xu, yu = next(u)
                np.testing.assert_array_equal(yf, yu)
                got = np.asarray(
                    device_normalize(jnp_asarray(xu), u.mean, u.std)
                )
                # bit-for-bit: device_normalize subtracts then DIVIDES
                # in fp32, the exact op sequence of the C++ float32 wire
                np.testing.assert_array_equal(got, xf)
        finally:
            f.close()
            u.close()

    def test_u8_thread_determinism(self):
        images, labels = _data()

        def run(n_threads):
            ld = NativeImageLoader(
                images, labels, BATCH, crop=(8, 8), wire="uint8",
                n_threads=n_threads, seed=5, shuffle=True, train=True,
            )
            out = [(x.copy(), y.copy()) for x, y in _take(ld, 12)]
            ld.close()
            return out

        for (xa, ya), (xb, yb) in zip(run(1), run(4)):
            np.testing.assert_array_equal(xa, xb)
            np.testing.assert_array_equal(ya, yb)

    def test_bad_wire_rejected(self):
        images, labels = _data()
        with pytest.raises(ValueError, match="wire"):
            NativeImageLoader(images, labels, BATCH, wire="bf16")


def jnp_asarray(x):
    import jax.numpy as jnp

    return jnp.asarray(x)


class TestTokenLoader:
    """The LM-path loader over the shared ring engine: shuffled
    fixed-length windows of a flat token stream."""

    def _corpus(self, n=1024):
        return np.arange(n, dtype=np.int32)

    def test_windows_partition_the_corpus(self):
        # one epoch must visit every window exactly once (batch 4 x
        # seq 8 over 256 tokens = 32 windows = 8 batches/epoch)
        ld = NativeTokenLoader(self._corpus(256), 4, 8, seed=3)
        try:
            assert ld.batches_per_epoch == 8
            seen = []
            for _ in range(ld.batches_per_epoch):
                seen.append(next(ld))
            toks = np.concatenate([b.reshape(-1) for b in seen])
            np.testing.assert_array_equal(
                np.sort(toks), np.arange(256, dtype=np.int32)
            )
            # windows are contiguous runs
            firsts = np.concatenate([b[:, 0] for b in seen])
            assert (firsts % 8 == 0).all()
        finally:
            ld.close()

    def test_thread_count_does_not_change_stream(self):
        ref = NativeTokenLoader(self._corpus(), 4, 16, n_threads=1,
                                seed=7)
        many = NativeTokenLoader(self._corpus(), 4, 16, n_threads=7,
                                 seed=7)
        try:
            for _ in range(20):
                np.testing.assert_array_equal(next(ref), next(many))
        finally:
            ref.close()
            many.close()

    def test_epochs_reshuffle_deterministically(self):
        a = NativeTokenLoader(self._corpus(), 8, 8, seed=1)
        b = NativeTokenLoader(self._corpus(), 8, 8, seed=1)
        try:
            bpe = a.batches_per_epoch
            e0 = [next(a) for _ in range(bpe)]
            e1 = [next(a) for _ in range(bpe)]
            assert any(
                not np.array_equal(x, y) for x, y in zip(e0, e1)
            )  # different epoch order
            for x in e0:
                np.testing.assert_array_equal(x, next(b))  # same seed
        finally:
            a.close()
            b.close()

    def test_serialize_restore_repositions(self):
        ld = NativeTokenLoader(self._corpus(), 4, 16, seed=5)
        try:
            for _ in range(5):
                next(ld)
            state = ld.serialize()
            want = [next(ld) for _ in range(4)]
            for _ in range(3):
                next(ld)
            ld.restore(state)
            for w in want:
                np.testing.assert_array_equal(next(ld), w)
        finally:
            ld.close()

    def test_too_small_corpus_rejected(self):
        with pytest.raises(ValueError, match="cannot fill"):
            NativeTokenLoader(np.arange(16, dtype=np.int32), 4, 8)


class TestLoaderThroughput:
    def test_loader_host_pipeline_rate(self):
        """Native-input evidence (VERDICT r3 #3): measure what the
        loader+host-cast pipeline alone produces at bench shapes
        (128x224x224x3 uint8 -> crop/flip/normalize -> bf16 host cast,
        no device in the loop).  On a multi-core host the worker threads
        scale; on a 1-core host the pipeline is itself host-bound.
        Only a sanity floor is asserted here (wall-clock
        throughput assertions don't belong in a unit suite)."""
        import time

        import ml_dtypes

        batch, image = 128, 224
        n_data = 512
        rng = np.random.RandomState(0)
        images = rng.randint(
            0, 256, size=(n_data, image + 8, image + 8, 3), dtype=np.uint8
        )
        labels = rng.randint(0, 1000, size=(n_data,)).astype(np.int32)
        loader = NativeImageLoader(
            images, labels, batch, crop=(image, image), n_threads=8,
            seed=0, shuffle=True, train=True,
            mean=(123.7, 116.3, 103.5), std=(58.4, 57.1, 57.4),
        )
        try:
            # warm the ring
            slot, xv, yv = loader.acquire()
            loader.release(slot)
            k = 12
            t0 = time.perf_counter()
            for _ in range(k):
                slot, xv, yv = loader.acquire()
                # the bench's host-side work: bf16 cast detaching the view
                _ = xv.astype(ml_dtypes.bfloat16)
                loader.release(slot)
            dt = time.perf_counter() - t0
        finally:
            loader.close()
        imgs_per_sec = k * batch / dt
        # Sanity floor only: wall-clock throughput in a unit suite must
        # not fail under CI load.  Whether the loader keeps a chip fed
        # is a cell's to say (none feeds through it: PERF.md section 7).
        assert imgs_per_sec > 20, (
            f"loader+cast produced only {imgs_per_sec:.0f} img/s - "
            "the native pipeline is pathologically slow"
        )
