"""ctypes binding for the native (C++) input pipelines.

SURVEY.md section 2 "native-code obligations": the reference's host-side
data path is Chainer's MultiprocessIterator plus pinned-memory staging
buffers; ``csrc/loader.cpp`` is the TPU rebuild's native equivalent — a
shared worker-thread ring engine with two loaders on top: image batches
(crop / flip / normalize off the GIL — :class:`NativeImageLoader`, the
ImageNet path) and token-stream batches (shuffled fixed-length windows —
:class:`NativeTokenLoader`, the LM path).  This module compiles the
library on first use with ``g++`` (no pybind11 in the image; plain C ABI
+ ctypes) and wraps each loader as a Python iterator.

Falls back cleanly: ``native_available()`` is False when no compiler is
present, and the loaders raise with a clear message — callers (e.g. the
ImageNet example) can then use SerialIterator.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Sequence, Tuple

import numpy as np

_BUILD_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LIB_ERR: Optional[str] = None


def _source_path() -> str:
    # csrc/ ships inside the package (see pyproject [tool.setuptools
    # .package-data]) so installed trees can build the loader too.
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "csrc", "loader.cpp",
    )


def _build_dir() -> str:
    d = os.path.join(os.path.dirname(_source_path()), "_build")
    try:
        os.makedirs(d, exist_ok=True)
        if not os.access(d, os.W_OK):
            raise OSError
    except OSError:
        # Installed into a read-only site-packages: build in a user cache.
        d = os.path.join(
            os.environ.get(
                "XDG_CACHE_HOME", os.path.expanduser("~/.cache")
            ),
            "chainermn_tpu",
        )
        os.makedirs(d, exist_ok=True)
    return d


def _load_library() -> ctypes.CDLL:
    """Compile (if stale) and dlopen the loader library."""
    global _LIB, _LIB_ERR
    with _BUILD_LOCK:
        if _LIB is not None:
            return _LIB
        if _LIB_ERR is not None:
            raise RuntimeError(_LIB_ERR)
        src = _source_path()
        # Key the artifact on the source CONTENT, not mtime: packaging can
        # normalize timestamps, and a stale .so with an older ABI would
        # fail symbol resolution below.  A new source hash -> new filename.
        import hashlib

        with open(src, "rb") as f:
            tag = hashlib.sha256(f.read()).hexdigest()[:12]
        build = _build_dir()
        so = os.path.join(build, f"libcmn_loader_{tag}.so")
        try:
            if not os.path.exists(so):
                # Compile to a per-process temp name, then atomically
                # rename: concurrent processes (jax.distributed workers)
                # may race to build the same artifact, and dlopen of a
                # half-written file would poison _LIB_ERR for the
                # process lifetime.
                tmp = f"{so}.tmp{os.getpid()}"
                cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                       "-pthread", src, "-o", tmp]
                subprocess.run(cmd, check=True, capture_output=True,
                               text=True)
                os.replace(tmp, so)
                # drop artifacts of older source revisions
                for stale in os.listdir(build):
                    if (stale.startswith("libcmn_loader")
                            and stale.endswith(".so")
                            and stale != os.path.basename(so)):
                        try:
                            os.unlink(os.path.join(build, stale))
                        except OSError:
                            pass
            lib = ctypes.CDLL(so)
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            _LIB_ERR = f"native loader unavailable: {detail}"
            raise RuntimeError(_LIB_ERR) from e
        lib.cmn_loader_create.restype = ctypes.c_void_p
        lib.cmn_loader_create.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int,
        ]
        lib.cmn_loader_acquire_u8.restype = ctypes.c_int
        lib.cmn_loader_acquire_u8.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ]
        lib.cmn_token_loader_create.restype = ctypes.c_void_p
        lib.cmn_token_loader_create.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, ctypes.c_int,
        ]
        lib.cmn_loader_acquire.restype = ctypes.c_int
        lib.cmn_loader_acquire.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ]
        lib.cmn_loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.cmn_loader_seek.restype = ctypes.c_int
        lib.cmn_loader_seek.argtypes = [ctypes.c_void_p, ctypes.c_longlong]
        for f in ("cmn_loader_epoch", "cmn_loader_iteration",
                  "cmn_loader_batches_per_epoch"):
            getattr(lib, f).restype = ctypes.c_longlong
            getattr(lib, f).argtypes = [ctypes.c_void_p]
        lib.cmn_loader_destroy.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return lib


def native_available() -> bool:
    try:
        _load_library()
        return True
    except RuntimeError:
        return False


def device_normalize(x, mean, std, dtype=None):
    """``(x - mean) / std`` for a uint8 wire batch, ON DEVICE.

    Call inside the jitted train step with a ``wire="uint8"`` loader's
    ``mean`` / ``std``: subtract-then-DIVIDE in fp32 — the exact
    operation sequence of the C++ float32 wire path
    (``loader.cpp``: ``(float(px) - mean[k]) / stddev[k]``), so the two
    wire modes agree bit-for-bit (IEEE fp32 subtraction and division
    are exactly rounded; a multiply by a precomputed reciprocal would
    differ by 1-2 ulp).  It fuses into the first conv's input, so it is
    free next to the transfer bytes it saves.  ``dtype`` casts the
    result (``jnp.bfloat16`` for the standard TPU input design).
    """
    import jax.numpy as jnp

    mean = jnp.asarray(np.asarray(mean), jnp.float32)
    std = jnp.asarray(np.asarray(std), jnp.float32)
    out = (x.astype(jnp.float32) - mean) / std
    return out.astype(dtype) if dtype is not None else out


def _check_no_held(held: set, op: str) -> None:
    # the native seek quiesces and restarts workers, clearing in_use:
    # a still-held zero-copy view would be silently overwritten
    if held:
        raise RuntimeError(
            f"{op}() with acquired slot(s) {sorted(held)} outstanding — "
            "release() them first (their zero-copy views would be "
            "overwritten by restarted workers)"
        )


class NativeImageLoader:
    """Threaded native batch loader over an in-memory uint8 image array.

    Yields ``(x, y)``: y int32 (batch,) and x (batch, crop_h, crop_w, c)
    in one of two wire formats:

    * ``wire="float32"`` (default) — normalized ``(pixel - mean) / std``
      float32, ready to cast and feed.
    * ``wire="uint8"`` — raw cropped/flipped uint8; normalize ON DEVICE
      inside the jitted step (:func:`device_normalize`).  A quarter of
      float32's bytes over the host->device link, which is the
      standard TPU input design (not timed on a chip: no cell feeds
      through this loader).
      Augmentation is keyed on (seed, sample ordinal), so both wire
      modes produce identical crops/flips for the same seed.

    Batch order, shuffling and augmentation are deterministic in
    ``seed`` for any ``n_threads``.  Drop-last epoch semantics (matches
    SerialIterator's guarantee that batch sizes stay mesh-divisible).
    """

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 batch_size: int, *,
                 crop: Optional[Tuple[int, int]] = None,
                 n_threads: int = 4, ring: int = 8, seed: int = 0,
                 shuffle: bool = True, train: bool = True,
                 mean: Sequence[float] = (0.0,),
                 std: Sequence[float] = (255.0,),
                 wire: str = "float32"):
        lib = _load_library()
        if wire not in ("float32", "uint8"):
            raise ValueError(f"wire must be 'float32' or 'uint8', got {wire!r}")
        images = np.ascontiguousarray(images, dtype=np.uint8)
        labels = np.ascontiguousarray(labels, dtype=np.int32)
        if images.ndim != 4:
            raise ValueError("images must be (n, h, w, c) uint8")
        n, h, w, c = images.shape
        crop_h, crop_w = crop if crop is not None else (h, w)
        mean = np.ascontiguousarray(
            np.broadcast_to(np.asarray(mean, np.float32), (c,))
        )
        std = np.ascontiguousarray(
            np.broadcast_to(np.asarray(std, np.float32), (c,))
        )
        # Keep references: the C++ side borrows these buffers.
        self._images, self._labels = images, labels
        self._mean, self._std = mean, std
        self._lib = lib
        self._wire_u8 = wire == "uint8"
        self._shape = (batch_size, crop_h, crop_w, c)
        self._create_args = (n, h, w, c, batch_size, crop_h, crop_w,
                             int(n_threads), int(ring), int(seed),
                             int(bool(shuffle)), int(bool(train)))
        self._handle = None
        self._held = set()
        self._create()

    @property
    def mean(self) -> np.ndarray:
        """Per-channel mean — pass to :func:`device_normalize` in
        ``wire="uint8"`` mode."""
        return self._mean

    @property
    def std(self) -> np.ndarray:
        return self._std

    @property
    def wire(self) -> str:
        return "uint8" if self._wire_u8 else "float32"

    def _create(self):
        (n, h, w, c, batch, crop_h, crop_w, n_threads, ring, seed,
         shuffle, train) = self._create_args
        self._handle = self._lib.cmn_loader_create(
            self._images.ctypes.data_as(ctypes.c_void_p),
            self._labels.ctypes.data_as(ctypes.c_void_p),
            n, h, w, c, batch, crop_h, crop_w,
            n_threads, ring, seed, shuffle, train,
            self._mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            int(self._wire_u8),
        )
        if not self._handle:
            raise ValueError(
                "cmn_loader_create rejected the configuration (check "
                "batch_size <= n, crop <= image size, threads/ring > 0)"
            )

    # -- iterator protocol --------------------------------------------
    def __iter__(self):
        return self

    def __next__(self) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking: returns copies (the slot is released immediately).
        For zero-copy access use :meth:`acquire` / :meth:`release`."""
        slot, x_view, y_view = self.acquire()
        try:
            return np.array(x_view), np.array(y_view)
        finally:
            self.release(slot)

    def acquire(self) -> Tuple[int, np.ndarray, np.ndarray]:
        """Zero-copy: (slot_id, x_view, y_view); views are valid until
        ``release(slot_id)``.  Feed them straight to ``device_put`` (which
        copies to device memory) and release.  ``x_view`` dtype follows
        the wire format (float32 or uint8)."""
        if self._wire_u8:
            xp = ctypes.POINTER(ctypes.c_uint8)()
            yp = ctypes.POINTER(ctypes.c_int32)()
            slot = self._lib.cmn_loader_acquire_u8(
                self._handle, ctypes.byref(xp), ctypes.byref(yp)
            )
        else:
            xp = ctypes.POINTER(ctypes.c_float)()
            yp = ctypes.POINTER(ctypes.c_int32)()
            slot = self._lib.cmn_loader_acquire(
                self._handle, ctypes.byref(xp), ctypes.byref(yp)
            )
        if slot < 0:
            raise StopIteration
        self._held.add(slot)
        b, ch, cw, c = self._shape
        x = np.ctypeslib.as_array(xp, shape=(b, ch, cw, c))
        y = np.ctypeslib.as_array(yp, shape=(b,))
        return slot, x, y

    def release(self, slot: int) -> None:
        self._held.discard(slot)
        if self._handle:  # releasing after close() is a no-op, not a crash
            self._lib.cmn_loader_release(self._handle, slot)

    # -- bookkeeping (SerialIterator-compatible surface) ---------------
    @property
    def epoch(self) -> int:
        return int(self._lib.cmn_loader_epoch(self._handle))

    @property
    def epoch_detail(self) -> float:
        bpe = int(self._lib.cmn_loader_batches_per_epoch(self._handle))
        return int(self._lib.cmn_loader_iteration(self._handle)) / bpe

    @property
    def batches_per_epoch(self) -> int:
        return int(self._lib.cmn_loader_batches_per_epoch(self._handle))

    # -- checkpoint protocol (SerialIterator-compatible) ----------------
    def serialize(self):
        return {
            "iteration": int(self._lib.cmn_loader_iteration(self._handle))
        }

    def restore(self, state):
        """Reposition at ``state['iteration']`` via the native seek.

        Determinism is keyed on (seed, ticket), so seeking re-aims the
        worker tickets directly — O(1) in the target iteration (no
        producing/discarding of skipped batches), works forwards and
        backwards.
        """
        target = int(state["iteration"])
        _check_no_held(self._held, "restore")
        if self._lib.cmn_loader_seek(self._handle, target) != 0:
            raise ValueError(f"cmn_loader_seek({target}) failed")

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.cmn_loader_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeTokenLoader:
    """Threaded native batch loader over a flat int32 token stream.

    The LM-family counterpart of :class:`NativeImageLoader`: the corpus
    is cut into ``n_tokens // seq_len`` fixed windows; each epoch visits
    a (seeded, per-epoch) shuffled permutation of windows in batches of
    ``batch_size`` (drop-last), assembled by C++ worker threads into the
    shared staging ring.  Yields int32 (batch, seq_len) arrays — feed
    them to ``step.place_batch`` and train with ``lm_loss``.

    Deterministic in ``seed`` for any thread count; ``serialize`` /
    ``restore`` reposition via the native O(ring) seek, matching the
    checkpointer's iterator contract.
    """

    def __init__(self, tokens: np.ndarray, batch_size: int, seq_len: int,
                 *, n_threads: int = 4, ring: int = 8, seed: int = 0,
                 shuffle: bool = True):
        lib = _load_library()
        tokens = np.ascontiguousarray(tokens, dtype=np.int32).reshape(-1)
        if tokens.size < seq_len * batch_size:
            raise ValueError(
                f"corpus of {tokens.size} tokens cannot fill one "
                f"(batch={batch_size}) x (seq_len={seq_len}) batch"
            )
        self._tokens = tokens  # the C++ side borrows this buffer
        self._lib = lib
        self._shape = (batch_size, seq_len)
        self._create_args = (int(batch_size), int(seq_len),
                             int(n_threads), int(ring), int(seed),
                             int(bool(shuffle)))
        self._handle = None
        self._held = set()
        self._create()

    def _create(self):
        batch, seq_len, n_threads, ring, seed, shuffle = self._create_args
        self._handle = self._lib.cmn_token_loader_create(
            self._tokens.ctypes.data_as(ctypes.c_void_p),
            self._tokens.size, batch, seq_len, n_threads, ring, seed,
            shuffle,
        )
        if not self._handle:
            raise ValueError(
                "cmn_token_loader_create rejected the configuration"
            )

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        slot, toks = self.acquire()
        try:
            return np.array(toks)
        finally:
            self.release(slot)

    def acquire(self) -> Tuple[int, np.ndarray]:
        """Zero-copy: (slot_id, tokens_view); the view is valid until
        ``release(slot_id)``."""
        yp = ctypes.POINTER(ctypes.c_int32)()
        slot = self._lib.cmn_loader_acquire(self._handle, None,
                                            ctypes.byref(yp))
        if slot < 0:
            raise StopIteration
        self._held.add(slot)
        return slot, np.ctypeslib.as_array(yp, shape=self._shape)

    def release(self, slot: int) -> None:
        self._held.discard(slot)
        if self._handle:  # releasing after close() is a no-op, not a crash
            self._lib.cmn_loader_release(self._handle, slot)

    @property
    def epoch(self) -> int:
        return int(self._lib.cmn_loader_epoch(self._handle))

    @property
    def batches_per_epoch(self) -> int:
        return int(self._lib.cmn_loader_batches_per_epoch(self._handle))

    def serialize(self):
        return {
            "iteration": int(self._lib.cmn_loader_iteration(self._handle))
        }

    def restore(self, state):
        target = int(state["iteration"])
        _check_no_held(self._held, "restore")
        if self._lib.cmn_loader_seek(self._handle, target) != 0:
            raise ValueError(f"cmn_loader_seek({target}) failed")

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.cmn_loader_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
