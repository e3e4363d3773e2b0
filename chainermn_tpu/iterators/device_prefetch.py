"""Host->device transfer overlap for input pipelines.

Reference parity: the reference hid input latency with multiprocess
workers feeding pinned CUDA buffers (``chainer.iterators``'s prefetch +
CuPy streams).  The TPU-native equivalent exploits JAX's *asynchronous
dispatch*: ``device_put`` (and any jitted step) returns before the
transfer/compute finishes, so placing batch ``i+1`` immediately after
dispatching step ``i`` overlaps the H2D copy with device compute — no
threads, no streams, just not blocking on the next array.

``prefetch_to_device`` wraps a host-batch iterator so that ``depth``
batches are always resident (or in flight) on the device: the caller
pops a ready batch, and the wrapper tops the queue back up *before*
returning, which is when the previous step's compute is still running.

Typical wiring (the ``--native-loader`` path)::

    loader = NativeImageLoader(...)
    it = prefetch_to_device(iter(loader), step.place_batch, depth=2)
    for batch in it:            # already a placed global jax.Array
        params, opt_state, m = step(params, opt_state, batch)

Telemetry (``observability.timeline``; nothing of it exists while
telemetry is off): ``feed.collate`` spans ``next(host iterator)``, and
``feed.h2d`` runs from the ``place_fn`` call (the ``device_put``
*enqueue*) to the placed batch being ready.  The main thread must not wait for the copy, so each placed batch
gets a short-lived daemon thread that ``block_until_ready``s it inside
the span and drops it: the span sits on that thread's line of the trace.
"""

from __future__ import annotations

import collections
import threading
from typing import Callable, Iterator, Optional

from ..observability import timeline as _obs


def _await_copy(placed) -> None:
    """``feed.h2d``: wait, inside a span, for one placed batch.  Runs on
    a thread of its own, so that the span opens at the enqueue whatever
    earlier copies are still doing; the thread holds a reference to the
    batch until its copy completed, no longer."""
    import jax

    nbytes = sum(getattr(x, "nbytes", 0)
                 for x in jax.tree_util.tree_leaves(placed))
    with _obs.span("feed.h2d", bytes=nbytes) as sp:
        try:
            jax.block_until_ready(placed)
        except RuntimeError as e:
            # deleted (donated) before it was ready, or a transfer that
            # failed, which the step that takes the batch raises itself:
            # the span is then no copy's time, and says so
            sp.set(aborted=type(e).__name__)


class _DevicePrefetcher:
    def __init__(self, it: Iterator, place_fn: Callable, depth: int,
                 snapshot_states: bool = True):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._it = it
        self._place = place_fn
        self._depth = depth
        self._buf: collections.deque = collections.deque()
        # Serialize-state snapshot taken just before each buffered batch
        # was drawn, aligned 1:1 with _buf.  Checkpointing through the
        # prefetcher must not skip buffered-but-unconsumed batches: the
        # resumable position is where the *oldest unconsumed* batch was
        # fetched, not where the underlying iterator has raced ahead to.
        # COST CONTRACT: this calls the wrapped iterator's serialize()
        # once per batch drawn, so it must be O(1) (SerialIterator's is);
        # pass snapshot_states=False for iterators with an expensive
        # serialize() — checkpointing through the prefetcher is then
        # disabled rather than silently wrong (a naive passthrough would
        # serialize the raced-ahead position and drop buffered batches
        # at resume).
        self._states: collections.deque = collections.deque()
        self._can_serialize = snapshot_states and hasattr(it, "serialize")
        self._done = False

    def _top_up(self) -> None:
        while len(self._buf) < self._depth and not self._done:
            with _obs.span("feed.collate"):
                state = self._it.serialize() if self._can_serialize \
                    else None
                try:
                    host = next(self._it)
                except StopIteration:
                    self._done = True
                    return
            # async dispatch: returns a jax.Array immediately, the copy
            # proceeds while the caller's current step computes
            placed = self._place(host)
            self._watch(placed)
            self._buf.append(placed)
            self._states.append(state)

    @staticmethod
    def _watch(placed) -> None:
        """Start the ``feed.h2d`` observer of one batch while telemetry
        is on; asked per batch, because telemetry may be installed after
        the prefetcher was built.  Off, no thread exists."""
        if _obs.active() is not None:
            threading.Thread(target=_await_copy, args=(placed,),
                             name="feed-h2d", daemon=True).start()

    def __iter__(self):
        return self

    def __next__(self):
        self._top_up()
        if not self._buf:
            raise StopIteration
        out = self._buf.popleft()
        self._states.popleft()
        # queue the replacement transfer NOW, behind the step the caller
        # is about to dispatch with `out`
        self._top_up()
        return out

    next = __next__

    # serialize/restore are exposed via __getattr__ (not class methods)
    # so hasattr() feature detection keeps working: a wrapped iterator
    # without serialize() must leave the prefetcher without one too
    # (Trainer.state_dict treats that as "no iterator state", a
    # graceful no-op).  When the underlying iterator HAS them, ours win
    # — the naive passthrough would serialize the raced-ahead position
    # and silently drop the buffered batches at resume.
    def _serialize(self):
        if self._states:
            return self._states[0]
        return self._it.serialize()

    def _restore(self, state):
        self._it.restore(state)
        self._buf.clear()
        self._states.clear()
        self._done = False

    def __getattr__(self, name):
        it = self.__dict__.get("_it")
        if it is None:  # mid-construction / unpickling
            raise AttributeError(name)
        if name == "serialize":
            if self.__dict__.get("_can_serialize"):
                return self._serialize
            # NEVER fall through to the wrapped iterator's serialize:
            # with snapshotting disabled it would record the raced-ahead
            # position and silently drop buffered batches at resume.
            raise AttributeError(name)
        if name == "restore" and hasattr(it, "restore"):
            return self._restore
        # bookkeeping passthrough (epoch, batches_per_epoch, ...);
        # raises AttributeError naturally for absent names
        return getattr(it, name)


def prefetch_to_device(iterator: Iterator, place_fn: Callable,
                       depth: int = 2,
                       snapshot_states: bool = True) -> Iterator:
    """Wrap ``iterator`` so ``depth`` placed batches are always in
    flight.  ``place_fn`` maps one host batch to device array(s) —
    usually ``step.place_batch`` (which shards over the data mesh) or a
    ``functools.partial(jax.device_put, device=...)``.

    ``depth=2`` is classic double-buffering: one batch being consumed
    by the running step, one transferring behind it.  Larger depths only
    help when transfer time exceeds a whole step.

    The wrapped iterator must yield host data whose buffers remain valid
    until ``place_fn`` returns (``place_fn`` hands the bytes to the
    runtime); zero-copy loader views should be copied or cast (e.g. the
    bf16 host cast) before being yielded.

    ``snapshot_states``: when the wrapped iterator has ``serialize()``,
    it is called once per batch drawn so a checkpoint resumes at the
    oldest *unconsumed* batch — that call must be O(1) (SerialIterator's
    is).  Pass ``False`` for third-party iterators whose serialize is
    O(dataset): per-batch snapshotting stops, and the prefetcher exposes
    no ``serialize()`` at all (Trainer then records no iterator state)
    instead of silently recording the raced-ahead position.
    """
    return _DevicePrefetcher(iter(iterator), place_fn, depth,
                             snapshot_states=snapshot_states)
