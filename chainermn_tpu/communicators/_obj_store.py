"""Host-side object (control-plane) transport.

Reference parity: the ``*_obj`` methods of
``chainermn/communicators/mpi_communicator_base.py`` (pickle + chunked MPI
send with a ~256 MB cap per message).

TPU-native redesign: object traffic is *control plane*, not ICI traffic.

* Single controller (``jax.process_count() == 1``): every rank lives in this
  process, so transport is an in-memory mailbox.  ``send_obj``/``recv_obj``
  still round-trip through pickle so that anything a multi-process run would
  reject (unpicklable payloads) fails identically in tests.
* Multi-process: rides ``jax.experimental.multihost_utils`` (which uses the
  jax.distributed KV store / host collectives underneath).  Rank-addressed
  send/recv between processes maps onto the distributed KV store.
"""

from __future__ import annotations

import collections
from typing import Any

import jax
import numpy as np

from .communicator_base import dumps, loads
from ..observability import timeline as _obs
from ..resilience import fault_injection as _fi
from ..resilience import protocol as _proto
from ..resilience import tags as _tags
from ..resilience.errors import PayloadCorruptionError
from ..resilience.retry import RetryPolicy, call_with_retry

# Chunk cap mirroring the reference's max message length for pickled sends
# (mpi_communicator_base.py, ~256 MB).  Applies to the KV-store path.
MAX_OBJ_CHUNK_BYTES = 256 * 1024 * 1024


def _recv_timeout_ms() -> int:
    """TOTAL blocking-recv budget for the KV-store path, split across the
    retry policy's attempts.  A peer that died never publishes its key; a
    bounded wait turns that into a ``TransientCommError`` (naming the
    peer, attempts, and elapsed time) the global except hook can contain
    instead of a 10-minute hang."""
    import os

    return int(os.environ.get("CHAINERMN_TPU_OBJ_TIMEOUT_MS", 600_000))


def _obj_policy() -> RetryPolicy:
    """Retry policy for host-side exchanges (bounded attempts, jitter-free
    exponential backoff — deterministic for tests)."""
    import os

    return RetryPolicy(
        max_attempts=int(
            os.environ.get("CHAINERMN_TPU_OBJ_MAX_ATTEMPTS", 4)
        )
    )


def _maybe_fault(site: str, peer=None, payload: Any = None) -> Any:
    """Injection point with retry: with no injector active this is one
    ``is None`` check; with one active, injected transient timeouts are
    absorbed by the (deterministic) retry schedule and the possibly
    mutated payload (truncation faults) is returned."""
    if _fi.active() is None:
        return payload
    return call_with_retry(
        lambda: _fi.fire(site, peer=peer, payload=payload),
        site=site, peer=peer, policy=_obj_policy(),
    )


def _loads_checked(data: bytes, site: str, peer=None) -> Any:
    """Unpickle with taxonomy: a truncated / torn payload surfaces as a
    recoverable :class:`PayloadCorruptionError`, not a bare pickle error."""
    try:
        return loads(data)
    except Exception as e:
        raise PayloadCorruptionError(
            f"{site}: payload failed to unpickle "
            f"({type(e).__name__}: {e})",
            site=site, peer=peer,
        ) from e


def _check_rank(value: int, size: int, name: str) -> None:
    if not 0 <= value < size:
        raise ValueError(f"{name} {value} out of range for size {size}")


class LocalObjStore:
    """In-process mailbox — all ranks share one controller."""

    def __init__(self, size: int):
        self._size = size
        self._mail: dict = collections.defaultdict(collections.deque)

    def send(self, obj: Any, dest: int, tag: int = _tags.DEFAULT) -> None:
        _check_rank(dest, self._size, "dest")
        with _obs.span("obj_store.send", peer=dest) as sp:
            payload = _maybe_fault("obj_store.send", peer=dest,
                                   payload=dumps(obj))
            sp.set(bytes=len(payload))
            self._mail[(dest, tag)].append(payload)
            _proto.record_op("send", tag=tag, peer=dest, payload=payload)

    def recv(self, source: int, tag: int = _tags.DEFAULT,
             dest: int = 0) -> Any:
        """Drain the mailbox of rank ``dest``.

        Under one controller there is no ambient "my rank", so the receiving
        rank is an explicit argument (default 0 mirrors the common
        root-receives pattern).  ``source`` is accepted for MPI-shaped parity
        but not matched on: messages to one rank form a single FIFO per tag,
        exactly like MPI_ANY_SOURCE.
        """
        del source
        _check_rank(dest, self._size, "dest")
        with _obs.span("obj_store.recv", peer=dest) as sp:
            _maybe_fault("obj_store.recv", peer=dest)
            box = self._mail[(dest, tag)]
            if not box:
                raise RuntimeError(
                    f"recv_obj: no message pending for rank {dest}/tag "
                    f"{tag} (single-controller recv must follow the "
                    "matching send)"
                )
            payload = box.popleft()
            sp.set(bytes=len(payload))
            # local recv has no ambient "my rank": the mailbox owner
            # (dest) stands in as the recorded peer
            _proto.record_op("recv", tag=tag, peer=dest, payload=payload)
            return _loads_checked(payload, "obj_store.recv", dest)

    def recv_for(self, dest: int, tag: int = _tags.DEFAULT) -> Any:
        return self.recv(source=-1, tag=tag, dest=dest)

    def bcast(self, obj: Any, root: int = 0) -> Any:
        # single controller: every rank's payload is this caller's payload,
        # so any in-range root broadcasts the same object
        _check_rank(root, self._size, "root")
        with _obs.span("obj_store.exchange", peer=root) as sp:
            payload = _maybe_fault("obj_store.exchange", peer=root,
                                   payload=dumps(obj))
            sp.set(bytes=len(payload))
            _proto.record_op("exchange", payload=payload)
            return _loads_checked(payload, "obj_store.exchange", root)

    def gather(self, obj: Any, root: int = 0) -> list:
        _check_rank(root, self._size, "root")
        with _obs.span("obj_store.exchange", peer=root) as sp:
            payload = _maybe_fault("obj_store.exchange", peer=root,
                                   payload=dumps(obj))
            sp.set(bytes=len(payload))
            _proto.record_op("exchange", payload=payload)
            return [_loads_checked(payload, "obj_store.exchange", root)
                    for _ in range(self._size)]

    def allgather(self, obj: Any) -> list:
        with _obs.span("obj_store.exchange") as sp:
            payload = _maybe_fault("obj_store.exchange",
                                   payload=dumps(obj))
            sp.set(bytes=len(payload))
            _proto.record_op("exchange", payload=payload)
            return [_loads_checked(payload, "obj_store.exchange")
                    for _ in range(self._size)]


class MultiprocessObjStore:
    """Cross-process object transport over the jax.distributed control plane.

    Collective ops (bcast/gather/allgather) use ``multihost_utils`` host
    collectives on the pickled payload; addressed send/recv uses the
    KV store exposed by the distributed client.
    """

    def __init__(self, size: int, rank_to_process=None):
        self._size = size
        self._seq = collections.Counter()
        # rank -> owning process index (from the topology's device order);
        # lets collective roots be expressed as *ranks*, as in the
        # reference's MPI world where rank == process.
        self._rank_to_process = (
            tuple(rank_to_process) if rank_to_process is not None else None
        )

    def _root_process(self, root: int) -> int:
        """Process index owning rank ``root``."""
        _check_rank(root, self._size, "root")
        if self._rank_to_process is None:
            # Without a topology, rank == process is only a safe reading
            # when the world has exactly one rank per process; guessing
            # otherwise would silently pick the wrong payload.
            if self._size != jax.process_count():
                raise ValueError(
                    f"root rank {root} cannot be mapped to a process "
                    "(no rank->process topology; pass rank_to_process)"
                )
            return root
        return self._rank_to_process[root]

    # -- collectives ---------------------------------------------------
    def _host_allgather_bytes(self, payload: bytes) -> list:
        """Host-collective byte exchange.

        The retryable part is the injection point, which fires BEFORE
        the collective: a rank whose injected transient fault precedes
        the exchange simply joins late on its retry — peers block in the
        collective until it arrives (tail latency, not deadlock).  The
        real ``process_allgather`` is deliberately NOT retried: a
        one-sided transient failure (rank A's receive times out after
        rank B's call already returned) would make A's retry pair with
        B's *next* exchange, silently shifting the collective stream by
        one message.  Addressed KV-store recv (idempotent reads) keeps
        the full real-failure retry path; a genuinely failed collective
        propagates as an error for auto-resume to handle.
        """
        from jax.experimental import multihost_utils

        with _obs.span("obj_store.exchange", bytes=len(payload)):
            p = _maybe_fault("obj_store.exchange", payload=payload)
            nproc = jax.process_count()
            n = len(p)
            # Single-round fast path: one fixed 4 KiB bucket carries an
            # in-band 8-byte length header plus the payload.  The fixed
            # SHAPE means process_allgather compiles exactly one XLA
            # program for every small exchange ever (compiling per
            # byte-length costs ~100 ms a shape, and two rounds —
            # lengths then payload — doubles the collective latency
            # that dominates sub-second recovery).  Only when some
            # rank's payload spills past the bucket do all ranks — each
            # reading the same gathered headers — agree to run a second
            # power-of-two-bucketed round with the full payloads.
            hdr = 8
            r1 = 4096
            buf = np.zeros((r1,), np.uint8)
            buf[:hdr] = np.frombuffer(
                np.int64(n).tobytes(), np.uint8
            )
            body = min(n, r1 - hdr)
            buf[hdr:hdr + body] = np.frombuffer(p[:body], np.uint8)
            g1 = multihost_utils.process_allgather(buf)
            lengths = [
                int(np.frombuffer(g1[q, :hdr].tobytes(), np.int64)[0])
                for q in range(nproc)
            ]
            maxlen = max(lengths)
            if maxlen <= r1 - hdr:
                out = [
                    g1[q, hdr:hdr + lengths[q]].tobytes()
                    for q in range(nproc)
                ]
            else:
                bucket = max(1 << max(maxlen - 1, 0).bit_length(), r1)
                buf2 = np.zeros((bucket,), np.uint8)
                arr = np.frombuffer(p, np.uint8)
                buf2[: arr.size] = arr
                g2 = multihost_utils.process_allgather(buf2)
                out = [
                    g2[q, : lengths[q]].tobytes() for q in range(nproc)
                ]
            # recorded on transport SUCCESS only (a lockstep retry
            # re-records on every rank together, so attempt counts
            # stay symmetric); the digest is this rank's contribution
            _proto.record_op("exchange", payload=payload)
            return out

    def bcast(self, obj: Any, root: int = 0) -> Any:
        """Every process returns the payload contributed by the process
        owning rank ``root`` (an honest arbitrary-root broadcast: the
        underlying transport is an allgather, so selecting the root's
        payload costs nothing extra)."""
        src = self._root_process(root)
        payloads = self._host_allgather_bytes(dumps(obj))
        return _loads_checked(payloads[src], "obj_store.exchange", src)

    def allgather(self, obj: Any) -> list:
        return [
            _loads_checked(p, "obj_store.exchange", i)
            for i, p in enumerate(self._host_allgather_bytes(dumps(obj)))
        ]

    def gather(self, obj: Any, root: int = 0) -> list:
        """Process-ordered list of every process's payload.

        MPI's gather delivers the list only at ``root``; the host-side
        transport here is an allgather, so every process receives it — a
        documented superset (content identical at root).  ``root`` is
        still validated so out-of-range ranks fail loudly."""
        self._root_process(root)
        return self.allgather(obj)

    # -- addressed send/recv over the KV store -------------------------
    def _kv(self):
        from jax._src import distributed

        client = distributed.global_state.client
        if client is None:
            raise RuntimeError(
                "multi-process obj transport requires jax.distributed."
                "initialize()"
            )
        return client

    def send(self, obj: Any, dest: int, tag: int = _tags.DEFAULT) -> None:
        with _obs.span("obj_store.send", peer=dest) as sp:
            self._send(obj, dest, tag, sp)

    def _send(self, obj: Any, dest: int, tag: int, sp) -> None:
        payload = _maybe_fault("obj_store.send", peer=dest,
                               payload=dumps(obj))
        sp.set(bytes=len(payload))
        key = f"cmn_obj/{jax.process_index()}->{dest}/{tag}/{self._seq[(dest, tag)]}"
        self._seq[(dest, tag)] += 1
        client = self._kv()

        def kv_set(k, v):
            # allow_overwrite: a retry after a PARTIALLY successful
            # publish re-sets keys that already exist; without it the
            # coordination service raises ALREADY_EXISTS and the retry
            # layer would convert a recoverable transient failure into a
            # hard crash.  The payload for a given (key, seq) is
            # deterministic, so overwriting is value-identical.
            client.key_value_set_bytes(k, v, allow_overwrite=True)

        def publish():
            for i in range(0, max(len(payload), 1), MAX_OBJ_CHUNK_BYTES):
                kv_set(f"{key}/{i}", payload[i : i + MAX_OBJ_CHUNK_BYTES])
            kv_set(f"{key}/len", str(len(payload)).encode())

        call_with_retry(publish, site="obj_store.send", peer=dest,
                        policy=_obj_policy())
        _proto.record_op("send", tag=tag, peer=dest, payload=payload)

    def recv(self, source: int, tag: int = _tags.DEFAULT,
             dest: int = None) -> Any:
        if dest is not None and dest != jax.process_index():
            raise ValueError(
                f"multi-process recv_obj can only receive for this process "
                f"(index {jax.process_index()}), got dest={dest}"
            )
        key = f"cmn_obj/{source}->{jax.process_index()}/{tag}/{self._seq[('r', source, tag)]}"
        self._seq[("r", source, tag)] += 1
        client = self._kv()
        policy = _obj_policy()
        # the env timeout is the TOTAL wait budget across all attempts
        # AND all chunk gets: every blocking get's timeout is capped by
        # the remaining budget (a deadline, not a per-get slice), so a
        # dead peer mid-multi-chunk-payload still errors near the
        # configured bound instead of budget x chunks later
        import time as _time

        per_attempt = max(_recv_timeout_ms() // policy.max_attempts, 1)
        deadline = _time.monotonic() + _recv_timeout_ms() / 1000.0

        def bounded_get(k):
            remaining = int((deadline - _time.monotonic()) * 1000)
            return client.blocking_key_value_get_bytes(
                k, max(min(per_attempt, remaining), 1)
            )

        def attempt():
            _fi.fire("obj_store.recv", peer=source)
            total = int(bounded_get(f"{key}/len"))
            payload = b"".join(
                bounded_get(f"{key}/{i}")
                for i in range(0, max(total, 1), MAX_OBJ_CHUNK_BYTES)
            )
            return payload[:total]

        with _obs.span("obj_store.recv", peer=source) as sp:
            data = call_with_retry(attempt, site="obj_store.recv",
                                   peer=source, policy=policy)
            sp.set(bytes=len(data))
        _proto.record_op("recv", tag=tag, peer=source, payload=data)
        return _loads_checked(data, "obj_store.recv", source)


def create_obj_store(size: int, process_count: int = 1,
                     rank_to_process=None):
    if process_count > 1:
        return MultiprocessObjStore(size, rank_to_process=rank_to_process)
    return LocalObjStore(size)
