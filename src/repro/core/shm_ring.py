"""Shared-memory frame ring: the ``shm://`` data plane (paper §3.1 adjacency).

Co-located client↔worker pairs skip the serialize→socket→deserialize round
trip entirely: the worker encodes each element batch *directly* into a slot
of a POSIX shared-memory segment (``memoryview``-based encode, no
intermediate ``bytes``), and the client decodes buffer views straight out of
the slot.  Only a tiny descriptor — ``(slot, length, seq)`` — travels on the
existing RPC control channel, so ordering, retries and failure handling all
stay on the one code path the ``tcp://`` transport already exercises.

Topology is strictly SPSC per ring: ONE worker produces into it, ONE client
session consumes from it (the client's fetch-window threads share the ring;
worker-side slot allocation is serialized by an internal lock).  Slots are
fixed-size frames; a frame larger than ``slot_bytes`` falls back to the
inline RPC payload transparently.

Lease protocol
--------------
* worker: ``try_acquire()`` → write frame into ``slot_view(slot)`` →
  ``commit(slot, length)`` → ship the descriptor in the RPC response.
  ``try_acquire()`` returning ``None`` (ring full — the consumer is behind)
  means *fall back inline for this response*; production never blocks on
  the ring, so a consumer that stops releasing (crash, abandoned iterator)
  degrades throughput but never deadlocks the worker.
* client: ``payload(slot, length, seq)`` → decode (views borrow the slot) →
  ``release(slot)`` once the decoded views are dead (copied out, or the
  consumer advanced past the zero-copy lease).

Crash safety: slots leased to a dead client are never reclaimed — the
worker simply finds the ring full and serves inline; the segment itself is
``unlink``-ed by the owning worker on ``stop()``.  An attached (non-owner)
ring is explicitly unregistered from the CPython ``resource_tracker`` —
otherwise the *attaching* process's tracker would unlink a segment the
worker still owns when that process exits (CPython registers on attach,
not only on create).
"""
from __future__ import annotations

import os
import struct
import threading
import uuid
from multiprocessing import resource_tracker, shared_memory
from typing import List, Optional

# /dev/shm names created by this module all carry this prefix so test
# harnesses (tests/conftest.py) can sweep for leaked segments without
# tripping over unrelated system segments.
SEGMENT_PREFIX = "repro_ring_"

_MAGIC = 0x52503147  # "RP1G"
_HEADER = struct.Struct("<IIQQ")  # magic, slots, slot_bytes, reserved
_SLOT_REC = struct.Struct("<B3xIQ")  # state, seq, committed length
_PAYLOAD_ALIGN = 4096

FREE, LEASED = 0, 1

# Segment names created by THIS process: lets attach() skip the
# resource-tracker unregister when creator and attacher share a process
# (the common single-process test topology), where unregistering would
# strip the creator's own registration and make its unlink() complain.
_OWNED_NAMES: set = set()

DEFAULT_SLOTS = 8
DEFAULT_SLOT_BYTES = 16 << 20  # generous: ftruncate'd pages cost nothing
MAX_RING_BYTES = 512 << 20  # cap a single attach request


class ShmRingError(RuntimeError):
    """Ring-protocol violation (bad magic, stale seq, bad geometry)."""


def new_segment_name() -> str:
    """A fresh segment name that carries the pid of the process naming it,
    so a leak sweep can tell its own segments from a concurrent process's."""
    return f"{SEGMENT_PREFIX}{os.getpid()}_{uuid.uuid4().hex[:12]}"


def unlink_segment(name: str) -> None:
    """Remove a segment by name for a creator that is gone; no-op if the
    segment was never made.  The creator shared this process's resource
    tracker, whose registration ``unlink`` drops too."""
    _OWNED_NAMES.discard(name)
    try:
        shm = shared_memory.SharedMemory(name=name)
    except (FileNotFoundError, ValueError):  # never made, or died half made
        return
    shm.close()
    shm.unlink()


def _payload_offset(slots: int) -> int:
    raw = _HEADER.size + slots * _SLOT_REC.size
    return (raw + _PAYLOAD_ALIGN - 1) // _PAYLOAD_ALIGN * _PAYLOAD_ALIGN


class ShmRing:
    """SPSC ring of fixed-size frame slots over ``multiprocessing.shared_memory``."""

    def __init__(
        self, shm: shared_memory.SharedMemory, slots: int, slot_bytes: int, owner: bool
    ):
        self._shm = shm
        self.slots = slots
        self.slot_bytes = slot_bytes
        self.owner = owner
        self._payload_off = _payload_offset(slots)
        self._lock = threading.Lock()  # serializes producer-side allocation
        self._seq = 0
        self._views: List[Optional[memoryview]] = [None] * slots
        self._closed = False

    # ------------------------------------------------------------------
    # Construction
    # ------------------------------------------------------------------
    @classmethod
    def create(
        cls,
        slots: int = DEFAULT_SLOTS,
        slot_bytes: int = DEFAULT_SLOT_BYTES,
        *,
        name: Optional[str] = None,
    ) -> "ShmRing":
        """Create and own a new ring segment (worker side).

        ``name`` (default: a fresh ``SEGMENT_PREFIX`` name) lets another
        process know the segment before it exists.
        """
        slots = max(1, int(slots))
        slot_bytes = max(4096, int(slot_bytes))
        size = _payload_offset(slots) + slots * slot_bytes
        if size > MAX_RING_BYTES:
            raise ShmRingError(f"ring geometry too large: {size} bytes")
        name = name or new_segment_name()
        shm = shared_memory.SharedMemory(name=name, create=True, size=size)
        _OWNED_NAMES.add(shm.name)
        _HEADER.pack_into(shm.buf, 0, _MAGIC, slots, slot_bytes, 0)
        # slot table is already zeroed (fresh pages): every slot starts FREE
        return cls(shm, slots, slot_bytes, owner=True)

    @classmethod
    def attach(cls, name: str, *, adopt: bool = False) -> "ShmRing":
        """Attach to an existing ring by segment name (client side).

        ``adopt`` takes the segment over from a creator that shares this
        process's resource tracker (a forked child): the registration is
        kept, and the caller removes the segment with :func:`unlink_segment`.
        """
        shm = shared_memory.SharedMemory(name=name)
        # CPython registers shared memory with the resource tracker on
        # ATTACH as well as create; without this unregister, the attaching
        # process's tracker unlinks the worker's segment at exit.  When the
        # attacher IS the creator's process (single-process deployments),
        # keep the registration — it belongs to the creator.
        if not adopt and shm.name not in _OWNED_NAMES:
            try:
                resource_tracker.unregister(shm._name, "shared_memory")
            except Exception:
                pass  # tracker bookkeeping only; never fail an attach on it
        magic, slots, slot_bytes, _ = _HEADER.unpack_from(shm.buf, 0)
        if magic != _MAGIC:
            shm.close()
            raise ShmRingError(f"segment {name} is not a repro ring")
        return cls(shm, slots, slot_bytes, owner=False)

    @property
    def name(self) -> str:
        return self._shm.name

    # ------------------------------------------------------------------
    # Producer side (worker)
    # ------------------------------------------------------------------
    def try_acquire(self) -> Optional[int]:
        """Claim a FREE slot for writing, or ``None`` when the ring is full."""
        with self._lock:
            for i in range(self.slots):
                off = _HEADER.size + i * _SLOT_REC.size
                if self._shm.buf[off] == FREE:
                    self._shm.buf[off] = LEASED
                    return i
        return None

    def commit(self, slot: int, length: int) -> int:
        """Publish a written frame; returns the descriptor ``seq``."""
        with self._lock:
            self._seq = (self._seq + 1) & 0xFFFFFFFF
            seq = self._seq
        _SLOT_REC.pack_into(
            self._shm.buf, _HEADER.size + slot * _SLOT_REC.size, LEASED, seq, length
        )
        return seq

    def cancel(self, slot: int) -> None:
        """Return an acquired-but-unwritten slot to the free pool."""
        self.release(slot)

    # ------------------------------------------------------------------
    # Consumer side (client)
    # ------------------------------------------------------------------
    def payload(self, slot: int, length: int, seq: Optional[int] = None) -> memoryview:
        """Borrow a read view of a committed frame.

        The view (and anything decoded zero-copy from it) is valid until
        ``release(slot)``; with ``seq`` the slot record is checked against
        the descriptor so a protocol bug surfaces as ``ShmRingError``
        instead of silent corruption.
        """
        if not 0 <= slot < self.slots or length > self.slot_bytes:
            raise ShmRingError(f"bad descriptor: slot={slot} len={length}")
        if seq is not None:
            state, rec_seq, rec_len = _SLOT_REC.unpack_from(
                self._shm.buf, _HEADER.size + slot * _SLOT_REC.size
            )
            if state != LEASED or rec_seq != seq or rec_len != length:
                raise ShmRingError(
                    f"stale descriptor: slot={slot} seq={seq} "
                    f"(slot record: state={state} seq={rec_seq} len={rec_len})"
                )
        return self.slot_view(slot)[:length]

    def release(self, slot: int) -> None:
        """Return a consumed slot to the producer's free pool."""
        _SLOT_REC.pack_into(
            self._shm.buf, _HEADER.size + slot * _SLOT_REC.size, FREE, 0, 0
        )

    # ------------------------------------------------------------------
    # Shared
    # ------------------------------------------------------------------
    def slot_view(self, slot: int) -> memoryview:
        """Full writable view of one slot's payload area (cached export)."""
        v = self._views[slot]
        if v is None:
            a = self._payload_off + slot * self.slot_bytes
            v = self._views[slot] = self._shm.buf[a : a + self.slot_bytes]
        return v

    def is_free(self, slot: int) -> bool:
        return self._shm.buf[_HEADER.size + slot * _SLOT_REC.size] == FREE

    def free_slots(self) -> int:
        return sum(1 for i in range(self.slots) if self.is_free(i))

    def close(self) -> None:
        """Drop this process's mapping (best effort).

        Zero-copy consumers may still hold numpy views into the mapping;
        closing then raises ``BufferError`` — we leave the mmap for GC in
        that case rather than invalidating live arrays.
        """
        if self._closed:
            return
        for i, v in enumerate(self._views):
            if v is not None:
                try:
                    v.release()
                except BufferError:
                    self._leave_mapping_to_exit()
                    return
                self._views[i] = None
        try:
            self._shm.close()
        except BufferError:
            self._leave_mapping_to_exit()
            return
        self._closed = True

    def _leave_mapping_to_exit(self) -> None:
        # A borrowed view outlived us; the mapping can only go away at
        # process exit.  Shadow SharedMemory.close so its __del__ doesn't
        # retry the doomed mmap close and print BufferError noise.
        self._shm.close = lambda: None  # type: ignore[method-assign]
        self._closed = True

    def unlink(self) -> None:
        """Remove the segment name (owner side; mappings survive unlink)."""
        if not self.owner:
            return
        _OWNED_NAMES.discard(self._shm.name)
        try:
            self._shm.unlink()
        except FileNotFoundError:
            pass

    def __del__(self) -> None:
        # Release the cached slot views BEFORE SharedMemory.__del__ tries to
        # close its mmap — otherwise every GC'd ring spews "BufferError:
        # cannot close exported pointers exist" noise at interpreter exit.
        try:
            self.close()
        except Exception:
            pass
