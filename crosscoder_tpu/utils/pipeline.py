"""Bounded in-flight pipeline driver for device→host streaming loops.

The recurring shape on a TPU host: dispatch device work chunk by chunk,
fetch each result to host — but fetching immediately serializes a device
round trip per chunk, and dispatching everything up front fills HBM with
queued intermediates. The fix everywhere (buffer refresh, norm
calibration, dashboard harvest) is the same bounded FIFO window.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable, Iterable, TypeVar

T = TypeVar("T")

# see sharded_program_guard() — reentrant so a guarded serve may trigger
# a guarded refill on the same thread
_XLA_CPU_PROGRAM_LOCK = threading.RLock()


def sharded_program_guard():
    """Serialize collective-bearing program execution on XLA:CPU.

    Two programs with collectives executing concurrently on the same set
    of host devices can deadlock the CPU runtime: each program's
    per-device executions block in a collective rendezvous while
    occupying scheduler threads, starving the other program's remaining
    participants (``collective_ops_utils.h`` "waiting for all participants
    to arrive"). Hardware backends pipeline concurrent programs, so this
    returns a null context off-CPU. Dispatch is asynchronous — releasing
    the lock when the python call returns would not close the race — so
    on CPU a caller must also run :func:`finish_on_cpu` on the program's
    outputs before leaving the block."""
    import jax

    if jax.default_backend() == "cpu":
        return _XLA_CPU_PROGRAM_LOCK
    return contextlib.nullcontext()


def finish_on_cpu(tree) -> None:
    """Block until ``tree``'s arrays have finished computing, on the CPU
    backend only — the execute-to-completion half of
    :func:`sharded_program_guard` (a no-op elsewhere: hardware backends
    keep the async pipeline)."""
    import jax

    if jax.default_backend() == "cpu":
        jax.block_until_ready(tree)

# chunks kept in flight: device compute overlaps the host fetch/scatter of
# earlier chunks (1 = fully serial)
DEFAULT_DEPTH = 3


def drive(produced: Iterable[T], drain: Callable[[T], None], depth: int = DEFAULT_DEPTH) -> None:
    """Consume ``produced`` (an iterator that DISPATCHES device work as it
    is advanced) keeping at most ``depth`` items in flight, calling
    ``drain`` on each in FIFO order."""
    inflight: list[T] = []
    for item in produced:
        inflight.append(item)
        if len(inflight) >= depth:
            drain(inflight.pop(0))
    for item in inflight:
        drain(item)


class LaunchSequencer:
    """Ticketed program-launch ordering across threads.

    SPMD multi-process meshes require every process to ENQUEUE the same
    collective programs in the same order — two threads racing their
    dispatches resolve differently per host and deadlock the cross-host
    rendezvous (the reason the trainer historically disabled prefetch on
    pods). The fix: every launch site calls :meth:`reserve` on the MAIN
    thread, in program order — identical on every process by SPMD
    construction — and executes its launches under :meth:`turn`, which
    blocks until all earlier tickets have released. Reservation order is
    thereby the pod-wide launch order, regardless of which thread runs
    each launch or when the OS schedules it.

    Single-process runs don't need one (any interleaving is correct
    there); the trainer only builds a sequencer when
    ``multihost.needs_launch_tickets()`` says the mesh spans processes.
    """

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._next = 0       # next ticket to hand out
        self._head = 0       # lowest ticket not yet released
        self._released: set[int] = set()
        self._invalid = False

    def reserve(self) -> int:
        """Claim the next launch slot (call on the deciding thread, in
        program order)."""
        with self._cond:
            ticket = self._next
            self._next += 1
            return ticket

    @contextlib.contextmanager
    def turn(self, ticket: int):
        """Run a launch under its reserved slot: entry blocks until every
        earlier ticket has released; exit releases this one (also on
        exceptions, so a failed launch never wedges the sequence)."""
        with self._cond:
            while not self._invalid and self._head != ticket:
                self._cond.wait()
        try:
            yield
        finally:
            self.skip(ticket)

    def skip(self, ticket: int) -> None:
        """Release a ticket without running anything under it (a launch
        site that reserved but then bailed — e.g. a failed submit)."""
        with self._cond:
            self._released.add(ticket)
            while self._head in self._released:
                self._released.remove(self._head)
                self._head += 1
            self._cond.notify_all()

    def invalidate(self) -> None:
        """Retire the whole sequence at a mesh-epoch change (elastic
        shrink/grow re-mesh). Tickets reserved before the epoch change
        order launches against a backend that is about to be torn down:
        their ordering no longer means anything, but a ticket that was
        reserved and never released would block every later ``turn`` —
        including the quiesce drain of the old world's in-flight work —
        behind a turn that can never come. After ``invalidate`` every
        outstanding and future ticket passes straight through ``turn``
        (the trainer builds a FRESH sequencer for the new epoch's world,
        so post-remesh ordering starts clean)."""
        with self._cond:
            self._invalid = True
            self._cond.notify_all()


class QuantumDispatcher:
    """Dedicated dispatcher thread for refill harvest quanta.

    The refill engine's host cost is per-dispatch; running those
    dispatches on the train loop's thread
    puts that cost inside the step cadence even when the device work
    overlaps perfectly. This offloads them: the serve path posts CREDIT
    (how many quanta the pacing schedule allows) via :meth:`submit` and
    returns immediately; the daemon thread spends accumulated credit by
    calling ``pump(credit)`` — which must take
    :func:`sharded_program_guard` itself around any program execution.

    :meth:`drain` quiesces: blocks until all posted credit is spent and
    the pump is idle, then re-raises any exception the pump hit (refill
    failures surface on the serve thread at the next cycle boundary, not
    as a dead daemon). Used by the buffer at cycle completion and before
    any state mutation that invalidates in-flight work (restore, forced
    refresh, close).

    FAIRNESS UNDER FAN-OUT (multi-tenant serving, train/fleet.py): extra
    consumers may register their own pumps via :meth:`add_channel` and
    post credit with ``submit(credit, channel=...)``. With one channel
    (every pre-fleet caller) the drain loop keeps the exact historical
    semantics — grab ALL accumulated credit, one pump call. With several,
    it services channels ROUND-ROBIN in bounded chunks of ``quantum``
    credits, so one slow consumer's backlog cannot starve the shared
    refill pump: the refill channel gets a turn after at most
    ``(n_channels - 1) * quantum`` foreign credits, regardless of how
    deep the slow channel's queue runs.
    """

    #: per-turn credit chunk per channel in multi-channel round-robin
    QUANTUM = 4

    def __init__(self, pump: Callable[[int], None], name: str = "refill-dispatch") -> None:
        self._cond = threading.Condition()
        # channel key None is the primary (legacy single-channel) pump
        self._pumps: dict[str | None, Callable[[int], None]] = {None: pump}
        self._credits: dict[str | None, int] = {None: 0}
        self._order: list[str | None] = [None]
        self._rr = 0
        self._busy = False
        self._closed = False
        self._error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, name=name, daemon=True)
        self._thread.start()

    def add_channel(self, name: str, pump: Callable[[int], None]) -> None:
        """Register a named consumer channel with its own pump."""
        with self._cond:
            if self._closed:
                raise RuntimeError("QuantumDispatcher is closed")
            if name is None or name in self._pumps:
                raise ValueError(f"channel {name!r} invalid or already registered")
            self._pumps[name] = pump
            self._credits[name] = 0
            self._order.append(name)

    def _take_locked(self) -> tuple[str | None, int]:
        """Pick the next (channel, credit) to service; caller holds the
        lock and has established that some credit exists."""
        if len(self._order) == 1:
            # single channel: grab-all, exactly the pre-channel behavior
            credit, self._credits[None] = self._credits[None], 0
            return None, credit
        for _ in range(len(self._order)):
            ch = self._order[self._rr % len(self._order)]
            self._rr += 1
            if self._credits[ch] > 0:
                credit = min(self._credits[ch], self.QUANTUM)
                self._credits[ch] -= credit
                return ch, credit
        raise AssertionError("unreachable: credit vanished under the lock")

    def _run(self) -> None:
        while True:
            with self._cond:
                while not any(self._credits.values()) and not self._closed:
                    self._cond.wait()
                if self._closed and not any(self._credits.values()):
                    return
                ch, credit = self._take_locked()
                self._busy = True
            try:
                if self._error is None:
                    self._pumps[ch](credit)
            except BaseException as e:  # noqa: BLE001 — re-raised in drain()
                with self._cond:
                    self._error = e
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()

    def submit(self, credit: int, channel: str | None = None) -> None:
        """Post dispatch credit; returns immediately."""
        if credit <= 0:
            return
        with self._cond:
            if self._closed:
                raise RuntimeError("QuantumDispatcher is closed")
            if channel not in self._credits:
                raise ValueError(f"unknown channel {channel!r}")
            self._credits[channel] += credit
            self._cond.notify_all()

    def drain(self) -> None:
        """Block until idle (all credit spent, every channel); re-raise
        any pump error."""
        with self._cond:
            while any(self._credits.values()) or self._busy:
                self._cond.wait()
            if self._error is not None:
                err, self._error = self._error, None
                raise err

    def close(self) -> None:
        """Drain, then stop the thread (idempotent; swallows pump errors —
        close runs in teardown paths where raising would mask the primary
        failure)."""
        with self._cond:
            if self._closed and not self._thread.is_alive():
                return
            self._closed = True
            self._cond.notify_all()
        self._thread.join()
        with self._cond:
            self._error = None
