"""Heartbeat deadman watchdog: abort a stalled run so supervision can restart.

The supervisor can only restart what *returns or raises*; a hung compiled
scan (hung runtime, deadlocked collective, the injected ``stall`` failpoint)
does neither, so today it holds the run hostage forever.  `Watchdog` is the
missing detector: a daemon thread armed with a progress deadline, fed by
the telemetry progress beats — every runner draw block, warmup segment,
checkpoint write, and in-scan ``jax.debug.callback`` heartbeat calls
`telemetry.notify_progress`, which the started watchdog subscribes to.  If
no beat arrives within ``deadline_s`` the watchdog declares a stall: it
emits a ``chain_health`` ``status="stall"`` trace event and fires
``on_stall`` — by default ``_thread.interrupt_main()``, which raises
KeyboardInterrupt in the main thread.  `supervise.supervised_sample`
converts that interrupt into a `StallError` **only when the watchdog
actually fired** (``consume_stall``); a genuine Ctrl-C passes through
untouched, so the watchdog never eats a user interrupt.

The default abort targets the thread that STARTED the watchdog (the one
running the supervised attempt).  When that is the main thread it delivers
a real SIGINT (``pthread_kill``): that unblocks interruptible C calls —
``time.sleep``, EINTR-aware I/O, the injected ``stall`` failpoint —
immediately, which ``_thread.interrupt_main()`` cannot.  A supervised run
on a worker thread gets ``PyThreadState_SetAsyncExc`` instead (Python
routes signals to the main thread only), which lands at the next bytecode
boundary — and never shoots an unrelated main loop.  Honest limit: a
thread wedged inside a NON-interruptible C region (a truly hung XLA
dispatch that never rechecks signals) only sees the interrupt when that
call returns.  For that class, pass an escalating
``on_stall`` (e.g. one that records state and ``os._exit``\\ s so a
process supervisor takes over) — the default stays in-process because
that is what checkpoint-restart supervision can use.

Choose ``deadline_s`` longer than the worst single dispatch *including its
compile*: beats only arrive when a dispatch returns, so a deadline shorter
than one compile+block round-trip false-positives on a healthy run.
"""

from __future__ import annotations

import _thread
import contextlib
import threading
import time
from typing import Any, Callable, Iterator, Optional

from . import telemetry

__all__ = ["StallError", "Watchdog", "active_watchdogs", "watched"]


class StallError(RuntimeError):
    """The watchdog aborted a run that stopped emitting progress beats."""


# started watchdogs, for observers: the metrics exporter reports the
# active deadman deadline (stark_watchdog_deadline_seconds) without any
# wiring between supervise and the status daemon.  Guarded by a lock —
# start/stop may race with a scrape thread.
_ACTIVE: "list[Watchdog]" = []
_ACTIVE_LOCK = threading.Lock()


def active_watchdogs() -> "list[Watchdog]":
    """Snapshot of currently-started watchdogs (observability read-only)."""
    with _ACTIVE_LOCK:
        return list(_ACTIVE)


def _interrupt_thread(target: threading.Thread) -> None:
    """Abort the (stalled) ``target`` thread with KeyboardInterrupt
    semantics — the thread that was running the supervised attempt when
    the watchdog started, NOT unconditionally the process main thread (a
    server calling supervised_sample from a worker must not have its main
    loop shot).

    Main thread: a real SIGINT via ``pthread_kill`` — it unblocks
    interruptible C calls (``time.sleep``, EINTR-aware I/O) immediately,
    where ``_thread.interrupt_main()`` only schedules the exception for
    the next bytecode boundary — useless against the very stall being
    aborted.  Non-main thread: Python only delivers signals to the main
    thread, so the fallback is ``PyThreadState_SetAsyncExc`` — delivery
    waits for the next bytecode boundary (breaks Python-level stalls;
    a blocking C call is only broken once it returns).
    """
    import ctypes
    import signal

    if target is threading.main_thread():
        try:
            signal.pthread_kill(target.ident, signal.SIGINT)
            return
        except Exception:  # noqa: BLE001 — fall back, never die in the watcher
            _thread.interrupt_main()
            return
    if target.ident is not None and target.is_alive():
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(target.ident), ctypes.py_object(KeyboardInterrupt)
        )


class Watchdog:
    """Deadman timer over the telemetry progress beats.

    ``beat()`` re-arms the deadline; `start` subscribes it to
    `telemetry.notify_progress` so the existing beat sources feed it with
    no extra wiring.  When the deadline lapses the watchdog fires ONCE per
    stall (the timer re-arms after firing, so a restart that itself stalls
    is caught again), sets the stalled flag, and calls ``on_stall``.
    """

    def __init__(
        self,
        deadline_s: float,
        *,
        poll_s: Optional[float] = None,
        on_stall: Optional[Callable[[], None]] = None,
        trace: Optional[Any] = None,
        label: str = "run",
    ):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        # poll fast enough to detect within ~deadline*1.25 but never spin
        self.poll_s = (
            float(poll_s) if poll_s is not None
            else min(max(deadline_s / 4.0, 0.05), 1.0)
        )
        self.on_stall = on_stall
        self.label = label
        self.stall_count = 0
        # the watchdog thread must not read the ambient ContextVar trace
        # (threads do not inherit the installing context): capture at
        # construction like the debug-callback mirror does
        self._trace = telemetry.resolve_trace(trace)
        self._last = time.monotonic()
        self._stalled = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # the thread the default abort targets: whoever starts the
        # watchdog is the thread running the supervised attempt
        self._target: threading.Thread = threading.current_thread()

    def beat(self) -> None:
        """Progress observed: re-arm the deadline (any thread may call)."""
        self._last = time.monotonic()

    def consume_stall(self) -> bool:
        """True iff a stall fired since the last call; clears the flag.

        The supervisor's KeyboardInterrupt handler uses this to tell a
        watchdog abort from a user Ctrl-C.
        """
        was = self._stalled.is_set()
        self._stalled.clear()
        return was

    def start(self) -> "Watchdog":
        if self._thread is not None:
            raise RuntimeError("watchdog already started")
        self._target = threading.current_thread()
        self.beat()
        self._stop.clear()
        # flight-recorder capture window: a watchdog-armed run is one
        # whose stalls must leave a postmortem (scoped install — the
        # zero-listener contract holds while no watchdog is armed)
        self._recorder = telemetry.flight_recorder().install()
        telemetry.add_progress_listener(self.beat)
        self._thread = threading.Thread(
            target=self._watch, name=f"stark-watchdog-{self.label}", daemon=True
        )
        with _ACTIVE_LOCK:
            _ACTIVE.append(self)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        telemetry.remove_progress_listener(self.beat)
        rec, self._recorder = getattr(self, "_recorder", None), None
        if rec is not None:
            rec.uninstall()
        with _ACTIVE_LOCK:
            if self in _ACTIVE:
                _ACTIVE.remove(self)
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=self.poll_s * 4 + 1.0)

    def _watch(self) -> None:
        while not self._stop.wait(self.poll_s):
            idle = time.monotonic() - self._last
            if idle <= self.deadline_s:
                continue
            self.stall_count += 1
            self._stalled.set()
            # the stall IS the forensic moment: emit the stall event and
            # dump the postmortem bundle before firing the abort (the
            # workdir was set by whoever supervises this run; no
            # workdir → recorded only).  The progress context names WHAT
            # the run was waiting on (e.g. the mesh fleet's
            # waiting_on_shards) so the stall and its postmortem carry
            # the culprit, not just the silence.
            ctx = {
                k: v for k, v in telemetry.progress_context().items()
                if k not in ("status", "label", "deadline_s", "idle_s",
                             "stall_count")
            }
            telemetry.flight_recorder().record_anomaly(
                "stall",
                self._trace,
                "chain_health",
                status="stall",
                label=self.label,
                deadline_s=self.deadline_s,
                idle_s=round(idle, 3),
                stall_count=self.stall_count,
                **ctx,
            )
            try:
                if self.on_stall is not None:
                    self.on_stall()
                else:
                    _interrupt_thread(self._target)
            except Exception:  # noqa: BLE001 — the watchdog must outlive its hook
                pass
            # re-arm rather than fire in a tight loop: the abort needs up
            # to a deadline's grace to take effect (interrupt_main lands
            # at the next bytecode boundary)
            self.beat()

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()


@contextlib.contextmanager
def watched(deadline_s: Optional[float], **kwargs) -> Iterator[Optional[Watchdog]]:
    """``with watched(deadline_s) as wd:`` — None deadline = no watchdog."""
    if deadline_s is None:
        yield None
        return
    wd = Watchdog(deadline_s, **kwargs)
    wd.start()
    try:
        yield wd
    finally:
        wd.stop()
