"""DeviceStream: the seam isolating host<->device sync points.

The serving engine never calls ``np.asarray`` on a device array directly;
every host-visible transfer goes through its stream, which comes in two
flavors:

* :class:`DeviceStream` — the BLOCKING policy (and the default).  ``fetch``
  is an immediate host sync (counted in ``host_syncs`` so tests can assert
  a pass performed no transfer), ``submit`` delivers a ticket inline, and
  ``sync`` is a no-op because nothing is ever in flight.  The simulated
  clock path runs on this stream, bit-identical to the pre-stream engine.

* :class:`OverlappedStream` — the wall-clock overlapped policy.  ``submit``
  enqueues a delivery ticket on a BOUNDED queue consumed by one background
  worker thread; the bound is the dispatch-ahead depth, so a host that
  outruns delivery blocks on ``submit`` instead of growing an unbounded
  backlog of undelivered tokens.  The worker resolves each ticket's device
  arrays (jax async dispatch means that resolution is the only wait),
  fires streaming callbacks, and finalizes metrics — while the engine's
  main thread is already dispatching the next pass.  ``sync`` drains the
  queue (the engine calls it before anything that must see complete token
  streams: preemption replay snapshots, deadline expiry, fault requeues).

Waits show in the profiler's trace: ``serving.fetch`` (arg ``pass_id``)
around each device->host transfer, on the delivery worker when overlapped,
and, overlapped only, ``serving.stream_wait`` wherever the engine thread
blocks on the queue (a full ``submit``, or ``sync``).

Worker exceptions are captured and re-raised on the next ``submit``/
``sync`` so a failing callback surfaces in the serve loop instead of dying
silently on a daemon thread.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, List, Optional

import numpy as np

from repro.serving.metrics import span


@dataclasses.dataclass
class TokenRec:
    """One slot that sampled a token in a dispatched pass."""
    slot: int
    req: Any                    # serving.engine.Request
    finishing: bool             # this token hits the request's limit
    corrupted: bool             # dispatched while an unrepaired fault was live


@dataclasses.dataclass
class Ticket:
    """One dispatched pass awaiting delivery: the (unfetched) device array
    of sampled tokens plus everything delivery needs — recipients, the
    dispatch timestamp for the straggler/utilization gauges, the engine
    clock reading the tokens are stamped with, and the warmup flag that
    keeps first-execution-per-shape samples out of the straggler model."""
    engine: Any                 # serving.engine.ServingEngine
    t0: float                   # host perf-clock at dispatch
    warmup: bool                # first run of this executable shape
    sampled: Any                # (B,) int32 device array
    recs: List[TokenRec]
    now: float                  # engine clock at dispatch (token timestamps)
    pass_id: int = -1           # the engine's pass counter at dispatch: ties
                                # the pass's spans across threads


class DeviceStream:
    """Blocking sync policy: transfers happen inline, nothing is ever
    pending.  Also the instrumentation point — ``host_syncs`` counts every
    device->host transfer the engine performed."""

    def __init__(self) -> None:
        self.host_syncs = 0

    def fetch(self, arr, dtype=None, *, pass_id: int) -> np.ndarray:
        """Device -> host transfer (THE sync point) of pass ``pass_id``."""
        self.host_syncs += 1
        with span("serving.fetch", pass_id=pass_id):
            return (np.asarray(arr) if dtype is None
                    else np.asarray(arr, dtype))

    def submit(self, ticket: Ticket) -> None:
        ticket.engine._deliver_ticket(ticket)

    def pending(self) -> int:
        return 0

    def sync(self) -> None:
        pass

    def close(self) -> None:
        pass


class OverlappedStream(DeviceStream):
    """Background delivery over a bounded queue (see module docstring).

    ``depth`` bounds how many dispatched-but-undelivered passes may exist;
    the engine's dispatch loop blocks on ``submit`` past it.
    """

    def __init__(self, depth: int = 4) -> None:
        super().__init__()
        self._q: "queue.Queue[Optional[Ticket]]" = queue.Queue(
            maxsize=max(1, int(depth)))
        self._exc: Optional[BaseException] = None
        self._closed = False
        self._worker = threading.Thread(
            target=self._run, name="serving-delivery", daemon=True)
        self._worker.start()

    def _run(self) -> None:
        while True:
            ticket = self._q.get()
            if ticket is None:
                self._q.task_done()
                return
            try:
                ticket.engine._deliver_ticket(ticket)
            except BaseException as e:     # surface on the engine thread
                self._exc = e
            finally:
                self._q.task_done()

    def _raise_pending(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            raise exc

    def submit(self, ticket: Ticket) -> None:
        self._raise_pending()
        if self._closed:
            raise RuntimeError("OverlappedStream is closed")
        try:
            self._q.put_nowait(ticket)
        except queue.Full:
            with span("serving.stream_wait"):
                self._q.put(ticket)

    def pending(self) -> int:
        return int(self._q.unfinished_tasks)

    def sync(self) -> None:
        """Block until every submitted ticket has been delivered."""
        with span("serving.stream_wait"):
            self._q.join()
        self._raise_pending()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._worker.join(timeout=10.0)
