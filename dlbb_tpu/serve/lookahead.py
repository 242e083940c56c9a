"""Input look-ahead: a queued request's input is ready before its slot is.

A request's prompt input (the family's ``prompt_input``: draw, pad, cast,
upload) depends on nothing but the request, and the request waits in the
scheduler's queue before a slot frees.  :class:`InputLookahead` hands the
inputs of the first :data:`INPUT_LOOKAHEAD` queued requests to ONE worker
thread, which prepares them while the device runs chunks and decode
units; at admission the scheduler takes the prepared array, waits for it
if the worker is in the middle of it, or prepares it inline if the worker
has not started it.  Same function, same arguments, same array: the work
is overlapped, not changed (``docs/serving.md``, "Input look-ahead").

The worker opens no span and emits no event: the benchmark names a device
idle gap by the latest-started open span of ANY thread, so a worker span
would rename the scheduler's gaps (``docs/observability.md`` §1).
"""

from __future__ import annotations

import time
from concurrent.futures import Future, ThreadPoolExecutor
from itertools import islice
from typing import Any, Callable, Iterable, Optional

from dlbb_tpu.serve.traffic import Request

# Inputs prepared ahead of admission, at most: the head of the queue.  One
# covers a steady backlog (a prompt's draw takes a third of its prefill);
# three let the worker build a lead over a short prompt's prefill followed
# by a long prompt's draw, and hold under 3 x 7.3 MB on the device for the
# widest input any cell sends.
INPUT_LOOKAHEAD = 3


class InputLookahead:
    """The prepared inputs of the queue's head, and the worker that makes
    them.  One per ``run_trace``, used as a context manager: the worker
    starts at the first :meth:`top_up` and is joined on the way out."""

    def __init__(self, prepare: Callable[[Request], Any]) -> None:
        self._prepare = prepare
        self._pool: Optional[ThreadPoolExecutor] = None
        self._ahead: dict[int, Future] = {}
        # admissions whose input was ready at the take; those that waited
        # for the worker or prepared inline, and the sum of those waits
        self.ready = 0
        self.waited = 0
        self.wait_s = 0.0

    def __enter__(self) -> "InputLookahead":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    @property
    def ready_share(self) -> float:
        """Share of the takes so far that found their input ready."""
        takes = self.ready + self.waited
        return self.ready / takes if takes else 0.0

    def top_up(self, queue: Iterable[Request]) -> None:
        """Hand the worker whichever of the first ``INPUT_LOOKAHEAD``
        requests of ``queue`` it does not hold yet."""
        for req in islice(queue, INPUT_LOOKAHEAD):
            if len(self._ahead) >= INPUT_LOOKAHEAD:
                break
            if req.rid not in self._ahead:
                if self._pool is None:
                    self._pool = ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix="serve-input")
                self._ahead[req.rid] = self._pool.submit(self._prepare, req)

    def take(self, req: Request) -> Any:
        """The input of a request being admitted: the prepared one if the
        worker is done with it, else after the wait for a worker in the
        middle of it, else prepared inline.  What the worker raised is
        raised here."""
        fut = self._ahead.pop(req.rid, None)
        if fut is not None and fut.done():
            self.ready += 1
            return fut.result()
        t0 = time.perf_counter()
        try:
            if fut is None or fut.cancel():
                return self._prepare(req)
            return fut.result()
        finally:
            self.waited += 1
            self.wait_s += time.perf_counter() - t0

    def drop(self, rid: int) -> None:
        """A request left the queue without being admitted: what the
        worker made or raised for it goes with it, unread."""
        fut = self._ahead.pop(rid, None)
        if fut is not None:
            fut.cancel()

    def close(self) -> None:
        """Drop what is prepared and join the worker."""
        self._ahead.clear()
        if self._pool is not None:
            self._pool.shutdown(wait=True, cancel_futures=True)
            self._pool = None
