"""How the benchmark drives ``ServingEngine.run_trace`` without showing
it the future, and how it takes its own clock.

``run_trace(trace, feed=, control=)`` are the hooks ``serve/fleet.py``
uses.  :class:`DueFeed` stands in for the engine's arrival deque and
exposes its head only once the clock has passed that request's due time;
until then the head is a far-future sentinel, as the fleet's open feed
shows.  So the engine's scan planner (``pending[0].arrival_s - now``)
can never end a fused scan just in time for an arrival it should not
know of.  :class:`Observer` is the control plane reduced to a clock: it
sets the run's origin on the benchmark's own ``perf_counter`` and stamps
every request lifecycle event as the engine emits it.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Iterable, Iterator, Optional


class _NotYet:
    """The head of a feed whose next request is not due."""

    __slots__ = ()
    arrival_s = 1.0e12
    rid = -1


NOT_YET = _NotYet()


class Observer:
    """``control=`` for one run: clock origin and event timestamps."""

    # the engine reads these two each loop; no degradation here
    spec_enabled = True
    horizon_cap = None

    def __init__(self, mark: Callable[[], int] = lambda: 0) -> None:
        # ``mark()`` is read as the clock starts: the compile count that
        # may not move from there on
        self._mark = mark
        self.mark_at_start = 0
        self.t0: Optional[float] = None
        # event name -> rid -> seconds since t0 (last occurrence)
        self.at: dict[str, dict[int, float]] = {}

    def now(self) -> float:
        """Seconds since the engine started serving; before that,
        negative infinity, so nothing is due."""
        if self.t0 is None:
            return float("-inf")
        return time.perf_counter() - self.t0

    # -- the engine's side -------------------------------------------------

    def sync_start(self) -> float:
        self.mark_at_start = self._mark()
        self.t0 = time.perf_counter()
        return self.t0

    def beat(self) -> None:
        pass

    def check(self) -> None:
        pass

    def take_cancels(self) -> tuple:
        return ()

    def on_event(self, rid: int, event: str, extra: dict) -> None:
        self.at.setdefault(event, {})[rid] = self.now()


class DueFeed:
    """The trace as the engine may see it: nothing before it is due."""

    def __init__(self, requests: Iterable[Any], clock) -> None:
        self._items = deque(sorted(requests,
                                   key=lambda r: (r.arrival_s, r.rid)))
        self._clock = clock

    def __bool__(self) -> bool:
        return bool(self._items)

    def __len__(self) -> int:
        return len(self._items)

    def __iter__(self) -> Iterator[Any]:
        return iter(list(self._items))

    def __getitem__(self, idx: int) -> Any:
        if idx != 0:
            raise IndexError("a feed only exposes its head")
        head = self._items[0]
        return head if head.arrival_s <= self._clock() else NOT_YET

    def popleft(self) -> Any:
        if self[0] is NOT_YET:
            raise IndexError("the next request is not due yet")
        return self._items.popleft()
