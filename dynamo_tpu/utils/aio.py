"""Small asyncio helpers."""

from __future__ import annotations

import asyncio
import logging
import random
import time
from typing import Callable, Optional

logger = logging.getLogger(__name__)

# the event loop's heartbeat: how often it asks itself the time, and how
# late an answer is worth a line in the log (the step loop holds its
# hand-overs and resumes to the same limit, ``engine/steptrace.py``)
LAG_PERIOD_S = 0.1
LAG_WARN_S = 0.25


def decorrelated_jitter(prev_s: float, base_s: float, cap_s: float) -> float:
    """Next backoff sleep: uniform between the base and 3x the previous
    sleep, capped — retries from many callers spread out instead of
    arriving at the recovering server in lockstep."""
    return min(cap_s, random.uniform(base_s, max(prev_s, base_s) * 3))


async def reap_task(task: Optional[asyncio.Task]) -> None:
    """Cancel a child task and await it, without eating the caller's own
    cancellation.

    ``try: await task except CancelledError: pass`` is subtly wrong: if the
    *caller* is cancelled while awaiting the child, the same exception type is
    raised and gets swallowed — the caller keeps running and (since asyncio
    delivers cancellation once) can never be cancelled again.
    """
    if task is None:
        return
    task.cancel()
    # ``await task`` cannot distinguish the child's CancelledError from the
    # caller's own (pre-3.11 there is no Task.cancelling()), so use
    # asyncio.wait: it never propagates the child's exception, meaning a
    # CancelledError out of it is only ever OURS — on every version.
    await asyncio.wait({task})
    if not task.cancelled():
        exc = task.exception()
        if exc is not None:
            raise exc


async def watch_loop_lag(observe: Callable[[float], None],
                         who: str) -> None:
    """Heartbeat of the running event loop: sleep ``LAG_PERIOD_S``, hand
    ``observe`` the seconds it woke later than that (what every ready
    callback of this process had to wait at that moment), and say so once
    in the log past ``LAG_WARN_S``. Runs until cancelled."""
    while True:
        t0 = time.perf_counter()
        await asyncio.sleep(LAG_PERIOD_S)
        lag = max(0.0, time.perf_counter() - t0 - LAG_PERIOD_S)
        observe(lag)
        if lag > LAG_WARN_S:
            logger.warning("event loop late: the %s's loop was away "
                           "%.1f ms", who, lag * 1000.0)


__all__ = ["decorrelated_jitter", "reap_task", "watch_loop_lag"]
