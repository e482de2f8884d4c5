"""The closed loop of `rados bench`: n ops in flight, each worker issues
its next op when its last one was acknowledged."""

from __future__ import annotations

import asyncio
import time
from typing import Awaitable, Callable, List, Tuple

# (index, t_issue, t_done, ok, nbytes), times on time.perf_counter()
Record = Tuple[int, float, float, bool, int]


async def closed_loop(in_flight: int,
                      op: Callable[[int], Awaitable[Tuple[bool, int]]],
                      go_on: Callable[[int], bool],
                      first_index: int = 0) -> List[Record]:
    """Run `op(i)` for i = first_index, first_index+1, ... with `in_flight`
    workers, asking `go_on(i)` before each issue; an op that raises is a
    failed record.  Returns when every issued op has ended."""
    records: List[Record] = []
    next_index = first_index

    async def worker() -> None:
        nonlocal next_index
        while go_on(next_index):
            i = next_index
            next_index += 1
            t0 = time.perf_counter()
            try:
                ok, nbytes = await op(i)
            except asyncio.CancelledError:
                raise
            except Exception:
                ok, nbytes = False, 0
            records.append((i, t0, time.perf_counter(), ok, nbytes))

    await asyncio.gather(*(worker() for _ in range(in_flight)))
    return records
