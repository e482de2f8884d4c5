"""Snapshots of the program's perf counters, and their deltas.

The cluster is in-process: every OSD has a `ctx.perf` collection of counter
sets.  Some sets are the daemon's own (`wire`, `optracker`, `osd`), some are
one object shared by the whole process (`ec_tpu`, the resident store's,
`gf2_sched`, `ec_plugin`).  A snapshot sums each key over the DISTINCT
counter-set objects, so a shared set counts once however many daemons list
it.  The client's objecter and messenger sets are added under their names.

A snapshot is flat: {"set.key": number}; a time average or histogram gives
"set.key.sum" and "set.key.count".
"""

from __future__ import annotations

from typing import Dict, Iterable


def _flatten(set_name: str, dump: dict, into: Dict[str, float]) -> None:
    for key, val in dump.items():
        if isinstance(val, dict):
            count = val.get("avgcount", val.get("count"))
            if count is None or "sum" not in val:
                continue
            for part, v in (("sum", val["sum"]), ("count", count)):
                name = f"{set_name}.{key}.{part}"
                into[name] = into.get(name, 0) + v
        elif isinstance(val, (int, float)) and not isinstance(val, bool):
            name = f"{set_name}.{key}"
            into[name] = into.get(name, 0) + val


def snapshot(collections: Iterable, extra_sets: Iterable = (),
             meter=None) -> Dict[str, float]:
    """`collections`: PerfCountersCollection-like (dump() names its sets,
    get(name) gives the set object); `extra_sets`: single counter sets
    (name, dump()); `meter`: the process's compile meter."""
    flat: Dict[str, float] = {}
    seen = set()
    for coll in collections:
        for name in coll.dump():
            pc = coll.get(name)
            if pc is None or id(pc) in seen:
                continue
            seen.add(id(pc))
            _flatten(name, pc.dump(), flat)
    for pc in extra_sets:
        if id(pc) not in seen:
            seen.add(id(pc))
            _flatten(pc.name, pc.dump(), flat)
    if meter is not None:
        _flatten("compile_meter", meter.snapshot(), flat)
    return flat


def delta(after: Dict[str, float], before: Dict[str, float]) -> Dict[str, float]:
    return {k: v - before.get(k, 0) for k, v in after.items()}
