"""The comparison that decides `correct`.  Exact: bytes, not tolerances,
so every limit is 0 (or, for a counter that has to move, at least 1).

Each check yields {"name", "value", "limit", "ok"}; a run prints them all.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterable, List

import numpy as np

# a run in which any of these moved was served, in part, by the CPU
FALLBACK_KEYS = ("ec_tpu.breaker_trip", "ec_tpu.breaker_fallback",
                 "ec_tpu.breaker_open_lanes", "ec_plugin.cpu_fallback",
                 "ec_plugin.device_failed")


def check(name: str, value, limit, ok: bool) -> dict:
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def at_most(name: str, value, limit=0) -> dict:
    return check(name, value, limit, value <= limit)


def at_least(name: str, value, limit=1) -> dict:
    return check(name, value, limit, value >= limit)


async def readback(client, pool: int, names: Iterable[str],
                   payload_of: Callable[[str], bytes]) -> dict:
    """Objects whose get is not byte-identical to the regenerated payload
    (a get that raises counts as one)."""
    bad = 0
    for oid in names:
        try:
            got = await client.get(pool, oid)
        except Exception:
            bad += 1
            continue
        if bytes(got) != payload_of(oid):
            bad += 1
    return at_most("readback_objects_not_identical", bad)


def stored_shards(osds: Iterable, pool: int, names: Iterable[str]) -> Dict:
    """{oid: {shard: [bytes, ...]}} as the live OSDs' object stores hold
    them now (every copy of a shard position, wherever it lies)."""
    want = set(names)
    have: Dict[str, Dict[int, list]] = {oid: {} for oid in want}
    for osd in osds:
        for oid, shard in osd.store.list_objects(pool):
            if oid in want:
                got = osd.store.read((pool, oid, shard))
                if got is not None:
                    chunk = getattr(got[0], "view", got[0])
                    have[oid].setdefault(shard, []).append(bytes(chunk))
    return have


def shards(held: Dict, payload_of: Callable[[str], bytes],
           reference: Callable[[bytes], list]) -> List[dict]:
    """Every object has each of its k+m shard positions in some live
    store, and every stored copy equals the plain reference's shard."""
    missing = differing = 0
    for oid, by_shard in held.items():
        want = reference(payload_of(oid))
        for pos, ref in enumerate(want):
            copies = by_shard.get(pos)
            if not copies:
                missing += 1
            elif any(c != ref for c in copies):
                differing += 1
    return [at_most("shards_missing", missing),
            at_most("shards_differing_from_reference", differing)]


def fallbacks(moved: Dict[str, float]) -> List[dict]:
    return [at_most(key, moved.get(key, 0)) for key in FALLBACK_KEYS]


def sample(acked: List[int], n: int, last: int, seed: int) -> List[int]:
    """`n` of the acknowledged ops, drawn from the seed, the `last` most
    recently acknowledged always among them (they come first)."""
    tail = acked[len(acked) - min(last, len(acked)):]
    rest = acked[:len(acked) - len(tail)]
    more = max(0, min(len(rest), n - len(tail)))
    rng = np.random.default_rng(int(seed))
    return tail[::-1] + [rest[j] for j in
                         rng.choice(len(rest), size=more, replace=False)]
