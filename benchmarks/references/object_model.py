"""Plain reference for what an object store may answer: a dict-backed model
of put / get / delete on named objects, and a checker of a run's history
against it.  It imports nothing of the program.

A history is a list of ops, each (name, kind, version stamp, t_issue,
t_ack) on one clock.  Ops on one name may overlap in time, so the model
does not pick one order: it says which answers SOME order allows.

    latest_acked_wins   a get returns the payload of a put to that name
                        that was either the newest acknowledged before the
                        get was issued or in flight with the get, never an
                        older one; "no such object" only where a delete (or
                        nothing at all) stands in that place.
    delete_is_complete  once a delete is acknowledged and no put to the
                        name that could come after it has been issued, the
                        name is gone: `final()` says what a name may hold
                        when the history ends, and a name whose every
                        admissible final state is "absent" must have left
                        nothing behind.

"Newest" needs care where writes overlap: write W is DEFINITELY
SUPERSEDED at time T when some other write to the name was issued after
W was acknowledged and was itself acknowledged by T.  A get issued at T
may return any write that began before the get ended and was not
definitely superseded at T.  Two gets in turn (the second issued after
the first was answered) may not go backwards: the second may not return
a write that was acknowledged before the first one's write was issued.
A write that failed or never came back may or may not have happened: it
stays admissible and supersedes nothing.

Payloads are made from a seed alone (`Payloads`): a 16-byte stamp (rank,
version) and seeded bytes of the name's size, so that a reply is checked
in full against the version it claims and nobody keeps what was sent.
"""

from __future__ import annotations

import math
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

ABSENT = 0  # the version a delete writes, and what a name starts as
STAMP = struct.Struct("<QQ")  # rank, version
CORRUPT = -1  # a reply that is not the payload its stamp claims

PUT, GET, DELETE = "put", "get", "delete"


class Payloads:
    """The bytes of (rank, version) at a size: the stamp, then a slice of
    one pool of seeded bytes at an offset the pair picks."""

    def __init__(self, seed: int, max_bytes: int) -> None:
        self.max_bytes = int(max_bytes)
        self._span = 2 * self.max_bytes
        self._pool = memoryview(
            np.random.default_rng(int(seed)).bytes(self._span + max_bytes))

    def _tail(self, rank: int, version: int, size: int) -> memoryview:
        off = (rank * 2654435761 + version * 40503) % self._span
        return self._pool[off:off + size - STAMP.size]

    def data(self, rank: int, version: int, size: int) -> bytes:
        if not STAMP.size <= size <= self.max_bytes:
            raise ValueError(f"no payload of {size} bytes")
        return b"".join((STAMP.pack(rank, version),
                         self._tail(rank, version, size)))

    def version_of(self, reply, rank: int, size: int) -> int:
        """The version a get's reply carries, checked in full: CORRUPT
        unless it is, byte for byte, the payload of (rank, that version)
        at the name's size."""
        view = memoryview(reply)
        if len(view) != size:
            return CORRUPT
        got_rank, version = STAMP.unpack_from(view)
        if got_rank != rank or version <= ABSENT:
            return CORRUPT
        if view[STAMP.size:] != self._tail(rank, version, size):
            return CORRUPT
        return version


@dataclass
class Op:
    name: str
    kind: str
    version: int          # put: the version written; delete: ABSENT;
    #                       get: filled by the answer
    t_issue: float
    t_ack: float = math.inf   # inf: not (or not successfully) answered
    ok: bool = False


@dataclass
class _Name:
    writes: List[Op] = field(default_factory=list)
    gets: List[Op] = field(default_factory=list)


class History:
    """Record ops as they are issued and answered; check afterwards."""

    def __init__(self) -> None:
        self._names: Dict[str, _Name] = {}
        self.ops = 0

    def _of(self, name: str) -> _Name:
        got = self._names.get(name)
        if got is None:
            got = self._names[name] = _Name()
        return got

    def issue(self, name: str, kind: str, t_issue: float,
              version: int = ABSENT) -> Op:
        op = Op(name, kind, version if kind == PUT else ABSENT, t_issue)
        per = self._of(name)
        (per.gets if kind == GET else per.writes).append(op)
        self.ops += 1
        return op

    @staticmethod
    def ack(op: Op, t_ack: float, answer: Optional[int] = None) -> None:
        """The op was answered at `t_ack`; a get's `answer` is the version
        its reply carried (ABSENT for "no such object", CORRUPT)."""
        op.t_ack, op.ok = t_ack, True
        if op.kind == GET:
            op.version = answer

    # -- the model ---------------------------------------------------------

    @staticmethod
    def _superseded_at(writes: List[Op]) -> Dict[int, float]:
        """For each write (by id), the time from which it is definitely
        superseded: the earliest acknowledgement among the writes issued
        after its own."""
        out = {}
        for w in writes:
            later = [x.t_ack for x in writes if x.t_issue > w.t_ack]
            out[id(w)] = min(later, default=math.inf)
        return out

    @staticmethod
    def _admitted(writes: List[Op], sup: Dict[int, float], t_issue: float,
                  t_ack: float) -> set:
        out = {w.version for w in writes
               if w.t_issue < t_ack and sup[id(w)] > t_issue}
        # the initial absence, superseded by the first acknowledged write
        if min((w.t_ack for w in writes), default=math.inf) > t_issue:
            out.add(ABSENT)
        return out

    def admissible(self, name: str, t_issue: float,
                   t_ack: float) -> set:
        """The versions a get of `name` over [t_issue, t_ack] may return
        (ABSENT among them where "no such object" is a legal answer)."""
        writes = self._of(name).writes
        return self._admitted(writes, self._superseded_at(writes), t_issue,
                              t_ack)

    def check_gets(self) -> dict:
        """Every answered get against the model: counts of gets checked,
        answers that no order admits (a stale or lost version, an absence
        where an object stands, a presence after a delete), corrupt
        replies, and pairs of gets that went backwards."""
        checked = refused = corrupt = backwards = 0
        examples: List[Tuple] = []
        for name, per in self._names.items():
            if not per.gets:
                continue
            writes = per.writes
            sup = self._superseded_at(writes)
            by_version = {w.version: w for w in writes if w.kind == PUT}
            answered = sorted((g for g in per.gets if g.ok),
                              key=lambda g: g.t_issue)
            floor_t = -math.inf  # no later get may return a write that
            #                      was acknowledged before this time
            pending: List[Op] = []  # answered gets not yet folded in
            for g in answered:
                # fold in the gets that were answered before g was issued
                still = []
                for p in pending:
                    if p.t_ack < g.t_issue:
                        w = by_version.get(p.version)
                        if w is not None:
                            floor_t = max(floor_t, w.t_issue)
                    else:
                        still.append(p)
                pending = still
                checked += 1
                if g.version == CORRUPT:
                    corrupt += 1
                    examples.append((name, "corrupt", g.t_issue))
                    continue
                ok = self._admitted(writes, sup, g.t_issue, g.t_ack)
                if g.version not in ok:
                    refused += 1
                    examples.append((name, g.version, sorted(ok)))
                    continue
                w = by_version.get(g.version)
                if w is not None and w.t_ack < floor_t:
                    backwards += 1
                    examples.append((name, "backwards", g.version))
                pending.append(g)
        return {"gets_checked": checked, "gets_not_admitted": refused,
                "gets_corrupt": corrupt, "gets_gone_backwards": backwards,
                "examples": examples[:8]}

    def final(self, name: str) -> set:
        """The versions the name may hold once every op has ended (ABSENT
        for "nothing")."""
        times = [t for w in self._of(name).writes
                 for t in (w.t_issue, w.t_ack) if t != math.inf]
        end = max(times, default=0.0) + 1.0
        return self.admissible(name, end, end)

    def names(self) -> List[str]:
        return list(self._names)

    def must_be_absent(self) -> List[str]:
        """Names of which the cluster must hold nothing at the end."""
        return [n for n in self._names if self.final(n) == {ABSENT}]

    def must_hold(self) -> Dict[str, int]:
        """Names whose final state is one known version."""
        out = {}
        for n in self._names:
            fin = self.final(n)
            if len(fin) == 1 and ABSENT not in fin:
                out[n] = next(iter(fin))
        return out

    def quiet_delete(self, op: Op) -> bool:
        """True where the delete `op`, just acknowledged, stands
        alone: every other write to its name was acknowledged before it
        was issued, so the name must be gone this instant."""
        return all(w is op or w.t_ack < op.t_issue
                   for w in self._of(op.name).writes)
