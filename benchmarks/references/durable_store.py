"""Plain reference for what a durable object store holds after a power cut:
a dict of key -> (bytes, meta) with xattrs and omap, an append-only list of
the transactions handed to it, and a `crash()` that keeps exactly those
whose commit was reported.  It imports nothing of the program.

The store under test is handed the same transactions; after a crash and a
reopen from its files alone it has to hold the same keys, bytes, metas,
xattrs and omap as `crash()` gives:

    durable_at_ack   a transaction whose commit was reported is there,
                     whole, after any later power cut
    atomic           a transaction whose commit was NOT reported (the power
                     went while it was being committed) is wholly there or
                     wholly absent: `crash(in_flight=True)` is the other
                     state the store may be in, and there is no third

A transaction is a list of operations, applied in the order a store
applies the parts of a transaction (deletes, whole writes, writes at an
offset, omap sets, omap removals):

    ("delete", key)                      the object, its xattrs, its omap
    ("write", key, data, meta)           the whole object; xattrs stay
    ("write_at", key, off, data, size, meta, prev)
                                         data over [off, off + len) of the
                                         object zero-extended to `size`
                                         and to the extent's end; with
                                         `prev`, the object as it was
                                         (bytes and meta) stands there
                                         afterwards, if there was one
    ("omap_set", key, {k: v})
    ("omap_rm", key, [k, ...])
    ("setattr", key, name, value)        an xattr, a transaction of its own
    ("rmattr", key, name)                in the stores this models

`key` is any hashable, `meta` any value compared with ==.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

_ORDER = {"delete": 0, "write": 1, "write_at": 2, "omap_set": 3,
          "omap_rm": 4, "setattr": 5, "rmattr": 6}


class State:
    """What a store holds: plain dicts, compared with ==."""

    def __init__(self) -> None:
        self.objects: Dict[object, Tuple[bytes, object]] = {}
        self.xattrs: Dict[object, Dict[str, bytes]] = {}
        self.omap: Dict[object, Dict[str, bytes]] = {}

    def apply(self, ops: List[tuple]) -> None:
        for op in sorted(ops, key=lambda op: _ORDER[op[0]]):  # stable
            kind, key = op[0], op[1]
            if kind == "delete":
                self.objects.pop(key, None)
                self.xattrs.pop(key, None)
                self.omap.pop(key, None)
            elif kind == "write":
                self.objects[key] = (bytes(op[2]), op[3])
            elif kind == "write_at":
                _, _, off, data, size, meta, prev = op
                had = self.objects.get(key)
                if had is not None and prev is not None:
                    self.objects[prev] = had
                buf = bytearray(had[0] if had is not None else b"")
                want = max(size, off + len(data), len(buf))
                buf.extend(bytes(want - len(buf)))
                buf[off:off + len(data)] = bytes(data)
                self.objects[key] = (bytes(buf), meta)
            elif kind == "omap_set":
                self.omap.setdefault(key, {}).update(op[2])
            elif kind == "omap_rm":
                table = self.omap.get(key, {})
                for k in op[2]:
                    table.pop(k, None)
            elif kind == "setattr":
                self.xattrs.setdefault(key, {})[op[2]] = bytes(op[3])
            elif kind == "rmattr":
                self.xattrs.get(key, {}).pop(op[2], None)
            else:
                raise ValueError(f"unknown operation {kind!r}")

    def as_dicts(self) -> tuple:
        """(objects, xattrs, omap) with nothing empty left in, so that two
        states that hold the same compare equal."""
        return (dict(self.objects),
                {k: dict(v) for k, v in self.xattrs.items() if v},
                {k: dict(v) for k, v in self.omap.items() if v})


class DurableStore:
    def __init__(self) -> None:
        self.log: List[list] = []  # every transaction submitted, in order
        self.reported = 0          # how many of them had their commit
        #                            reported, always a prefix: a store
        #                            commits in the order it is handed

    def submit(self, ops: List[tuple]) -> int:
        """A transaction handed to the store; its commit is not reported
        yet.  Returns its index."""
        if self.reported != len(self.log):
            raise RuntimeError("one transaction in flight at a time: the "
                               "stores this models commit on their caller")
        self.log.append(list(ops))
        return len(self.log) - 1

    def commit_reported(self, index: int) -> None:
        if index != self.reported:
            raise RuntimeError(f"commit {index} reported out of order "
                               f"(next is {self.reported})")
        self.reported = index + 1

    def commit(self, ops: List[tuple]) -> None:
        self.commit_reported(self.submit(ops))

    def state(self, upto: int) -> State:
        out = State()
        for ops in self.log[:upto]:
            out.apply(ops)
        return out

    def now(self) -> State:
        """What a read finds: everything submitted."""
        return self.state(len(self.log))

    def crash(self, in_flight: bool = False) -> State:
        """What a power cut leaves: the transactions whose commit was
        reported and, with `in_flight`, the one being committed as well
        (the other admissible outcome while one is)."""
        return self.state(min(len(self.log),
                              self.reported + (1 if in_flight else 0)))

    def admissible_after_crash(self) -> List[State]:
        states = [self.crash()]
        if self.reported < len(self.log):
            states.append(self.crash(in_flight=True))
        return states
