"""Controls: the timed path broken underneath the harness, to show that
`correct` comes out false when a guarantee of the configuration is broken.
`run.py --control <kind>` applies one to the started cluster; the
benchmark's own runs never do.

    store_flip   one OSD stores every shard with one byte flipped (reads
                 served from residents never see it; only the comparison
                 of stored shards with the reference does)
    store_drop   one OSD acknowledges shard writes and stores nothing
    reply_flip   every 7th get answers with one byte flipped
"""

from __future__ import annotations

KINDS = ("store_flip", "store_drop", "reply_flip")


def apply(kind: str, cluster, client) -> str:
    """Break the path; returns a line saying what was broken."""
    if kind in ("store_flip", "store_drop"):
        osd_id = max(cluster.osds)
        store = cluster.osds[osd_id].store
        inner = store.queue_transaction

        def queue_transaction(txn, on_commit=None):
            if kind == "store_drop":
                txn.writes = []
            else:
                flipped = []
                for key, chunk, meta in txn.writes:
                    buf = bytearray(getattr(chunk, "view", chunk))
                    if buf:
                        buf[len(buf) // 2] ^= 0x01
                    flipped.append((key, bytes(buf), meta))
                txn.writes = flipped
            return inner(txn, on_commit)

        store.queue_transaction = queue_transaction
        return f"osd.{osd_id}: {kind}"
    if kind == "reply_flip":
        inner_get = client.get
        count = [0]

        async def get(*args, **kwargs):
            data = await inner_get(*args, **kwargs)
            count[0] += 1
            if count[0] % 7 == 0 and len(data):
                data = bytearray(data)
                data[len(data) // 2] ^= 0x01
            return data

        client.get = get
        return "client.get: every 7th reply has one byte flipped"
    raise ValueError(f"unknown control {kind!r}; there is {list(KINDS)}")
