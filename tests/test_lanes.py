"""The BatchingQueue's lane table (parallel/service.LANES) and the one
rule that picks a lane for a codec (rados/ecutil.lane_for).

Every lane, at every field width it serves, is held to its numpy mirror
and to the CPU codec: the device program, the breaker's fallback and the
codec's own encode are three implementations of one product.  The choice
of lane is held to what the benchmark's cells have run on the chip
(PERF_LEDGER.jsonl: RS w=8 on packedbit / packedbit_resident, cauchy_good
on packetrows) and to the int8 pair for the widths the schedule lanes do
not take."""

import numpy as np
import pytest

from ceph_tpu.ec.plugins.tpu import TECHNIQUES
from ceph_tpu.ec.registry import registry
from ceph_tpu.parallel.service import (LANES, BatchingQueue,
                                       _cpu_apply_request)
from ceph_tpu.rados import ecutil
from ceph_tpu.rados.ecutil import StripeInfo, lane_for

K, M = 4, 2
PACKETSIZE = 16

#: (lane, w): the five lanes, and w=16 on the two that take it
LANE_CASES = [("packed", 8), ("packed", 16), ("resident", 8),
              ("resident", 16), ("packedbit", 8), ("packedbit_resident", 8),
              ("packetrows", 8)]


def lane_case(kind: str, w: int, cols: int = 2048, seed: int = 0):
    """(codec, request) for one lane: a jerasure CPU codec whose layout
    the lane serves, and the request its plans would submit — (mbits,
    rows, w, out_rows, kind[, packetsize])."""
    profile = {"plugin": "jerasure", "k": str(K), "m": str(M), "w": str(w)}
    if kind == "packetrows":
        profile.update(technique="cauchy_good", packetsize=str(PACKETSIZE))
    else:
        profile.update(technique="reed_sol_van")
    codec = registry.factory("jerasure", "", profile)
    rows = np.random.default_rng(seed).integers(
        0, 256, (K, cols), dtype=np.uint8)
    dtype = np.int8 if kind in ("packed", "resident") else np.uint8
    item = (np.asarray(codec.bit_generator()).astype(dtype), rows, w, M, kind)
    return codec, item + ((PACKETSIZE,) if kind == "packetrows" else ())


def check_lane_result(codec, item, got):
    """One request's result against the lane's mirror and the CPU codec."""
    kind, rows = item[4], item[1]
    want = _cpu_apply_request(kind, *item[:4], *item[5:])
    if LANES[kind].resident:
        (got, got_rows), (want, want_rows) = got, want
        assert np.array_equal(np.asarray(got_rows), want_rows)
        # the resident bit-rows ARE the encoded object: data ‖ parity
        packed = ecutil._pack_rows(got_rows, item[2], K + M, rows.shape[1])
        assert np.array_equal(packed[:K], rows)
        assert np.array_equal(packed[K:], got)
    assert got.dtype == np.uint8 and np.array_equal(got, want)
    assert np.array_equal(got, np.asarray(codec.encode_chunks(rows)))


def test_table_is_the_five_lanes():
    assert list(LANES) == ["packed", "resident", "packedbit",
                           "packedbit_resident", "packetrows"]
    assert {kind for kind, _ in LANE_CASES} == set(LANES)


@pytest.mark.parametrize("kind,w", LANE_CASES)
def test_lane_matches_its_mirror_and_the_cpu_codec(kind, w):
    # 2048 columns, and a width that is no power of two (bucket padding);
    # both are whole u32 words and whole w*packetsize blocks
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        cases = [lane_case(kind, w, cols, seed=cols) for cols in (2048, 1152)]
        futs = [q.submit(*item) for _, item in cases]
        q.flush()
        for (codec, item), fut in zip(cases, futs):
            check_lane_result(codec, item, fut.result(timeout=120))
        d = q.perf.dump()
        assert d[f"submit_{kind}"] == 2 and d["dispatch"] == 1  # coalesced
        assert d["breaker_fallback"] == 0
    finally:
        q.close()


def test_submit_group_of_mixed_kinds_lands_each_in_its_own_group():
    cases = [lane_case(kind, w, seed=i)
             for i, (kind, w) in enumerate(LANE_CASES)]
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        # every request twice: same signature, same group
        items = [item for _, item in cases] * 2
        futs = q.submit_group(items)
        q.flush()
        for (codec, item), fut in zip(cases * 2, futs):
            check_lane_result(codec, item, fut.result(timeout=120))
        d = q.perf.dump()
        assert d["submit"] == 2 * len(LANE_CASES)
        assert d["dispatch"] == len(LANE_CASES)
        assert d["submit_group"] == 1
        for kind in LANES:
            assert d[f"submit_{kind}"] == 2 * sum(
                k == kind for k, _ in LANE_CASES), kind
    finally:
        q.close()


@pytest.mark.parametrize("kind,item,match", [
    ("packedbit", (np.zeros((8, 16), np.uint8),
                   np.zeros((1, 64), np.uint8), 16, 1), "w=8"),
    ("packedbit_resident", (np.zeros((8, 16), np.uint8),
                            np.zeros((2, 100), np.uint8), 8, 1), "32-byte"),
    ("packetrows", (np.zeros((8, 16), np.uint8),
                    np.zeros((2, 100), np.uint8), 8, 1, 16),
     "whole w\\*packetsize"),
])
def test_a_refused_request_refuses_the_group_before_anything_queues(
        kind, item, match):
    _, good = lane_case("packed", 8)
    bad = item[:4] + (kind,) + item[4:]
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        with pytest.raises(ValueError, match=match):
            q.submit_group([good, bad])
        assert q.submits == 0 and not q._groups
    finally:
        q.close()


# -- the choice of lane --------------------------------------------------------

#: technique -> (profile beyond k/m, the w values its registry entry takes
#: here, byte or packet layout)
PROFILES = {
    "reed_sol_van": ({}, (8, 16), "byte"),
    "reed_sol_r6_op": ({}, (8, 16), "byte"),
    "cauchy_orig": ({"packetsize": "16"}, (8, 4), "packet"),
    "cauchy_good": ({"packetsize": "16"}, (8, 4), "packet"),
    "liberation": ({"packetsize": "16"}, (7, 5), "packet"),
    "blaum_roth": ({"packetsize": "16"}, (6, 4), "packet"),
    "liber8tion": ({"packetsize": "16"}, (8,), "packet"),
}


def test_every_tpu_technique_has_a_profile_here():
    assert set(PROFILES) == set(TECHNIQUES)


@pytest.mark.parametrize("technique,w", [
    (t, w) for t, (_, ws, _) in PROFILES.items() for w in ws])
def test_lane_choice_per_technique_and_width(technique, w):
    extra, _, layout = PROFILES[technique]
    codec = registry.factory("tpu", "", {
        "plugin": "tpu", "technique": technique, "k": "4", "m": "2",
        "w": str(w), **extra})
    assert codec.w == w and codec.bit_layout == layout
    sinfo = StripeInfo(4, codec.get_chunk_size(4 * 4096) * 4)
    if layout == "packet":
        # what k10m4c.write4m ran: one lane, nothing resident
        assert lane_for(codec) == ("packetrows", np.uint8)
        assert lane_for(codec, resident=True) is None
        assert ecutil._lane(codec, sinfo) == ("packetrows", np.uint8, 16)
        assert not ecutil.planar_eligible(codec)
    elif w == 8:
        # what the RS cells ran
        assert lane_for(codec) == ("packedbit", np.uint8)
        assert lane_for(codec, resident=True, cols=sinfo.chunk_size) \
            == ("packedbit_resident", np.uint8)
        assert ecutil._lane(codec, sinfo) == ("packedbit", np.uint8)
    else:
        assert lane_for(codec) == ("packed", np.int8)
        assert lane_for(codec, resident=True, cols=sinfo.chunk_size) \
            == ("resident", np.int8)
        assert ecutil._lane(codec, sinfo) == ("packed", np.int8)


def test_lane_choice_edges(monkeypatch):
    rs = registry.factory("tpu", "", {"plugin": "tpu", "k": "4", "m": "2",
                                      "technique": "reed_sol_van"})
    # a resident whose columns are not whole u32 words keeps int8 planes
    assert lane_for(rs, resident=True, cols=100) == ("resident", np.int8)
    # lrc remaps chunks: no lane, the codec's own path
    lrc = registry.factory("lrc", "", {"plugin": "lrc", "k": "4", "m": "2",
                                       "l": "3"})
    assert lrc.get_chunk_mapping()
    assert ecutil._lane(lrc, StripeInfo(4, 4 * 4096)) is None
    # the switch: w=8 byte-layout codes on the int8 pair, nothing else moves
    monkeypatch.setenv("CEPH_TPU_PACKEDBIT", "0")
    assert lane_for(rs) == ("packed", np.int8)
    assert lane_for(rs, resident=True, cols=4096) == ("resident", np.int8)
    cauchy = registry.factory("tpu", "", {
        "plugin": "tpu", "k": "4", "m": "2", "technique": "cauchy_good",
        "packetsize": "16"})
    assert lane_for(cauchy) == ("packetrows", np.uint8)
