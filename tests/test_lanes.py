"""The BatchingQueue's lane table (parallel/service.LANES) and the one
rule that picks a lane for a codec (rados/ecutil.lane_for).

Every lane, at every field width it serves, is held to its numpy mirror
and to the CPU codec: the device program, the breaker's fallback and the
codec's own encode are three implementations of one product.  The choice
of lane is held to what the benchmark's cells have run on the chip
(PERF_LEDGER.jsonl: RS w=8 on packedbit / packedbit_resident, cauchy_good
on packetrows) and to the int8 pair for the widths the schedule lanes do
not take.  The sixth lane, `subchunk` (PR 52), has its own file
(tests/test_clay_lane.py); here it is held to the table's common rules,
and every deployment the benchmark has is held to the lane, the request
and the programs it had before that lane came."""

import glob
import json
import os

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

#: (lane, w): the six lanes, and w=16 on the two that take it
LANE_CASES = [("packed", 8), ("packed", 16), ("resident", 8),
              ("resident", 16), ("packedbit", 8), ("packedbit_resident", 8),
              ("packetrows", 8), ("subchunk", 8)]


def lane_case(kind: str, w: int, cols: int = 2048, seed: int = 0):
    """(codec, request) for one lane: a jerasure CPU codec whose layout
    the lane serves, and the request its plans would submit — (mbits,
    rows, w, out_rows, kind[, packetsize])."""
    if kind == "subchunk":
        # clay k=4 m=2 d=5: chunks of 8 sub-chunks; 1024-byte chunks
        # here, and a width that is not whole chunks rounded up to them
        cols = -(-cols // 1024) * 1024
        codec = registry.factory("clay", "", {"plugin": "clay", "k": str(K),
                                              "m": str(M)})
        rows = np.random.default_rng(seed).integers(
            0, 256, (K, cols), dtype=np.uint8)
        sinfo = StripeInfo(K, K * 1024)
        return codec, (ecutil._encode_matrix(codec, ecutil._lane(codec, sinfo)),
                       rows, w, M, kind, 1024)
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
    if kind == "subchunk":
        # the codec encodes a chunk at a time: its planes are the chunk's
        chunk = item[5]
        cpu = np.concatenate(
            [np.asarray(codec.encode_chunks(
                np.ascontiguousarray(rows[:, c:c + chunk])))
             for c in range(0, rows.shape[1], chunk)], axis=1)
    else:
        cpu = np.asarray(codec.encode_chunks(rows))
    assert np.array_equal(got, cpu)


def test_table_is_the_six_lanes():
    assert list(LANES) == ["packed", "resident", "packedbit",
                           "packedbit_resident", "packetrows", "subchunk"]
    assert {kind for kind, _ in LANE_CASES} == set(LANES)


@pytest.mark.parametrize("kind,w", LANE_CASES)
def test_lane_matches_its_mirror_and_the_cpu_codec(kind, w):
    # 2048 columns, and a width that is no power of two (bucket padding);
    # both are whole u32 words and whole w*packetsize blocks (the
    # sub-chunk lane's are whole 1024-byte chunks: 2 and 3 of them)
    q = BatchingQueue(max_delay=60.0, mesh=False)
    try:
        widths = (2048, 3072) if kind == "subchunk" else (2048, 1152)
        cases = [lane_case(kind, w, cols, seed=cols) for cols in widths]
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
    ("subchunk", (np.zeros((1, 12), np.uint8),
                  np.zeros((2, 1000), np.uint8), 8, 1, 1024),
     "whole chunks"),
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


# -- what the sixth lane may not move ---------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(REPO, "benchmarks", "configs",
                                        "*.json")))

#: (plugin, technique, k, m) -> what a 4 MiB put of that pool was at the
#: commit before the lane (b705623, computed there): lane_for, lane_for
#: for a resident, _lane, the request's matrix shape and dtype, its rows'
#: shape, (w, out_rows, kind[, packetsize]), the staged width
AT_HEAD = {
    ("tpu", "reed_sol_van", 8, 3): (
        ("packedbit", np.uint8), ("packedbit_resident", np.uint8),
        ("packedbit", np.uint8), (24, 64), "uint8", (8, 524288),
        (8, 3, "packedbit"), 524288),
    ("tpu", "reed_sol_van", 4, 2): (
        ("packedbit", np.uint8), ("packedbit_resident", np.uint8),
        ("packedbit", np.uint8), (16, 32), "uint8", (4, 1048576),
        (8, 2, "packedbit"), 1048576),
    ("tpu", "cauchy_good", 10, 4): (
        ("packetrows", np.uint8), None, ("packetrows", np.uint8, 2048),
        (32, 80), "uint8", (10, 458752), (8, 4, "packetrows", 2048), 524288),
    # the deployment PR 52 adds: the one pool the sixth lane takes
    ("clay", None, 8, 4): (
        ("subchunk", np.uint8), None, ("subchunk", np.uint8, 4096),
        (4, 18), "uint8", (8, 524288), (8, 4, "subchunk", 4096), 524288),
}


def _deployment(path):
    with open(path) as f:
        cfg = json.load(f)
    prof = cfg["profile"]
    codec = registry.factory(prof["plugin"], "", dict(prof))
    k = codec.get_data_chunk_count()
    sinfo = StripeInfo(k, k * codec.get_chunk_size(k * cfg["stripe_unit"]))
    key = (prof["plugin"], prof.get("technique"), int(prof["k"]),
           int(prof["m"]))
    return key, codec, sinfo


def _compiles_of_puts(deployments):
    """Programs XLA compiles for one small put of each deployment in
    turn, through one queue, from cold caches."""
    import jax

    from ceph_tpu.ops import gf2
    from ceph_tpu.utils.jaxdev import compile_meter

    jax.clear_caches()
    with gf2._XOR_LOCK:
        gf2._XOR_SCHEDULES.clear()
    meter, counts = compile_meter(), []
    q = BatchingQueue(mesh=False)
    try:
        for _, codec, sinfo in deployments:
            before = meter.count
            ecutil.batched_encode(codec, sinfo, bytes(sinfo.stripe_width),
                                  queue=q)
            counts.append(meter.count - before)
    finally:
        q.close()
    return counts


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_a_deployments_lane_request_and_programs_are_what_they_were(path):
    key, codec, sinfo = _deployment(path)
    (want_lane_for, want_resident, want_lane, mshape, mdtype, rshape, rest,
     staged) = AT_HEAD[key]
    assert lane_for(codec) == want_lane_for
    assert lane_for(codec, resident=True,
                    cols=128 * sinfo.chunk_size) == want_resident
    assert ecutil._lane(codec, sinfo) == want_lane
    item, _ = ecutil._encode_plan_parts(codec, sinfo, bytes(4 << 20))
    assert (item[0].shape, str(item[0].dtype)) == (mshape, mdtype)
    assert type(item[1]).__name__ == "StripeRows"
    assert item[1].shape == rshape and tuple(item[2:]) == rest
    from ceph_tpu.parallel.service import staged_cols

    assert staged_cols(rest[2], rest[0], (rest[3:] or (0,))[0],
                       rshape[1]) == staged
    assert ecutil.planar_eligible(codec) == (want_resident is not None)
    # a clay pool beside it in the process: the put compiles what it
    # compiled alone, no program more and none less
    clay = _deployment(os.path.join(REPO, "benchmarks", "configs",
                                    "ec-k8m4-clay.json"))
    alone, = _compiles_of_puts([(key, codec, sinfo)])
    beside = _compiles_of_puts([clay, (key, codec, sinfo)])
    assert alone > 0 and beside[0] > 0
    if key != clay[0]:
        assert beside[1] == alone, (alone, beside)
