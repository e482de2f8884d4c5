"""AOT compiles for the v5e of the programs chip_smoke.py's main path hits,
at the shapes it hits them with (on-chip-measurement guide §2, third
rehearsal): the TPU compiler is installed here and compiles for a chip
that is described, not attached.  Nothing runs, so these say nothing of
results or times — only that the chip's compiler accepts the programs
and that they fit the device's memory.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports every test file), the persistent compilation cache is off
around the compiles (an entry written without a chip cannot be read
back), and everything compiles in the test's own process.

What the compiler said when these were written (PR 23; compiler seconds
on the sandbox CPU and bytes from memory_analysis — not device metrics):
every program is accepted.  The packed-bit
boundary does NOT fuse: temp memory is 60x the data bytes for the encode
apply (1.0 GB per 16 MiB dispatch), 160x for a decode matrix, 66x for
to_packedbit and 220x for from_packedbit (3.7 GB for 11 rows x 2 MiB) —
the lane-hostile [R, B//32, 32] relayout in ops/gf2.py.  And compile
time GROWS with width below 2 MiB columns: 6 s at 128 KiB, 24 s at
512 KiB (one 4 MiB object), 58 s at 1 MiB, then 4 s at 2 MiB.  That is
why the one-object width is marked slow here (the tier-1 run keeps the
16 MiB dispatch width), and why the queue's watchdog no longer counts
compile seconds (parallel/service.py).
"""

import os

import numpy as np
import pytest

K, M = 8, 3
HBM_BYTES = 16 << 30  # one v5e chip

#: the queue's 16 MiB dispatch ([8, 2 MiB]) and one 4 MiB object
#: ([8, 512 KiB]) — the widths chip_smoke.py's traffic produces
WIDTHS = {"dispatch16MiB": 2 << 20, "object4MiB": 512 << 10}
BOTH_WIDTHS = ["dispatch16MiB",
               pytest.param("object4MiB", marks=pytest.mark.slow)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mats():
    """The bit-matrices the smoke's codecs produce: the k=8 m=3
    reed_sol_van generator, the decode matrix for erasures {1,4,9}, and
    the cauchy_good k=10 m=4 generator."""
    from ceph_tpu.ec.gf import gf
    from ceph_tpu.ec.matrices import (cauchy_good_matrix,
                                      matrix_to_bitmatrix,
                                      vandermonde_coding_matrix)

    mat = vandermonde_coding_matrix(K, M, 8)
    full = np.vstack([np.eye(K, dtype=np.int64), mat])
    chosen = [c for c in range(K + M) if c not in (1, 4, 9)][:K]
    inv = gf(8).invert_matrix(full[chosen])
    return {
        "encode": matrix_to_bitmatrix(mat, 8).astype(np.uint8),
        "decode": matrix_to_bitmatrix(inv, 8).astype(np.uint8),
        "cauchy": matrix_to_bitmatrix(
            cauchy_good_matrix(10, 4, 8), 8).astype(np.uint8),
    }


def _compile(fn, *specs, **static):
    compiled = fn.lower(*specs, **static).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    # beside the smoke's 1 GiB resident store and a second round in flight
    assert total < HBM_BYTES // 2, f"program needs {total} B of a 16 GiB chip"
    print(f"MEM args={ma.argument_size_in_bytes} out={ma.output_size_in_bytes}"
          f" temp={ma.temp_size_in_bytes}")
    return compiled


def _spec(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", BOTH_WIDTHS)
@pytest.mark.parametrize("matrix", ["encode", "decode"])
def test_apply_packedbit(one_chip, mats, matrix, width):
    from ceph_tpu.ops.gf2 import apply_packedbit_fn

    _compile(apply_packedbit_fn(mats[matrix]),
             _spec((K, WIDTHS[width]), np.uint8, one_chip))


@pytest.mark.parametrize("width", BOTH_WIDTHS)
def test_encode_packedbit_resident(one_chip, mats, width):
    from ceph_tpu.ops.gf2 import encode_packedbit_resident_fn

    _compile(encode_packedbit_resident_fn(mats["encode"]),
             _spec((K, WIDTHS[width]), np.uint8, one_chip))


@pytest.mark.parametrize("width", BOTH_WIDTHS)
def test_packedbit_converters(one_chip, width):
    from ceph_tpu.ops.gf2 import from_packedbit, to_packedbit

    B = WIDTHS[width]
    n = K + M
    _compile(to_packedbit, _spec((n, B), np.uint8, one_chip))
    _compile(from_packedbit, _spec((n * 8, B // 32), np.uint32, one_chip),
             out_rows=n)


@pytest.mark.parametrize("width", BOTH_WIDTHS)
def test_encode_subchunk(one_chip, width):
    """The sub-chunk lane's program (PR 52) for clay k=8 m=4 d=11 at the
    cell's chunk, 4096 B of 64 sub-chunks: the compiler takes the three
    stages' transposes at both widths (here 5 s at the dispatch width,
    24 s at one object's, temp 1.3 GB and 0.27 GB)."""
    from ceph_tpu.ec.registry import registry
    from ceph_tpu.ops.gf2 import encode_subchunk_fn

    codec = registry.factory("clay", "", {"plugin": "clay", "k": "8",
                                          "m": "4", "d": "11"})
    g = codec.encode_geometry()
    _compile(encode_subchunk_fn(g.q, g.t, 4096, g.pair, g.pair_inv,
                                g.generator),
             _spec((8, WIDTHS[width]), np.uint8, one_chip))


def test_xor_packed_planes_decode(one_chip, mats):
    """The resident decode: the 3-erasure signature over u32 plane words."""
    from ceph_tpu.ops.gf2 import xor_packed_fn

    _compile(xor_packed_fn(mats["decode"]),
             _spec((K * 8, (2 << 20) // 32), np.uint32, one_chip))


def test_xor_packed_cauchy_rows(one_chip, mats):
    """cauchy_good k=10 m=4 through the _apply_rows seam: the schedule
    over raw uint8 packet rows of one 4 MiB object (pow2-bucketed)."""
    from ceph_tpu.ops.gf2 import bucket_columns, xor_packed_fn

    cols = bucket_columns(-(-(4 << 20) // 10) // 8)
    _compile(xor_packed_fn(mats["cauchy"]),
             _spec((10 * 8, cols), np.uint8, one_chip))


@pytest.mark.parametrize("blocks", [32, 64, 128])
def test_apply_packetrows_cauchy(one_chip, mats, blocks):
    """The queue's packet-layout lane for cauchy_good k=10 m=4 w=8
    packetsize=2048 at the widths the served path makes: one 4 MiB put's
    28 blocks a row bucket to 32, groups of 2 to 64, of 3-4 to 128, as
    u32 words.  Block transposes and schedule fuse: no temporaries
    (sandbox compiler, PR 28; 1.5-4 s a width)."""
    from ceph_tpu.ops.gf2 import apply_packetrows_fn

    compiled = apply_packetrows_fn(mats["cauchy"], 8, 2048).lower(
        _spec((10, blocks * 8 * 2048 // 4), np.uint32, one_chip)).compile()
    ma = compiled.memory_analysis()
    assert ma.output_size_in_bytes == 4 * blocks * 8 * 2048
    assert ma.temp_size_in_bytes < ma.argument_size_in_bytes


@pytest.mark.parametrize("src", [
    (88, 16384),   # k=8 m=3, 4 MiB: one page a bit-row
    (48, 32768),   # k=4 m=2, 4 MiB: two pages a bit-row
    (88, 128),     # k=8 m=3, one 4 KiB stripe (and every width up to it)
])
def test_slab_install(one_chip, src):
    """The fused install (compaction to the trim width it is told, pad,
    page view, row selection, donated scatter) as one program, keyed by
    the source's shape."""
    from ceph_tpu.ops.slab import install_fn, install_pages

    pw = (64 << 10) // 4  # osd_tier_page_bytes default, in u32 words
    compiled = _compile(
        install_fn(src, pw, True),
        _spec((256, pw), np.uint32, one_chip),
        _spec(src, np.uint32, one_chip),
        _spec((2, install_pages(src, src[1], pw) + 1), np.int32, one_chip))
    # donated: the update must be in place, not a second slab
    assert "input_output_alias" in compiled.as_text()


@pytest.mark.parametrize("cols_full,cols_b", [
    (65536, 16384),  # a 4 MiB put's rows out of a 16 MiB round
    (32768, 128),    # one stripe's out of a round a 4 MiB put leads
])
def test_plane_window(one_chip, cols_full, cols_b):
    """A request's columns out of a coalesced group's plane rows (the
    queue's resident fan-out), told the offset and the width."""
    from ceph_tpu.ops.slab import plane_window_fn

    _compile(plane_window_fn(88, cols_full, cols_b),
             _spec((88, cols_full), np.uint32, one_chip),
             _spec((3,), np.int32, one_chip))


@pytest.mark.parametrize("rows", [1, 256])
def test_slab_gather(one_chip, rows):
    from ceph_tpu.ops.slab import gather_fn

    pw = (64 << 10) // 4
    _compile(gather_fn(pw, rows), _spec((256, pw), np.uint32, one_chip),
             _spec((rows,), np.int32, one_chip))


@pytest.mark.parametrize("pages,n_rows", [
    (64, 64),    # k=8 m=3, 4 MiB: the data rows a served read packs
    (128, 88),   # the whole resident (planar_rows): 88 pages, bucket 128
    (8, 8),      # one shard's rows (planar_shard_bytes)
])
def test_slab_gather_span(one_chip, pages, n_rows):
    """A span's further sub-slabs and the cut of its bit-rows: the two
    programs PR 33 put where the read kept an eager concatenate, slice
    and reshape, at the served read's shapes."""
    from ceph_tpu.ops.slab import gather_into_fn, span_rows_fn

    pw = (64 << 10) // 4
    _compile(gather_into_fn(pw, pages),
             _spec((pages, pw), np.uint32, one_chip),
             _spec((256, pw), np.uint32, one_chip),
             _spec((pages,), np.int32, one_chip),
             _spec((pages,), np.bool_, one_chip))
    _compile(span_rows_fn(pages, pw, n_rows, pw, False),
             _spec((pages, pw), np.uint32, one_chip),
             _spec((2,), np.int32, one_chip))


@pytest.mark.parametrize("width", [
    "object4MiB", pytest.param("dispatch16MiB", marks=pytest.mark.slow)])
def test_mesh_encode_resident_four_chips(topo, mats, width):
    """The --multichip step's write program on a 2x2 mesh of the described
    devices, columns sharded as MeshDispatcher lays them out: every output
    spans the four chips and the hot path holds no collective.  (Here the
    16 MiB dispatch is the slow one: its per-chip shard is [8, 512 KiB].)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ceph_tpu.ops.gf2 import encode_packedbit_resident_fn

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("stripe", "col"))
    cols = NamedSharding(mesh, P(None, ("stripe", "col")))
    compiled = _compile(encode_packedbit_resident_fn(mats["encode"]),
                        _spec((K, WIDTHS[width]), np.uint8, cols))
    for out in compiled.output_shardings:
        assert len(out.device_set) == 4, out
    text = compiled.as_text()
    for op in ("all-gather", "all-reduce", "all-to-all",
               "collective-permute"):
        assert op not in text, op
