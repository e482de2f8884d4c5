"""Plain reference for `technique=cauchy_good` (and `cauchy_orig`) pools:
jerasure's Cauchy Reed-Solomon bit-matrix codes over GF(2^w), packet for
packet, in numpy.

It imports nothing of the program.  The construction follows the published
algorithms (Plank and Xu, "Optimizing Cauchy Reed-Solomon codes for
fault-tolerant network storage applications", NCA 2006; jerasure 2.0's
cauchy.c and jerasure.c):

  * `cauchy_original_coding_matrix`: M[i][j] = 1 / (i ^ (m + j)) over
    GF(2^w); w=8 uses the polynomial 0x11d, as gf-complete does;
  * `cauchy_improve_coding_matrix` (cauchy_good only): every column scaled
    so that row 0 is all ones, then every later row divided by the one of
    its elements that leaves its bit-matrix with the fewest ones;
  * `jerasure_matrix_to_bitmatrix`: element e becomes the w x w block whose
    column x holds the bits of e * 2^x (bit l in row l);
  * the chunk-size rule of ErasureCodeJerasure::get_chunk_size: the stripe
    of k * stripe_unit bytes is padded up to a multiple of
    k * w * packetsize * sizeof(int), and a chunk is a k-th of that;
  * `jerasure_bitmatrix_encode`: a chunk is a run of w*packetsize-byte
    blocks of w packets each; bit-row j*w + l of the bit-matrix stands for
    packet l of every block of chunk j, and coding packet i*w + l of a
    block is the XOR of the data packets of that block that row i*w + l
    of the bit-matrix selects.

Departures from jerasure, each without effect here: jerasure's
`cauchy_good_general_coding_matrix` takes its matrix for m == 2 from a
hard-coded table of best elements (cauchy_best_r6.c) instead of the
construction above, and the program under test omits that table too
(ceph_tpu/ec/matrices.py `cauchy_good_matrix`); it is irrelevant at m = 4,
and at m = 2 this file follows the general construction, as the program
does.  GF(2^w) here is w in {4, 8, 16} with gf-complete's default
polynomials; the deployment uses w = 8.

`shards(profile, stripe_unit, payload)` is what a pool with that profile
has to hold for an object: the payload padded with zeros to a whole number
of stripes of k chunks, shard i the concatenation of chunk i of every
stripe, parity shards by the packet-wise bit-matrix encode.
"""

from __future__ import annotations

import functools

import numpy as np

_POLY = {4: 0x13, 8: 0x11D, 16: 0x1100B}
_SIZEOF_INT = 4
_DEFAULT_PACKETSIZE = 2048


@functools.lru_cache(maxsize=None)
def _tables(w: int):
    size = 1 << w
    exp = [0] * (2 * size)
    log = [0] * size
    x = 1
    for i in range(size - 1):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & size:
            x ^= _POLY[w]
    for i in range(size - 1, 2 * size):
        exp[i] = exp[i - (size - 1)]
    return exp, log


def gf_mul(a: int, b: int, w: int = 8) -> int:
    if a == 0 or b == 0:
        return 0
    exp, log = _tables(w)
    return exp[log[a] + log[b]]


def gf_div(a: int, b: int, w: int = 8) -> int:
    if b == 0:
        raise ZeroDivisionError("division by 0 in GF(2^w)")
    if a == 0:
        return 0
    exp, log = _tables(w)
    return exp[log[a] - log[b] + (1 << w) - 1]


def _element_bits(e: int, w: int) -> list:
    """The w x w bit block of element e, as jerasure_matrix_to_bitmatrix
    lays it out: block[l][x] = bit l of e * 2^x."""
    block = [[0] * w for _ in range(w)]
    for x in range(w):
        for l in range(w):
            block[l][x] = (e >> l) & 1
        e = gf_mul(e, 2, w)
    return block


def _n_ones(e: int, w: int) -> int:
    """cauchy_n_ones: the ones in the element's bit block."""
    return sum(sum(row) for row in _element_bits(e, w))


@functools.lru_cache(maxsize=None)
def coding_matrix(technique: str, k: int, m: int, w: int = 8) -> tuple:
    """The m coding rows (tuples of k ints over GF(2^w))."""
    if technique not in ("cauchy_good", "cauchy_orig"):
        raise ValueError(f"this reference has no technique {technique!r}")
    if k + m > (1 << w):
        raise ValueError("k + m exceeds the field")
    mat = [[gf_div(1, i ^ (m + j), w) for j in range(k)] for i in range(m)]
    if technique == "cauchy_orig":
        return tuple(tuple(row) for row in mat)
    # cauchy_improve_coding_matrix
    for j in range(k):
        if mat[0][j] != 1:
            inv = gf_div(1, mat[0][j], w)
            for i in range(m):
                mat[i][j] = gf_mul(mat[i][j], inv, w)
    for i in range(1, m):
        best = sum(_n_ones(e, w) for e in mat[i])
        best_j = -1
        for j in range(k):
            if mat[i][j] != 1:
                inv = gf_div(1, mat[i][j], w)
                ones = sum(_n_ones(gf_mul(e, inv, w), w) for e in mat[i])
                if ones < best:
                    best, best_j = ones, j
        if best_j != -1:
            inv = gf_div(1, mat[i][best_j], w)
            mat[i] = [gf_mul(e, inv, w) for e in mat[i]]
    return tuple(tuple(row) for row in mat)


@functools.lru_cache(maxsize=None)
def bitmatrix(technique: str, k: int, m: int, w: int = 8) -> np.ndarray:
    """jerasure_matrix_to_bitmatrix of the coding matrix: [m*w, k*w]."""
    bm = np.zeros((m * w, k * w), dtype=np.uint8)
    for i, row in enumerate(coding_matrix(technique, k, m, w)):
        for j, e in enumerate(row):
            bm[i * w:(i + 1) * w, j * w:(j + 1) * w] = _element_bits(e, w)
    bm.setflags(write=False)
    return bm


def chunk_size(k: int, w: int, packetsize: int, stripe_width: int) -> int:
    """ErasureCodeJerasure::get_chunk_size for the cauchy techniques
    (no per-chunk alignment)."""
    alignment = k * w * packetsize * _SIZEOF_INT
    tail = stripe_width % alignment
    padded = stripe_width + (alignment - tail if tail else 0)
    return padded // k


def bitmatrix_encode(bm: np.ndarray, k: int, m: int, w: int, packetsize: int,
                     data: np.ndarray) -> np.ndarray:
    """jerasure_bitmatrix_encode: `data` is [k, size] bytes, size a whole
    number of w*packetsize-byte blocks; returns the [m, size] coding
    chunks.  Any [m*w, k*w] bit-matrix (liberation's, a decode
    signature's) encodes the same way."""
    size = data.shape[1]
    block = w * packetsize
    if data.shape[0] != k or size % block:
        raise ValueError(f"chunks of {size} B are not whole {block} B blocks")
    # [chunk, block, packet of the block, byte of the packet]
    packets = data.reshape(k, size // block, w, packetsize)
    coding = np.zeros((m, size // block, w, packetsize), dtype=np.uint8)
    for i in range(m):
        for l in range(w):
            for col in np.nonzero(bm[i * w + l])[0]:
                j, x = divmod(int(col), w)
                coding[i, :, l, :] ^= packets[j, :, x, :]
    return coding.reshape(m, size)


def shapes(profile: dict, stripe_unit: int, object_bytes: int) -> dict:
    """What a pool of this profile makes of an object of that size."""
    k, m = int(profile["k"]), int(profile["m"])
    w = int(profile.get("w", 8))
    packetsize = int(profile.get("packetsize", _DEFAULT_PACKETSIZE))
    chunk = chunk_size(k, w, packetsize, k * stripe_unit)
    n_stripes = max(1, -(-object_bytes // (k * chunk)))
    return {"stripe_width": k * chunk, "chunk_size": chunk,
            "stripes": n_stripes, "padded_bytes": n_stripes * k * chunk,
            "shards": k + m, "shard_bytes": n_stripes * chunk}


def data_rows(profile: dict, stripe_unit: int, payload: bytes) -> np.ndarray:
    """The k data shards as [k, stripes * chunk] bytes: the payload padded
    with zeros to whole stripes, row i the chunk i of every stripe."""
    k = int(profile["k"])
    s = shapes(profile, stripe_unit, len(payload))
    buf = np.zeros(s["padded_bytes"], dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    return np.ascontiguousarray(
        buf.reshape(s["stripes"], k, s["chunk_size"]).transpose(1, 0, 2)
        .reshape(k, s["shard_bytes"]))


def shards(profile: dict, stripe_unit: int, payload: bytes) -> list:
    """The k+m shards (bytes) a pool of this profile stores for `payload`."""
    k, m = int(profile["k"]), int(profile["m"])
    w = int(profile.get("w", 8))
    packetsize = int(profile.get("packetsize", _DEFAULT_PACKETSIZE))
    if w not in _POLY:
        raise ValueError(f"this reference has no GF(2^{w})")
    data = data_rows(profile, stripe_unit, payload)
    coding = bitmatrix_encode(
        bitmatrix(profile.get("technique", "cauchy_good"), k, m, w),
        k, m, w, packetsize, data)
    return [row.tobytes() for row in data] + [row.tobytes() for row in coding]
