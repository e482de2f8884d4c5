"""Plain reference for `plugin=clay` pools: the coupled-layer (Clay) MSR
code over GF(2^8), one plane and one pair at a time, in numpy.

It imports nothing of the program.  The construction follows the paper
(Vajha et al., "Clay Codes: Moulding MDS Codes to Yield an MSR Code",
FAST 2018) and upstream's plugin (src/erasure-code/clay/ErasureCodeClay.cc,
read when this was written; `/root/reference` is not on this machine and
the repository's corpus holds no clay vectors, so no byte of this file was
compared with upstream's output: tests/test_clay_lane.py pins the
construction three other ways instead — against the tree's CPU codec, by
decoding every loss of m chunks back through that codec, and by the MSR
property, one lost chunk rebuilt from a q-th of each of d helpers).

The rules it follows, with upstream's defaults scalar_mds=jerasure,
technique=reed_sol_van:

  * q = d - k + 1; nu = the least number that makes k + m + nu a multiple
    of q (virtual chunks of zeros, none for k=8 m=4 d=11); t = (k+m+nu)/q.
  * Nodes sit on a q x t grid: node (x, y) has the index y*q + x.  Chunk
    i is node i for i < k; nodes k .. k+nu-1 are the virtual ones; parity
    chunk k+j is node k+nu+j.
  * A chunk is q^t sub-chunks of equal size; sub-chunk ("plane") z has the
    digit vector z_0 .. z_t-1 in base q, z_0 the most significant.
  * In plane z, node (x, y) is a dot if z_y = x and stays uncoupled:
    U = C.  Otherwise it is paired with node (z_y, y) in plane z*, which
    is z with its y-th digit set to x.  Of a pair the node with the
    larger x is the first.
  * The pairwise transform is the 2+2 code of jerasure's reed_sol_van
    (k=2 m=2 w=8): with (C, C*) of a pair as its data, first node first,
    (U, U*) are its two parities, in the same order.  Any two of the four
    give the other two.
  * Each plane of uncoupled values is a codeword of the scalar MDS code,
    jerasure's reed_sol_van with k+nu data and m parities, nodes in index
    order.
  * Encoding is the layered decode with the m parity nodes erased: a
    plane's score is the number of erased nodes that are dots in it;
    planes are taken in order of their score; in a plane every intact
    node's U comes from its pair's two C (or is C, on a dot), the scalar code
    gives the erased nodes' U, and an erased node's C comes back from its
    pair: from its partner's C and its own U where the partner is intact,
    from both U where the partner is erased too (then the pair is solved
    once, by its first node), or is U on a dot.
  * The chunk size: the stripe of k * stripe_unit bytes is padded up to a
    multiple of q^t * k * a, where a = 32 is what the scalar code's
    chunk-size rule makes of one byte (k=2, w=8: 2 * 8 * sizeof(int) / 2),
    and a chunk is a k-th of that.

GF(2^8) uses the polynomial 0x11d, as gf-complete does for w=8; the
tables are this file's own.  The reed_sol_van coding matrix is the
sibling reference's (benchmarks/references/reed_sol_van.py
`coding_matrix`: the published Vandermonde construction).

`shards(profile, stripe_unit, payload)` is what a pool with that profile
has to hold for an object: the payload padded with zeros to whole stripes
of k chunks, each stripe encoded by itself, shard i the concatenation of
chunk i of every stripe.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.references.reed_sol_van import coding_matrix

_POLY = 0x11D
_SCALAR_ALIGN = 32


@functools.lru_cache(maxsize=None)
def _tables():
    exp, log = [0] * 510, [0] * 256
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    return exp, log


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    exp, log = _tables()
    return exp[log[a] + log[b]]


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    exp, log = _tables()
    return exp[255 - log[a]]


@functools.lru_cache(maxsize=None)
def _times(c: int) -> np.ndarray:
    return np.array([gf_mul(c, x) for x in range(256)], dtype=np.uint8)


def _combine(coefs, values) -> np.ndarray:
    """sum of coefs[i] * values[i] over GF(2^8), bytewise."""
    acc = np.zeros_like(values[0])
    for c, v in zip(coefs, values):
        if c:
            acc ^= _times(c)[v]
    return acc


def geometry(profile: dict) -> dict:
    k, m = int(profile["k"]), int(profile["m"])
    d = int(profile.get("d", k + m - 1))
    if int(profile.get("w", 8)) != 8:
        raise ValueError("this reference is GF(2^8) only")
    if (profile.get("scalar_mds", "jerasure"), profile.get(
            "technique", "reed_sol_van")) != ("jerasure", "reed_sol_van"):
        raise ValueError("this reference is scalar_mds=jerasure "
                         "technique=reed_sol_van only")
    if not k <= d <= k + m - 1:
        raise ValueError(f"d={d} is not within [{k}, {k + m - 1}]")
    q = d - k + 1
    nu = (q - (k + m) % q) % q
    t = (k + m + nu) // q
    return {"k": k, "m": m, "d": d, "q": q, "t": t, "nu": nu,
            "sub_chunks": q ** t}


def chunk_size(geo: dict, stripe_width: int) -> int:
    align = geo["sub_chunks"] * geo["k"] * _SCALAR_ALIGN
    return max(1, -(-stripe_width // align)) * align // geo["k"]


def _digits(z: int, q: int, t: int) -> list:
    out = [0] * t
    for i in range(t - 1, -1, -1):
        out[i] = z % q
        z //= q
    return out


def _pair_solve(known: dict, want: tuple) -> list:
    """The 2+2 pairwise code: ids 0, 1 are a pair's coupled values (first
    node first), 2, 3 its uncoupled ones; `known` holds two of the four,
    `want` names others.  Solved by the 2x2 system over GF(2^8)."""
    g = coding_matrix(2, 2)
    rows = {0: (1, 0), 1: (0, 1), 2: g[0], 3: g[1]}
    (ia, a), (ib, b) = sorted(known.items())
    (p, r), (s, u) = rows[ia], rows[ib]
    det_inv = gf_inv(gf_mul(p, u) ^ gf_mul(r, s))
    inv = ((gf_mul(u, det_inv), gf_mul(r, det_inv)),
           (gf_mul(s, det_inv), gf_mul(p, det_inv)))
    c = [_combine(inv[0], (a, b)), _combine(inv[1], (a, b))]
    return [c[i] if i < 2 else _combine(rows[i], c) for i in want]


def encode_stripes(geo: dict, data: np.ndarray) -> np.ndarray:
    """[n_stripes, k, chunk] uint8 -> [m, n_stripes, chunk]: the parity
    chunks of every stripe.  A stripe is encoded by itself; the walk
    below, plane by plane and pair by pair, is the same for each, so a
    "sub-chunk" here is that sub-chunk of every stripe ([n_stripes, sc]):
    numpy's axis, not another algorithm."""
    k, m, q, t, nu = (geo[x] for x in ("k", "m", "q", "t", "nu"))
    n_planes = geo["sub_chunks"]
    n_stripes = data.shape[0]
    sc = data.shape[2] // n_planes
    n_nodes = q * t
    erased = set(range(k + nu, n_nodes))
    gen = coding_matrix(k + nu, m)
    # C[node][z], U[node][z]: sub-chunks of sc bytes, a stripe a row
    C = np.zeros((n_nodes, n_planes, n_stripes, sc), dtype=np.uint8)
    C[:k] = data.reshape(n_stripes, k, n_planes, sc).transpose(1, 2, 0, 3)
    U = np.zeros_like(C)
    vec = [_digits(z, q, t) for z in range(n_planes)]
    score = [sum(1 for n in erased if vec[z][n // q] == n % q)
             for z in range(n_planes)]

    def star(z, x, y):
        return z + (x - vec[z][y]) * q ** (t - 1 - y)

    for level in sorted(set(score)):
        planes = [z for z in range(n_planes) if score[z] == level]
        for z in planes:
            for node in range(n_nodes):
                if node in erased:
                    continue
                x, y = node % q, node // q
                zy = vec[z][y]
                partner, zs = y * q + zy, star(z, x, y)
                if zy == x:
                    U[node, z] = C[node, z]
                else:
                    # both coupled values are known: an erased partner is
                    # a dot here, so plane z* scores one less and gave
                    # its C back at an earlier level
                    first = x > zy
                    U[node, z], = _pair_solve(
                        {0 if first else 1: C[node, z],
                         1 if first else 0: C[partner, zs]},
                        (2 if first else 3,))
            for j in range(m):
                U[k + nu + j, z] = _combine(
                    gen[j], [U[i, z] for i in range(k + nu)])
        for z in planes:
            for node in erased:
                x, y = node % q, node // q
                zy = vec[z][y]
                partner, zs = y * q + zy, star(z, x, y)
                if zy == x:
                    C[node, z] = U[node, z]
                elif partner not in erased:
                    first = x > zy
                    C[node, z], = _pair_solve(
                        {1 if first else 0: C[partner, zs],
                         2 if first else 3: U[node, z]},
                        (0 if first else 1,))
                elif x > zy:
                    C[node, z], C[partner, zs] = _pair_solve(
                        {2: U[node, z], 3: U[partner, zs]}, (0, 1))
    return C[k + nu:].transpose(0, 2, 1, 3).reshape(m, n_stripes, -1)


def shapes(profile: dict, stripe_unit: int, object_bytes: int) -> dict:
    """The sizes a pool of this profile gives an object: what the
    configuration states as `derived`."""
    geo = geometry(profile)
    k, m = geo["k"], geo["m"]
    chunk = chunk_size(geo, k * stripe_unit)
    width = k * chunk
    stripes = max(1, -(-object_bytes // width))
    return {"q": geo["q"], "t": geo["t"], "nu": geo["nu"],
            "stripe_width": width, "chunk_size": chunk,
            "sub_chunks": geo["sub_chunks"],
            "sub_chunk_bytes": chunk // geo["sub_chunks"],
            "repair_sub_chunks_per_helper": geo["sub_chunks"] // geo["q"],
            "object_bytes": object_bytes, "stripes_per_object": stripes,
            "padded_bytes_per_object": stripes * width,
            "shards": k + m, "shard_bytes": stripes * chunk,
            "stored_bytes_per_object": stripes * chunk * (k + m),
            "remote_sub_writes": k + m - 1}


def shards(profile: dict, stripe_unit: int, payload: bytes) -> list:
    """The k+m shards (bytes) a pool of this profile stores for `payload`."""
    geo = geometry(profile)
    k, m = geo["k"], geo["m"]
    chunk = chunk_size(geo, k * stripe_unit)
    width = k * chunk
    n_stripes = max(1, -(-len(payload) // width))
    buf = np.zeros(n_stripes * width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    stripes = buf.reshape(n_stripes, k, chunk)
    parity = encode_stripes(geo, stripes)
    return [stripes[:, i].tobytes() for i in range(k)] + \
        [parity[j].tobytes() for j in range(m)]
