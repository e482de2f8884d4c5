"""Plain reference for `technique=reed_sol_van` pools: jerasure's systematic
Vandermonde Reed-Solomon code over GF(2^8), byte for byte, in numpy.

It imports nothing of the program.  The construction follows the published
algorithm (Plank, "A tutorial on Reed-Solomon coding for fault-tolerance in
RAID-like systems", 1997, with the 2003 correction; jerasure's
`reed_sol_vandermonde_coding_matrix`): an extended Vandermonde matrix made
systematic by column operations, row k scaled to all ones, first column of
the later rows scaled to one.  GF(2^8) uses the polynomial 0x11d, as
gf-complete does for w=8.

`shards(profile, stripe_unit, payload)` is what a pool with that profile
has to hold for an object: the payload padded with zeros to a whole stripe
of k * stripe_unit bytes, shard i the concatenation of chunk i of every
stripe, parity shards the matrix applied bytewise.
"""

from __future__ import annotations

import functools

import numpy as np

_POLY = 0x11D


@functools.lru_cache(maxsize=None)
def _tables():
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY
    exp[255:510] = exp[0:255]
    return exp, log


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    exp, log = _tables()
    return int(exp[log[a] + log[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    exp, log = _tables()
    return int(exp[255 - log[a]])


@functools.lru_cache(maxsize=None)
def coding_matrix(k: int, m: int) -> tuple:
    """The m coding rows (tuples of k ints) of the systematic matrix."""
    rows, cols = k + m, k
    if rows > 256:
        raise ValueError("k + m exceeds GF(2^8)")
    d = [[0] * cols for _ in range(rows)]
    d[0][0] = 1
    d[rows - 1][cols - 1] = 1
    for i in range(1, rows - 1):
        acc = 1
        for j in range(cols):
            d[i][j] = acc
            acc = gf_mul(acc, i)
    for i in range(1, cols):
        pivot = next((j for j in range(i, rows) if d[j][i]), None)
        if pivot is None:
            raise ValueError("Vandermonde matrix cannot be made systematic")
        if pivot != i:
            d[i], d[pivot] = d[pivot], d[i]
        if d[i][i] != 1:
            inv = gf_inv(d[i][i])
            for r in range(rows):
                d[r][i] = gf_mul(inv, d[r][i])
        for j in range(cols):
            e = d[i][j]
            if j != i and e:
                for r in range(rows):
                    d[r][j] ^= gf_mul(e, d[r][i])
    for j in range(cols):
        if d[cols][j] != 1:
            inv = gf_inv(d[cols][j])
            for r in range(cols, rows):
                d[r][j] = gf_mul(inv, d[r][j])
    for r in range(cols + 1, rows):
        if d[r][0] != 1:
            inv = gf_inv(d[r][0])
            for j in range(cols):
                d[r][j] = gf_mul(d[r][j], inv)
    return tuple(tuple(row) for row in d[cols:])


@functools.lru_cache(maxsize=None)
def _mul_table(c: int) -> np.ndarray:
    return np.array([gf_mul(c, x) for x in range(256)], dtype=np.uint8)


def shards(profile: dict, stripe_unit: int, payload: bytes) -> list:
    """The k+m shards (bytes) a pool of this profile stores for `payload`."""
    k, m = int(profile["k"]), int(profile["m"])
    if int(profile.get("w", 8)) != 8:
        raise ValueError("this reference is GF(2^8) only")
    width = k * stripe_unit
    n_stripes = max(1, -(-len(payload) // width))
    buf = np.zeros(n_stripes * width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = buf.reshape(n_stripes, k, stripe_unit).transpose(1, 0, 2) \
        .reshape(k, n_stripes * stripe_unit)
    out = [data[i].tobytes() for i in range(k)]
    for row in coding_matrix(k, m):
        acc = np.zeros(data.shape[1], dtype=np.uint8)
        for coef, src in zip(row, data):
            if coef == 1:
                acc ^= src
            elif coef:
                acc ^= _mul_table(coef)[src]
        out.append(acc.tobytes())
    return out
