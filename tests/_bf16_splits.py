"""NumPy's side of `highest`'s six bf16 products: the rounded three-way
splits and the float64 sums of their products, for the tests of the packed
helpers in `stark_tpu/ops/precision.py` (`test_hier_fused.py` with exact
rows, `test_ops_fused.py` without)."""

import numpy as np


def _round_bf16(v):
    """NumPy's bfloat16 rounding to nearest, ties to even, in float32."""
    bits = np.asarray(v, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32)


def _np_split3(v):
    v = np.asarray(v, np.float32)
    hi = _round_bf16(v)
    mid = _round_bf16(v - hi)
    return [t.astype(np.float64) for t in (hi, mid, v - hi - mid)]


#: (i, j) of the six products `highest` forms (hi 0, mid 1, lo 2) and of
#: the three it leaves out
_SIX = [(0, 0), (0, 1), (1, 0), (0, 2), (2, 0), (1, 1)]
_NINE = _SIX + [(1, 2), (2, 1), (2, 2)]


def _np_products(a, b, pairs):
    """float64 sum over ``pairs`` of a's split i times b's split j."""
    sa, sb = _np_split3(a), _np_split3(b)
    return sum(sa[i] @ sb[j] for i, j in pairs)


def _cancelling_case(rows, rng):
    """Two factors, (rows, 4) each, whose six `highest` products add to 0
    over the 4 while the three it leaves out do not.  Entries are h + m + l
    with h = ±1, m = ±2**-10, l = ±2**-20 (each its own rounded split); the
    signs of the first factor's (h, m, l) and of the second's are, over the
    4, s = (1, 1, 1, 1), t = u = (1, -1, -1, 1) and S = (1, 1, -1, -1),
    T = (1, -1, 1, -1), U = (1, -1, -1, 1): s·S = s·T = t·S = s·U = u·S =
    t·T = 0, so the products hh, hm, mh, hl, lh and mm cancel, every partial
    sum a float32 in any order, while m·l and l·l add up to 4 (2**-30 +
    2**-40).  Each row takes a random sign of its own."""
    def pattern(p, q, r):
        return np.array(p) + 2.0**-10 * np.array(q) + 2.0**-20 * np.array(r)

    first = pattern((1, 1, 1, 1), (1, -1, -1, 1), (1, -1, -1, 1))
    second = pattern((1, 1, -1, -1), (1, -1, 1, -1), (1, -1, -1, 1))
    signs = [rng.choice([-1.0, 1.0], size=(rows, 1)) for _ in range(2)]
    return tuple((sg * v[None]).astype(np.float32)
                 for sg, v in zip(signs, (first, second)))
