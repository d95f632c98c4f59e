"""ops/exactf64.py against the host's IEEE doubles, bit for bit, and the
score formulas built on it against the reference's Python float formulas.

The device computes the reference's float64 score expressions in integer
arithmetic because the TPU's emulated f64 is not correctly rounded. Integer
operations are exact on every backend, so equality with IEEE here (on the
CPU backend) is equality on the chip.
"""
import numpy as np
import pytest

import kubernetes_tpu.ops  # noqa: F401  (x64)
import jax

from kubernetes_tpu.ops import exactf64 as X
from kubernetes_tpu.ops import kernels as K

N = 40000


def to_pair(v):
    v = np.asarray(v, np.float64)
    m = np.zeros(v.shape, np.int64)
    e = np.full(v.shape, X.ZERO_E, np.int64)
    fr, ex = np.frexp(v)
    nz = v != 0
    m[nz] = (fr[nz] * (1 << 53)).astype(np.int64)
    e[nz] = ex[nz] - 53
    return m, e


def from_pair(p):
    m, e = np.asarray(p[0]), np.asarray(p[1])
    return np.where(m == 0, 0.0, np.ldexp(m.astype(np.float64),
                                          np.maximum(e, -1100)))


def same(got, want):
    got, want = np.asarray(got), np.asarray(want)
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, [(got.flat[i], want.flat[i]) for i in bad[:5]]


@pytest.fixture(scope="module")
def doubles():
    """Non-negative normal doubles over 64 binades, salted with equal
    pairs, zeros and one-ulp neighbours (massive cancellation)."""
    rng = np.random.RandomState(1)
    x = rng.rand(N) * 2.0 ** rng.randint(-60, 4, N)
    y = rng.rand(N) * 2.0 ** rng.randint(-60, 4, N)
    x[::5], y[::5] = rng.rand(N)[::5], rng.rand(N)[::5]
    y[::9] = x[::9]
    y[::10] = 0.0
    x[::23] = np.where(y[::23] > 0, np.nextafter(y[::23], 10.0), x[::23])
    return x, y


class TestOperations:
    def test_fdiv_int_is_correctly_rounded(self):
        rng = np.random.RandomState(2)
        b = np.concatenate([
            rng.randint(1, 1 << 53, N, dtype=np.int64),     # full width
            rng.randint(1, 5000, N).astype(np.int64),       # pod counts
            (1 << rng.randint(0, 53, N)).astype(np.int64)])  # powers of two
        a = (b * rng.rand(b.size)).astype(np.int64)
        a[::7] = b[::7]                       # quotient exactly 1
        a[::11] = 0
        a[::13] = np.maximum(b[::13] - 1, 0)  # just under 1
        a[::17] = 1                           # smallest quotient
        a = np.minimum(a, b)
        got = from_pair(jax.jit(X.fdiv_int)(a, b))
        # Python int / int is the correctly rounded exact quotient
        want = np.array([x / y for x, y in zip(a.tolist(), b.tolist())])
        same(got, want)

    def test_fsub(self, doubles):
        x, y = doubles
        hi, lo = np.maximum(x, y), np.minimum(x, y)
        same(from_pair(jax.jit(X.fsub)(to_pair(hi), to_pair(lo))), hi - lo)

    def test_fadd(self, doubles):
        x, y = doubles
        same(from_pair(jax.jit(X.fadd)(to_pair(x), to_pair(y))), x + y)

    def test_fmul(self, doubles):
        x, y = doubles
        same(from_pair(jax.jit(X.fmul)(to_pair(x), to_pair(y))), x * y)
        c = 1.0 - 2.0 / 3.0
        same(from_pair(jax.jit(
            lambda p: X.fmul(p, X.constant(c)))(to_pair(x))), x * c)

    def test_fmul_small_and_ftrunc(self, doubles):
        x, _y = doubles
        same(from_pair(jax.jit(
            lambda p: X.fmul_small(p, 10))(to_pair(x))), x * 10.0)
        z = np.random.RandomState(3).rand(N) * 12
        same(jax.jit(X.ftrunc)(to_pair(z)), z.astype(np.int64))

    def test_constant_round_trips(self):
        for c in (0.0, 1.0, 10.0, 2.0 / 3.0, 1.0 - 2.0 / 3.0, 0.1):
            m, e = X.constant(c)
            assert (0.0 if m == 0 else float(m) * 2.0 ** e) == c


class TestSmallDiv:
    def test_counts_like_floor_division_and_saturates(self):
        rng = np.random.RandomState(7)
        den = rng.randint(1, 1 << 50, N).astype(np.int64)
        q = rng.randint(0, 14, N)
        num = den * q + (den * rng.rand(N)).astype(np.int64)
        num[::9] = den[::9] * q[::9]          # exact multiples
        got = jax.jit(lambda a, b: X.small_div(a, b, 10))(num, den)
        same(got, np.minimum(num // den, 10))

    def test_resource_priorities_match_the_integer_formulas(self):
        """least / most / RTCR through _local_total against the reference's
        integer formulas written with Python's own //."""
        rng = np.random.RandomState(8)
        n = 5000
        ac = rng.randint(0, 64000, n).astype(np.int64)
        am = rng.randint(0, 1 << 45, n).astype(np.int64)
        rc = (ac * rng.rand(n) * 1.05).astype(np.int64)
        rm = (am * rng.rand(n) * 1.05).astype(np.int64)
        ac[::40] = 0
        rc[::13] = ac[::13]

        def least(r, c):
            return (c - r) * 10 // c if c > 0 and r <= c else 0

        def most(r, c):
            return r * 10 // c if c > 0 and r <= c else 0

        def rtcr(r, c):
            p = 100 if c == 0 or r > c else 100 - (c - r) * 100 // c
            return 10 - (10 * p) // 100

        for key, fn in (("least_requested", least), ("most_requested", most),
                        ("rtcr", rtcr)):
            w = {**{k: 0 for k in K.DEFAULT_WEIGHTS}, key: 1}
            got = jax.jit(lambda a, b, c, d, w=w: K._local_total(
                w, a, b, c, d))(rc, rm, ac, am)
            want = [(fn(a, c) + fn(b, d)) // 2 for a, b, c, d in zip(
                rc.tolist(), rm.tolist(), ac.tolist(), am.tolist())]
            same(got, np.asarray(want))


class TestScoreFormulas:
    """The kernels' three float64 score expressions vs the oracle's Python
    float formulas, on inputs chosen to sit on and next to score
    boundaries."""

    BAL = {**{k: 0 for k in K.DEFAULT_WEIGHTS}, "balanced": 1}

    @staticmethod
    def py_balanced(rc, rm, ac, am):
        cf = 1.0 if ac == 0 else rc / ac
        mf = 1.0 if am == 0 else rm / am
        if cf >= 1 or mf >= 1:
            return 0
        return int((1 - abs(cf - mf)) * 10.0)

    def _check_balanced(self, rc, rm, ac, am):
        got = jax.jit(lambda a, b, c, d: K._local_total(self.BAL, a, b, c,
                                                        d))(rc, rm, ac, am)
        want = [self.py_balanced(*t) for t in zip(
            rc.tolist(), rm.tolist(), ac.tolist(), am.tolist())]
        same(got, np.asarray(want))

    def test_balanced_headline_node_grid(self):
        """Every (cpu, memory) fill of the 4-CPU / 32-Gi node in steps of
        one bench pod, plus an empty memory fraction. This grid holds the
        points the TPU's emulated f64 got wrong (3200m and 3600m CPU against
        zero memory read 2 and 1 where IEEE truncates to 1 and 0)."""
        mi = 1 << 20
        rc = np.repeat(np.arange(0, 4100, 100), 66).astype(np.int64)
        rm = np.tile(np.arange(0, 66) * 500 * mi, 41).astype(np.int64)
        ac = np.full_like(rc, 4000)
        am = np.full_like(rc, 32 << 30)
        self._check_balanced(rc, rm, ac, am)
        i = int(np.flatnonzero((rc == 3200) & (rm == 0))[0])
        assert self.py_balanced(3200, 0, 4000, 32 << 30) == 1
        assert int(np.asarray(K._local_total(
            self.BAL, rc[i:i + 1], rm[i:i + 1], ac[i:i + 1],
            am[i:i + 1]))[0]) == 1

    def test_balanced_random_and_degenerate(self):
        rng = np.random.RandomState(4)
        ac = rng.randint(0, 64000, N).astype(np.int64)
        am = rng.randint(0, 1 << 45, N).astype(np.int64)
        rc = (ac * rng.rand(N) * 1.05).astype(np.int64)
        rm = (am * rng.rand(N) * 1.05).astype(np.int64)
        ac[::50] = 0                          # zero capacity reads as full
        am[::77] = 0
        rc[::31] = ac[::31]                   # exactly full
        self._check_balanced(rc, rm, ac, am)

    def test_ratio_score_and_zone_blend(self):
        """SelectorSpread / InterPodAffinity: int(10 * (num / den)) and the
        2/3 zone blend, over every (num, den) pair of small counts — the
        exact-integer cases (10 * 3 / 6) are where a last-digit error
        flips the truncation."""
        den = np.repeat(np.arange(1, 121), 121).astype(np.int64)
        num = np.tile(np.arange(0, 121), 120).astype(np.int64)
        keep = num <= den
        num, den = num[keep], den[keep]
        got = jax.jit(lambda a, b: X.ftrunc(K._ratio_score(a, b)))(num, den)
        want = [int(10.0 * (a / b)) for a, b in zip(num.tolist(),
                                                    den.tolist())]
        same(got, np.asarray(want))

        zw = K.ZONE_WEIGHTING

        def blend(a, b, c, d):
            f = K._ratio_score(a, b)
            zs = K._ratio_score(c, d)
            return X.ftrunc(X.fadd(X.fmul(f, K._F_NODE_W),
                                   X.fmul(K._F_ZONE_W, zs)))

        rng = np.random.RandomState(5)
        j = rng.permutation(num.size)
        got = jax.jit(blend)(num, den, num[j], den[j])
        want = [int((10.0 * (a / b)) * (1.0 - zw) + zw * (10.0 * (c / d)))
                for a, b, c, d in zip(num.tolist(), den.tolist(),
                                      num[j].tolist(), den[j].tolist())]
        same(got, np.asarray(want))


class TestStartOrderKey:
    def test_keys_order_and_tie_like_the_floats(self):
        rng = np.random.RandomState(6)
        t = np.concatenate([
            1.7e9 + rng.rand(2000) * 1e6,       # full 53-bit mantissas
            [np.inf, 0.0, -0.0, -1.5, 1e-300, 1.7e9, 1.7e9],
            np.nextafter(1.7e9, np.inf, dtype=np.float64)[None]])
        k = K.start_order_key(t)
        assert k.dtype == np.int64
        i, j = rng.randint(0, t.size, 20000), rng.randint(0, t.size, 20000)
        assert np.array_equal(k[i] < k[j], t[i] < t[j])
        assert np.array_equal(k[i] == k[j], t[i] == t[j])
        assert K.start_order_key(np.inf) == K.START_KEY_INF
        assert k.max() == K.START_KEY_INF
