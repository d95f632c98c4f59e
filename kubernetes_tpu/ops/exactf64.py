"""Correctly rounded float64 arithmetic carried out in int64 operations.

Three of the reference's priorities truncate a float64 expression to an
integer score (BalancedResourceAllocation, SelectorSpread, InterPodAffinity:
`int((1 - |a/b - c/d|) * 10)` and friends). The oracle computes them in IEEE
double precision, and "identical binding decisions" means the device must
produce the same integer even when the expression lands within one ulp of a
score boundary. The TPU has no native f64: XLA emulates it, and the emulation
is not IEEE (measured on a v5e: division, subtraction and multiplication each
disagree with IEEE in the last digits for most inputs, a stored double does
not keep its 53 bits, and BalancedResourceAllocation flips at 3200m/4000m CPU
against an empty memory fraction). A tolerance cannot repair a truncation, so
the kernels compute these expressions here instead: every operation below
returns exactly the value IEEE 754 round-to-nearest-even would, using only
integer adds, compares, shifts and multiplies, which every backend executes
exactly. There is no integer division either: each vector int64 `//` costs
the TPU compiler seconds (see `fdiv_int`), so `small_div` counts instead.

A non-negative double is a pair `(m, e)` of int64 arrays meaning `m * 2**e`,
normalised so that `2**52 <= m < 2**53`; zero is `(0, ZERO_E)` with an
exponent below every normal value's, so `(e, m)` orders lexicographically
like the values. Operands are non-negative and finite, integers entering
`fdiv_int` are below 2**53 (exactly representable, as the reference's
`float64(x)` conversions assume), and no result is subnormal: the kernels'
quotients are at least 2**-53 and everything else is built from them and
small constants.

The public operations are jitted so that their traces are shared by the many
kernels that inline them. `tests/test_exactf64.py` checks every operation
bit-for-bit against the host's IEEE doubles.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

ZERO_E = -4096           # exponent of the canonical zero, below any normal
_GUARD = 9               # guard bits carried through add/sub alignment
_I64 = jnp.int64


def _i(x):
    return jnp.asarray(x, _I64)


def _round(M, E, sticky, L):
    """The double nearest `(M + f) * 2**E`, ties to even, where `M > 0` has
    `L` significant bits and `0 <= f < 1` is nonzero exactly when `sticky`.
    A sticky fraction needs `L >= 54`, so that it lies below the rounding
    position; at most 10 bits are ever dropped and at most 53 restored, so
    no shift reaches the word width."""
    r = jnp.maximum(L - 53, 0)              # bits to drop
    keep = M >> r
    rem = M - (keep << r)
    half = (_i(1) << r) >> 1                # 0 when nothing is dropped
    up = (r > 0) & ((rem > half)
                    | ((rem == half) & (sticky | ((keep & 1) == 1))))
    m = keep + up
    carry = m == (1 << 53)                  # rounded up to the next binade
    l = jnp.maximum(53 - L, 0)              # short mantissa: exact, shift up
    return (jnp.where(carry, 1 << 52, m) << l), E + r + carry - l


def _zero_if(zero, x):
    return jnp.where(zero, 0, x[0]), jnp.where(zero, ZERO_E, x[1])


def _bitlen(x):
    """Number of significant bits of non-negative int64 `x` (0 for 0)."""
    return 64 - jax.lax.clz(x)


def constant(c: float):
    """The `(m, e)` pair of a non-negative Python float, as Python ints."""
    if c == 0.0:
        return 0, ZERO_E
    f, p = math.frexp(c)                    # c = f * 2**p, 0.5 <= f < 1
    return int(f * (1 << 53)), p - 53


@jax.jit
def fdiv_int(a, b):
    """fl(a / b) for integers `0 <= a <= b`, `0 < b < 2**53`: restoring long
    division of the normalised operands, one quotient bit per step; only a
    remainder below 2**54 is ever held. No integer division is used: the TPU
    compiler spends seconds on each vector int64 `//` (measured: 7 s alone,
    minutes for nine in one kernel)."""
    a, b = jnp.broadcast_arrays(_i(a), _i(b))
    la, lb = _bitlen(a), _bitlen(b)
    A = a << jnp.minimum(53 - la, 52)       # both in [2**52, 2**53), or a = 0
    B = b << (53 - lb)

    def step(_, carry):
        R, Q = carry
        bit = R >= B
        return (jnp.where(bit, R - B, R) << 1, (Q << 1) + bit)

    # Q = floor(A * 2**54 / B), in (2**53, 2**55); a remainder is sticky
    R, Q = jax.lax.fori_loop(0, 55, step, (A, jnp.zeros_like(A)))
    return _zero_if(a == 0, _round(Q, la - lb - 54, R != 0,
                                   54 + (Q >= (1 << 54))))


def small_div(num, den, qmax: int):
    """`num // den` for `num >= 0`, `den > 0` when the quotient is known to
    be at most `qmax` (a larger one saturates there): the count of
    multiples of `den` that fit. Exact, and free of integer division (see
    fdiv_int)."""
    k = jnp.arange(1, qmax + 1, dtype=_I64)
    return jnp.sum(_i(num)[..., None] >= k * _i(den)[..., None],
                   axis=-1, dtype=_I64)


def _align(my, ey, ex):
    """`my << _GUARD` shifted down to exponent `ex >= ey`; returns the
    shifted mantissa and whether nonzero bits fell off."""
    Y = my << _GUARD
    d = jnp.minimum(ex - ey, 63)            # Y < 2**62: 63 drops everything
    Ysh = Y >> d
    return Ysh, (Ysh << d) != Y


@jax.jit
def fsub(x, y):
    """fl(x - y) for `x >= y >= 0`."""
    (mx, ex), (my, ey) = x, y
    Ysh, lost = _align(my, ey, ex)
    # exact difference = (X - Ysh - 1) + (1 - fraction) when bits were lost
    M = (mx << _GUARD) - Ysh - lost
    return _zero_if(M == 0, _round(M, ex - _GUARD, lost, _bitlen(M)))


@jax.jit
def fadd(x, y):
    """fl(x + y) for `x, y >= 0`."""
    (mx, ex), (my, ey) = x, y
    swap = (ey > ex) | ((ey == ex) & (my > mx))
    mx, my = jnp.where(swap, my, mx), jnp.where(swap, mx, my)
    ex, ey = jnp.where(swap, ey, ex), jnp.where(swap, ex, ey)
    Ysh, lost = _align(my, ey, ex)
    M = (mx << _GUARD) + Ysh                # in [2**61, 2**63), or 0
    return _zero_if(M == 0, _round(M, ex - _GUARD, lost,
                                   62 + (M >= (1 << 62))))


@jax.jit
def fmul(x, y):
    """fl(x * y): the 106-bit product of the mantissas from 27-bit limbs,
    reduced to its top 63 bits plus a sticky flag."""
    (mx, ex), (my, ey) = x, y
    mx, my = _i(mx), _i(my)
    lo27 = (1 << 27) - 1
    a1, a0 = mx >> 27, mx & lo27
    b1, b0 = my >> 27, my & lo27
    hi = a1 * b1                            # * 2**54
    mid = a1 * b0 + a0 * b1                 # * 2**27
    # product = 2**43 * (hi * 2**11 + (mid >> 16)) + W
    W = ((mid & 0xFFFF) << 27) + a0 * b0
    T = (hi << 11) + (mid >> 16) + (W >> 43)   # in [2**61, 2**63), or 0
    return _zero_if(T == 0, _round(
        T, _i(ex) + _i(ey) + 43, (W & ((1 << 43) - 1)) != 0,
        62 + (T >= (1 << 62))))


@partial(jax.jit, static_argnames="k")
def fmul_small(x, k: int):
    """fl(x * k) for a small positive integer `k` (the product of the
    mantissa and `k` is an exact int64)."""
    assert 0 < k < (1 << _GUARD)
    m, e = x
    M = m * k
    return _zero_if(M == 0, _round(M, e, False, _bitlen(M)))


def ftrunc(x):
    """int64(x): truncation toward zero of a non-negative value below
    2**53 (no left shift of a full mantissa fits more)."""
    m, e = x
    return m >> jnp.clip(-e, 0, 63)


def select(pred, x, y):
    """Elementwise `where` over `(m, e)` pairs."""
    return (jnp.where(pred, _i(x[0]), _i(y[0])),
            jnp.where(pred, _i(x[1]), _i(y[1])))


def ge(x, y):
    """x >= y for non-negative pairs."""
    return (x[1] > y[1]) | ((x[1] == y[1]) & (x[0] >= y[0]))
