"""Fold ``max_i32``: the int32 max, whose identity is the least int32.
Its narrower type, for the precision control, is int16: each running max
wraps at 16 bits."""

from chipbench.folds.sum_i32 import wrap_bits

IDENTITY = -(1 << 31)


def combine(acc, x):
    return max(acc, int(x))


def wrap(v):
    return wrap_bits(v, 32)


def narrow(v):
    return wrap_bits(v, 16)
