"""Fold ``sum_i32``: the int32 sum, which wraps past 2**31 - 1 as the
stated int32 does.  Its narrower type, for the precision control, is
int16."""

IDENTITY = 0


def wrap_bits(v: int, bits: int) -> int:
    """``v`` wrapped to a signed integer of ``bits`` bits."""
    half = 1 << (bits - 1)
    return (v + half) % (1 << bits) - half


def combine(acc, x):
    return acc + int(x)


def wrap(v):
    return wrap_bits(v, 32)


def narrow(v):
    return wrap_bits(v, 16)
