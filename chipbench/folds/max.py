"""Fold ``max``: the float32 max, element by element over a row of any
width (a scalar is a row of one).  Its identity is the least finite
float32, the identity the service's float32 max states.  Its narrower
type, for the precision control, is float16: each running max is rounded
to it."""

import numpy as np

IDENTITY = np.finfo(np.float32).min


def combine(acc, x):
    return np.maximum(acc, np.asarray(x, np.float32))


def wrap(v):
    return np.asarray(v, np.float32)


def narrow(v):
    return np.asarray(v, np.float16)
