"""The one input generator: the same seed gives the same inputs, seeds
beyond 32 bits stay distinct, and each value kind has its shape."""

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench.data import make_ring

ONE = SingleDeviceSharding(jax.devices()[0])
INTS = {"kind": "ints", "low": -1024, "high": 1024}
GRADS = {"kind": "blockscaled_normal", "block": 256, "exp_low": -3,
         "exp_high": 3}


def ring(values, seed, shape=(4, 1024), n=2):
    return [np.asarray(a) for a in make_ring(values, shape, ONE, n, seed)]


def test_same_seed_same_inputs_and_distinct_ring_entries():
    a, b = ring(INTS, 7), ring(INTS, 7)
    assert all((x == y).all() for x, y in zip(a, b))
    assert not (a[0] == a[1]).all()


def test_seeds_beyond_32_bits_are_distinct():
    big = 2**40 + 7
    assert not (ring(INTS, big)[0] == ring(INTS, big % 2**32)[0]).all()


def test_ints_are_integer_valued_in_range():
    x = ring(INTS, 3)[0]
    assert x.dtype == np.float32 and (x == np.round(x)).all()
    assert x.min() >= -1024 and x.max() <= 1024


def test_blockscaled_normal_varies_magnitude_by_block():
    x = ring(GRADS, 3)[0].reshape(4, -1, 256)
    amax = np.abs(x).max(-1)
    assert amax.max() / amax.min() > 100


def test_unknown_kind_and_ragged_blocks_are_refused():
    with pytest.raises(ValueError):
        ring({"kind": "zipf"}, 1)
    with pytest.raises(ValueError):
        ring(GRADS, 1, shape=(4, 1000))
