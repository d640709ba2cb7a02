"""rANS coder: exact round trips, near-entropy rates, beats Huffman on
skewed alphabets (the production coder for WaterSIC code streams)."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import empirical_entropy, huffman_bits
from repro.core.rans import RansCodec


def test_roundtrip_and_rate():
    rng = np.random.default_rng(0)
    z = (rng.standard_normal(8192) * 1.2).round().astype(np.int64)
    c = RansCodec.from_data(z)
    payload = c.encode(z)
    np.testing.assert_array_equal(c.decode(payload, z.size), z)
    bits = 8 * len(payload) / z.size
    h = empirical_entropy(z)
    assert h - 1e-6 <= bits <= h + 0.05  # within 0.05 b/sym of entropy


def test_beats_huffman_when_skewed():
    rng = np.random.default_rng(1)
    z = (rng.standard_normal(16384) * 0.5).round().astype(np.int64)
    c = RansCodec.from_data(z)
    rb = c.measure_bits_per_symbol(z)
    hb = huffman_bits(z.reshape(-1, 1))
    assert rb < hb - 0.05  # integer codeword lengths cost Huffman here


def test_single_symbol_degenerate():
    z = np.zeros(100, np.int64)
    c = RansCodec.from_data(z)
    payload = c.encode(z)
    np.testing.assert_array_equal(c.decode(payload, z.size), z)


def test_unknown_symbol_raises():
    c = RansCodec.from_data(np.array([0, 1, 2]))
    with pytest.raises(ValueError):
        c.encode(np.array([5]))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(1, 2000),
       scale=st.floats(0.1, 8.0))
def test_property_roundtrip(seed, n, scale):
    rng = np.random.default_rng(seed)
    z = (rng.standard_normal(n) * scale).round().astype(np.int64)
    c = RansCodec.from_data(z)
    np.testing.assert_array_equal(c.decode(c.encode(z), z.size), z)
