"""The gather-free reorder-bitmap shifts of ``core/reliability.py`` equal
the traced-offset slices they replace (``jnp.roll``, ``dynamic_slice``,
``dynamic_update_slice``), bit for bit, on random maps under ``vmap``."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reliability as rel

W = rel.REORDER_WINDOW
NBITS = 64  # sack_bitmap_bits
ROWS = 64


def _roll_shift_left(bitmap, shift):
    n = bitmap.shape[0]
    return jnp.roll(bitmap, -shift) & (jnp.arange(n) < (n - shift))


def _slice_sack_segment(bitmap, off):
    padded = jnp.concatenate([bitmap, jnp.zeros((NBITS,), bool)])
    return jax.lax.dynamic_slice(padded, (off,), (NBITS,))


def _update_place_segment(bits, off):
    placed = jnp.zeros((W + NBITS,), bool)
    return jax.lax.dynamic_update_slice(placed, bits,
                                        (jnp.clip(off, 0, W),))[:W]


# site: (new form, oracle, map width, range of the random amounts).  The
# receiver's SACK offset is never negative (``dynamic_slice`` would wrap
# one); the sender's may be, for a stale segment.
SITES = {
    "shift_left": (rel._shift_left, _roll_shift_left, W, (0, W)),
    "sack_segment": (lambda b, o: rel._sack_segment(b, o, NBITS),
                     _slice_sack_segment, W, (0, 2 * W)),
    "place_segment": (lambda b, o: rel._place_segment(b, o, W),
                      _update_place_segment, NBITS, (-W, 2 * W)),
}
SHIFTS = [0, 1, 31, 32, 63, 64, 511, 512, "random"]
OFFSETS = [0, 1, 37, 64, 128, 447, 448, 449, 511, 512, 513, 576, 1000,
           "random"]
STALE = [-600, -64, -1]


@pytest.mark.parametrize("site,amount", (
    [("shift_left", s) for s in SHIFTS]
    + [("sack_segment", o) for o in OFFSETS]
    + [("place_segment", o) for o in STALE + OFFSETS]))
def test_gather_free_bitmap_matches_traced_slice(site, amount):
    new, oracle, width, (lo, hi) = SITES[site]
    rng = np.random.default_rng(zlib.crc32(f"{site}:{amount}".encode()))
    maps = jnp.asarray(rng.random((ROWS, width)) < rng.random((ROWS, 1)))
    if amount == "random":
        amounts = rng.integers(lo, hi + 1, ROWS)
    else:
        amounts = np.full(ROWS, amount)
    amounts = jnp.asarray(amounts, jnp.int32)
    got = jax.jit(jax.vmap(new))(maps, amounts)
    want = jax.jit(jax.vmap(oracle))(maps, amounts)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
