"""The masked-aggregation kernels' tile rule, in pure Python (no kernel
runs): the stack tile is the widest lane multiple dividing the padded D
whose (N, block_d) f32 tile stays within ``TILE_BYTES``, capped by an
explicit ``block_d``."""
import pytest

from repro.kernels.masked_agg import kernel as mk

D_125M = 162_417_408            # protocol-125m: 128 · 2 · 3 · 73 · 2897


@pytest.mark.parametrize("n,d,cap,block,steps", [
    (8, D_125M, None, 56_064, 2_897),        # 1,794,048 B a step
    (16, D_125M, None, 28_032, 5_794),       # twice the rows, half the width
    (8, 128 * 2_897, None, 128, 2_897),      # D / 128 prime
    (8, D_125M, 2_048, 768, 211_481),        # a cap is honoured: the old rule
    (8, 128 * 73 * 2, 128 * 73, 9_344, 2),   # a cap that picks an odd width
    (5, 384, None, 384, 1),                  # a tiny width: the whole row
    (256, 1 << 20, None, 2_048, 512),        # the most nodes at 2048 columns
])
def test_tile_rule(n, d, cap, block, steps):
    got = mk._fit_block(n, d, cap)
    assert (got, d // got) == (block, steps)
    assert got % mk.LANE == 0 and d % got == 0
    rows = -(-n // mk.SUBLANE) * mk.SUBLANE
    assert rows * got * 4 <= mk.TILE_BYTES
    assert cap is None or got <= cap
