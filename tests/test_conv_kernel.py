"""The Mamba-2 mixer's convolution as one Pallas kernel (ops/conv_kernel.py),
run here in interpret mode against `sequence.causal_conv`, the plain form it
replaces in a served entry: the channels read where they lie in a wider
array, the positions before a block from the tile that ends where it starts
(zeros before position 0), the taps in that function's order and the bias
before the silu. Who takes it, and what the batcher stamps and counts, is in
test_falcon_h1.py and test_nemotron_h.py. Times come from the chip (PERF.md
section 6, PR 63); the compile for a v5e is in test_tpu_compile.py."""

import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tf_serving_tpu.models import sequence
from distributed_tf_serving_tpu.ops import conv_kernel

# name -> (rows, positions, the array's width, the channels' offset in it, the channels, taps, a bias,
#          the lanes and the positions of a block)
CASES = {
    # [z | x | B | C | dt] as `in_proj` leaves it, the channels x | B | C behind z
    "Nemotron-H's widths, 2 rows": (2, 16, 18560, 8192, 10240, 4, True, 1024, 16),
    "Falcon-H1's widths, 8 rows, three tiles a block": (8, 24, 9248, 4096, 5120, 4, True, 1024, 24),
    "a window 128 lanes into a wider array": (2, 32, 640, 128, 384, 4, True, 128, 32),
    "the same without the bias": (2, 32, 640, 128, 384, 4, False, 128, 32),
    "an array of the channels' own, blocks of 512 lanes": (2, 40, 1536, 0, 1536, 4, True, 512, 40),
    # 1,536 positions are three blocks of 512 at 1,024 lanes: the positions before a block cross its edge
    "three blocks of positions a row": (2, 1536, 2048, 1024, 1024, 4, True, 1024, 512),
    "two blocks of positions, no bias, 8 rows of 256 lanes": (8, 4096, 512, 256, 256, 4, False, 256, 2048),
    "two taps": (2, 64, 384, 128, 256, 2, True, 128, 64),
    "nine taps: a whole tile before": (2, 64, 384, 128, 256, 9, True, 128, 64),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_kernel_is_causal_conv_to_float32_rounding(name):
    """The same products added in the same order: what differs is whether a
    product and the addition behind it round once or twice."""
    n, length, width, offset, channels, taps, bias, lanes, positions = CASES[name]
    rng = np.random.default_rng(len(name))
    x = jnp.asarray(rng.standard_normal((n, length, width)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (channels, taps)), jnp.bfloat16)
    b = jnp.asarray(rng.uniform(-0.5, 0.5, (channels,)), jnp.bfloat16) if bias else None
    assert conv_kernel.whole_blocks(offset, channels, length, taps) == (lanes, positions, "")
    got = conv_kernel.causal_conv(x, w, b, offset=offset, channels=channels, interpret=True)
    want = sequence.causal_conv(x[..., offset:offset + channels], w, b)
    assert got.shape == (n, length, channels) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape,why", [
    ((2, 16, 640, 64, 384, 4), "the channels start half a lane tile in"),
    ((2, 16, 640, 128, 320, 4), "the channels are no whole lane tiles"),
    ((2, 20, 640, 128, 384, 4), "the positions are no whole sublane tiles"),
    ((2, 16, 640, 128, 384, 10), "the positions before a block are more than one tile"),
])
def test_shapes_that_are_no_whole_blocks_are_refused(shape, why):
    n, length, width, offset, channels, taps = shape
    assert conv_kernel.whole_blocks(offset, channels, length, taps)[2], why
    with pytest.raises(ValueError, match="no whole blocks"):
        conv_kernel.causal_conv(jnp.zeros((n, length, width)), jnp.zeros((channels, taps)), offset=offset,
                                channels=channels, interpret=True)


def test_a_block_is_whole_tiles_inside_its_bytes():
    assert conv_kernel.lanes_a_block(8192, 10240) == conv_kernel.lanes_a_block(4096, 5120) == 1024
    assert conv_kernel.lanes_a_block(2880, 2880) == 0  # olmo_hybrid's q and k: 22.5 lane tiles
    assert conv_kernel.positions_a_block(2048, 1024) == 512 and 4 * 512 * 1024 == conv_kernel.BLOCK_BYTES
    assert conv_kernel.positions_a_block(200, 128) == 200 and conv_kernel.positions_a_block(150, 128) == 0
