"""Per-channel quantizers (int8 + packed int4) and the device dequant kernel.

The QuantizedStore backend writes swap units as quantized values + one fp32
scale per output channel (~4x fewer stored bytes than fp32 at int8, ~8x at
int4). Swap-in then transfers only the quantized payload host->device and
reconstructs the fp parameters THERE — the dequant multiply rides the H2D
DMA the swap-in pays anyway, so the host-side critical path does no extra
work per byte saved. (The fused path, kernels/swap_linear_q.py, goes one
step further and never reconstructs fp at all.)

Layout: values are [R, C] int8 where C is the channel (last) axis of the
original tensor and R the flattened rest; ``scales`` is [C] fp32. Output is
``out[r, c] = values[r, c] * scales[c]`` cast to the target dtype — a pure
VPU elementwise kernel, gridded over (row, channel) tiles so one tile of the
unit streams through VMEM while the next transfers (same double-buffered
shape as swap_linear's weight stream).

int4 carrier layout (``pack_int4`` / ``unpack_int4``, bit-exact contract
asserted in tests): two 4-bit two's-complement values share one int8 carrier
byte — row pair (2r, 2r+1) of the logical [R, C] value grid maps to carrier
row r with the EVEN row in the low nibble and the ODD row in the high
nibble. Odd R pads one zero row. Packing along rows (not channels) keeps the
per-channel scales axis intact and lets a (bk/2, bn) carrier tile of the
fused matmul unpack independently of its neighbours.

Error bounds (documented contract, asserted in tests): quantization is
symmetric round-to-nearest, so round-tripping a tensor x reproduces it
within ``|x̂ - x| <= scale_c / 2`` elementwise — ``max|x[:, c]| / 254`` per
channel at int8 (127 steps), ``max|x[:, c]| / 14`` at int4 (7 steps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# int8 VMEM tiling is (32, 128): row blocks stay a multiple of 32 and channel
# blocks a multiple of 128. A (256, 2048) tile double-buffers in ~5 MB of
# scoped VMEM with an fp32 output, well inside the 16 MB limit at any width.
_BLOCK_R = 256
_BLOCK_C = 2048


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def dequant_int8(values: jax.Array, scales: jax.Array,
                 out_dtype=jnp.float32, *,
                 interpret: bool = False) -> jax.Array:
    """values [R, C] int8, scales [C] fp32 -> [R, C] out_dtype.

    Gridded over (row, channel) tiles; ragged edge tiles are read padded
    and written masked, so no padded copy of the unit is ever made."""
    R, C = values.shape
    assert scales.shape == (C,), (values.shape, scales.shape)
    br, bc = min(_BLOCK_R, R), min(_BLOCK_C, C)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(R, br), pl.cdiv(C, bc)),
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),      # quantized tile
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),       # channel scales
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        interpret=interpret,
    )(values, scales.reshape(1, C))


def _channel_grid(arr: np.ndarray) -> np.ndarray:
    x = np.asarray(arr, np.float32)
    return x.reshape(-1, x.shape[-1]) if x.ndim >= 2 else x.reshape(1, -1)


def quantize_int8(arr: np.ndarray):
    """Build-time host quantizer: symmetric per-channel int8.

    Channels are the LAST axis (output features of (in, out) matmuls and of
    HWIO convs); the rest flattens to rows. Returns (values int8 [R, C],
    scales fp32 [C]). Zero channels get scale 1.0 so dequant is exact there.
    """
    x2 = _channel_grid(arr)
    amax = np.max(np.abs(x2), axis=0)
    scales = np.where(amax > 0.0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(x2 / scales[None, :]), -127, 127).astype(np.int8)
    return q, scales


def quantize_int4(arr: np.ndarray):
    """Build-time host quantizer: symmetric per-channel int4, packed.

    Same channel convention as :func:`quantize_int8` but 7 steps per side,
    and the values come back packed two-per-byte (see module docstring for
    the carrier layout). Returns (carrier int8 [ceil(R/2), C], scales fp32
    [C]). Round-trip error bound: ``max|x[:, c]| / 14`` per channel.
    """
    x2 = _channel_grid(arr)
    amax = np.max(np.abs(x2), axis=0)
    scales = np.where(amax > 0.0, amax / 7.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(x2 / scales[None, :]), -7, 7).astype(np.int8)
    return pack_int4(q), scales


def pack_int4(q: np.ndarray) -> np.ndarray:
    """[R, C] int4-valued int8 -> [ceil(R/2), C] int8 carrier (two's
    complement nibbles: even row -> low, odd row -> high; odd R pads 0)."""
    R, C = q.shape
    if R % 2:
        q = np.concatenate([q, np.zeros((1, C), np.int8)], axis=0)
    u = q.view(np.uint8) & 0xF
    return ((u[1::2] << 4) | u[0::2]).view(np.int8)


def unpack_int4(carrier: np.ndarray, rows: int) -> np.ndarray:
    """Host-side inverse of :func:`pack_int4`: [Rp, C] carrier -> [rows, C]
    sign-extended int8 values (the zero pad row, if any, is sliced off).

    This runs on the swap-in loader thread for every lazily-dequantized
    leaf (see quantized_store), so it is written to touch the carrier a
    minimal number of times: arithmetic right-shift sign-extends the high
    nibble directly, and ``(u << 4) >> 4`` sign-extends the low one — two
    strided writes into the output instead of mask/compare temporaries.
    """
    s = carrier.view(np.int8)
    out = np.empty((2 * s.shape[0], s.shape[1]), np.int8)
    np.right_shift(s, 4, out=out[1::2])                     # high nibble
    low = (carrier.view(np.uint8) << 4).view(np.int8)
    np.right_shift(low, 4, out=out[0::2])                   # low nibble
    return out[:rows]
