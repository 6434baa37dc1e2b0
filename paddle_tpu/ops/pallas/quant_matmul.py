"""Pallas TPU fused dequant-matmul (reference: PHI
``fusion/gpu/weight_only_linear_kernel.cu`` — reimagined for TPU).

Weight-only-quantized decode is HBM-bound: the win is that weights cross
HBM at 1/2 (int8) or 1/4 (int4) the bytes. The XLA path *hopes* the
`dequant -> matmul` chain fuses; this kernel guarantees it: int8/int4
blocks DMA into VMEM, dequantize against their per-(128-row, column)
scales in-register, and feed the MXU — the full-precision weight never
exists outside VMEM.

- grid (out_blocks, in_blocks); in innermost so the fp32 accumulator
  scratch carries partial sums across the contraction.
- activations [m, din] with m padded to the 8-sublane minimum (decode m
  is the batch size).
- int4: two nibbles per int8 byte along the input dim, sign-extended with
  arithmetic shifts in-kernel.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QUANT_BLOCK = 128  # rows per scale group (quantize_blockwise block_size)


from . import interpret_enabled as _interpret


def _pick(total: int, preferred: int, unit: int) -> int:
    b = min(preferred, total)
    b -= b % unit
    while b > unit and total % b:
        b -= unit
    return b if b and total % b == 0 else 0


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc, *, bits, bk, bn, nin):
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    w = w_ref[...].astype(jnp.int32)
    if bits == 4:
        lo = (w << 28) >> 28                       # sign-extend low nibble
        hi = w >> 4                                # arithmetic: signed high
        w = jnp.stack([lo, hi], axis=1).reshape(bk, bn)
    scales = s_ref[0, :bk // QUANT_BLOCK, :]       # drop the 8-sublane pad
    wf = w.astype(jnp.float32).reshape(bk // QUANT_BLOCK, QUANT_BLOCK, bn)
    wf = (wf * scales.astype(jnp.float32)[:, None, :]).reshape(bk, bn)
    acc[:] += lax.dot_general(
        x_ref[...].astype(jnp.float32), wf, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ni == nin - 1)
    def _finalize():
        o_ref[...] = acc[:].astype(o_ref.dtype)


def quant_matmul_pallas(x, qweight, scales, bits: int = 8,
                        block_out: int = 512, block_in: int = 512):
    """x [m, din] @ dequant(qweight, scales) -> [m, dout].

    qweight: int8 [din, dout] (bits=8) or [din/2, dout] (bits=4, packed);
    scales: [din/QUANT_BLOCK, dout]."""
    m, din = x.shape
    dout = qweight.shape[1]
    bk = _pick(din, block_in, QUANT_BLOCK)
    bn = _pick(dout, block_out, 128)
    assert bk and bn, (din, dout)
    nin, nout = din // bk, dout // bn

    mp = max(8, m + (-m) % 8)
    if mp != m:
        x = jnp.pad(x, ((0, mp - m), (0, 0)))

    if bits == 4:
        w_spec = pl.BlockSpec((bk // 2, bn), lambda no, ni: (ni, no))
    else:
        w_spec = pl.BlockSpec((bk, bn), lambda no, ni: (ni, no))

    # Mosaic tiling: a scales block of (bk/128, bn) rows-per-block (often 4)
    # violates the 8-sublane minimum. Regroup to [nin, rows_pad, dout] with
    # the per-block rows padded up to a multiple of 8; the kernel slices the
    # real rows back off. The pad touches only the tiny scales array.
    rows = bk // QUANT_BLOCK
    rows_pad = max(8, rows + (-rows) % 8)
    s3 = scales.reshape(nin, rows, dout)
    if rows_pad != rows:
        s3 = jnp.pad(s3, ((0, 0), (0, rows_pad - rows), (0, 0)))

    kernel = functools.partial(_qmm_kernel, bits=bits, bk=bk, bn=bn, nin=nin)
    out = pl.pallas_call(
        kernel,
        name=f"quant_matmul_int{bits}",
        grid=(nout, nin),
        in_specs=[
            pl.BlockSpec((mp, bk), lambda no, ni: (0, ni)),
            w_spec,
            pl.BlockSpec((1, rows_pad, bn), lambda no, ni: (ni, 0, no)),
        ],
        out_specs=pl.BlockSpec((mp, bn), lambda no, ni: (0, no)),
        scratch_shapes=[pltpu.VMEM((mp, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((mp, dout), x.dtype),
        interpret=_interpret(),
    )(x, qweight, s3)
    return out[:m]


def use_quant_matmul(x2d, qweight, block_size: int) -> bool:
    """The fused kernel targets decode-sized activations (small m) where
    the weight stream dominates; big-m training matmuls go to XLA."""
    m, din = x2d.shape
    dout = qweight.shape[1]
    return (block_size == QUANT_BLOCK and m <= 64
            and _pick(din, 512, QUANT_BLOCK) > 0
            and _pick(dout, 512, 128) > 0)
