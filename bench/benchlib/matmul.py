"""Matrix products of the plain references at a stated precision, the same
on every backend.

  "highest"  fp32 products and sums (`Precision.HIGHEST`);
  "high"     the three-pass bf16 product a TPU runs for `Precision.HIGH`:
             each operand split into a bf16 head and a bf16 tail, and
             head x head + head x tail + tail x head summed in fp32. Written
             out here so that the control reads the same on the CPU, where
             XLA ignores the precision flag, as on the chip.

The split keeps the top 16 bits of each fp32 word (sign, exponent and 7
mantissa bits: a bf16 number) by masking them. A round trip through
`astype(bfloat16)` would not do: XLA may drop a convert pair where it
allows excess precision, and on a TPU that left the tail 0 in some uses and
the control reading like one bf16 pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")
_EXACT = jax.lax.Precision.HIGHEST


def _bf16_head(x):
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    head = _bf16_head(x)
    return head, _bf16_head(x - head)


def einsum(spec: str, a, b, precision: str = "highest"):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=_EXACT)
    if precision != "high":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    (ah, al), (bh, bl) = _split(a), _split(b)
    return (jnp.einsum(spec, ah, bh, precision=_EXACT)
            + jnp.einsum(spec, ah, bl, precision=_EXACT)
            + jnp.einsum(spec, al, bh, precision=_EXACT))


def dot(a, b, precision: str = "highest"):
    return einsum("ij,jk->ik", a, b, precision)
