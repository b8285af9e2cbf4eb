"""Matrix products at a stated precision, the same on every backend.

``highest`` is float32 throughout (six bf16 passes on a TPU). The lower
ones are written out, so that a control reads the same on the CPU as on
the chip: ``high`` is the three-pass product of bf16 halves (a_hi b_hi +
a_hi b_lo + a_lo b_hi), ``bfloat16`` one pass of operands rounded to bf16;
both accumulate in float32.
"""

from __future__ import annotations


def einsum_at(spec: str, a, b, precision: str = "highest"):
    import jax.numpy as jnp

    f32, bf16 = jnp.float32, jnp.bfloat16
    if precision == "highest":
        return jnp.einsum(spec, a.astype(f32), b.astype(f32),
                          precision="highest")

    def one(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=f32)

    a_hi, b_hi = a.astype(bf16), b.astype(bf16)
    if precision == "bfloat16":
        return one(a_hi, b_hi)
    if precision == "high":
        a_lo = (a.astype(f32) - a_hi.astype(f32)).astype(bf16)
        b_lo = (b.astype(f32) - b_hi.astype(f32)).astype(bf16)
        return one(a_hi, b_hi) + one(a_hi, b_lo) + one(a_lo, b_hi)
    raise ValueError(f"no such precision: {precision!r}")


def matmul_at(a, b, precision: str = "highest"):
    return einsum_at("ij,jk->ik", a, b, precision)
