"""Operands made on the device from a seed: the paper's Eq. (6) inputs.

Eq. (6) of arXiv:2306.11975 draws ``(U - 1/2) * exp(phi * N)`` with U
uniform on [0, 1) and N standard normal; ``phi`` widens the exponent
spread. Everything here runs as one jitted program per operand set, on
the device, in the type the timed program takes:

* ``dw``  — a float64 value carried as an exact double-float32 pair
  ``(hi, lo)``: ``hi`` is the float32 Eq. (6) draw and ``lo`` a uniform
  draw on the multiples of ``ulp(hi) * 2^-25`` within half an ulp of
  ``hi``, so the low words are (all but never) zero and every slice of
  the split carries bits, and ``hi + lo`` spans at most 49 bits: exact
  in float64.
* ``f32`` — the float32 Eq. (6) draw alone.

The same seed gives the same words on every run; the host reference is
built from the words pulled back, never from a host-side draw.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LO_BITS = 25            # lo = j * ulp(hi) * 2^-25, |j| <= 2^24


def seed_words(seed: int):
    """The two 32-bit halves of a seed (seeds may exceed 32 bits)."""
    seed = int(seed) & (2 ** 64 - 1)
    return (jnp.asarray(seed & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray(seed >> 32, jnp.uint32))


def key_for(lo, hi, stream: int):
    """The PRNG key of operand stream ``stream`` of a seed's halves."""
    key = jax.random.fold_in(jax.random.key(lo), hi)
    return jax.random.fold_in(key, stream)


def _eq6(key, shape, phi: float):
    ku, kn = jax.random.split(key)
    u = jax.random.uniform(ku, shape, jnp.float32) - 0.5
    return u * jnp.exp(phi * jax.random.normal(kn, shape, jnp.float32))


def draw(key, shape, phi: float, kind: str):
    """One operand: ``(hi, lo)`` for ``dw``, a single array for ``f32``."""
    k1, k2 = jax.random.split(key)
    hi = _eq6(k1, shape, phi)
    if kind == "f32":
        return hi
    if kind != "dw":
        raise ValueError(f"unknown operand kind {kind!r}")
    j = jax.random.randint(k2, shape, -2 ** (LO_BITS - 1), 2 ** (LO_BITS - 1)
                           + 1, jnp.int32)
    _, e = jnp.frexp(hi)                   # ulp(hi) = 2^(e - 24)
    lo = jnp.ldexp(j.astype(jnp.float32), e - 24 - LO_BITS)
    return hi, jnp.where(hi == 0, 0.0, lo).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("shapes", "phi", "kind",
                                             "shardings"))
def _draw_all(lo, hi, *, shapes, phi, kind, shardings):
    out = []
    for i, shape in enumerate(shapes):
        x = draw(key_for(lo, hi, i), shape, phi, kind)
        if shardings is not None:
            x = jax.tree.map(
                lambda v, s=shardings[i]: jax.lax.with_sharding_constraint(
                    v, s), x)
        out.append(x)
    return tuple(out)


def make_operands(seed: int, shapes, phi: float, kind: str,
                  shardings=None):
    """Every operand of a cell in one jitted call, ready on the device.

    ``shapes`` is a sequence of shape tuples; ``shardings`` (optional)
    one ``jax.sharding.Sharding`` per operand. Operand ``i`` is drawn
    from stream ``i`` of ``seed``, so adding operands never changes the
    earlier ones."""
    shapes = tuple(tuple(int(d) for d in s) for s in shapes)
    out = _draw_all(*seed_words(seed), shapes=shapes, phi=float(phi),
                    kind=kind, shardings=None if shardings is None
                    else tuple(shardings))
    return jax.block_until_ready(out)
