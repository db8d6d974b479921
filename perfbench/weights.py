"""Seeded weights made by the benchmark, on the device, in one jitted call.

`hashed_int8(cfg, mesh, dtype, seed)` returns a parameter tree of the
package's own structure (the shapes of `quantize_params(init_params(...))`,
found with `jax.eval_shape`, and the package's own sharding rules) whose
int8 weights are a hash of (seed, leaf, element index), uniform in
[-127, 127], with one scale per tensor chosen so that the dequantized
weight has the standard deviation the package's init gives it
(fan_in ** -0.5). Every leaf is born in the dtype and the sharding it is
served in; nothing is drawn on the host or in a wider type.

Why it exists: the package's seeded init draws one whole layer in bf16 on
the mesh's first device and quantizes it there op by op. For Mixtral-8x7B
over four chips that is 2.6 GiB of bf16 experts plus 1.75 GiB float32
temporaries on top of the device's 10.9 GiB share of the int8 tree, and it
ends in RESOURCE_EXHAUSTED on a 16 GB chip (my chip run, PR 24).

The values are not the package quantizer's (a normal draw rounded to its
per-channel absmax); they are int8 weights of the same shapes, scales and
spread. Speed does not depend on them, and the plain reference reads the
same tree.

A configuration's adapter (contract: docstring of perfbench/run.py) calls
`hashed_int8(..., ones=NORMS + (<its own norm gains' leaf names>,))`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

NORMS = ("ln1", "ln2", "post_ln1", "post_ln2", "final_norm")
UNIFORM_INT8_STD = 73.3          # std of a uniform integer in [-127, 127]


def _mix(h):
    """lowbias32: a 32-bit integer hash (elementwise, fuses)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def _hash_field(shape, salt: int):
    """uint32 pseudo-random field: a hash chain over the element's index
    along every axis, so no two layers or experts repeat."""
    h = jnp.full(shape, salt & 0xFFFFFFFF, jnp.uint32)
    for axis in range(len(shape)):
        index = lax.broadcasted_iota(jnp.uint32, shape, axis)
        h = _mix(h ^ (index + jnp.uint32((0x9E3779B9 * (axis + 1)) & 0xFFFFFFFF)))
    return h


def _int8(shape, salt: int):
    value = (_hash_field(shape, salt) >> 24).astype(jnp.int32) - 128
    return jnp.clip(value, -127, 127).astype(jnp.int8)


def _uniform(shape, dtype, salt: int, std: float):
    unit = (_hash_field(shape, salt) >> 8).astype(jnp.float32) * (2.0 ** -24)
    return ((2.0 * unit - 1.0) * (3.0 ** 0.5) * std).astype(dtype)


def filled(shapes, seed: int, ones=NORMS):
    """The tree of `shapes` (ShapeDtypeStructs, and QuantizedTensors of
    them) filled from the seed: an int8 leaf as the module text says; a
    leaf whose name is in `ones` (a norm's gain) with ones; any other
    leaf uniform with the spread fan_in ** -0.5, where fan_in is the
    last axis but one, or the only axis of a one-dimensional leaf (a
    bias)."""
    from polykey_tpu.models.quant import QuantizedTensor

    is_q = lambda x: isinstance(x, QuantizedTensor)  # noqa: E731
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_q)
    out = []
    for number, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        salt = seed * 1000003 + number * 7919
        if is_q(leaf):
            q = leaf.q
            fan_in = q.shape[-1] if name == "embed" else q.shape[-2]
            scale = fan_in ** -0.5 / UNIFORM_INT8_STD
            out.append(leaf.replace(
                q=_int8(q.shape, salt),
                s=jnp.full(leaf.s.shape, scale, leaf.s.dtype)))
        elif name in ones:
            out.append(jnp.ones(leaf.shape, leaf.dtype))
        else:
            fan_in = leaf.shape[-2] if leaf.ndim > 1 else leaf.shape[0]
            out.append(_uniform(leaf.shape, leaf.dtype, salt, fan_in ** -0.5))
    return jax.tree_util.tree_unflatten(treedef, out)


def fill_function(cfg, mesh, dtype, seed: int, ones=NORMS):
    """The jitted, argument-less function that makes the whole tree."""
    from polykey_tpu.models.quant import quantize_params
    from polykey_tpu.models.transformer import init_params
    from polykey_tpu.parallel.sharding import param_shardings

    shapes = jax.eval_shape(
        lambda: quantize_params(
            init_params(jax.random.PRNGKey(0), cfg, dtype), cfg)
    )

    def fill():
        return filled(shapes, seed, ones)

    return jax.jit(fill, out_shardings=param_shardings(cfg, mesh, shapes))


def hashed_int8(cfg, mesh, dtype, seed: int, ones=NORMS) -> dict:
    return fill_function(cfg, mesh, dtype, seed, ones)()
