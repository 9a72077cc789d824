"""Seeded bf16 weights for a configuration, made on the device.

Each tensor slice has its own key, ``fold_in(root, tensor, layer,
expert)``, where ``tensor`` is the tensor's index in its architecture
module's ``TENSORS`` (``bench/arch/<model_type>.py``), and its values use
integer arithmetic up to one multiply and one rounding to bf16. So the
plain reference regenerates any one layer or expert alone and gets bit
for bit the values the program was served. The draw is an Irwin-Hall sum
of four random bytes (bell-shaped, bounded at 3.45 standard deviations),
scaled like the usual fan-in init: ``1/sqrt(fan_in)`` for projections,
0.02 for the embedding and router. Norm scales lie in [0.875, 1.125].

The architecture module lays the slices out in the program's parameter
tree (``serving_tree``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_IH_STD = math.sqrt(4 * (256 ** 2 - 1) / 12)      # std of a sum of 4 bytes


def root_key(seed: int):
    """Key of a seed of up to 64 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _key(root, tensors, name, layer=0, expert=0):
    k = jax.random.fold_in(root, tensors.index(name))
    return jax.random.fold_in(jax.random.fold_in(k, layer), expert)


def draw(root, tensors, name: str, shape, scale: float, layer=0, expert=0):
    """One slice in bf16 of the tensor ``name`` of ``tensors`` (the
    architecture's ``TENSORS``). ``scale`` None: a norm scale."""
    bits = jax.random.bits(_key(root, tensors, name, layer, expert), shape,
                           jnp.uint32)
    if scale is None:
        b = (bits & 0xFF).astype(jnp.int32) - 128
        return (1.0 + b.astype(jnp.float32) / 1024.0).astype(jnp.bfloat16)
    s = sum(((bits >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4))
    return ((s - 510).astype(jnp.float32)
            * np.float32(scale / _IH_STD)).astype(jnp.bfloat16)


def serving_params(arch, conf: dict, seed: int, mesh=None):
    """All weights of ``arch.serving_tree`` in one jitted call, placed in
    the program's layout (``repro.sharding.param_specs``) on ``mesh``."""
    D = arch.dims(conf)
    root = root_key(seed)
    build = jax.jit(lambda r: arch.serving_tree(r, D))
    if mesh is None:
        return build(root)
    from repro.sharding import make_shardings, param_specs
    shapes = jax.eval_shape(build, root)
    sh = make_shardings(mesh, param_specs(shapes, mesh=mesh))
    return jax.jit(lambda r: arch.serving_tree(r, D), out_shardings=sh)(root)
