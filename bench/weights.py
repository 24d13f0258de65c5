"""Seeded weights of a merged zoo, made on the device in one jitted call.

A zoo is one shared trunk (embedding and every layer) and one private head
(final norm and unembedding) per fine-tuned variant.  The family's module
(``bench/families/<family>.py``) makes each, in the parameter layout the
program's adapter reads; :func:`check_layout` holds them to the adapter's
own ``eval_params`` before anything runs.  The generator is the
benchmark's, not the program's, so the plain references can rebuild the
same weights from the seed without taking anything the program made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import model


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, including ones wider than 32
    bits: the low word seeds the key and the high word is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def normal(key, shape, std, dtype):
    """Normal draws of standard deviation ``std``, made in float32 and cast
    to ``dtype``: the one distribution every family's generator uses."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0,))
def _zoo(m: model.Model, key):
    fam = model.family(m.family)
    k_trunk, k_heads = jax.random.split(key)
    heads = [fam.head(m, jax.random.fold_in(k_heads, v))
             for v in range(m.variants)]
    return fam.trunk(m, k_trunk), heads


def zoo(m: model.Model, seed: int) -> tuple:
    """(trunk, [head per variant]) for ``seed``, built on the default device
    in one compiled call, in the dtypes they are served in."""
    return _zoo(m, seed_key(seed))


def variant_params(trunk: dict, head: dict) -> dict:
    """One variant's full parameter tree: the shared trunk and its head."""
    return {**trunk, **head}


def check_layout(params: dict, expected: dict) -> None:
    """Raise unless ``params`` has exactly the paths, shapes and dtypes of
    ``expected`` (the adapter's ``eval_params``)."""
    got = {jax.tree_util.keystr(p): (tuple(x.shape), jnp.dtype(x.dtype))
           for p, x in jax.tree_util.tree_leaves_with_path(params)}
    want = {jax.tree_util.keystr(p): (tuple(x.shape), jnp.dtype(x.dtype))
            for p, x in jax.tree_util.tree_leaves_with_path(expected)}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))[:6]
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter layout: {diff}")
