"""Seeded token traffic for training cells, made on the device.

A traffic file's ``tokens`` entry names the distribution:

    {"dist": "zipf", "exponent": 1.0}

Token ranks follow a Zipf law, p(rank r) ~ (r + 1)^-exponent, as word
frequencies in text do; a permutation of the vocabulary drawn from the seed
decides which id holds which rank, so frequent ids are spread over the
embedding table.  Every seed gives the same shapes; only the ids differ.
Rows are ``seq + 1`` long: the step reads ``tokens = row[:-1]`` and
``labels = row[1:]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# a seed may exceed what 32 bits hold: fold its low and high words in
_WORD = (1 << 32) - 1


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from a non-negative seed of up to 64 bits, and a stream
    number that keeps weights and data apart."""
    if seed < 0 or seed >> 64:
        raise ValueError(f"seed {seed} is not a 64-bit non-negative integer")
    key = jax.random.PRNGKey(stream)
    key = jax.random.fold_in(key, seed & _WORD)
    return jax.random.fold_in(key, (seed >> 32) & _WORD)


def make_batch_fn(seed: int, vocab: int, batch: int, seq: int, spec: dict,
                  sharding=None):
    """``fn(step) -> {"tokens", "labels"}``, [batch, seq] int32 each, made
    by one jitted program from the seed and the step number, and laid out
    as ``sharding`` says (the same ids however they are laid out)."""
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    exponent = float(spec["exponent"])
    key = seed_key(seed, stream=1)

    # the key is an argument, not a constant of the program, so that one
    # compiled program (and one persistent-cache entry) serves every seed
    @functools.partial(jax.jit, out_shardings=sharding)
    def fn(key, step):
        perm_key, step_key = jax.random.split(key)
        step_key = jax.random.fold_in(step_key, step)
        ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
        cdf = jnp.cumsum(ranks ** -exponent)
        u = jax.random.uniform(step_key, (batch, seq + 1)) * cdf[-1]
        rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1)
        rows = jax.random.permutation(perm_key, vocab)[rank].astype(jnp.int32)
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}

    return lambda step: fn(key, jnp.int32(step))
