"""Deterministic synthetic data generators (offline container — DESIGN.md §6).

All generators are *stateless functions of (seed, step)*: a restarted job
re-produces the exact batch stream, which is what makes checkpoint/restart
bit-exact (training/fault.py) and elastic re-sharding trivial (any host can
compute any batch slice).

Tasks are constructed so that learning is measurable within a few hundred
steps (the convergence benchmarks need a real signal to separate FP8's
divergence from S2FP8's convergence, reproducing the paper's mechanism):

  * lm_batch: order-k Markov token stream — a transformer must learn the
    transition table; cross-entropy has a known floor (the chain's entropy).
  * seq2seq_batch: reversal task (copy task family the tiny-Transformer
    literature uses).
  * ncf_batch: low-rank user x item preference matrix with logistic noise.
  * cifar_batch: class-conditional Gaussian blobs at CIFAR shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int, step: int, salt: int = 0):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), step), salt)


_MARKOV_FLOOR = -4.0         # logit of every non-successor token


def make_markov_table(seed: int, vocab: int, branching: int = 4):
    """Each token has `branching` likely successors.

    Returns the chain's sparse form ``(succ [V, branching] int32, logit
    [V, branching] f32)``: row ``v`` of the dense ``[V, V]`` logit table is
    ``_MARKOV_FLOOR`` except ``logit[v]`` at columns ``succ[v]``.  The dense
    table would be V^2 floats (275 GB at a 262144 vocab); :func:`lm_batch`
    rebuilds only the rows it samples from.
    """
    rng = np.random.default_rng(seed)
    succ = np.empty((vocab, branching), np.int32)
    logit = np.empty((vocab, branching), np.float32)
    for v in range(vocab):
        succ[v] = rng.choice(vocab, size=branching, replace=False)
        logit[v] = rng.normal(2.0, 0.5, branching)
    return jnp.asarray(succ), jnp.asarray(logit)


@functools.partial(jax.jit, static_argnames=("batch", "seq", "vocab"))
def _markov_stream(key, succ, logit, *, batch: int, seq: int, vocab: int):
    k0, ks = jax.random.split(key)
    first = jax.random.randint(k0, (batch,), 0, vocab)
    rows = jnp.arange(batch)[:, None]

    def gen(tok, kt):
        dense = jnp.full((batch, vocab), _MARKOV_FLOOR, jnp.float32)
        dense = dense.at[rows, succ[tok]].set(logit[tok])
        nxt = jax.random.categorical(kt, dense, axis=-1)
        return nxt, nxt

    keys = jax.random.split(ks, seq)
    _, toks = jax.lax.scan(gen, first, keys)
    toks = jnp.moveaxis(toks, 0, 1)                    # [B, S]
    tokens = jnp.concatenate([first[:, None], toks[:, :-1]], axis=1)
    return {"tokens": tokens.astype(jnp.int32), "labels": toks.astype(jnp.int32)}


def lm_batch(seed: int, step: int, batch: int, seq: int, vocab: int,
             table=None):
    """Markov stream: tokens[t+1] ~ softmax(row tokens[t] of the chain)."""
    if table is None:
        table = make_markov_table(seed, vocab)
    succ, logit = table
    return _markov_stream(_key(seed, step), succ, logit, batch=batch,
                          seq=seq, vocab=vocab)


def seq2seq_batch(seed: int, step: int, batch: int, src_len: int, tgt_len: int,
                  vocab: int):
    """Reversal: target = reversed source (shifted for teacher forcing)."""
    k = _key(seed, step)
    src = jax.random.randint(k, (batch, src_len), 2, vocab)
    rev = src[:, ::-1][:, :tgt_len]
    bos = jnp.ones((batch, 1), jnp.int32)
    dec_in = jnp.concatenate([bos, rev[:, :-1]], axis=1)
    return {"enc_tokens": src.astype(jnp.int32),
            "dec_tokens": dec_in.astype(jnp.int32),
            "dec_labels": rev.astype(jnp.int32)}


def ncf_batch(seed: int, step: int, batch: int, n_users: int, n_items: int,
              rank: int = 8):
    """Implicit feedback from a fixed low-rank preference matrix."""
    ku = jax.random.PRNGKey(seed)
    u_emb = jax.random.normal(jax.random.fold_in(ku, 1), (n_users, rank))
    i_emb = jax.random.normal(jax.random.fold_in(ku, 2), (n_items, rank))
    k = _key(seed, step)
    k1, k2, k3 = jax.random.split(k, 3)
    users = jax.random.randint(k1, (batch,), 0, n_users)
    items = jax.random.randint(k2, (batch,), 0, n_items)
    score = jnp.einsum("br,br->b", u_emb[users], i_emb[items]) / jnp.sqrt(rank)
    prob = jax.nn.sigmoid(2.0 * score)
    labels = (jax.random.uniform(k3, (batch,)) < prob).astype(jnp.int32)
    return {"users": users, "items": items, "labels": labels}


def cifar_batch(seed: int, step: int, batch: int, n_classes: int = 10):
    """Class-conditional Gaussian blobs at CIFAR-10 shapes."""
    kc = jax.random.PRNGKey(seed)
    centers = jax.random.normal(jax.random.fold_in(kc, 7),
                                (n_classes, 32, 32, 3)) * 0.8
    k = _key(seed, step)
    k1, k2 = jax.random.split(k)
    labels = jax.random.randint(k1, (batch,), 0, n_classes)
    noise = jax.random.normal(k2, (batch, 32, 32, 3)) * 0.6
    return {"images": centers[labels] + noise, "labels": labels}


class HostPrefetcher:
    """Overlaps next-batch generation with the current step (thread pool).

    On real multi-host pods each process generates only its addressable
    slice (stateless (seed, step, host_id) indexing makes that exact).
    """

    def __init__(self, gen_fn, n_prefetch: int = 2):
        import concurrent.futures as cf
        self._gen = gen_fn
        self._pool = cf.ThreadPoolExecutor(max_workers=1)
        self._pending = {}
        self._n = n_prefetch

    def get(self, step: int):
        for s in range(step, step + self._n):
            if s not in self._pending:
                self._pending[s] = self._pool.submit(self._gen, s)
        fut = self._pending.pop(step)
        return fut.result()
