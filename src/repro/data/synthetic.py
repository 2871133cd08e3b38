"""Synthetic token corpus with learnable structure (offline C4 stand-in).

A per-seed first-order Markov chain over the vocabulary: transition
logits = Zipf unigram bias + a sparse high-probability successor pattern.
Low-entropy enough that a tiny LM's perplexity drops fast, high-entropy
enough that pruning damage is measurable — which is all the paper's
experiments need (EXPERIMENTS.md validates *orderings*, not absolute C4
perplexities; see DESIGN.md §8).

Determinism contract: every batch is a pure function of (seed, stream,
step) via fold_in — restarting a crashed run re-generates the identical
token stream, so checkpoint-resume is bit-exact (tested).
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp

STREAM_TRAIN = 0
STREAM_CALIB = 1
STREAM_EVAL = 2


def zipf_logits(vocab: int, alpha: float = 1.2) -> jax.Array:
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    return -alpha * jnp.log(ranks)


# Vocabularies up to this many (V, V) entries (64 MiB of f32) keep the
# whole transition table; larger ones build each row when sampled.
TABLE_MAX_ENTRIES = 1 << 24


class MarkovCorpus:
    """First-order Markov token source with Zipf marginals.

    Transition logits out of token ``t`` are the Zipf bias, ``peak`` on
    ``t``'s three preferred successors, and Gaussian noise.  A small
    vocabulary keeps them as one (V, V) table.  At a published
    vocabulary (151,936) that table would be 92 GB, so each row is built
    when it is sampled, with noise keyed by ``t``: the same chain in
    distribution, but not the same draws as a table would give.
    """

    def __init__(self, vocab: int, seed: int = 0, alpha: float = 1.2,
                 peak: float = 8.0):
        self.vocab = vocab
        self.seed = seed
        self.peak = peak
        key = jax.random.key(seed)
        k1, self._noise_key = jax.random.split(key)
        self._base = zipf_logits(vocab, alpha)               # (V,)
        # each token gets a few strongly-preferred successors
        self._succ = jax.random.randint(k1, (vocab, 3), 0, vocab)
        self._table = None
        if vocab * vocab <= TABLE_MAX_ENTRIES:
            boost = jnp.zeros((vocab, vocab)).at[
                jnp.arange(vocab)[:, None], self._succ
            ].add(peak)
            noise = 0.5 * jax.random.normal(self._noise_key, (vocab, vocab))
            self._table = self._base[None, :] + boost + noise  # (V, V)

    def transition_logits(self, tok: jax.Array) -> jax.Array:
        """Logits of the next token after each of ``tok`` (B,) → (B, V)."""
        if self._table is not None:
            return self._table[tok]

        def row(t):
            boost = jnp.zeros((self.vocab,)).at[self._succ[t]].add(self.peak)
            noise = 0.5 * jax.random.normal(
                jax.random.fold_in(self._noise_key, t), (self.vocab,))
            return self._base + boost + noise
        return jax.vmap(row)(tok)

    @functools.partial(jax.jit, static_argnames=("self", "batch", "length"))
    def sample(self, key, batch: int, length: int) -> jax.Array:
        """(batch, length) int32 token matrix."""
        k0, kseq = jax.random.split(key)
        t0 = jax.random.categorical(
            k0, jnp.broadcast_to(self._base, (batch, self.vocab)))

        def step(tok, k):
            nxt = jax.random.categorical(k, self.transition_logits(tok))
            return nxt, nxt

        _, toks = jax.lax.scan(step, t0, jax.random.split(kseq, length - 1))
        return jnp.concatenate(
            [t0[None], toks], axis=0).T.astype(jnp.int32)     # (B, L)

    def batch_key(self, stream: int, step: int) -> jax.Array:
        key = jax.random.key(self.seed)
        key = jax.random.fold_in(key, stream)
        return jax.random.fold_in(key, step)

    def batch_at(self, stream: int, step: int, batch: int,
                 length: int) -> jax.Array:
        return self.sample(self.batch_key(stream, step), batch, length)
