"""Synthetic, deterministic, shard-aware data pipeline.

The LM stream generates order-k Markov token sequences from a fixed random
transition table: learnable structure (so training loss demonstrably falls)
with zero I/O. Batches are pure functions of (seed, step) — every data-parallel
shard can materialize exactly its slice without any host-side state, and a
restart from a checkpoint resumes the stream deterministically.

The convex-experiment generators (regression / two-class) reproduce the data
protocols of the paper's §5 simulations: Gaussian-cubed heavy-tailed design
matrices, Student-t planted models, Gaussian class clouds.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Language-model token stream
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TokenStream:
    vocab_size: int
    seq_len: int                 # tokens per example INCLUDING the shift target
    batch_size: int              # global batch
    seed: int = 0
    markov_temperature: float = 0.3

    def _table_key(self) -> jax.Array:
        return jax.random.key(self.seed)

    def batch(self, step: int) -> dict:
        """Global batch at `step`: {"tokens": (B, seq_len+1) int32}."""
        key = jax.random.fold_in(self._table_key(), step + 1)
        return {"tokens": _markov_tokens(
            key, self._table_key(), self.batch_size, self.seq_len + 1,
            self.vocab_size, self.markov_temperature)}

    def __iter__(self) -> Iterator[dict]:
        step = 0
        while True:
            yield self.batch(step)
            step += 1


@partial(jax.jit, static_argnames=("vocab",))
def _markov_factors(table_key: jax.Array, vocab: int) -> tuple:
    # low-rank logits table (V, r) @ (r, V), kept as its factors: a row is
    # formed only when a token needs it, so a 50k vocab never holds V² floats
    r = 32
    ka, kb = jax.random.split(table_key)
    a = jax.random.normal(ka, (vocab, r))
    b = jax.random.normal(kb, (r, vocab))
    return a, b


def _markov_tokens(key, table_key, batch, length, vocab, temperature):
    a, b = _markov_factors(table_key, vocab)
    r = a.shape[1]

    k0, kscan = jax.random.split(key)
    first = jax.random.randint(k0, (batch,), 0, vocab, jnp.int32)

    def step(tok, k):
        logits = a[tok] @ b / jnp.sqrt(r) / temperature
        nxt = jax.random.categorical(k, logits)
        return nxt.astype(jnp.int32), nxt.astype(jnp.int32)

    keys = jax.random.split(kscan, length - 1)
    _, rest = jax.lax.scan(step, first, keys)
    return jnp.concatenate([first[None], rest], axis=0).T  # (B, length)


def synthetic_lm_batches(vocab_size: int, seq_len: int, batch_size: int,
                         steps: int, seed: int = 0) -> Iterator[dict]:
    stream = TokenStream(vocab_size, seq_len, batch_size, seed)
    for t in range(steps):
        yield stream.batch(t)


# ---------------------------------------------------------------------------
# Modality-frontend stand-ins + generic batch construction
# ---------------------------------------------------------------------------
def batch_for_shape(cfg, batch_size: int, seq_len: int, step: int = 0,
                    seed: int = 0) -> dict:
    """A real (allocated) batch matching launch.input_specs layouts."""
    key = jax.random.fold_in(jax.random.key(seed), step)
    if cfg.frontend == "audio":
        ke, kt = jax.random.split(key)
        return {
            "embeds": jax.random.normal(ke, (batch_size, seq_len, cfg.d_model),
                                        jnp.float32) * 0.02,
            "targets": jax.random.randint(kt, (batch_size, seq_len), 0,
                                          cfg.vocab_size, jnp.int32),
        }
    if cfg.frontend == "vision":
        ke, kt = jax.random.split(key)
        text_len = seq_len - cfg.num_patches
        return {
            "image_embeds": jax.random.normal(
                ke, (batch_size, cfg.num_patches, cfg.d_model),
                jnp.float32) * 0.02,
            "tokens": jax.random.randint(kt, (batch_size, text_len + 1), 0,
                                         cfg.vocab_size, jnp.int32),
        }
    stream = TokenStream(cfg.vocab_size, seq_len, batch_size, seed)
    return stream.batch(step)


# ---------------------------------------------------------------------------
# Convex-experiment data (paper §5 protocols)
# ---------------------------------------------------------------------------
def synthetic_regression(key: jax.Array, n_samples: int, dim: int,
                         design: str = "gauss3", model: str = "student_t"):
    """b = A x* with heavy-tailed A and/or x* (paper Fig. 3a / Figs. 5–6)."""
    ka, kx = jax.random.split(key)
    a = jax.random.normal(ka, (n_samples, dim))
    if design == "gauss3":
        a = a ** 3
    if model == "student_t":
        x_star = jax.random.t(kx, df=1.0, shape=(dim,))
    elif model == "gauss3":
        x_star = jax.random.normal(kx, (dim,)) ** 3
    else:
        x_star = jax.random.normal(kx, (dim,))
    return a, a @ x_star, x_star


def synthetic_two_class(key: jax.Array, n_per_class: int, dim: int,
                        separation: float = 2.0):
    """Two Gaussian clouds, labels ±1 (paper Fig. 2a–b SVM protocol)."""
    k1, k2 = jax.random.split(key)
    mu = jnp.ones((dim,)) * separation / jnp.sqrt(dim)
    xa = jax.random.normal(k1, (n_per_class, dim)) + mu
    xb = jax.random.normal(k2, (n_per_class, dim)) - mu
    x = jnp.concatenate([xa, xb], axis=0)
    y = jnp.concatenate([jnp.ones(n_per_class), -jnp.ones(n_per_class)])
    return x, y
