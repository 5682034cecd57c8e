"""Training batches from the seed, made on the device in one jitted call.

The language follows the repository's synthetic corpus
(``repro.data.pipeline.SyntheticLMDataset``): a Zipf unigram mixed with an
order-1 Markov chain over token bands, so the loss has structure to learn.
Documents of geometric length (mean ``doc_mean``) are packed back to back,
each closed by the end-of-document token, into rows of ``seq_len``.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.chip.weights import seed_key

_DATA_STREAM = 1


def make_pool(t: Dict[str, Any], vocab: int, seed: int, n_batches: int,
              sharding: Any = None) -> jax.Array:
    """(n_batches, batch, seq_len) int32 tokens; every row differs."""
    d = t["data"]
    B, S = t["batch"], t["seq_len"]
    R = n_batches * B
    n_states, p_band = int(d["n_states"]), float(d["band_prob"])
    eos, p_end = int(d["eos"]), 1.0 / float(d["doc_mean"])
    band = max(vocab // n_states, 1)

    def build(key):
        k_state, k_next, k_rows = jax.random.split(key, 3)
        state_of = jax.random.randint(k_state, (vocab,), 0, n_states)
        next_state = jax.random.permutation(k_next, n_states)
        ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
        cdf = jnp.cumsum(1.0 / ranks)
        cdf = cdf / cdf[-1]

        def body(cur, k):
            ku, kb, kz, ke, ks = jax.random.split(k, 5)
            band_tok = (cur * band + jax.random.randint(kb, (R,), 0, band)) \
                % vocab
            zipf_tok = jnp.minimum(jnp.searchsorted(
                cdf, jax.random.uniform(kz, (R,))), vocab - 1)
            tok = jnp.where(jax.random.uniform(ku, (R,)) < p_band, band_tok,
                            zipf_tok).astype(jnp.int32)
            end = jax.random.uniform(ke, (R,)) < p_end
            tok = jnp.where(end, eos, tok)
            nxt = jnp.where(end, jax.random.randint(ks, (R,), 0, n_states),
                            next_state[state_of[tok]])
            return nxt, tok

        cur0 = jax.random.randint(k_rows, (R,), 0, n_states)
        _, toks = jax.lax.scan(body, cur0, jax.random.split(k_rows, S))
        return toks.T.reshape(n_batches, B, S)

    return jax.jit(build, out_shardings=sharding)(
        seed_key(seed, _DATA_STREAM))
