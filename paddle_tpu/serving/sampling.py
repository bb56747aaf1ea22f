"""paddle_tpu.serving.sampling — batched, fully-vectorized token sampling.

Greedy / temperature / top-k / top-p over a ``[B, V]`` logits block, written
so one fixed-shape XLA program serves EVERY per-request sampling config: the
knobs arrive as ``[B]`` arrays (``temperature == 0`` → greedy, ``top_k <= 0``
→ disabled, ``top_p >= 1`` → disabled), never as Python branches, so a batch
can mix greedy and nucleus requests without a recompile.

Seed-determinism contract (the reason this lives next to ``core.random``
instead of calling ``numpy.random``): randomness enters ONLY through the
per-request key — derived from the global ``core.random`` generator when the
request is admitted — folded with the request's own token index. A request's
sampled tokens therefore depend on (paddle seed, request seed, token index)
and on nothing else: not the slot it landed in, not which other requests
shared its decode batches. That invariant is what makes interleaved
continuous-batching output bitwise-equal to a solo run (tested in
tests/test_serving.py).

Sampling itself uses the Gumbel-max trick (argmax(logits + gumbel) ~
Categorical(softmax(logits))): one argmax over the already-materialized
logits row instead of a cumulative-sum search, and the same code path as
greedy (which just omits the noise).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..profiler.spans import scope as _scope


def request_key(base_key, seed):
    """Raw ``uint32`` key data for one request: the engine's base key (drawn
    from ``core.random`` at engine construction) folded with the request
    seed. Host-side helper — runs once per admission."""
    return jax.random.key_data(jax.random.fold_in(base_key, int(seed)))


def gumbel_rows(key_data, token_idx, vocab):
    """``[B, vocab]`` Gumbel noise, row b drawn from
    fold_in(request_key_b, token_idx_b) — independent of batch composition.

    `key_data` is raw ``uint32 [B, 2]`` (typed keys don't batch across the
    host/step boundary as plainly); `token_idx` is ``int32 [B]``, the
    per-request generated-token counter."""

    def row(kd, idx):
        k = jax.random.fold_in(jax.random.wrap_key_data(kd), idx)
        return jax.random.gumbel(k, (vocab,), jnp.float32)

    with _scope("sampling"):
        return jax.vmap(row)(key_data, token_idx)


def filter_top_k(logits, top_k):
    """Keep each row's `top_k` highest logits (ties keep all tied values —
    the standard sort-threshold caveat); ``top_k <= 0`` disables the filter
    for that row. Shapes: logits ``[B, V]`` float, top_k ``[B]`` int."""
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=-1)
    keep = (top_k[:, None] <= 0) | (logits >= kth)
    return jnp.where(keep, logits, -jnp.inf)


def filter_top_p(logits, top_p):
    """Nucleus filter: keep each row's smallest prefix of descending-sorted
    tokens whose PRECEDING cumulative probability is < top_p (so the top-1
    token always survives, even for tiny p); ``top_p >= 1`` disables the
    filter for that row. Operates on already temperature-scaled logits."""
    p = jnp.clip(top_p, 1e-6, 1.0)[:, None]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    kept = jnp.where(before < p, sorted_desc, jnp.inf)
    threshold = jnp.min(kept, axis=-1, keepdims=True)
    keep = (top_p[:, None] >= 1.0) | (logits >= threshold)
    return jnp.where(keep, logits, -jnp.inf)


def sample_tokens(logits, temperature, top_k, top_p, gumbel):
    """One token per row: greedy argmax where ``temperature == 0``, else
    Gumbel-max over the temperature-scaled, top-k/top-p-filtered logits.

    All inputs are arrays (``logits [B, V]``, knobs ``[B]``, ``gumbel
    [B, V]``) so the call is shape-stable regardless of the per-request
    configs in the batch."""
    with _scope("sampling"):
        logits = logits.astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1)
        safe_t = jnp.where(temperature > 0, temperature, 1.0)
        scaled = logits / safe_t[:, None]
        filtered = filter_top_p(filter_top_k(scaled, top_k), top_p)
        sampled = jnp.argmax(filtered + gumbel, axis=-1)
        return jnp.where(temperature > 0, sampled,
                         greedy).astype(jnp.int32)
