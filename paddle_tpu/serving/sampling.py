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

Both filters keep ``logits >= threshold(row)``, and the threshold is found by
SELECTION over the row, never by ordering it: a sort of the vocabulary to
read one value was the largest device group of a decode step. Every float32
has an int32 image with the same order (``_order_keys``); the threshold's
image is built bit by bit from the top, one compare-and-reduce pass over the
block a bit (``_select_key``). For top-k the pass counts, so the threshold is
the exact k-th largest value, ties and all, for any k — which is why
``lax.approx_max_k`` is not used (its recall is below 1: it may miss one of
the k, and the kept set would no longer be what the request asked for). A
row's threshold reads that row alone, so batch composition still cannot reach
a request's tokens. A batch in which no row asks for a filter runs none of
its passes (``lax.cond`` on the knobs, which are data: still one executable).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

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


def _flip_negatives(i):
    """Flip the low 31 bits of the negative int32s (its own inverse)."""
    return jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)


def _order_keys(x):
    """int32 image of float32 `x` whose signed order is the float order
    (``-inf`` lowest; ``-0.0`` one below ``+0.0``, which compare equal again
    once mapped back)."""
    return _flip_negatives(lax.bitcast_convert_type(x, jnp.int32))


def _key_floats(keys):
    """The float32 whose ``_order_keys`` image is `keys`."""
    return lax.bitcast_convert_type(_flip_negatives(keys), jnp.float32)


def _select_key(keys, target, weights=None):
    """Per row of int32 `keys` ``[B, V]``, the LARGEST int32 ``t`` with
    ``sum(weights[keys >= t]) >= target`` (`weights` None counts ids), or
    the lowest int32 where no ``t`` reaches `target`. The sum can only fall
    as ``t`` rises, so ``t`` is built from its top bit down: 32 passes of
    one compare-and-reduce over the block, no ordering of it."""

    def bit(i, t):
        # the sign bit first (lowest int32 -> 0), then 30..0 set in turn
        cand = t ^ jnp.left_shift(jnp.int32(1), 31 - i)
        hit = keys >= cand[:, None]
        if weights is None:
            mass = jnp.sum(hit, axis=-1, dtype=jnp.int32)
        else:
            mass = jnp.sum(jnp.where(hit, weights, 0.0), axis=-1)
        return jnp.where(mass >= target, cand, t)

    lowest = jnp.full(keys.shape[:1], jnp.iinfo(jnp.int32).min, jnp.int32)
    return lax.fori_loop(0, 32, bit, lowest)


def top_k_threshold(logits, top_k):
    """``[B]`` float32: each row's `top_k`-th largest logit, exactly (k is
    clipped to ``[1, V]``, so ``top_k > V`` gives the row's minimum)."""
    k = jnp.clip(top_k, 1, logits.shape[-1]).astype(jnp.int32)
    return _key_floats(_select_key(_order_keys(logits), k))


def top_p_threshold(logits, top_p):
    """``[B]`` float32: the lowest value level of each row whose PRECEDING
    probability mass (the ids strictly above it) is ``< top_p``. A level
    ``v`` survives iff the mass at or above the next float up is below
    `top_p`, so the threshold is the largest ``t`` whose mass at or above it
    reaches `top_p`: the same selection as top-k, over probabilities."""
    p = jnp.clip(top_p, 1e-6, 1.0)
    probs = jax.nn.softmax(logits, axis=-1)
    return _key_floats(_select_key(_order_keys(logits), p, probs))


def filter_top_k(logits, top_k):
    """Keep each row's `top_k` highest logits; ties at the k-th value keep
    every tied id. ``top_k <= 0`` disables the filter for that row. Shapes:
    logits ``[B, V]`` float32, top_k ``[B]`` int."""
    keep = (top_k[:, None] <= 0) | (
        logits >= top_k_threshold(logits, top_k)[:, None])
    return jnp.where(keep, logits, -jnp.inf)


def filter_top_p(logits, top_p):
    """Nucleus filter: keep each row's ids from the top down while the
    PRECEDING cumulative probability is < top_p (so the top-1 token always
    survives, even for tiny p; ids tied in value stand or fall together);
    ``top_p >= 1`` disables the filter for that row. Operates on already
    temperature-scaled logits.

    The mass above a level is a float32 sum in the reduction's order, not a
    cumulative sum down a sorted row: the kept set can differ from a sorted
    form's only for an id whose preceding mass lies within float32 summation
    error (~1e-6) of `top_p`."""
    keep = (top_p[:, None] >= 1.0) | (
        logits >= top_p_threshold(logits, top_p)[:, None])
    return jnp.where(keep, logits, -jnp.inf)


def sample_tokens(logits, temperature, top_k, top_p, gumbel):
    """One token per row: greedy argmax where ``temperature == 0``, else
    Gumbel-max over the temperature-scaled, top-k/top-p-filtered logits.

    All inputs are arrays (``logits [B, V]``, knobs ``[B]``, ``gumbel
    [B, V]``) so the call is shape-stable regardless of the per-request
    configs in the batch. Each filter's passes run only if some sampling
    row asks for it; a greedy row asks for neither."""
    with _scope("sampling"):
        logits = logits.astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1)
        sampling = temperature > 0
        safe_t = jnp.where(sampling, temperature, 1.0)
        scaled = logits / safe_t[:, None]
        k_on = sampling & (top_k > 0)
        p_on = sampling & (top_p < 1.0)
        everything = jnp.full(scaled.shape[:1], -jnp.inf, jnp.float32)

        def kept(threshold):
            return jnp.where(scaled >= threshold[:, None], scaled, -jnp.inf)

        kth = jnp.where(k_on, lax.cond(
            jnp.any(k_on), lambda: top_k_threshold(scaled, top_k),
            lambda: everything), -jnp.inf)
        nucleus = jnp.where(p_on, lax.cond(
            jnp.any(p_on), lambda: top_p_threshold(kept(kth), top_p),
            lambda: everything), -jnp.inf)
        # an id stays iff it clears both thresholds (-inf where a row's
        # filter is off): the higher one
        filtered = kept(jnp.maximum(kth, nucleus))
        sampled = jnp.argmax(filtered + gumbel, axis=-1)
        return jnp.where(sampling, sampled, greedy).astype(jnp.int32)


# ------------------------------------------------- block-diffusion decoding --
# A block-diffusion decoder (models/sdar_moe.py) reads, in one forward, the
# logits at each of a block's positions for that position's OWN token, and
# unmasks some of the positions that are still masked: which ones is decided
# by how sure the model is of each (the probability of the id it sampled).

STRATEGIES = ("low_confidence_static", "low_confidence_dynamic")


def sample_block(logits, temperature, top_k, top_p, gumbel, mask_id):
    """``(ids [N], confidence [N])`` of N rows (slots x a block's rows, the
    knobs repeated a row): :func:`sample_tokens` over logits in which the
    mask id can never win, and beside each id its probability under the
    row's softmax (float32, temperature 1, the mask id left out)."""
    with _scope("sampling"):
        logits = logits.astype(jnp.float32)
        ids = lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(ids == jnp.int32(mask_id), -jnp.inf, logits)
    tok = sample_tokens(logits, temperature, top_k, top_p, gumbel)
    with _scope("sampling"):
        picked = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
        conf = jnp.exp(picked - jax.nn.logsumexp(logits, axis=-1))
    return tok, conf


def unmask_select(conf, masked, steps_done, denoising_steps, strategy,
                  threshold):
    """Which masked positions of each block to unmask after a denoise
    forward: bool ``[S, B]``. ``conf`` [S, B] float32 confidences, ``masked``
    [S, B] bool, ``steps_done`` [S] the block's denoise forwards before this
    one. ``low_confidence_static``: the ``ceil(masked / steps_left)`` most
    confident, so that ``denoising_steps`` forwards leave nothing masked;
    ``low_confidence_dynamic``: every one whose confidence is over
    ``threshold``, and at least the most confident one. Equal confidences
    rank by position, the earlier first."""
    if strategy not in STRATEGIES:
        raise ValueError(f"unmask strategy {strategy!r} is not one of "
                         f"{STRATEGIES}")
    with _scope("unmask_select"):
        B = conf.shape[-1]
        c = jnp.where(masked, conf.astype(jnp.float32), -1.0)
        pos = jnp.arange(B, dtype=jnp.int32)
        ahead = (c[:, None, :] > c[:, :, None]) | (
            (c[:, None, :] == c[:, :, None])
            & (pos[None, None, :] < pos[None, :, None]))
        rank = ahead.sum(-1).astype(jnp.int32)  # 0: the most confident
        if strategy == "low_confidence_dynamic":
            return masked & ((c > jnp.float32(threshold)) | (rank < 1))
        n = masked.sum(-1).astype(jnp.int32)
        left = jnp.maximum(jnp.int32(denoising_steps)
                           - steps_done.astype(jnp.int32), 1)
        return masked & (rank < ((n + left - 1) // left)[:, None])
