"""On-device batched token sampling.

Temperature / top-k / top-p / greedy for a whole decode batch in one fused
XLA program (per-request parameters as vectors, so mixed sampling configs
batch together — no per-request host round trips).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@jax.jit
def sample_tokens(
    logits: jnp.ndarray,  # [B, V] float32
    keys: jnp.ndarray,  # [B, 2] uint32 PRNG keys (jax.random.key data)
    temperature: jnp.ndarray,  # [B] 0 => greedy
    top_k: jnp.ndarray,  # [B] 0 => disabled
    top_p: jnp.ndarray,  # [B] 1.0 => disabled
) -> jnp.ndarray:  # [B] int32
    """The hot paths are gated with lax.cond so a batch that needs none of
    the machinery pays none of it: an all-greedy batch is one argmax, and
    a sampled batch pays the top-k search only if a row asks for top-k and
    the nucleus search only if a row asks for one (_apply_topk_topp)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def do_sample(scaled: jnp.ndarray) -> jnp.ndarray:
        scaled = _apply_topk_topp(scaled, top_k, top_p)

        def sample_one(key_data, row):
            key = jax.random.wrap_key_data(key_data)
            return jax.random.categorical(key, row)

        sampled = jax.vmap(sample_one)(keys, scaled)
        return jnp.where(temperature <= 0.0, greedy, sampled).astype(jnp.int32)

    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / t
    all_greedy = jnp.all(temperature <= 0.0)
    return jax.lax.cond(all_greedy, lambda s: greedy, do_sample, scaled)


def _flip(b: jnp.ndarray) -> jnp.ndarray:
    # sign-magnitude <-> two's complement order; its own inverse
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _order_key(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> int32, order-preserving: a < b <=> key(a) < key(b), but
    for -0.0, whose key is just below 0.0's."""
    return _flip(jax.lax.bitcast_convert_type(x, jnp.int32))


def _key_value(k: jnp.ndarray) -> jnp.ndarray:
    """The float32 of an order key (a key between two finite values' keys
    is a finite value's)."""
    return jax.lax.bitcast_convert_type(_flip(k), jnp.float32)


def _cut_value(scaled: jnp.ndarray, weigh, bound: jnp.ndarray) -> jnp.ndarray:
    """Per row of ``scaled`` [B, V] the smallest float32 t, [B], with
    sum(weigh(s) over the row's s > t) < bound. The predicate is monotone
    in t, so a search over the float32 order finds it: the int32 keys
    between the row's smallest and largest value, probed at the three
    quarter points a trip, 16 trips for the whole int32 range. A trip is
    ONE pass over ``scaled`` (the three compare-select-reduces fuse, and
    ``weigh`` is recomputed, not stored), and the pass is what a trip
    costs. The row's values >= t are then its values >= the smallest
    VALUE that meets the predicate. A row whose largest value fails it
    (bound <= 0) keeps that value alone."""

    def mean(a, b):  # floor((a + b) / 2) without overflow
        return (a & b) + ((a ^ b) >> 1)

    def trip(_, lo_hi):
        lo, hi = lo_hi
        mid = mean(lo, hi)
        q1, q3 = mean(lo, mid), mean(mid, hi)
        w = weigh(scaled)
        ok1, ok2, ok3 = (
            jnp.sum(jnp.where(scaled > _key_value(q)[:, None], w, 0), axis=-1)
            < bound
            for q in (q1, mid, q3)
        )
        lo = jnp.where(ok1, lo, jnp.where(ok2, q1, jnp.where(ok3, mid, q3)) + 1)
        hi = jnp.where(ok1, q1, jnp.where(ok2, mid, jnp.where(ok3, q3, hi)))
        return lo, hi

    ends = (_order_key(jnp.min(scaled, axis=-1)),
            _order_key(jnp.max(scaled, axis=-1)))
    return _key_value(jax.lax.fori_loop(0, 16, trip, ends)[1])


def _apply_topk_topp(
    scaled: jnp.ndarray, top_k: jnp.ndarray, top_p: jnp.ndarray
) -> jnp.ndarray:
    """Mask temperature-scaled logits to the top-k / nucleus support:
    keep s where s >= max(kth_val, thresh), NEG_INF elsewhere, ties at
    either cut kept, with

      * kth_val = the min(top_k, V)-th largest value of the row (top_k 0:
        no cut),
      * thresh = the smallest value v of the row with
        mass(s > v) < top_p * Z, mass summing exp(s - max) and Z that sum
        over the WHOLE row: the nucleus of the unfiltered distribution
        (top_p >= 1: no cut).

    Neither needs the row sorted: each is a threshold search (_cut_value)
    and each runs only if a row of the batch asks for it."""
    B, V = scaled.shape
    no_cut = jnp.full((B,), -jnp.inf, jnp.float32)

    def topk_cut(_):
        k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
        return _cut_value(scaled, lambda s: 1, k)  # a count

    def nucleus_cut(_):
        top = jnp.max(scaled, axis=-1, keepdims=True)
        mass = lambda s: jnp.exp(s - top)  # noqa: E731
        cut = _cut_value(scaled, mass, top_p * jnp.sum(mass(scaled), axis=-1))
        return jnp.where(top_p < 1.0, cut, -jnp.inf)

    kth_val = jax.lax.cond(
        jnp.any(top_k > 0), topk_cut, lambda _: no_cut, None)
    thresh = jax.lax.cond(
        jnp.any(top_p < 1.0), nucleus_cut, lambda _: no_cut, None)
    cut = jnp.maximum(kth_val, thresh)[:, None]
    return jnp.where(scaled < cut, NEG_INF, scaled)


def filtered_dist(
    logits: jnp.ndarray,  # [B, V] float32
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
) -> jnp.ndarray:
    """The exact masked/temperature-scaled logits sample_tokens draws
    from (speculative acceptance must score proposals against the SAME
    distribution the plain sampler uses)."""
    t = jnp.maximum(temperature, 1e-6)[:, None]
    return _apply_topk_topp(logits / t, top_k, top_p)


def speculative_accept(
    logits: jnp.ndarray,  # [B, T, V] f32: position t predicts token t+1
    proposals: jnp.ndarray,  # [B, T-1] int32, -1 = no proposal (never accepts)
    keys_accept: jnp.ndarray,  # [B, T-1, 2] uint32 key data (accept draws)
    keys_sample: jnp.ndarray,  # [B, T, 2] uint32 key data (corr/bonus draws)
    temperature: jnp.ndarray,  # [B] 0 => greedy rows
    top_k: jnp.ndarray,  # [B]
    top_p: jnp.ndarray,  # [B]
) -> tuple[jnp.ndarray, jnp.ndarray]:  # (out_tokens [B, T], n_acc [B])
    """Rejection-sampling acceptance for deterministic (prompt-lookup)
    drafts — the draft distribution is a point mass on the proposal, so:

      * accept proposal d_t with probability p_t(d_t)  (min(1, p/q), q=1)
      * on rejection, sample the correction from the residual
        max(0, p - q) ∝ p with d_t masked out — lossless in distribution
      * greedy rows (temperature 0) degenerate to accept iff d_t == argmax

    The full-acceptance bonus position (t = T-1) samples normally.
    ``out_tokens[:, t]`` is d_t for t < n_acc and the correction/bonus at
    t = n_acc; the caller emits exactly n_acc + 1 tokens per row."""
    B, T, V = logits.shape
    g = T - 1
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, T]
    is_greedy = (temperature <= 0.0)[:, None]  # [B, 1]
    d = jnp.maximum(proposals, 0)  # [B, g] safe gather index
    valid = proposals >= 0
    accept_greedy = (d == greedy[:, :g]) & valid
    greedy_out = (accept_greedy, greedy)

    def sampled_path(_):
        # per-position filtered distributions (flattened over B*T); the
        # searches and the softmax run ONLY for batches with sampled rows
        # (same all-greedy gating discipline as sample_tokens)
        scaled = filtered_dist(
            logits.reshape(B * T, V), jnp.repeat(temperature, T),
            jnp.repeat(top_k, T), jnp.repeat(top_p, T),
        ).reshape(B, T, V)
        probs = jax.nn.softmax(scaled, axis=-1)
        p_d = jnp.take_along_axis(probs[:, :g], d[..., None], axis=-1)[..., 0]

        def uniform_one(key_data):
            return jax.random.uniform(jax.random.wrap_key_data(key_data))

        u = jax.vmap(jax.vmap(uniform_one))(keys_accept)  # [B, g]
        accept = jnp.where(is_greedy, accept_greedy, (u < p_d) & valid)

        # corrections: residual distribution (proposal masked) at t < g;
        # plain distribution at the bonus position t = g and at invalid
        # (unproposed) positions — index V is out of range, one_hot of it
        # is all-zeros, so those rows mask nothing
        d_mask = jnp.where(valid, d, V)
        d_full = jnp.concatenate(
            [d_mask, jnp.full((B, 1), V, jnp.int32)], axis=1
        )
        mask = jax.nn.one_hot(d_full, V, dtype=bool)  # [B, T, V]
        resid = jnp.where(mask, NEG_INF, scaled)

        def cat_one(key_data, row):
            return jax.random.categorical(
                jax.random.wrap_key_data(key_data), row
            ).astype(jnp.int32)

        corr = jax.vmap(jax.vmap(cat_one))(keys_sample, resid)  # [B, T]
        # greedy rows' correction = argmax (d != argmax on rejection)
        return accept, jnp.where(is_greedy, greedy, corr)

    all_greedy = jnp.all(temperature <= 0.0)
    accept, corr = jax.lax.cond(
        all_greedy, lambda _: greedy_out, sampled_path, None
    )
    n_acc = jnp.sum(
        jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
    )  # [B]
    t_idx = jnp.arange(T)[None, :]
    out = jnp.where(
        t_idx < n_acc[:, None],
        jnp.concatenate([d, jnp.zeros((B, 1), jnp.int32)], axis=1),
        corr,
    ).astype(jnp.int32)
    return out, n_acc


def apply_penalties(
    logits: jnp.ndarray,  # [B, V] float32
    counts: jnp.ndarray,  # [B, V] int32 output-token counts
    prompt_mask: jnp.ndarray,  # [B, V] bool: token appeared in the prompt
    freq_pen: jnp.ndarray,  # [B] float32 (0 = off)
    pres_pen: jnp.ndarray,  # [B] float32 (0 = off)
    rep_pen: jnp.ndarray,  # [B] float32 (1.0 = off)
) -> jnp.ndarray:
    """OpenAI/HF sampling penalties, vLLM semantics: frequency and
    presence penalize OUTPUT tokens (additive on logits); repetition
    penalizes prompt AND output tokens (divide positive logits by r,
    multiply negative ones — the HF formula)."""
    cf = counts.astype(jnp.float32)
    logits = logits - freq_pen[:, None] * cf
    logits = logits - pres_pen[:, None] * (cf > 0)
    seen = prompt_mask | (counts > 0)
    r = jnp.where(rep_pen[:, None] <= 0.0, 1.0, rep_pen[:, None])
    penalized = jnp.where(logits > 0, logits / r, logits * r)
    return jnp.where(seen, penalized, logits)


def bump_counts(counts: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    """counts[b, tokens[b]] += 1 for every row (decode-window step)."""
    B = tokens.shape[0]
    return counts.at[jnp.arange(B), tokens].add(1)


TOPK_LOGPROBS = 20  # OpenAI's top_logprobs cap; the host slices per-request


def sample_first_token(
    logits: jnp.ndarray,  # [1, V] float32
    keys: jnp.ndarray,  # [1, 2]
    temperature: jnp.ndarray,  # [1]
    top_k: jnp.ndarray,  # [1]
    top_p: jnp.ndarray,  # [1]
    freq_pen: jnp.ndarray,  # [1]
    pres_pen: jnp.ndarray,  # [1]
    rep_pen: jnp.ndarray,  # [1]
    prompt_ids: jnp.ndarray,  # [P] int32 padded with V (dropped)
    gen_ids: jnp.ndarray,  # [G] int32 padded with V — nonempty on replay
) -> jnp.ndarray:  # [1] int32
    """The prefill's first-token sample with full penalty semantics:
    prompt-membership mask + output counts rebuilt from the id lists (the
    replay-after-preemption case), so the first token is drawn from the
    same distribution a decode window would use."""
    V = logits.shape[-1]
    mask = jnp.zeros((V,), jnp.bool_).at[prompt_ids].set(True, mode="drop")
    counts = jnp.zeros((V,), jnp.int32).at[gen_ids].add(1, mode="drop")
    logits = apply_penalties(
        logits.astype(jnp.float32), counts[None], mask[None],
        freq_pen, pres_pen, rep_pen,
    )
    return sample_tokens.__wrapped__(logits, keys, temperature, top_k, top_p)


def token_logprobs(
    logits: jnp.ndarray,  # [B, V] float32 (raw model logits)
    chosen: jnp.ndarray,  # [B] int32 the emitted token
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(chosen_logprob [B], top_ids [B, K], top_logprobs [B, K]) of the
    model's distribution (raw log-softmax — reported logprobs are
    pre-temperature/penalty, the model's own distribution)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    chosen_lp = jnp.take_along_axis(logp, chosen[:, None], axis=1)[:, 0]
    top_lp, top_ids = jax.lax.top_k(logp, TOPK_LOGPROBS)
    return chosen_lp, top_ids.astype(jnp.int32), top_lp


def make_keys(seeds: jnp.ndarray, steps: jnp.ndarray) -> jnp.ndarray:
    """Derive per-(request, step) key data from int seeds — deterministic
    replay per request without threading key state through the host."""
    def one(seed, step):
        return jax.random.key_data(jax.random.fold_in(jax.random.key(seed), step))

    return jax.vmap(one)(seeds, steps)
