"""Token sampling: greedy / temperature / top-p (nucleus).

Three entry points share one implementation:

* :func:`sample_tokens` — jitted batch sampler (the legacy host-driven
  decode path and tests).
* :func:`sample_token` — jitted single-logits sampler for prefill's first
  token; the logits stay on device, only the sampled id crosses to host.
* :func:`sample_from_logits` / :func:`fold_seeds` — pure bodies for
  inlining inside larger jitted programs (the fused decode step), where
  sampling must happen on device without a separate dispatch.

Every sampler on the device runs under the scope ``sample`` (the jitted
entry points here; the fused decode and verify paths put their
sample-and-latch bodies under it), so a profiler trace tells sampling
apart from the model step by each op's ``op_name``.

Seed folding: the engine derives a per-request ``seed_base =
(seed * 1_000_003) % SEED_MOD`` once at admission; each step's PRNG seed is
``(seed_base + n_generated) % SEED_MOD``. :func:`fold_seeds` reproduces that
arithmetic in uint32 on device, so host- and device-driven sampling are
bit-identical for the same request state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SEED_MOD = 2 ** 31 - 1
SEED_MULT = 1_000_003


def seed_base(seed: int) -> int:
    """Host-side per-request seed base (fits in uint32/int32)."""
    return (seed * SEED_MULT) % SEED_MOD


def fold_seeds(base, n_gen):
    """base: (B,) uint32 seed bases; n_gen: (B,) int32 tokens generated so
    far. Returns (B,) int32 PRNG seeds, identical to the host fold
    ``(seed * SEED_MULT + n_gen) % SEED_MOD``."""
    s = (base.astype(jnp.uint32) + n_gen.astype(jnp.uint32)) % jnp.uint32(
        SEED_MOD)
    return s.astype(jnp.int32)


def _sample_one(lg, temp, tp, seed):
    """lg: (V,) f32; temp/tp: f32 scalars; seed: int32 scalar -> int32."""
    greedy = jnp.argmax(lg).astype(jnp.int32)

    def sampled():
        scaled = lg / jnp.maximum(temp, 1e-6)
        sort_idx = jnp.argsort(-scaled)
        sorted_logits = scaled[sort_idx]
        probs = jax.nn.softmax(sorted_logits)
        cum = jnp.cumsum(probs)
        keep = cum - probs < tp               # first token always kept
        masked = jnp.where(keep, sorted_logits, -jnp.inf)
        choice = jax.random.categorical(jax.random.PRNGKey(seed), masked)
        return sort_idx[choice].astype(jnp.int32)

    return jax.lax.cond(temp <= 0.0, lambda: greedy, sampled)


def sample_from_logits(logits, temperature, top_p, seeds):
    """Pure (jit-inlinable) batch sampler. logits: (B, V) f32; temperature,
    top_p: (B,) f32; seeds: (B,) int32. temperature == 0 -> greedy.
    Returns (B,) int32."""
    return jax.vmap(_sample_one)(logits, temperature, top_p, seeds)


@jax.jit
def sample_tokens(logits, temperature, top_p, seeds):
    """Jitted batch sampler (see :func:`sample_from_logits`)."""
    with jax.named_scope("sample"):
        return sample_from_logits(logits, temperature, top_p, seeds)


@jax.jit
def sample_token(logits, temperature, top_p, seed):
    """One sequence's first token from device-resident logits (V,).
    Scalars are weak-typed, so repeated calls don't retrace."""
    with jax.named_scope("sample"):
        return _sample_one(logits.astype(jnp.float32),
                           jnp.float32(temperature), jnp.float32(top_p),
                           jnp.int32(seed))


# ---------------------------------------------------------------------------
# speculative decoding: acceptance test + residual resampling
# ---------------------------------------------------------------------------
# The engine's sampler is DETERMINISTIC given (seed_base, n_gen): position i
# of a sequence always samples the same token from the same logits. Under
# that sampler the target distribution at each position is a point mass on
# the seeded sample t_i, so the standard accept-with-prob-min(1, p/q) test
# collapses to an exact-match test (accept the draft token iff it equals
# t_i) and the residual distribution max(0, p - q) collapses to t_i itself —
# "residual resampling" emits the target's own seeded sample at the first
# mismatch. For greedy (temperature == 0) this is the classic argmax
# acceptance rule. The payoff: speculative output streams are token-
# identical to non-speculative decoding for EVERY sampling mode, not just
# distributionally equivalent.


def spec_targets(logits, temps, top_ps, seed_base, n_gen):
    """Seeded target samples for a block of verify positions.

    logits: (B, T, V) f32 — position j holds the target logits after feeding
    verify token j; temps/top_ps: (B,); seed_base: (B,) uint32; n_gen: (B,)
    tokens generated so far. Position j folds seed ``seed_base + n_gen + j``,
    matching what the non-speculative loop would fold when emitting that
    token. Returns (B, T) int32.
    """
    B, T, V = logits.shape
    flat = logits.reshape(B * T, V).astype(jnp.float32)
    n2 = n_gen[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    seeds = fold_seeds(jnp.repeat(seed_base, T), n2.reshape(-1))
    out = sample_from_logits(flat, jnp.repeat(temps, T),
                             jnp.repeat(top_ps, T), seeds)
    return out.reshape(B, T)


def spec_accept(targets, draft):
    """Acceptance test: how much of the draft survives verification.

    targets: (B, k+1) seeded target samples (see :func:`spec_targets`);
    draft: (B, k) proposed tokens. Returns ``(emit, n_emit)``:
    ``emit[b, j]`` marks verify position j as emittable (position 0 — the
    guaranteed target token — always is; position j > 0 iff every draft
    token before it matched), ``n_emit = 1 + accepted`` counts them. The
    emitted token at the first mismatch is ``targets`` at that position —
    the residual resample.
    """
    B = targets.shape[0]
    match = (targets[:, :-1] == draft).astype(jnp.int32)
    prefix = jnp.cumprod(match, axis=1)
    emit = jnp.concatenate(
        [jnp.ones((B, 1), jnp.int32), prefix], axis=1).astype(bool)
    return emit, emit.sum(axis=1).astype(jnp.int32)
