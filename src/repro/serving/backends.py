"""Engine cache backends.

SlotBackend  — contiguous per-slot KV/state cache, works for every family
               (attention, SSM, hybrid). The cache pytree has batch axis
               ``max_slots``; prefill fills one slot, decode steps all slots.
PagedBackend — vLLM-style paged KV pool with block tables, for attention
               families; decode attention goes through the paged-attention
               path. On TPU it takes the no-per-step-gather hot path by
               default: compiled Pallas kernels (shard_map'd over the
               kv-head axis under a mesh). On CPU the jnp references serve
               unless ``use_kernel=True`` asks for the "XLA twin", which has
               the same memory-traffic structure — a cached contiguous
               context view plus per-call tail buffers instead of a full
               page gather and pool scatter every step.

Both backends expose two decode paths:

* ``decode_batch(tokens)`` — legacy host-driven step: one jitted model call,
  the full ``(max_slots, V)`` logits come back to the host and the engine
  samples there. Every step pays a device->host logits transfer plus a
  second sampling dispatch.
* ``fused_decode(K, host_state)`` — device-resident fast path: a single
  jitted, donated call runs K decode steps under ``lax.fori_loop``, each
  step fusing model forward + top-p sampling + stop/length checks on
  device. Per-slot sampling state (temperature/top-p/seed base/limits) and,
  for the paged backend, block tables and lengths stay resident across
  calls; only ``(K, max_slots)`` token ids and tiny ``(max_slots,)``
  produced/done vectors are synced to the host. Logits never leave the
  device (asserted via ``TRANSFER_STATS``).

Speculative decoding adds a third call, ``spec_verify(draft_tokens)``: ONE
jitted forward verifies the k proposed tokens plus the guaranteed target
token for every slot (write KV at len..len+k, attend causally, sample all
k+1 seeded targets, latch stops/limits, truncate to the accepted prefix) —
the multi-token analogue of one fused step, with the same state-residency
and zero-logits-transfer contract. ``spec_headroom``/``reset_lens`` are its
host-side page-reservation and draft-rollback companions.

In a profiler trace every jitted program carries a fixed name
(``jit_fused_decode``, ``jit_spec_verify``, ``jit_prefill``,
``jit_swap``, ...), the on-device sampler runs under the scope
``sample`` and the decode attention under ``decode_attention`` (in each
op's ``op_name`` metadata), and ``fused_decode`` marks its host phases
with the spans ``engine.decode.prep`` (page headroom, copy-on-write,
table and state upload, up to the dispatch) and ``engine.decode.wait``
(from the dispatch until the tokens are on the host). With no profiler
session active a span costs a microsecond or two of host time.

Both backends speak the same prefill protocol to the engine:

  task = backend.start_prefill(seq_id, prompt)   # reserve slot/pages
  logits, n = backend.prefill_chunk(task, budget) # compute <= budget tokens
  ... repeat until logits is not None (prompt fully ingested) ...

``start_prefill`` on the paged backend also consults the prefix cache:
tokens covered by content-matched pages are skipped (``task.pos`` starts
past them), which is where shared-system-prompt workloads win. A sequence
only joins the decode batch once its prefill completes (``backend.activate``
is implied by the final chunk); mid-prefill sequences are excluded from
decode bookkeeping and their batch slots write to the trash page.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.distributed.sharding import ServeSharding
from repro.models import LM
from repro.models.layers import (NEG_INF, chunked_attention, mlp_layer,
                                 project_qkv, rms_norm)
from repro.models.moe import moe_ffn
from repro.models.transformer import _block
from repro.serving.kv_cache import OutOfPages, PagedKVCache
from repro.kernels.flash_attention.ops import paged_flash_prefill
from repro.kernels.paged_attention.ops import (
    fused_decode_attention, fused_decode_attention_sharded, kernels_compiled,
    paged_attention as paged_attn_kernel, paged_attention_sharded,
    shardable_kv_heads)
from repro.kernels.paged_attention.ref import (decode_tail_attention_ref,
                                               gather_kv, paged_attention_ref,
                                               paged_prefill_attention_ref)

from repro.serving.sampler import (fold_seeds, sample_from_logits,
                                   spec_accept, spec_targets)

ATTENTION_FAMILIES = ("dense", "moe", "vlm")

# -- host-transfer accounting -------------------------------------------------
# The fused decode path's contract is that logits never cross to the host;
# every logits device->host conversion in this module goes through
# ``_logits_to_host`` so tests can assert the fused path performs none.
# Sampled token ids / produced / done vectors are O(max_slots) ints and are
# the *intended* sync payload — they are not counted.
TRANSFER_STATS = {"decode_logits_transfers": 0, "decode_logits_bytes": 0}


def reset_transfer_stats() -> None:
    TRANSFER_STATS["decode_logits_transfers"] = 0
    TRANSFER_STATS["decode_logits_bytes"] = 0


def _logits_to_host(x) -> np.ndarray:
    out = np.asarray(x)
    TRANSFER_STATS["decode_logits_transfers"] += 1
    TRANSFER_STATS["decode_logits_bytes"] += out.nbytes
    return out


def _named(name: str, fn):
    """``fn`` (a ``partial`` or lambda) with the name its jitted program
    carries in HLO and in profiler traces, ``jit_<name>``: a ``partial``
    has no name of its own and lowers as ``jit__unknown``."""
    fn.__name__ = name
    return fn


def _upload_state(host_state: dict, shard: ServeSharding | None = None) -> dict:
    # copy: jnp.asarray may alias numpy memory on CPU, and the fused call
    # donates the state buffers. Sharded engines replicate the state onto
    # the mesh's device set — sampling is replicated by construction.
    if shard is not None:
        return {k: shard.replicate(np.array(v)) for k, v in host_state.items()}
    return {k: jnp.asarray(np.array(v)) for k, v in host_state.items()}


def _sample_and_latch(st, logits, tokens, n_gen, done, produced, live):
    """Device-side sample + stop/limit latch for one fused decode step —
    the single definition both backends inline, so their token-identity
    semantics cannot diverge. ``live`` slots take the sampled token and
    advance; a live slot hitting its stop token or generation limit
    latches ``done`` and freezes from the next step on."""
    with jax.named_scope("sample"):
        seeds = fold_seeds(st["seed_base"], n_gen)
        sampled = sample_from_logits(logits, st["temps"], st["top_ps"], seeds)
        tokens = jnp.where(live, sampled, tokens)
        n_gen = n_gen + live.astype(jnp.int32)
        hit_stop = (st["stop_tok"] >= 0) & (sampled == st["stop_tok"])
        done = done | (live & (hit_stop | (n_gen >= st["gen_limit"])))
        produced = produced + live.astype(jnp.int32)
    return tokens, n_gen, done, produced


def _spec_block_attention(q, k, v, lens, *, kv_major):
    """Attention for a speculative verify block of T tokens per slot.

    q: (B, T, H, D). k/v hold history PLUS the block's own KV (already
    written): kv-heads-major (B, KH, Smax, D) for the dense slot cache, or
    seq-major (B, S, KH, D) for a gathered page view. ``lens``: (B,) valid
    history length BEFORE the block — query j attends [0, lens + j + 1), the
    same visible set the sequential decode path sees at that position.
    """
    B, T, H, D = q.shape
    KH = k.shape[1] if kv_major else k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qr = q.reshape(B, T, KH, G, D).astype(jnp.float32)
    sub = "btkgd,bksd->bkgts" if kv_major else "btkgd,bskd->bkgts"
    s = jnp.einsum(sub, qr, k.astype(jnp.float32)) * scale
    S = s.shape[-1]
    ok = jnp.arange(S)[None, None, :] \
        < (lens[:, None] + 1 + jnp.arange(T))[:, :, None]      # (B, T, S)
    s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    sub = "bkgts,bksd->btkgd" if kv_major else "bkgts,bskd->btkgd"
    out = jnp.einsum(sub, p, v.astype(jnp.float32))
    return out.reshape(B, T, H, D).astype(q.dtype)


def _spec_accept_and_latch(st, logits, draft):
    """Device-side acceptance + stop/limit latch for one speculative round —
    the single definition both backends inline (the verify-path analogue of
    :func:`_sample_and_latch`). logits: (B, T, V) with T = k + 1; draft:
    (B, k). Emits the accepted draft prefix, the residual resample at the
    first mismatch (or the bonus token when everything matched), truncated
    at the first stop-token / generation-limit hit. Returns
    (targets (B, T), produced (B,), done (B,), st) with st's tokens/n_gen
    advanced by ``produced``.
    """
    T = logits.shape[1]
    with jax.named_scope("sample"):
        targets = spec_targets(logits, st["temps"], st["top_ps"],
                               st["seed_base"], st["n_gen"])
        emit, n_emit = spec_accept(targets, draft)
        n2 = st["n_gen"][:, None] + 1 \
            + jnp.arange(T, dtype=jnp.int32)[None, :]
        hit_stop = (st["stop_tok"][:, None] >= 0) \
            & (targets == st["stop_tok"][:, None])
        hit = emit & (hit_stop | (n2 >= st["gen_limit"][:, None]))
        any_hit = hit.any(axis=1)
        first_hit = jnp.argmax(hit, axis=1).astype(jnp.int32)
        produced = jnp.where(any_hit, first_hit + 1, n_emit)
        produced = jnp.where(st["active"], produced, 0)
        done = st["active"] & any_hit
        last = jnp.take_along_axis(
            targets, jnp.maximum(produced - 1, 0)[:, None], axis=1)[:, 0]
        tokens = jnp.where(produced > 0, last, st["tokens"])
    st = dict(st, tokens=tokens, n_gen=st["n_gen"] + produced)
    return targets, produced, done, st


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _chunk_layer(h, lp, cfg, positions, write_attend):
    """One transformer layer of a prefill chunk. The backends differ only in
    how a chunk's KV is written into their cache and attended against it —
    ``write_attend(q, k, v) -> (attn_out, new_cache_leaves)`` supplies that
    step; the residual/FFN structure stays in one place (mirrors
    transformer._block, which handles the no-cache and single-token cases).
    """
    B, S = h.shape[:2]
    xa = rms_norm(h, lp["norm1"], cfg.norm_eps)
    q, k, v = project_qkv(xa, lp["attn"], cfg, positions)
    a, new_cache = write_attend(q, k, v)
    h = h + (a.reshape(B, S, -1) @ lp["attn"]["wo"])
    g = rms_norm(h, lp["norm2"], cfg.norm_eps)
    if cfg.moe:
        f, _ = moe_ffn(g, lp["moe"], cfg, mode="dense")
    else:
        f = mlp_layer(g, lp["mlp"])
    return h + f, new_cache


@dataclass
class PrefillTask:
    """In-flight prompt ingestion state (one per admitted sequence)."""
    seq_id: str
    prompt: list
    pos: int = 0                    # next prompt position to compute
    cached_tokens: int = 0          # prefix tokens served from the page cache
    chunks: int = 0                 # chunks computed so far

    @property
    def remaining(self) -> int:
        return len(self.prompt) - self.pos

    @property
    def done(self) -> bool:
        return self.pos >= len(self.prompt)


class SlotBackend:
    """Contiguous cache with ``max_slots`` sequences of up to ``max_len``."""

    def __init__(self, model: LM, params, *, max_slots: int, max_len: int,
                 mesh=None):
        self.model = model
        self.params = params
        self.cfg = model.cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.cache = model.init_cache(max_slots, max_len)
        self.shard = ServeSharding(mesh, model.cfg) if mesh is not None \
            else None
        if self.shard is not None:
            self.params = self.shard.shard_params(params)
            self.cache = self.shard.shard_slot_cache(self.cache)
        self.free_slots = list(range(max_slots - 1, -1, -1))
        self.slot_of: dict[str, int] = {}

        def _insert(cache, slot_cache, slot):
            def ins(big, small):
                ax = 0 if big.ndim == 1 else 1
                idx = [slice(None)] * big.ndim
                idx[ax] = slot
                return big.at[tuple(idx)].set(
                    jnp.squeeze(small, ax) if small.ndim == big.ndim else small)
            return self._pin_cache(jax.tree.map(ins, cache, slot_cache))

        self._insert = jax.jit(_insert, donate_argnums=(0,))
        self._prefill = {}  # bucket -> jitted fn
        # one jit object; specializes per chunk-bucket shape
        self._chunk = jax.jit(self._chunk_impl, donate_argnums=(2,))

        def _decode(p, toks, cache):
            logits, cache = self.model.decode_step(p, toks, cache)
            return logits, self._pin_cache(cache)

        self._decode = jax.jit(_decode, donate_argnums=(2,))
        self._fused = {}        # K -> jitted multi-step decode+sample fn
        self._spec_fns = {}     # T -> jitted verify+accept fn
        self._dec_st = None     # device-resident per-slot decode state

    # -- sharded placement helpers ----------------------------------------------
    def _put(self, x):
        """Host upload: replicated onto the mesh device set when sharded."""
        return jnp.asarray(x) if self.shard is None \
            else self.shard.replicate(np.asarray(x))

    def _pin_cache(self, cache):
        """Pin cache leaves to their serving sharding inside jit, so the
        layout is a fixed point across donated calls (no-op unsharded)."""
        return cache if self.shard is None \
            else self.shard.pin_slot_cache(cache)

    def _pin_st(self, st):
        return st if self.shard is None else self.shard.pin_replicated(st)

    # -- capacity -------------------------------------------------------------
    def can_admit(self, n_prompt: int) -> bool:
        return bool(self.free_slots) and n_prompt < self.max_len

    @property
    def supports_chunked_prefill(self) -> bool:
        # SSM/hybrid state cannot be rebuilt from a cache slice, so those
        # families ingest prompts in one shot regardless of the budget
        return self.cfg.family in ATTENTION_FAMILIES

    # -- prefill protocol -------------------------------------------------------
    def start_prefill(self, seq_id: str, prompt: list) -> PrefillTask:
        slot = self.free_slots.pop()
        self.slot_of[seq_id] = slot
        return PrefillTask(seq_id=seq_id, prompt=list(prompt))

    def prefill_chunk(self, task: PrefillTask, budget: int | None = None):
        """Compute up to ``budget`` prompt tokens (all remaining if None).
        Returns (last_token_logits | None, tokens_computed)."""
        S = len(task.prompt)
        if budget is None or not self.supports_chunked_prefill:
            chunk = task.remaining
        else:
            chunk = min(max(budget, 1), task.remaining)
        if task.pos == 0 and chunk == S:
            logits = self._one_shot(task.seq_id, task.prompt)
            task.pos = S
            task.chunks += 1
            return logits, S
        logits = self._compute_chunk(task, chunk)
        task.pos += chunk
        task.chunks += 1
        if task.done:
            return logits, chunk
        return None, chunk

    def prefill(self, seq_id: str, prompt: list):
        """One-shot convenience: returns last-token logits (V,)."""
        task = self.start_prefill(seq_id, prompt)
        logits, _ = self.prefill_chunk(task, None)
        return logits

    # -- jitted bodies ----------------------------------------------------------
    def _one_shot(self, seq_id: str, prompt: list):
        slot = self.slot_of[seq_id]
        S = len(prompt)
        # SSM/hybrid state is polluted by right-padding, so those use exact
        # lengths (one compile per distinct length); attention families use
        # power-of-two buckets with a masked last_index.
        if self.cfg.family in ("ssm", "hybrid"):
            bucket = S
        else:
            bucket = min(_bucket(S), self.max_len)
        if bucket not in self._prefill:
            def fn(params, toks, true_len):
                logits, cache = self.model.prefill(
                    params, {"tokens": toks}, max_len=self.max_len,
                    last_index=true_len - 1, moe_mode="dense")
                cache["len"] = jnp.full_like(cache["len"], true_len)
                return logits, self._pin_cache(cache)
            self._prefill[bucket] = jax.jit(_named("prefill", fn))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :S] = prompt
        logits, slot_cache = self._prefill[bucket](
            self.params, self._put(toks), S)
        self.cache = self._insert(self.cache, slot_cache, slot)
        return logits[0]            # device-resident (V,)

    def _chunk_impl(self, params, toks, cache, slot, start, true_len):
        """One prefill chunk straight into the stacked slot cache.

        toks: (1, Cb) right-padded chunk; slot/start/true_len: traced
        scalars. Writes the chunk's KV at positions [start, start+true_len)
        of ``slot`` (padded rows are dropped out-of-bounds), then attends the
        chunk queries over the slot's cache rows [0, start+true_len).
        """
        cfg = self.cfg
        model = self.model
        Cb = toks.shape[1]
        x = model.embed_inputs(params, {"tokens": toks})
        positions = start + jnp.arange(Cb)[None, :]
        kv_len = start + true_len
        Smax = cache["k"].shape[3]
        wpos = start + jnp.arange(Cb)
        wpos = jnp.where(jnp.arange(Cb) < true_len, wpos, Smax)  # pad -> drop

        def body(h, xs):
            lp, kc, vc = xs                       # kc: (B, KH, Smax, hd)

            def write_attend(q, k, v):
                kc2 = kc.at[slot, :, wpos].set(k[0].astype(kc.dtype),
                                               mode="drop")
                vc2 = vc.at[slot, :, wpos].set(v[0].astype(vc.dtype),
                                               mode="drop")
                kg = jnp.swapaxes(kc2[slot], 0, 1)[None]  # (1, Smax, KH, hd)
                vg = jnp.swapaxes(vc2[slot], 0, 1)[None]
                a = chunked_attention(q, kg, vg, causal=True, q_offset=start,
                                      kv_len=kv_len)
                return a, (kc2, vc2)

            return _chunk_layer(h, lp, cfg, positions, write_attend)

        h, (nk, nv) = lax.scan(body, x, (params["layers"], cache["k"],
                                         cache["v"]))
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        idx = jnp.maximum(true_len - 1, 0)
        logits = model.logits(params, h[:, idx])
        cache = dict(cache)
        cache["k"], cache["v"] = nk, nv
        cache["len"] = cache["len"].at[slot].set(kv_len)
        return logits[0], self._pin_cache(cache)

    def _compute_chunk(self, task: PrefillTask, chunk: int):
        slot = self.slot_of[task.seq_id]
        bucket = min(_bucket(chunk), self.max_len)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :chunk] = task.prompt[task.pos:task.pos + chunk]
        logits, self.cache = self._chunk(
            self.params, self._put(toks), self.cache, slot, task.pos, chunk)
        return logits               # device-resident (V,)

    # -- decode -----------------------------------------------------------------
    def decode_batch(self, tokens_by_slot: np.ndarray):
        """tokens_by_slot: (max_slots,) int32. Returns logits (max_slots, V)."""
        logits, self.cache = self._decode(self.params,
                                          self._put(tokens_by_slot),
                                          self.cache)
        return _logits_to_host(logits)

    # -- fused decode fast path --------------------------------------------------
    @property
    def supports_fused_decode(self) -> bool:
        return True

    def _fused_impl(self, params, cache, st, *, K):
        """K fused decode+sample+stop-check steps, entirely on device.

        st holds per-slot (max_slots,) vectors: tokens, n_gen, temps,
        top_ps, seed_base, stop_tok, gen_limit, active. A slot stops
        updating (``done``) once it hits its stop token or generation
        limit; the cache still steps every slot — exactly what the legacy
        path did for freed slots — so active slots are bit-identical.
        Returns (tokens (K, B), produced (B,), done (B,), cache, st).
        """
        B = st["tokens"].shape[0]

        def body(i, carry):
            cache, tokens, n_gen, done, produced, out = carry
            logits, cache = self.model.decode_step(params, tokens, cache)
            cache = self._pin_cache(cache)
            live = st["active"] & ~done
            tokens, n_gen, done, produced = _sample_and_latch(
                st, logits, tokens, n_gen, done, produced, live)
            out = out.at[i].set(tokens)
            return cache, tokens, n_gen, done, produced, out

        cache, tokens, n_gen, done, produced, out = lax.fori_loop(
            0, K, body,
            (self._pin_cache(cache), st["tokens"], st["n_gen"],
             jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32),
             jnp.zeros((K, B), jnp.int32)))
        st = self._pin_st(dict(st, tokens=tokens, n_gen=n_gen))
        return out, produced, done, cache, st

    def fused_decode(self, K: int, host_state: dict | None = None):
        """Run K decode steps on device; sync only token ids and flags.

        host_state (when the engine's slot composition changed) re-seeds the
        device-resident state; otherwise the state carried from the previous
        call is reused. Returns (tokens (K, max_slots) np.int32,
        produced (max_slots,) np.int32, done (max_slots,) bool).
        """
        with TraceAnnotation("engine.decode.prep"):
            if host_state is not None:
                self._dec_st = _upload_state(host_state, self.shard)
            assert self._dec_st is not None, \
                "fused_decode needs host_state on the first call"
            if K not in self._fused:
                self._fused[K] = jax.jit(
                    _named("fused_decode", partial(self._fused_impl, K=K)),
                    donate_argnums=(1, 2))
        with TraceAnnotation("engine.decode.wait"):
            out, produced, done, self.cache, self._dec_st = self._fused[K](
                self.params, self.cache, self._dec_st)
            return np.asarray(out), np.asarray(produced), np.asarray(done)

    # -- speculative decoding ----------------------------------------------------
    @property
    def supports_spec_decode(self) -> bool:
        # the verify block rewrites cache positions; SSM/hybrid state cannot
        # be rolled back, so only attention families can speculate
        return self.cfg.family in ATTENTION_FAMILIES

    def spec_headroom(self, k: int) -> int:
        """How many draft tokens a verify round can take (the engine already
        bounds k by max_seq_len); the dense cache has no page pool to run
        dry, so the answer is always k."""
        return k

    def reset_lens(self, lens_by_seq: dict[str, int]) -> None:
        """Roll per-slot cache lengths back to the given values — the
        draft cache's truncate-on-reject between speculative rounds. Only
        the (max_slots,) length vector moves; KV rows past the new length
        are rewritten before the length ever crosses them. The caller
        covers every live slot, and a dead slot's length is never read
        before its next prefill resets it, so the vector is rebuilt from
        the host without pulling the device copy back."""
        lens = np.zeros((self.max_slots,), np.int32)
        for sid, n in lens_by_seq.items():
            lens[self.slot_of[sid]] = n
        self.cache = dict(self.cache)
        self.cache["len"] = self._put(lens)

    def spec_catch_up(self, seq_id: str, tokens: list, from_pos: int):
        """Draft-cache resync after non-speculative rounds advanced the
        emitted stream without the draft: compute KV for
        ``tokens[from_pos:]`` (already-emitted prompt+output tokens) into
        the sequence's slot via the chunked-prefill body, leaving its
        cache length at ``len(tokens)``. Logits are discarded on device."""
        task = PrefillTask(seq_id=seq_id, prompt=list(tokens), pos=from_pos)
        self._compute_chunk(task, task.remaining)

    def _spec_impl(self, params, cache, st, draft, *, T):
        """Verify T = k+1 tokens per slot in ONE forward: feed
        [last_token, draft_0..draft_{k-1}], write their KV at positions
        lens..lens+k (dead slots drop out-of-bounds), attend causally, then
        accept/latch on device. Rejected positions keep their (masked)
        writes — they sit past the rolled-back length and are overwritten
        before the length crosses them. Returns
        (tokens (T, B), produced (B,), done (B,), cache, st)."""
        cfg = self.cfg
        B = st["tokens"].shape[0]
        lens = cache["len"]
        tokens_in = jnp.concatenate([st["tokens"][:, None], draft], axis=1)
        x = jnp.take(params["embed"], tokens_in, axis=0)
        positions = lens[:, None] + jnp.arange(T)[None, :]
        Smax = cache["k"].shape[3]
        bidx = jnp.arange(B)[:, None]
        wpos = jnp.where(st["active"][:, None], positions, Smax)  # dead: drop

        def body(h, xs):
            lp, kc, vc = xs

            def write_attend(q, k, v):
                kc2 = kc.at[bidx, :, wpos].set(k.astype(kc.dtype),
                                               mode="drop")
                vc2 = vc.at[bidx, :, wpos].set(v.astype(vc.dtype),
                                               mode="drop")
                a = _spec_block_attention(q, kc2, vc2, lens, kv_major=True)
                return a, (kc2, vc2)

            return _chunk_layer(h, lp, cfg, positions, write_attend)

        h, (nk, nv) = lax.scan(body, x, (params["layers"], cache["k"],
                                         cache["v"]))
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = self.model.logits(params, h)                  # (B, T, V)
        targets, produced, done, st = _spec_accept_and_latch(st, logits,
                                                             draft)
        cache = dict(cache, k=nk, v=nv)
        cache["len"] = lens + produced
        return targets.T, produced, done, self._pin_cache(cache), \
            self._pin_st(st)

    def spec_verify(self, draft_tokens: np.ndarray, host_state=None):
        """One speculative round's verification: draft_tokens (B, k) from
        the draft's fused loop; one jitted call verifies, accepts, resamples
        the residual, and truncates the cache — logits never reach the host.
        Returns (tokens (k+1, B), produced (B,), done (B,)) numpy arrays."""
        if host_state is not None:
            self._dec_st = _upload_state(host_state, self.shard)
        assert self._dec_st is not None, \
            "spec_verify needs host_state on the first call"
        T = draft_tokens.shape[1] + 1
        if T not in self._spec_fns:
            self._spec_fns[T] = jax.jit(
                _named("spec_verify", partial(self._spec_impl, T=T)),
                donate_argnums=(1, 2))
        out, produced, done, self.cache, self._dec_st = self._spec_fns[T](
            self.params, self.cache, self._dec_st,
            self._put(np.ascontiguousarray(draft_tokens)))
        return np.asarray(out), np.asarray(produced), np.asarray(done)

    def free(self, seq_id: str):
        slot = self.slot_of.pop(seq_id)
        self.free_slots.append(slot)

    def publish(self, seq_id: str, tokens: list) -> None:
        """Preemption hook: the slot backend has no content-addressed cache
        to publish into — a preempted sequence restores by full recompute."""

    def slot(self, seq_id: str) -> int:
        return self.slot_of[seq_id]

    def cache_stats(self) -> dict:
        return {}


class PagedBackend:
    """Paged KV cache backend for attention-family models."""

    def __init__(self, model: LM, params, *, max_slots: int, max_len: int,
                 page_size: int = 128, num_pages: int | None = None,
                 use_kernel: bool | None = None,
                 enable_prefix_cache: bool = False,
                 mesh=None):
        cfg = model.cfg
        assert cfg.family in ATTENTION_FAMILIES, \
            "paged backend supports attention families"
        self.model = model
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.page_size = page_size
        self.pages_per_seq = -(-max_len // page_size)
        if num_pages is None:
            num_pages = max_slots * self.pages_per_seq + 1  # +1: trash page 0
        self.kv = PagedKVCache(num_pages, page_size,
                               enable_prefix_cache=enable_prefix_cache)
        # head-major pages: the kernels stream one (page, hd) tile per
        # (page, kv head)
        pool_shape = (cfg.num_layers, num_pages, cfg.num_kv_heads,
                      page_size, cfg.head_dim)
        dtype = jnp.dtype(cfg.param_dtype)
        self.shard = ServeSharding(mesh, cfg) if mesh is not None else None
        if self.shard is None:
            self.pools = {n: jnp.zeros(pool_shape, dtype) for n in "kv"}
        else:
            # pages shard along the kv-head axis; the host-side allocator
            # (tables, refcounts, prefix index) is one copy serving every
            # shard — see PagedKVCache's docstring
            self.params = self.shard.shard_params(params)
            self.pools = {n: self.shard.pool_zeros(pool_shape, dtype)
                          for n in "kv"}
        # Kernel dispatch. ``use_kernel=None`` resolves from the platform:
        # on TPU the compiled Pallas kernels serve, on CPU the jnp
        # references. GSPMD cannot partition a Pallas kernel body, so
        # under a mesh the kernels run per-shard via shard_map over the
        # kv-head axis — only possible when the head count divides the
        # model axis; otherwise the sharded jnp reference serves. On CPU
        # an explicit ``use_kernel=True`` runs the fused decode loop as
        # the "XLA twin": same no-per-step-gather/scatter structure
        # (cached context view + tail buffers + one deferred commit), jnp
        # ops instead of a kernel. The twin is never chosen on a TPU.
        compiled = use_kernel is not False and kernels_compiled()
        self.use_kernel = compiled if use_kernel is None else use_kernel
        kernel_fits = mesh is None or shardable_kv_heads(cfg.num_kv_heads,
                                                         mesh)
        self._kernel_sharded = (self.use_kernel and mesh is not None
                                and kernel_fits)
        self._fused_use_pallas = self.use_kernel and compiled and kernel_fits
        self._needs_view = self.use_kernel and not compiled
        self._fused_tail_path = self._fused_use_pallas or self._needs_view
        self._ctx_view = None       # gathered (L, B, S, KH, hd) ctx view
        self._gather_view = jax.jit(self._gather_view_impl)
        self.free_slots = list(range(max_slots - 1, -1, -1))
        self.slot_of: dict[str, int] = {}
        self.seq_of: dict[int, str] = {}
        self.decoding: set[str] = set()
        self._prefill = {}
        # one jit object; specializes per (chunk bucket, ctx-page bucket)
        self._chunk = jax.jit(self._chunk_prefill_impl, donate_argnums=(2,))
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._cow = jax.jit(self._cow_impl, donate_argnums=(0,))
        # swap-in upload (preemption restore): write saved page KV back
        # into freshly allocated pages; specializes per page count
        self._swap = jax.jit(_named(
            "swap", lambda pools, table, k, v: self._pin_pools({
                "k": pools["k"].at[:, table].set(k),
                "v": pools["v"].at[:, table].set(v)})),
            donate_argnums=(0,))
        self._fused = {}            # K -> jitted multi-step decode+sample fn
        self._spec_fns = {}         # T -> jitted verify+accept fn
        self._dec_st = None         # device-resident per-slot decode state
        self._dev_tables = None     # device-resident (tables, lens) pair
        self._dev_tables_key = None  # kv.table_version the pair was built at

    # -- capacity -------------------------------------------------------------
    def can_admit(self, n_prompt: int) -> bool:
        return (bool(self.free_slots)
                and self.kv.can_allocate(n_prompt + 1)
                and n_prompt < self.max_len)

    @property
    def supports_chunked_prefill(self) -> bool:
        return True

    # -- sharded placement helpers ----------------------------------------------
    def _put(self, x):
        """Host upload: replicated onto the mesh device set when sharded."""
        return jnp.asarray(x) if self.shard is None \
            else self.shard.replicate(np.asarray(x))

    def _pin_pools(self, pools):
        """Pin the page pools to their head-axis sharding inside jit, so
        the layout is a fixed point across donated calls (no-op unsharded)."""
        return pools if self.shard is None else self.shard.pin_pools(pools)

    def _pin_st(self, st):
        return st if self.shard is None else self.shard.pin_replicated(st)

    # -- jitted bodies ----------------------------------------------------------
    def _attend(self, q, kp, vp, tables, lens):
        if self.use_kernel:
            if self.shard is not None:
                if not self._kernel_sharded:
                    # kv heads don't divide the model axis: shard_map can't
                    # split the kernel — run the GSPMD-sharded reference
                    return paged_attention_ref(q, kp, vp, tables, lens)
                return paged_attention_sharded(q, kp, vp, tables, lens,
                                               mesh=self.shard.mesh)
            # interpret resolves once per process: compiled on TPU,
            # interpreter elsewhere
            return paged_attn_kernel(q, kp, vp, tables, lens)
        return paged_attention_ref(q, kp, vp, tables, lens)

    def _prefill_attend(self, q, kp, vp, tables, start, kv_len):
        """Chunked-prefill attention dispatch: the paged flash-prefill
        kernel streams pages straight from the pool when compiled Pallas
        is available on a single device; the gather reference otherwise
        (under a mesh GSPMD shards the gather + einsums — the decode hot
        loop is where shard_map pays)."""
        if (self.use_kernel and kernels_compiled() and self.shard is None):
            return paged_flash_prefill(q, kp, vp, tables, start, kv_len)
        return paged_prefill_attention_ref(q, kp, vp, tables, start, kv_len)

    def _cow_impl(self, pools, src, dst):
        """Copy-on-write: duplicate page ``src`` into ``dst`` on device
        (across every layer) before a write diverges a shared page."""
        return self._pin_pools(
            {"k": pools["k"].at[:, dst].set(pools["k"][:, src]),
             "v": pools["v"].at[:, dst].set(pools["v"][:, src])})

    def _prefill_impl(self, params, toks, pools, table, true_len, *, n_pages):
        """toks: (1, S_bucket); table: (n_pages,) page ids for this seq."""
        cfg = self.cfg
        model = self.model
        S = toks.shape[1]
        x = model.embed_inputs(params, {"tokens": toks})
        positions = jnp.arange(S)[None, :]

        def body(h, xs):
            lp, kp, vp = xs
            h2, (k, v), _ = _block(h, lp, cfg, positions, moe_mode="dense",
                                   return_kv=True)
            kpg = k[0].reshape(n_pages, self.page_size,
                               *k.shape[2:]).transpose(0, 2, 1, 3)
            vpg = v[0].reshape(n_pages, self.page_size,
                               *v.shape[2:]).transpose(0, 2, 1, 3)
            kp = kp.at[table].set(kpg.astype(kp.dtype))
            vp = vp.at[table].set(vpg.astype(vp.dtype))
            return h2, (kp, vp)

        h, (nk, nv) = lax.scan(body, x, (params["layers"], pools["k"],
                                         pools["v"]))
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        idx = jnp.maximum(true_len - 1, 0)
        logits = model.logits(params, h[:, idx])
        return logits[0], self._pin_pools({"k": nk, "v": nv})

    def _chunk_prefill_impl(self, params, toks, pools, table, write_pages,
                            write_offs, start, true_len):
        """One prefill chunk against the page pool.

        toks: (1, Cb) right-padded chunk starting at absolute position
        ``start``; table: (pages_per_seq,) the sequence's full block table
        (0-padded); write_pages/write_offs: (Cb,) per-token destination in
        the pool (padded rows are routed to trash page 0). The chunk's KV is
        written first, then its queries attend over [0, start+true_len) via
        the paged gather path — cached prefix pages are read, never
        recomputed.
        """
        cfg = self.cfg
        model = self.model
        x = model.embed_inputs(params, {"tokens": toks})
        positions = start + jnp.arange(toks.shape[1])[None, :]
        kv_len = start + true_len

        def body(h, xs):
            lp, kp, vp = xs

            def write_attend(q, k, v):
                kp2 = kp.at[write_pages, :, write_offs].set(
                    k[0].astype(kp.dtype))
                vp2 = vp.at[write_pages, :, write_offs].set(
                    v[0].astype(vp.dtype))
                a = self._prefill_attend(q, kp2, vp2, table[None],
                                         start, kv_len)
                return a, (kp2, vp2)

            return _chunk_layer(h, lp, cfg, positions, write_attend)

        h, (nk, nv) = lax.scan(body, x, (params["layers"], pools["k"],
                                         pools["v"]))
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        idx = jnp.maximum(true_len - 1, 0)
        logits = model.logits(params, h[:, idx])
        return logits[0], self._pin_pools({"k": nk, "v": nv})

    def _decode_forward(self, params, pools, tokens, tables, lens,
                        page_idx, off):
        """One decode-step transformer forward against the page pool:
        write each slot's new KV at (page_idx, off), attend over
        [0, lens+1). Shared by the legacy step and the fused loop (which
        routes dead slots' writes to the trash page via page_idx/off).
        Returns (logits (B, V), pools)."""
        cfg = self.cfg
        model = self.model
        B = tokens.shape[0]
        x = jnp.take(params["embed"], tokens[:, None], axis=0)
        positions = lens[:, None]

        def body(h, xs):
            lp, kp, vp = xs
            xa = rms_norm(h, lp["norm1"], cfg.norm_eps)
            q, k, v = project_qkv(xa, lp["attn"], cfg, positions)
            kp = kp.at[page_idx, :, off].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[page_idx, :, off].set(v[:, 0].astype(vp.dtype))
            with jax.named_scope("decode_attention"):
                a = self._attend(q[:, 0], kp, vp, tables, lens + 1)  # (B,H,hd)
            h = h + (a.reshape(B, 1, -1) @ lp["attn"]["wo"])
            g = rms_norm(h, lp["norm2"], cfg.norm_eps)
            if cfg.moe:
                f, _ = moe_ffn(g, lp["moe"], cfg, mode="dense")
            else:
                f = mlp_layer(g, lp["mlp"])
            return h + f, (kp, vp)

        h, (nk, nv) = lax.scan(body, x, (params["layers"], pools["k"],
                                         pools["v"]))
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = model.logits(params, h[:, 0])
        return logits, self._pin_pools({"k": nk, "v": nv})

    def _decode_impl(self, params, pools, tokens, tables, lens):
        """tokens: (B,); tables: (B, PPS); lens: (B,) current lengths.
        The page for position ``lens`` must already exist (ensure_slot)."""
        page_slot = lens // self.page_size                     # (B,)
        page_idx = jnp.take_along_axis(tables, page_slot[:, None], 1)[:, 0]
        off = lens % self.page_size
        return self._decode_forward(params, pools, tokens, tables, lens,
                                    page_idx, off)

    # -- prefill protocol --------------------------------------------------------
    def start_prefill(self, seq_id: str, prompt: list) -> PrefillTask:
        slot = self.free_slots.pop()
        self.slot_of[seq_id] = slot
        self.seq_of[slot] = seq_id
        prompt = list(prompt)
        pages, n_cached = self.kv.allocate_with_prefix(seq_id, prompt)
        return PrefillTask(seq_id=seq_id, prompt=prompt, pos=n_cached,
                           cached_tokens=n_cached)

    def prefill_chunk(self, task: PrefillTask, budget: int | None = None):
        """Compute up to ``budget`` prompt tokens (all remaining if None).
        Returns (last_token_logits | None, tokens_computed)."""
        S = len(task.prompt)
        chunk = task.remaining if budget is None \
            else min(max(budget, 1), task.remaining)
        if (task.pos == 0 and chunk == S
                and not self.kv.enable_prefix_cache):
            # legacy fast path: whole-prompt self-attention, block KV writes
            logits = self._one_shot(task.seq_id, task.prompt)
        else:
            logits = self._compute_chunk(task, chunk)
        task.pos += chunk
        task.chunks += 1
        if task.done:
            self.kv.commit_prefix(task.seq_id, task.prompt)
            self.decoding.add(task.seq_id)
            return logits, chunk
        return None, chunk

    def prefill(self, seq_id: str, prompt: list):
        """One-shot convenience: returns last-token logits (V,)."""
        task = self.start_prefill(seq_id, prompt)
        logits, _ = self.prefill_chunk(task, None)
        return logits

    def _one_shot(self, seq_id: str, prompt: list):
        S = len(prompt)
        bucket = min(_bucket(max(S, self.page_size)), self.max_len)
        bucket = -(-bucket // self.page_size) * self.page_size
        n_pages = bucket // self.page_size
        pages = self.kv._tables[seq_id]
        # padded tail of the bucket writes land in trash page 0 (copy — do
        # not mutate the sequence's own table)
        write_table = list(pages) + [0] * (n_pages - len(pages))
        write_table = write_table[:n_pages]
        if bucket not in self._prefill:
            self._prefill[bucket] = jax.jit(
                _named("prefill", partial(self._prefill_impl,
                                          n_pages=n_pages)),
                donate_argnums=(2,))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :S] = prompt
        logits, self.pools = self._prefill[bucket](
            self.params, self._put(toks), self.pools,
            self._put(np.array(write_table, np.int32)), S)
        self._invalidate_view()
        return logits               # device-resident (V,)

    def _compute_chunk(self, task: PrefillTask, chunk: int):
        ps = self.page_size
        pos = task.pos
        # COW any shared page this chunk writes into (only possible for the
        # recomputed final token of a page-aligned full prefix hit)
        for pi in range(pos // ps, (pos + chunk - 1) // ps + 1):
            cow = self.kv.writable_page(task.seq_id, pi * ps)
            if cow is not None:
                self.pools = self._cow(self.pools, *cow)
        table = self.kv._tables[task.seq_id]
        bucket = min(_bucket(chunk), self.max_len)
        write_pages = np.zeros((bucket,), np.int32)     # pad -> trash page 0
        write_offs = np.arange(bucket, dtype=np.int32) % ps
        for j in range(chunk):
            p = pos + j
            write_pages[j] = table[p // ps]
            write_offs[j] = p % ps
        # gather only as much context as the chunk can see, bucketed so the
        # jit specializes per power-of-two page count — not per max_len
        n_ctx = min(_bucket(-(-(pos + chunk) // ps), lo=1),
                    self.pages_per_seq)
        ctx_table = np.zeros((n_ctx,), np.int32)
        ctx_table[:min(len(table), n_ctx)] = table[:n_ctx]
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :chunk] = task.prompt[pos:pos + chunk]
        logits, self.pools = self._chunk(
            self.params, self._put(toks), self.pools,
            self._put(ctx_table), self._put(write_pages),
            self._put(write_offs), pos, chunk)
        self._invalidate_view()
        return logits               # device-resident (V,)

    # -- decode -----------------------------------------------------------------
    def decode_batch(self, tokens_by_slot: np.ndarray):
        """tokens_by_slot: (max_slots,). Inactive / mid-prefill slots write
        to trash page 0."""
        for sid in self.decoding:
            self.kv.ensure_slot(sid)
            # a decode write into a still-shared page must diverge first
            cow = self.kv.writable_page(sid, self.kv.length(sid))
            if cow is not None:
                self.pools = self._cow(self.pools, *cow)
        tables = np.zeros((self.max_slots, self.pages_per_seq), np.int32)
        lens = np.zeros((self.max_slots,), np.int32)
        for slot, sid in self.seq_of.items():
            if sid not in self.decoding:
                continue
            tables[slot] = self.kv.table_array([sid], self.pages_per_seq)[0]
            lens[slot] = self.kv.length(sid)
        logits, self.pools = self._decode(
            self.params, self.pools, self._put(tokens_by_slot),
            self._put(tables), self._put(lens))
        self._invalidate_view()
        for sid in self.decoding:
            self.kv.advance(sid)
        return _logits_to_host(logits)

    # -- fused decode fast path --------------------------------------------------
    @property
    def supports_fused_decode(self) -> bool:
        return True

    def _fused_impl(self, params, pools, st, tables, lens, *, K):
        """K fused decode+sample+stop-check steps against the page pool.

        Per step: write the fed token's KV at position ``lens`` (dead slots
        route to trash page 0), attend over the block tables, sample on
        device, advance lens/n_gen only for live slots, latch ``done`` on
        stop-token or generation-limit hits. The host pre-allocates pages
        and resolves copy-on-write for all K positions before the call, so
        the block tables are loop-invariant. Returns
        (tokens (K, B), produced (B,), done (B,), pools, st, lens).
        """
        ps = self.page_size
        B = st["tokens"].shape[0]

        def step(i, carry):
            pools, tokens, n_gen, lens, done, produced, out = carry
            live = st["active"] & ~done
            page_slot = lens // ps
            page_idx = jnp.take_along_axis(tables, page_slot[:, None], 1)[:, 0]
            page_idx = jnp.where(live, page_idx, 0)      # dead slots -> trash
            off = jnp.where(live, lens % ps, 0)
            logits, pools = self._decode_forward(params, pools, tokens,
                                                 tables, lens, page_idx, off)
            lens = lens + live.astype(jnp.int32)
            tokens, n_gen, done, produced = _sample_and_latch(
                st, logits, tokens, n_gen, done, produced, live)
            out = out.at[i].set(tokens)
            return pools, tokens, n_gen, lens, done, produced, out

        pools, tokens, n_gen, lens, done, produced, out = lax.fori_loop(
            0, K, step,
            (self._pin_pools(pools), st["tokens"], st["n_gen"], lens,
             jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32),
             jnp.zeros((K, B), jnp.int32)))
        st = self._pin_st(dict(st, tokens=tokens, n_gen=n_gen))
        if self.shard is not None:
            lens = self.shard.pin(lens, jax.sharding.PartitionSpec())
        return out, produced, done, pools, st, lens

    # -- fused decode, kernel path ----------------------------------------------
    def _gather_view_impl(self, pools, tables):
        """Materialize the contiguous (L, B, S, KH, hd) view of the
        committed pages — once per allocator state, not once per step.
        The cache is keyed on ``kv.table_version`` through
        ``_refresh_tables`` (a version bump re-uploads the tables and
        drops the view) plus explicit ``_invalidate_view`` calls at every
        pool-mutation site outside the fused loop."""
        view = {n: jax.vmap(lambda p: gather_kv(p, tables))(pools[n])
                for n in ("k", "v")}
        return view if self.shard is None else self.shard.pin_view(view)

    def _invalidate_view(self) -> None:
        """Drop the cached context view after any pool mutation outside
        the fused loop — the next fused call re-gathers. Seven sites:
        prefill writes (``_one_shot``, ``_compute_chunk``), legacy decode
        (``decode_batch``), COW resolution (``_resolve_cow``), the device
        table re-upload (``_refresh_tables``), spec-decode verification
        (``spec_verify``), and swap-in. ``fused_decode`` itself is exempt:
        it maintains ``self._ctx_view`` in place from the donated call's
        return. The cache-invalidation firstlint rule enforces this
        inventory — a new pool-mutating method without an invalidation
        call (or in-place view maintenance) fails CI."""
        self._ctx_view = None

    def _fused_kernel_impl(self, params, pools, view, st, tables, lens, *,
                           K):
        """K fused decode steps with no per-step page gather or scatter.

        The loop body never touches the page pool: each step appends its
        new KV to head-major (L, B, KH, K, hd) tail buffers and attends committed
        context + tail under ONE softmax — via the Pallas decode-tail
        kernel reading pages directly (TPU; shard_map'd over kv heads on a
        mesh, ``view`` is None), or via the cached contiguous ``view``
        (the XLA twin elsewhere). After the loop, one batched scatter
        commits the tails to the pool and advances the view in place, so
        the next call reuses it unless the allocator moved. Emits the same
        token stream as ``_fused_impl``: step i of slot b attends exactly
        positions [0, lens0[b] + produced[b] + 1) with the same values.
        """
        cfg = self.cfg
        ps = self.page_size
        B = st["tokens"].shape[0]
        L, KH, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
        dt = pools["k"].dtype
        lens0 = lens
        kv_ctx = (pools["k"], pools["v"]) if view is None \
            else (view["k"], view["v"])

        def forward(tokens, written, k_tails, v_tails):
            x = jnp.take(params["embed"], tokens[:, None], axis=0)
            positions = (lens0 + written)[:, None]
            tail_lens = written + 1
            bidx = jnp.arange(B)

            def body(h, xs):
                lp, kc, vc, kt, vt = xs
                xa = rms_norm(h, lp["norm1"], cfg.norm_eps)
                q, k, v = project_qkv(xa, lp["attn"], cfg, positions)
                kt = kt.at[bidx, :, written].set(k[:, 0].astype(dt))
                vt = vt.at[bidx, :, written].set(v[:, 0].astype(dt))
                with jax.named_scope("decode_attention"):
                    if view is not None:
                        a = decode_tail_attention_ref(q[:, 0], kc, vc, lens0,
                                                      kt, vt, tail_lens)
                    elif self.shard is not None:
                        a = fused_decode_attention_sharded(
                            q[:, 0], kc, vc, tables, lens0, kt, vt,
                            tail_lens, mesh=self.shard.mesh)
                    else:
                        a = fused_decode_attention(q[:, 0], kc, vc, tables,
                                                   lens0, kt, vt, tail_lens)
                h = h + (a.reshape(B, 1, -1) @ lp["attn"]["wo"])
                g = rms_norm(h, lp["norm2"], cfg.norm_eps)
                if cfg.moe:
                    f, _ = moe_ffn(g, lp["moe"], cfg, mode="dense")
                else:
                    f = mlp_layer(g, lp["mlp"])
                return h + f, (kt, vt)

            h, (k_tails, v_tails) = lax.scan(
                body, x, (params["layers"], *kv_ctx, k_tails, v_tails))
            h = rms_norm(h, params["final_norm"], cfg.norm_eps)
            return self.model.logits(params, h[:, 0]), k_tails, v_tails

        def step(i, carry):
            k_tails, v_tails, tokens, n_gen, done, produced, out = carry
            live = st["active"] & ~done
            # ``produced`` doubles as the tail write cursor: both advance
            # by ``live`` each step, so slot b's valid tail rows are
            # exactly [0, produced[b]) and this step writes row
            # produced[b] (dead slots overwrite that row in place — their
            # outputs are discarded by the live mask, like the trash-page
            # writes on the reference path)
            logits, k_tails, v_tails = forward(tokens, produced, k_tails,
                                               v_tails)
            tokens, n_gen, done, produced = _sample_and_latch(
                st, logits, tokens, n_gen, done, produced, live)
            out = out.at[i].set(tokens)
            return k_tails, v_tails, tokens, n_gen, done, produced, out

        k_tails, v_tails, tokens, n_gen, done, produced, out = lax.fori_loop(
            0, K, step,
            (jnp.zeros((L, B, KH, K, hd), dt),
             jnp.zeros((L, B, KH, K, hd), dt),
             st["tokens"], st["n_gen"], jnp.zeros((B,), bool),
             jnp.zeros((B,), jnp.int32), jnp.zeros((K, B), jnp.int32)))

        # one deferred commit: scatter every valid tail row into its page
        # (rows past ``produced`` drop via an out-of-bounds page id)
        jj = jnp.arange(K)[None, :]
        pos = lens0[:, None] + jj                               # (B, K)
        valid = jj < produced[:, None]
        page_slot = jnp.minimum(pos // ps, tables.shape[1] - 1)
        page_idx = jnp.take_along_axis(tables, page_slot, axis=1)
        page_idx = jnp.where(valid, page_idx, pools["k"].shape[1])
        off = pos % ps

        def commit(pool_l, tail_l):
            return pool_l.at[page_idx, :, off].set(
                tail_l.transpose(0, 2, 1, 3), mode="drop")

        pools = self._pin_pools(
            {"k": jax.vmap(commit)(pools["k"], k_tails),
             "v": jax.vmap(commit)(pools["v"], v_tails)})
        if view is not None:
            S = view["k"].shape[2]
            posv = jnp.where(valid, pos, S)       # invalid rows drop (OOB)
            brow = jnp.arange(B)[:, None]

            def advance(view_l, tail_l):
                return view_l.at[brow, posv].set(
                    tail_l.transpose(0, 2, 1, 3), mode="drop")

            view = {"k": jax.vmap(advance)(view["k"], k_tails),
                    "v": jax.vmap(advance)(view["v"], v_tails)}
            if self.shard is not None:
                view = self.shard.pin_view(view)
        lens = lens0 + produced
        st = self._pin_st(dict(st, tokens=tokens, n_gen=n_gen))
        if self.shard is not None:
            lens = self.shard.pin(lens, jax.sharding.PartitionSpec())
        return out, produced, done, pools, view, st, lens

    def fused_decode(self, K: int, host_state: dict | None = None):
        """Run up to K decode steps on device; sync only token ids and flags.

        Host-side prep per call: allocate page headroom for K tokens per
        decoding sequence (clamping K down if the pool is tight) and resolve
        copy-on-write for every page the loop will write. Block tables and
        lengths are uploaded only when the allocator state changed
        (``kv.table_version``) or the engine re-seeds the slot state;
        otherwise the device-resident copies carry over. Returns
        (tokens (K_eff, max_slots), produced, done) as numpy arrays.
        """
        with TraceAnnotation("engine.decode.prep"):
            K_eff = self._reserve_headroom(max(1, K))
            self._resolve_cow(K_eff)
            self._refresh_tables(force=host_state is not None)
            if host_state is not None:
                self._dec_st = _upload_state(host_state, self.shard)
            assert self._dec_st is not None, \
                "fused_decode needs host_state on the first call"
            if K_eff not in self._fused:
                # tables are NOT donated: the device copy is reused across
                # calls until the allocator bumps table_version
                if self._fused_tail_path:
                    self._fused[K_eff] = jax.jit(
                        _named("fused_decode",
                               partial(self._fused_kernel_impl, K=K_eff)),
                        donate_argnums=(1, 2, 3, 5))
                else:
                    self._fused[K_eff] = jax.jit(
                        _named("fused_decode",
                               partial(self._fused_impl, K=K_eff)),
                        donate_argnums=(1, 2, 4))
            tables_d, lens_d = self._dev_tables
            if self._needs_view and self._ctx_view is None:
                self._ctx_view = self._gather_view(self.pools, tables_d)
        with TraceAnnotation("engine.decode.wait"):
            if self._fused_tail_path:
                (out, produced, done, self.pools, self._ctx_view,
                 self._dec_st, lens_d) = self._fused[K_eff](
                    self.params, self.pools, self._ctx_view, self._dec_st,
                    tables_d, lens_d)
            else:
                out, produced, done, self.pools, self._dec_st, lens_d = \
                    self._fused[K_eff](self.params, self.pools,
                                       self._dec_st, tables_d, lens_d)
            self._dev_tables = (tables_d, lens_d)
            produced_np = np.asarray(produced)
            out, done = np.asarray(out), np.asarray(done)
        for slot, sid in self.seq_of.items():
            if sid in self.decoding:
                self.kv.advance_n(sid, int(produced_np[slot]))
        return out, produced_np, done

    def _reserve_headroom(self, n: int) -> int:
        """Reserve page headroom for up to ``n`` token writes per decoding
        sequence. Guarantees every live sequence ONE token of headroom
        first (the legacy ensure_slot contract: raise loudly rather than
        routing a live KV write to the trash page) — only then extends
        best-effort toward ``n``, so one sequence's multi-token headroom
        can never starve a later sequence out of its single page. Returns
        the write count the pool (and ``max_len``) can actually take."""
        for sid in self.decoding:
            if self.kv.ensure_capacity(sid, 1) <= 0:
                raise OutOfPages(f"{sid}: pool exhausted on decode append")
        for sid in self.decoding:
            ahead = max(1, min(n, self.max_len - self.kv.length(sid)))
            n = min(n, max(1, self.kv.ensure_capacity(sid, ahead)))
        return n

    def _resolve_cow(self, n_writes: int) -> None:
        """COW every still-shared page the next ``n_writes`` decode/verify
        token writes of each decoding sequence would land in."""
        ps = self.page_size
        for sid in self.decoding:
            pos0 = self.kv.length(sid)
            for pi in range(pos0 // ps, (pos0 + n_writes - 1) // ps + 1):
                cow = self.kv.writable_page(sid, pi * ps)
                if cow is not None:
                    self.pools = self._cow(self.pools, *cow)
                    self._invalidate_view()

    def _refresh_tables(self, force: bool) -> None:
        """(Re)upload the device-resident (block tables, lengths) pair when
        the allocator state moved from under the cached copy."""
        if (force or self._dev_tables is None
                or self._dev_tables_key != self.kv.table_version):
            tables = np.zeros((self.max_slots, self.pages_per_seq), np.int32)
            lens = np.zeros((self.max_slots,), np.int32)
            for slot, sid in self.seq_of.items():
                if sid in self.decoding:
                    tables[slot] = self.kv.table_array(
                        [sid], self.pages_per_seq)[0]
                    lens[slot] = self.kv.length(sid)
            self._dev_tables = (self._put(tables), self._put(lens))
            self._dev_tables_key = self.kv.table_version
            # allocator moved (or slot state re-seeded): the cached
            # context view's page mapping is stale with it
            self._invalidate_view()

    # -- speculative decoding ----------------------------------------------------
    @property
    def supports_spec_decode(self) -> bool:
        return True

    def spec_headroom(self, k: int) -> int:
        """Reserve page headroom for a verify round of k draft tokens + the
        guaranteed target token; returns the k the pool can actually take
        (the same reservation policy as ``fused_decode``)."""
        return self._reserve_headroom(k + 1) - 1

    def reset_lens(self, lens_by_seq: dict[str, int]) -> None:
        """Truncate-on-reject for the draft's paged cache between rounds:
        roll each sequence's logical length back (pages stay as headroom)."""
        for sid, n in lens_by_seq.items():
            self.kv.rollback_to(sid, n)

    def spec_catch_up(self, seq_id: str, tokens: list, from_pos: int):
        """Draft-cache resync after non-speculative rounds advanced the
        emitted stream without the draft: compute KV for
        ``tokens[from_pos:]`` into the sequence's pages via the
        chunked-prefill body, leaving its logical length at
        ``len(tokens)``. Logits are discarded on device."""
        want = len(tokens)
        self.kv.rollback_to(seq_id, from_pos)
        need = want - self.kv.length(seq_id)
        if self.kv.ensure_capacity(seq_id, need) < need:
            raise OutOfPages(f"{seq_id}: pool exhausted on draft catch-up")
        task = PrefillTask(seq_id=seq_id, prompt=list(tokens), pos=from_pos)
        self._compute_chunk(task, task.remaining)
        self.kv.advance_n(seq_id, need)
        self.kv.table_version += 1       # device lens copy is now stale

    def _spec_impl(self, params, pools, st, tables, lens, draft, *, T):
        """Verify T = k+1 tokens per slot against the page pool in ONE
        forward: write their KV at positions lens..lens+k (dead slots to
        trash page 0), attend over the block tables with per-position
        causal masks, then accept/latch on device. Returns
        (tokens (T, B), produced (B,), done (B,), pools, st, lens)."""
        cfg = self.cfg
        ps = self.page_size
        tokens_in = jnp.concatenate([st["tokens"][:, None], draft], axis=1)
        x = jnp.take(params["embed"], tokens_in, axis=0)
        positions = lens[:, None] + jnp.arange(T)[None, :]
        live = st["active"][:, None]
        page_slot = jnp.minimum(positions // ps, tables.shape[1] - 1)
        page_idx = jnp.take_along_axis(tables, page_slot, axis=1)
        page_idx = jnp.where(live, page_idx, 0)          # dead slots -> trash
        off = jnp.where(live, positions % ps, 0)

        def body(h, xs):
            lp, kp, vp = xs

            def write_attend(q, k, v):
                kp2 = kp.at[page_idx, :, off].set(k.astype(kp.dtype))
                vp2 = vp.at[page_idx, :, off].set(v.astype(vp.dtype))
                kg = gather_kv(kp2, tables)
                vg = gather_kv(vp2, tables)
                a = _spec_block_attention(q, kg, vg, lens, kv_major=False)
                return a, (kp2, vp2)

            return _chunk_layer(h, lp, cfg, positions, write_attend)

        h, (nk, nv) = lax.scan(body, x, (params["layers"], pools["k"],
                                         pools["v"]))
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = self.model.logits(params, h)                  # (B, T, V)
        targets, produced, done, st = _spec_accept_and_latch(st, logits,
                                                             draft)
        lens = lens + produced
        pools = self._pin_pools({"k": nk, "v": nv})
        if self.shard is not None:
            lens = self.shard.pin(lens, jax.sharding.PartitionSpec())
        return targets.T, produced, done, pools, self._pin_st(st), lens

    def spec_verify(self, draft_tokens: np.ndarray, host_state=None):
        """One speculative round's verification (page headroom must already
        be reserved via ``spec_headroom``). Resolves copy-on-write for every
        page the verify block writes, then runs verify + accept + residual
        resample + truncate in one jitted call; logits never reach the host.
        Returns (tokens (k+1, B), produced (B,), done (B,)) numpy arrays."""
        T = draft_tokens.shape[1] + 1
        self._resolve_cow(T)
        self._refresh_tables(force=host_state is not None)
        if host_state is not None:
            self._dec_st = _upload_state(host_state, self.shard)
        assert self._dec_st is not None, \
            "spec_verify needs host_state on the first call"
        if T not in self._spec_fns:
            self._spec_fns[T] = jax.jit(
                _named("spec_verify", partial(self._spec_impl, T=T)),
                donate_argnums=(1, 2, 4))
        tables_d, lens_d = self._dev_tables
        out, produced, done, self.pools, self._dec_st, lens_d = \
            self._spec_fns[T](self.params, self.pools, self._dec_st,
                              tables_d, lens_d,
                              self._put(np.ascontiguousarray(draft_tokens)))
        self._invalidate_view()
        self._dev_tables = (tables_d, lens_d)
        produced_np = np.asarray(produced)
        for slot, sid in self.seq_of.items():
            if sid in self.decoding:
                self.kv.advance_n(sid, int(produced_np[slot]))
        return np.asarray(out), produced_np, np.asarray(done)

    def free(self, seq_id: str):
        slot = self.slot_of.pop(seq_id)
        self.seq_of.pop(slot, None)
        self.decoding.discard(seq_id)
        self.free_slots.append(slot)
        self.kv.free(seq_id)

    # -- preemption support ------------------------------------------------------
    def publish(self, seq_id: str, tokens: list) -> None:
        """Register a preempted sequence's full pages (prompt AND decoded
        tokens) in the content index before they are freed: they park in
        the LRU and the restore prefill content-matches them back, so a
        preempt/restore round trip recomputes only the partial tail page.
        No-op when the prefix cache is disabled."""
        self.kv.commit_prefix(seq_id, tokens)

    def swap_out(self, seq_id: str) -> dict:
        """Copy a sequence's computed KV pages to host memory (the swap
        restore path, for when a prefix-cache hit cannot be counted on).
        Only the pages covering the sequence's logical length are saved —
        trailing headroom pages hold no committed KV. The caller frees the
        sequence afterwards; ``swap_in`` restores into fresh pages."""
        n_tokens = self.kv.length(seq_id)
        n_pages = self.kv.pages_needed(n_tokens)
        table = np.array(self.kv._tables[seq_id][:n_pages], np.int32)
        return {"k": np.asarray(self.pools["k"][:, table]),
                "v": np.asarray(self.pools["v"][:, table]),
                "n_tokens": n_tokens}

    def swap_in(self, seq_id: str, n_tokens: int, blob: dict) -> None:
        """Rebind a swapped-out sequence: reserve a slot, allocate fresh
        pages, upload the saved KV, and rejoin the decode set — no
        recompute. ``n_tokens`` must equal the blob's saved length."""
        assert n_tokens == blob["n_tokens"], \
            f"{seq_id}: swap blob holds {blob['n_tokens']} tokens, " \
            f"restore asked for {n_tokens}"
        slot = self.free_slots.pop()
        self.slot_of[seq_id] = slot
        self.seq_of[slot] = seq_id
        pages = self.kv.allocate(seq_id, n_tokens)
        self.pools = self._swap(self.pools,
                                self._put(np.array(pages, np.int32)),
                                self._put(blob["k"]), self._put(blob["v"]))
        self._invalidate_view()
        self.decoding.add(seq_id)

    def slot(self, seq_id: str) -> int:
        return self.slot_of[seq_id]

    def cache_stats(self) -> dict:
        s = dict(self.kv.stats)
        s["hit_rate"] = self.kv.hit_rate()
        s["cached_free_pages"] = self.kv.cached_free_pages
        return s
