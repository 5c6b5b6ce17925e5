"""Batched serving engine over the model's prefill/decode steps.

The engine runs a static-batch generate loop (prefill once, decode N) with
the chip's FaultContext applied — i.e. serving a fault-aware model ON the
faulty chip it was tuned for. Greedy or temperature sampling.

Sampling and decode are fused into ONE jitted step: log_softmax, the
greedy/categorical choice, the chosen-token logprob gather and the next
decode_step all run in a single dispatch per token, instead of a host
round-trip for each of them. Temperature is a traced scalar (one compile
covers greedy and every temperature); greedy token choice is exactly
``argmax`` — independent of the sampling key — so temperature=0.0
reproduces the unfused reference token-for-token.

The same fused step powers both serving engines (see README.md here):
``ServeEngine`` jits it, and the continuous-batching engine
(``repro.serve.continuous``; ``repro.fleet.serve.ShardedFleetServeEngine``
is it over a chip axis, the step vmapped over each device's chips) runs its
*masked* form — per-slot ``active`` masking over a paged cache — so
finished slots emit pad tokens with logprob 0 and stop writing KV until the
scheduler refills them. ``ServeEngine`` masks a faulty chip's weights
inside every GEMM; it is the reference the continuous engine's premasked
serving is pinned against.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.masking import FaultContext, healthy
from repro.models import model as M
from repro.serve.bucketing import (
    DEFAULT_PREFILL_BUCKETS,
    ladder_rung,
    validate_buckets,
)
from repro.serve.kvcache import DEFAULT_PAGE_SIZE, round_up_to_page


@dataclass
class GenerateResult:
    tokens: jax.Array  # (B, prompt + generated)
    logprobs: jax.Array  # (B, generated)


def make_sample_decode(cfg, *, pad_id: int = 0):
    """Build the fused sampling+decode step for one chip.

    ``(params, cur_logits, cache, key, ctx, temperature) ->
    (next_token, token_logprob, next_logits, cache, key)`` — log_softmax,
    the greedy/categorical choice, the chosen-token logprob gather and the
    next ``decode_step`` in a single traced body. ``ServeEngine`` jits it
    directly (one dispatch per token); the fleet engine vmaps it over the
    chips on each device, so a whole fleet advances one token per dispatch.

    With ``active`` (a per-slot bool mask) the step runs in *masked* form
    and returns ``(emitted, token_logprob, next_logits, cache, key,
    new_active, new_remaining)``: inactive slots emit ``pad_id`` with
    logprob 0, a slot retires when it samples ``eos_id`` (scalar; pass -1
    to disable) or exhausts its per-slot ``remaining`` budget, and the mask
    is forwarded to ``decode_step`` so retired slots stop writing KV
    (paged caches redirect their writes to the scratch page). ``cache`` may
    be the dense cache or a paged one — ``decode_step`` dispatches on it.
    """

    def sample_decode(
        p, cur, cache, key, ctx, temperature, active=None, eos_id=None, remaining=None
    ):
        lp = jax.nn.log_softmax(cur.astype(jnp.float32), axis=-1)
        key, sub = jax.random.split(key)
        # temperature is traced: guard the division so the (unused)
        # sampled branch stays finite when temperature == 0
        safe_t = jnp.maximum(temperature, 1e-6)
        sampled = jax.random.categorical(sub, lp / safe_t, axis=-1)
        nxt = jnp.where(temperature > 0, sampled, jnp.argmax(lp, axis=-1))
        tok_lp = jnp.take_along_axis(lp, nxt[:, None], axis=-1)[:, 0]
        if active is None:
            step_logits, cache = M.decode_step(p, nxt[:, None], cache, cfg, ctx)
            return nxt, tok_lp, step_logits[:, 0], cache, key
        emitted = jnp.where(active, nxt, jnp.asarray(pad_id, nxt.dtype))
        tok_lp = jnp.where(active, tok_lp, 0.0)
        new_active = active
        if eos_id is not None:
            new_active = new_active & (nxt != eos_id)
        new_remaining = remaining
        if remaining is not None:
            new_remaining = remaining - active.astype(remaining.dtype)
            new_active = new_active & (new_remaining > 0)
        step_logits, cache = M.decode_step(
            p, emitted[:, None], cache, cfg, ctx, active=new_active
        )
        return emitted, tok_lp, step_logits[:, 0], cache, key, new_active, new_remaining

    return sample_decode


class ServeEngine:
    """Static-batch serving: one rectangular prompt batch, N decode steps.

    ``max_len`` is the KV capacity. ``max_len=None`` derives it per
    ``generate`` call as ``prompt_len + max_new_tokens`` rounded up the
    bucket ladder — explicit capacity instead of a 4096-slot default.

    Prompt widths are BUCKETED (``repro.serve.bucketing``): ``generate``
    pads the prompt up to the smallest ladder rung that holds it and runs
    prefill with a *traced* ``valid_len``, so the compiled prefill program
    set is one program per (rung, capacity) pair instead of one per
    distinct prompt length — the ``RCP001:serve.prefill:prompt_len`` hazard
    the static analyzer used to baseline. ``prefill_buckets=None`` restores
    the exact-length behaviour; non-causal families (SSM state scans,
    encoders) always take the exact path since pad tokens would corrupt
    their state.
    """

    def __init__(
        self,
        cfg,
        params,
        ctx: Optional[FaultContext] = None,
        *,
        max_len: Optional[int] = 4096,
        page_size: int = DEFAULT_PAGE_SIZE,
        pad_id: int = 0,
        prefill_buckets=DEFAULT_PREFILL_BUCKETS,
    ):
        self.cfg = cfg
        self.params = params
        self.ctx = ctx or healthy()
        self.max_len = max_len
        self.page_size = page_size
        self.pad_id = pad_id
        if prefill_buckets is not None and not (cfg.has_ssm or cfg.is_encoder):
            self.prefill_buckets = validate_buckets(prefill_buckets)
        else:
            self.prefill_buckets = None
        self._prefill_len = jax.jit(
            lambda p, b, ctx, cache_len, valid_len=None: M.prefill(
                p, b, cfg, ctx, cache_len=cache_len, valid_len=valid_len
            ),
            static_argnums=3,
        )
        self._prefill = self._prefill_fixed_len
        # the generate loop re-binds cache/cur/key from each dispatch's
        # outputs, so those operands are donated: XLA aliases the KV cache
        # in place instead of copying it every token (repro.analysis DON001)
        self._decode = jax.jit(
            lambda p, t, c, ctx: M.decode_step(p, t, c, cfg, ctx),
            donate_argnums=(2,),
        )

        self._sample_decode = jax.jit(
            make_sample_decode(cfg, pad_id=pad_id), donate_argnums=(1, 2, 3)
        )

    def _prefill_fixed_len(self, p, b, ctx):
        """Unfused-protocol prefill at the engine's fixed capacity. With
        ``max_len=None`` the capacity depends on the generation budget only
        ``generate`` knows — call ``_prefill_len`` with it explicitly."""
        if self.max_len is None:
            raise ValueError(
                "ServeEngine(max_len=None) derives KV capacity per generate "
                "call; use _prefill_len(params, batch, ctx, cache_len) with "
                "cache_len_for(prompt_len, max_new_tokens)"
            )
        return self._prefill_len(p, b, ctx, self.max_len)

    def cache_len_for(self, prompt_len: int, max_new_tokens: int) -> int:
        """KV capacity one generate call needs. Bucketed engines quantize it
        up the (doubling-extended) ladder so capacity, like prompt width,
        draws from a closed set; unbucketed engines round to the page."""
        if self.max_len is not None:
            return self.max_len
        need = prompt_len + max_new_tokens
        if self.prefill_buckets is not None:
            return ladder_rung(need, self.prefill_buckets)
        return round_up_to_page(need, self.page_size)

    def generate(
        self,
        prompts: jax.Array,  # (B, S) token ids
        *,
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        key: Optional[jax.Array] = None,
        eos_id: Optional[int] = None,
    ) -> GenerateResult:
        plen = prompts.shape[1]
        cache_len = self.cache_len_for(plen, max_new_tokens)
        if self.prefill_buckets is not None:
            # pad the prompt up to its ladder rung (never past capacity) and
            # trace the real length: one compiled prefill per (rung,
            # capacity) pair regardless of the traffic's prompt lengths
            width = min(ladder_rung(plen, self.prefill_buckets), cache_len)
            padded = prompts
            if width > plen:
                padded = jnp.concatenate(
                    [
                        prompts,
                        jnp.full((prompts.shape[0], width - plen), self.pad_id,
                                 prompts.dtype),
                    ],
                    axis=1,
                )
            logits, cache = self._prefill_len(
                self.params, {"tokens": padded}, self.ctx, cache_len,
                jnp.int32(plen),
            )
        else:
            logits, cache = self._prefill_len(
                self.params, {"tokens": prompts}, self.ctx, cache_len
            )
        toks = [prompts]
        lps = []
        cur = logits
        key = key if key is not None else jax.random.PRNGKey(0)
        temp = jnp.float32(temperature)
        if eos_id is None:
            for _ in range(max_new_tokens):
                nxt, tok_lp, cur, cache, key = self._sample_decode(
                    self.params, cur, cache, key, self.ctx, temp
                )
                lps.append(tok_lp)
                toks.append(nxt[:, None])
        else:
            # EOS masking: a finished sequence emits pad_id with logprob 0
            # for the rest of the batch — the same per-slot semantics the
            # continuous engine retires slots under.
            active = jnp.ones((prompts.shape[0],), bool)
            eos = jnp.asarray(eos_id, jnp.int32)
            for _ in range(max_new_tokens):
                nxt, tok_lp, cur, cache, key, active, _ = self._sample_decode(
                    self.params, cur, cache, key, self.ctx, temp, active, eos
                )
                lps.append(tok_lp)
                toks.append(nxt[:, None])
        return GenerateResult(
            tokens=jnp.concatenate(toks, axis=1), logprobs=jnp.stack(lps, axis=1)
        )
