"""Minimal batched serving engine.

Weights load by memory-mapping the RawArray checkpoint (zero-copy until
pages are touched — the paper's mmap story applied to model serving, where
cold-start latency is checkpoint-read latency). Requests are batched,
prefilled together (right-aligned padding-free: equal-length prompts per
batch for simplicity), then decoded step by step with a shared KV cache.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import restore_pipelined
from ..models.config import ModelConfig


class ServeEngine:
    def __init__(
        self,
        model,
        params: Any = None,
        *,
        checkpoint: Optional[str] = None,
    ):
        self.model = model
        self.cfg: ModelConfig = model.cfg
        if params is None:
            if checkpoint is None:
                raise ValueError("need params or checkpoint")
            like = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
            # cold start is checkpoint-read latency: the overlapped restore
            # engine (DESIGN.md §13)
            params, _, _ = restore_pipelined(checkpoint, like)
        self.params = params
        self._prefill = jax.jit(model.prefill)
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def generate(
        self,
        prompts: np.ndarray,  # (B, S_prompt) int32 — equal lengths
        max_new: int = 32,
        *,
        temperature: float = 0.0,
        seed: int = 0,
    ) -> np.ndarray:
        B, S = prompts.shape
        logits, cache = self._prefill_with_capacity(prompts, S + max_new)

        out = np.zeros((B, max_new), dtype=np.int32)
        rng = jax.random.PRNGKey(seed)
        tok = self._sample(logits, temperature, rng)
        out[:, 0] = np.asarray(tok)[:, 0]
        for i in range(1, max_new):
            rng, sub = jax.random.split(rng)
            logits, cache = self._decode(self.params, cache, tok)
            tok = self._sample(logits, temperature, sub)
            out[:, i] = np.asarray(tok)[:, 0]
        return out

    def _prefill_with_capacity(self, prompts: np.ndarray, capacity: int):
        """Prefill such that the returned cache can absorb ``capacity - S``
        further decode steps. Family-dependent:

        * attention families: prompts are right-padded to ``capacity`` so the
          KV cache has room; ``pos`` is reset to the true prompt length
          (causal masking keeps the padding region dead until overwritten);
        * pure SSM: the cache is O(1) — plain prefill;
        * hybrid: the shared-attn cache is length-bound, so we allocate an
          empty capacity cache and replay the prompt token-by-token.
        """
        B, S = prompts.shape
        fam = self.cfg.family
        if fam == "ssm":
            return self._prefill(self.params, {"tokens": jnp.asarray(prompts)})
        if fam == "hybrid":
            cache = self.model.empty_cache(B, capacity)
            logits = None
            tok_arr = jnp.asarray(prompts)
            for t in range(S):
                logits, cache = self._decode(self.params, cache, tok_arr[:, t : t + 1])
            return logits, cache
        # prefill the first S-1 tokens (padded to capacity so the cache has
        # room), rewind pos, then feed the last prompt token as a decode step
        # — its logits are exactly the first-new-token distribution.
        padded = np.zeros((B, capacity), dtype=prompts.dtype)
        padded[:, : S - 1] = prompts[:, : S - 1]
        _, cache = self._prefill(self.params, {"tokens": jnp.asarray(padded)})
        cache["pos"] = jnp.asarray(S - 1, jnp.int32)
        logits, cache = self._decode(self.params, cache, jnp.asarray(prompts[:, S - 1 : S]))
        return logits, cache

    def _sample(self, logits: jax.Array, temperature: float, rng) -> jax.Array:
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return jax.random.categorical(rng, logits / temperature, axis=-1).astype(jnp.int32)[:, None]
