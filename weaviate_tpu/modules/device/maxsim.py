"""ColBERT-style MaxSim (late interaction) as a device rerank module.

The same Chamfer similarity ``index/multivector.py:maxsim_scores``
computes host-side — sum over query tokens of the max dot product over
document tokens — expressed over a BATCHED candidate axis so it slots
into the fused search program's rerank stage (reference
``hnsw/search.go:927`` rescore loop → one einsum per batch).
"""

from __future__ import annotations

import dataclasses
from typing import ClassVar

import numpy as np

from weaviate_tpu.modules.device.base import DeviceRerankModule


def batched_maxsim(q_tokens, q_mask, cand_tokens, cand_mask):
    """[B, C] masked MaxSim, jit-traceable — THE late-interaction core
    every device module composes (the finite-guard semantics live here
    once): masked doc tokens are -inf before the max; a candidate with
    no live tokens contributes 0 per query token (matching the host
    ``maxsim_scores`` guard); masked query tokens contribute 0. The
    product's operands are the candidate planes' dtype (bfloat16: the
    query's tokens are rounded to it), its sums float32."""
    import jax.numpy as jnp

    sims = jnp.einsum("bqd,bctd->bcqt", q_tokens.astype(cand_tokens.dtype),
                      cand_tokens, preferred_element_type=jnp.float32)
    sims = jnp.where(cand_mask[:, :, None, :], sims, -jnp.inf)
    best = jnp.max(sims, axis=3)                     # [B, C, Tq]
    best = jnp.where(jnp.isfinite(best), best, 0.0)
    best = jnp.where(q_mask[:, None, :], best, 0.0)
    return jnp.sum(best, axis=2)                     # [B, C]


def batched_maxsim_host(q_tokens, q_mask, cand_tokens, cand_mask
                        ) -> np.ndarray:
    """The numpy twin of :func:`batched_maxsim` (fallback tier): the
    query's tokens rounded to the candidates' dtype, float32 from there."""
    cand_tokens = np.asarray(cand_tokens)
    sims = np.einsum("bqd,bctd->bcqt",
                     np.asarray(q_tokens).astype(cand_tokens.dtype)
                     .astype(np.float32),
                     cand_tokens.astype(np.float32))
    sims = np.where(cand_mask[:, :, None, :], sims, -np.inf)
    best = sims.max(axis=3)
    best = np.where(np.isfinite(best), best, 0.0)
    best = np.where(q_mask[:, None, :], best, 0.0)
    return best.sum(axis=2).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class MaxSimRerank(DeviceRerankModule):
    """score[b, c] = Σ_q max_t  q_tokens[b, q] · cand_tokens[b, c, t]."""

    name: ClassVar[str] = "rerank-maxsim"

    def score(self, q_tokens, q_mask, cand_tokens, cand_mask):
        return batched_maxsim(q_tokens, q_mask, cand_tokens, cand_mask)

    def host_score(self, q_tokens, q_mask, cand_tokens, cand_mask
                   ) -> np.ndarray:
        return batched_maxsim_host(q_tokens, q_mask, cand_tokens,
                                   cand_mask)
