"""The cacheless greedy oracle the engine tests compare against.

Greedy generation by repeated full forward passes, no KV cache, no
engine: slow but obviously correct. The sequence is right-padded to one
fixed width and the forward is jitted, so a whole generation is ONE
compiled program instead of a fresh set of eager per-op programs for
every new length (which was most of the engine tests' compile time).
Causal attention makes the logits at a position independent of
everything to its right, so the padding changes no answer.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from gpustack_tpu.models.config import ModelConfig
from gpustack_tpu.models.transformer import forward


@functools.lru_cache(maxsize=None)
def _forward_for(cfg: ModelConfig):
    return jax.jit(lambda params, toks, pos: forward(params, cfg, toks, pos)[0])


def greedy_reference(
    cfg: ModelConfig, params: Dict[str, Any],
    prompt_ids: Sequence[int], n: int,
) -> List[int]:
    """``n`` greedy tokens after ``prompt_ids``."""
    ids = list(prompt_ids)
    width = 1 << (len(ids) + n - 1).bit_length()
    fwd = _forward_for(cfg)
    pos = jnp.arange(width, dtype=jnp.int32)[None, :]
    out: List[int] = []
    for _ in range(n):
        toks = np.zeros((1, width), np.int32)
        toks[0, : len(ids)] = ids
        logits = fwd(params, jnp.asarray(toks), pos)
        nxt = int(jnp.argmax(logits[0, len(ids) - 1]))
        out.append(nxt)
        ids.append(nxt)
    return out
