"""Jitted model execution: prefill, insert, decode — all static-shape.

Execution model (JetStream-style, TPU-first):

- One resident **decode batch** of ``max_slots`` rows over a shared KV cache.
  ``decode_step`` advances every active slot one token per call.
- **Prefill** runs per request at a power-of-two bucketed length (bounded jit
  specializations), into a scratch cache; **insert** copies the prompt KV
  into the slot's rows. Pad positions in the scratch cache are harmless: a
  slot's decode write at position p lands before any query attends p, so
  stale/pad KV beyond the current position is never visible through the
  causal mask.
- All sequencing state (last token, position, active mask) lives **on
  device** so the decode loop never blocks on a host roundtrip — the host
  fetches sampled tokens asynchronously a couple of steps behind (EOS
  handling lags; surplus tokens are dropped host-side). This is what makes
  decode throughput survive a high-latency host↔TPU link.
- Capacity: a slot auto-deactivates on device when it reaches
  ``max_seq_len`` (enforcing the KVCache bounds contract).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from collections import deque
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from gpustack_tpu.engine.sampling import (
    MAX_BIAS,
    SamplingState,
    candidates_form,
    decide,
    pass_quota,
    sample,
    sample_block,
)
from gpustack_tpu.models.config import ModelConfig
from gpustack_tpu.models.quant import QuantW, quant_pspecs
from gpustack_tpu.models.transformer import (
    KVCache,
    decode_attention_impl,
    forward,
    moe_dispatch,
    needs_xla_attention,
)
from gpustack_tpu.parallel.mesh import MeshPlan, make_mesh
from gpustack_tpu.parallel.sharding import SpecLayout, param_pspecs

logger = logging.getLogger(__name__)


def bias_arrays(logit_bias):
    """{token_id: bias} → fixed-width (ids i32[MAX_BIAS], vals
    f32[MAX_BIAS]) arrays (-1 = unused slot). NumPy, as every small
    value a wrapper hands its program: the call uploads it, where a
    ``jnp`` value is a program of its own, launched before the one it is
    for."""
    ids = [-1] * MAX_BIAS
    vals = [0.0] * MAX_BIAS
    if logit_bias:
        for j, (tid, bias) in enumerate(list(logit_bias.items())[:MAX_BIAS]):
            ids[j] = int(tid)
            vals[j] = float(bias)
    return np.asarray(ids, np.int32), np.asarray(vals, np.float32)


def draw_words(key):
    """A draw's key as the decode and first-token programs take it: the
    two ``uint32`` words of its data, which the program wraps
    (``jax.random.wrap_key_data``: nothing to lower, where a split or a
    ``fold_in`` inside it was 0.2 s of every start). The engine makes
    them on the host, ``(seed, count of draws)``, so no two draws use
    one key and the host splits nothing (``LLMEngine._draw``); a typed
    key, from a caller that is not the engine, gives its data (an eager
    call)."""
    return key if isinstance(key, np.ndarray) else jax.random.key_data(key)


def prefill_attention(
    platform: str, bucket: int, sp_mode: bool, cfg: ModelConfig
) -> str:
    """Which attention serves a prefill of ``bucket`` tokens: the one
    place that decides, from what the runner can observe.

    ``"ring"`` under ``sp`` (the cache is sharded over positions; no
    other path reads it). ``"flash"`` on a TPU for buckets >= 1024,
    where the XLA path's [B, H, T, S] fp32 score tensor starts to
    dominate prefill HBM traffic (at 32k it simply does not fit), for a
    model the kernel takes: a causal mask, and under it the band of a
    stack that keeps its sliding layers' rows at window size
    (``cfg.window_rows``: its sliding layers call the kernel with the
    window, its full layers without). ``"xla"`` otherwise: the compiled
    kernel exists only for the TPU (no serving path reaches the pallas
    interpreter), and a softcap, sinks or a window masked over ``S_max``
    rows need the einsum path's mask and scores
    (``transformer.needs_xla_attention``).
    """
    if sp_mode:
        return "ring"
    if platform == "tpu" and bucket >= 1024 and not needs_xla_attention(cfg):
        return "flash"
    return "xla"


def flash_tile(cfg: ModelConfig, bucket: int, tp: int = 1):
    """The tile the flash kernel's shapes choose for a prefill of
    ``bucket`` tokens from scratch, and the points of one call's grid on
    one device of ``tp``: what says in the log that a rule of
    ``ops/flash_attention.py choose_tiles`` engaged (a latent's own
    prefill call, :func:`latent_prefill_call`, takes the same rule's
    tile at its group of one and a key of ``head_dim``). From the
    configuration alone, outside any traced function."""
    from gpustack_tpu.ops import flash_attention as fa

    rows = -(-bucket // fa.SUB_K) * fa.SUB_K
    tiles = fa.choose_tiles(
        rows, rows, cfg.num_heads // cfg.num_kv_heads,
        max(cfg.head_dim, cfg.v_head_dim), jnp.dtype(cfg.dtype).itemsize,
    )
    stored = cfg.num_heads if cfg.is_mla else cfg.kv_heads_stored
    return tiles, fa.grid_points(tiles, rows, rows, max(1, stored // tp))


def latent_prefill_call(cfg: ModelConfig, mesh) -> str:
    """The name of the latent's own prefill call where a flash prefill of
    this model on this mesh makes it (``transformer._mla_over_own_rows``:
    one device, widths that are whole lane tiles), else ``""``: what the
    log says before the tile, and a trace's ``%mla_prefill_attention``."""
    if not cfg.is_mla or mesh.size != 1:
        return ""
    from gpustack_tpu.ops.mla_attention import mla_prefill_takes

    return "mla_prefill_attention" if mla_prefill_takes(
        cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim,
    ) else ""


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DecodeState:
    """Device-resident continuous-batch state."""

    cache: KVCache
    last_tokens: jax.Array   # i32 [B] — token to feed next step
    positions: jax.Array     # i32 [B] — next write position (== seq len)
    active: jax.Array        # bool [B]
    sampling: SamplingState
    # Generation by diffusion over blocks (``cfg.diffusion_block``: L):
    # ``positions`` is then the start of the block a slot is generating,
    # a whole number of blocks, and beside it the slot keeps the block:
    # its L token ids (the mask token's where undecided), which are
    # decided, and how many denoise passes it has had. None for any
    # other model (``last_tokens`` is then what a step feeds).
    block_tokens: Optional[jax.Array] = None    # i32 [B, L]
    block_decided: Optional[jax.Array] = None   # bool [B, L]
    block_pass: Optional[jax.Array] = None      # i32 [B]

    @staticmethod
    def create(cfg: ModelConfig, batch: int, max_len: int) -> "DecodeState":
        L = cfg.diffusion_block
        return DecodeState(
            cache=KVCache.create(cfg, batch, max_len),
            last_tokens=jnp.zeros((batch,), jnp.int32),
            positions=jnp.zeros((batch,), jnp.int32),
            active=jnp.zeros((batch,), jnp.bool_),
            sampling=SamplingState.create(batch),
            **({
                "block_tokens": jnp.full(
                    (batch, L), cfg.mask_token_id, jnp.int32
                ),
                "block_decided": jnp.zeros((batch, L), jnp.bool_),
                "block_pass": jnp.zeros((batch,), jnp.int32),
            } if L else {}),
        )


class ModelRunner:
    """Owns sharded params + jitted prefill/insert/decode for one model."""

    # insert() accepts the first token as a device scalar (no host
    # roundtrip) — the engine's dispatch-ahead admission relies on this.
    # The multi-host BroadcastingRunner does NOT set it: it serializes
    # insert args onto the follower command channel, which needs ints.
    supports_async_insert = True

    def __init__(
        self,
        cfg: ModelConfig,
        params: Dict[str, Any],
        plan: Optional[MeshPlan] = None,
        mesh: Optional[Mesh] = None,
        max_slots: int = 8,
        max_seq_len: int = 1024,
        prefill_buckets: Tuple[int, ...] = (),
    ):
        self.cfg = cfg
        self.plan = plan or MeshPlan()
        self.mesh = mesh or make_mesh(self.plan)
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        # Context-parallel serving: with sp > 1 the KV cache lives
        # seq-sharded over sp for the whole generation; prefill runs ring
        # attention, decode/verify the pmax/psum merge (ops/ring_attention).
        self.sp_mode = self.plan.sp > 1
        # a slot of rows a position and nothing else shards; what a
        # model keeps beside them (``cfg.beside_rows``: KVCache holds it)
        # does not yet
        if cfg.diffusion_block and self.mesh.size > 1:
            raise ValueError(
                f"{cfg.name} is generated by diffusion over blocks of "
                f"{cfg.diffusion_block} and is served on one device: the "
                "block pass, its decode kernel over the block's rows and "
                "the touched experts' kernel are not sharded (tp/ep/dp/sp); "
                f"got plan {self.plan}"
            )
        beside = cfg.beside_rows
        if beside and self.mesh.size > 1:
            raise ValueError(
                f"{cfg.name} {beside.keeps} and is served on one device: "
                f"what a slot keeps beside its rows ({beside.lost}) and "
                "the kernels that read it are not sharded yet (tp/ep/dp), "
                "and 'ring' attention (sp>1) shards a cache over "
                f"positions, which only rows have; got plan {self.plan}"
            )
        if self.sp_mode:
            if cfg.is_mla:
                raise ValueError(
                    "sp>1 serving shards the cache over its positions, "
                    "which cannot carry a latent (MLA) cache yet; serve "
                    f"{cfg.name} with sp=1"
                )
            if self.plan.dp != 1:
                raise ValueError(
                    "sp>1 serving requires dp=1 (one sequence-sharded "
                    f"replica); got plan {self.plan}"
                )
            if max_seq_len % self.plan.sp:
                raise ValueError(
                    f"max_seq_len {max_seq_len} must divide evenly over "
                    f"sp={self.plan.sp}"
                )
        if not prefill_buckets:
            b, buckets = 32, []
            while b < max_seq_len:
                buckets.append(b)
                b *= 2
            buckets.append(max_seq_len)
            prefill_buckets = tuple(buckets)
        if self.sp_mode:
            prefill_buckets = tuple(
                b for b in prefill_buckets if b % self.plan.sp == 0
            )
            if not prefill_buckets:
                raise ValueError(
                    f"no prefill bucket divides over sp={self.plan.sp}"
                )
        self.prefill_buckets = tuple(sorted(set(prefill_buckets)))

        specs = param_pspecs(params, train=False)
        if any(isinstance(x, QuantW) for x in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantW)
        )):
            specs = quant_pspecs(specs, params)
        def put(x, spec):
            if isinstance(x, QuantW):
                return jax.device_put(
                    x,
                    QuantW(
                        q=NamedSharding(self.mesh, spec.q),
                        s=NamedSharding(self.mesh, spec.s),
                    ),
                )
            return jax.device_put(x, NamedSharding(self.mesh, spec))

        self.params = jax.tree.map(
            put, params, specs,
            is_leaf=lambda x: isinstance(x, (QuantW, P)),
        )

        # The replica's whole multi-chip layout as ONE inspectable
        # object (parallel/sharding.SpecLayout): every NamedSharding the
        # runner dispatches against derives from it, and the engine
        # serves layout.describe() on its health surface.
        self.layout = SpecLayout(long_context=self.sp_mode)
        self._cache_sharding = NamedSharding(
            self.mesh, self.layout.cache(latent=cfg.is_mla)
        )
        self._slot_sharding = NamedSharding(
            self.mesh, self.layout.slot_state()
        )
        self._replicated = NamedSharding(
            self.mesh, self.layout.replicated()
        )

        # how the decode and first-token programs find a row's top
        # candidates: static, from the vocabulary's width alone
        self.sample_candidates = candidates_form(cfg.vocab_size)
        logger.info("sampling candidates: %s", self.sample_candidates)
        # how a decode step attends over the cache, as ``forward`` will
        # choose when the decode program is traced: ``kernel`` reads the
        # live slots' rows where they lie, ``xla`` every slot's slab
        # (a diffusion model's step is a pass over a block's rows)
        self.step_rows = cfg.diffusion_block or 1
        self.decode_attention = decode_attention_impl(
            cfg, self.step_rows, max_seq_len,
            self.mesh.devices.flat[0].platform, self.mesh,
        )
        logger.info("decode attention: %s", self.decode_attention)
        # how a decode step enumerates its experts' products, chosen the
        # same way: ``touched`` reads the experts the live rows chose,
        # ``dense`` every held one; None without experts
        self.decode_moe_dispatch = self.moe_dispatch_for(
            max_slots * self.step_rows, decode=True
        )
        if self.decode_moe_dispatch:
            logger.info("decode experts: %s", self.decode_moe_dispatch)
        # how a layer that keeps a recurrent state (state-space or
        # delta-rule: cfg.state_mixer) moves it, the form strings of
        # /healthz and the flight record: the chunked scan in a prefill,
        # in a decode step ``kernel`` (ops/ssm.py, ops/delta_rule.py:
        # the stacked state in place) or ``xla``; None without such
        # layers
        self.ssm_scan = self.ssm_update = None
        if cfg.state_mixer:
            from gpustack_tpu.models.hybrid import ssm_update_impl

            platform = self.mesh.devices.flat[0].platform
            self.ssm_scan = "chunked_einsum"
            self.ssm_update = ssm_update_impl(1, platform, self.mesh)
            logger.info(
                "%s layers: scan %s, update %s",
                {"ssm": "state-space", "delta": "delta-rule",
                 "kda": "delta-rule (a decay a channel)"}[
                    cfg.state_mixer
                ], self.ssm_scan, self.ssm_update,
            )
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._decode_routing = None
        self._denoise = jax.jit(self._denoise_impl, donate_argnums=(1,))
        self._denoise_probe = None
        self._insert_block = None
        self._none_freed = np.zeros((max_slots,), np.bool_)
        self._prefills: Dict[int, Any] = {}
        self._prefills_routing: Dict[int, Any] = {}
        self._logged_attn_buckets: set = set()
        self._prefill_embeds: Dict[int, Any] = {}
        self._sample_first: Optional[Any] = None
        self._inserts: Dict[int, Any] = {}
        self._embeds: Dict[int, Any] = {}
        self._verifies: Dict[int, Any] = {}
        self._ingests: Dict[int, Any] = {}
        self._prefix_prefills: Dict[Tuple[int, int, int], Any] = {}
        # under a share of the experts (cfg.experts_held): the router's
        # (token, expert) pairs the prefill programs have made. Each
        # program's count of the ones on held experts stays on the
        # device, beside the pairs it routed, until it is there to be
        # read (no sync, and no program to add them up); ``_pairs_read``
        # is the sum of those that were
        self._pairs_unread: deque = deque()
        self._pairs_mu = threading.Lock()
        self._pairs_read = {"held": 0, "absent": 0}

    # -- state ------------------------------------------------------------

    def new_state(self) -> DecodeState:
        state = DecodeState.create(self.cfg, self.max_slots, self.max_seq_len)
        return jax.device_put(
            state,
            DecodeState(
                cache=state.cache.shardings(
                    self._cache_sharding, self._replicated
                ),
                last_tokens=self._slot_sharding,
                positions=self._slot_sharding,
                active=self._slot_sharding,
                sampling=SamplingState(
                    *([self._slot_sharding] * 7),
                ),
                **({
                    "block_tokens": self._slot_sharding,
                    "block_decided": self._slot_sharding,
                    "block_pass": self._slot_sharding,
                } if self.cfg.diffusion_block else {}),
            ),
        )

    # -- prefill ----------------------------------------------------------

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        raise ValueError(
            f"prompt length {length} exceeds max bucket "
            f"{self.prefill_buckets[-1]}"
        )

    def attn_impl_for(self, bucket: int) -> str:
        """:func:`prefill_attention` for this runner's mesh and model,
        logged once per bucket."""
        impl = prefill_attention(
            self.mesh.devices.flat[0].platform, bucket, self.sp_mode,
            self.cfg,
        )
        if bucket not in self._logged_attn_buckets:
            # once per bucket, at compile time: the engine's log says
            # which kernel serves which prompt widths
            self._logged_attn_buckets.add(bucket)
            call = latent_prefill_call(self.cfg, self.mesh)
            logger.info(
                "prefill bucket %d: attention impl %s%s", bucket, impl,
                ", %s%s, %d grid points a call" % (
                    call and call + " ", *flash_tile(
                        self.cfg, bucket, int(self.mesh.shape.get("tp", 1))
                    )
                ) if impl == "flash" else "",
            )
        return impl

    def attn_label(self, bucket: Optional[int] = None) -> Optional[str]:
        """For a latent-attention model, the form of attention a step
        runs, for the step's flight record: ``"mla_flash"`` /
        ``"mla_xla"`` for a prefill of ``bucket`` tokens (decompressed,
        by :func:`prefill_attention`'s kernel), ``"mla_absorbed"`` for a
        step over the latent cache (``bucket`` None). None for any other
        model."""
        if not self.cfg.is_mla:
            return None
        if bucket is None:
            return "mla_absorbed"
        return "mla_" + self.attn_impl_for(bucket)

    def moe_pairs(self) -> Optional[Dict[str, int]]:
        """Under a share of the experts, the router's (token, expert)
        pairs of every prefill program so far, bucket padding included,
        by whether the pair's expert is held here; None for a model
        whose experts are all held. Never waits for the device (a
        health probe must not stand behind a prefill): while the count
        of the last prefill dispatched is not there yet, the counts as
        they were last read."""
        if not self.cfg.experts_held:
            return None
        with self._pairs_mu:
            unread, read = self._pairs_unread, dict(self._pairs_read)
            while unread and unread[0][0].is_ready():
                held, routed = unread.popleft()
                read["held"] += int(held)
                read["absent"] += routed - int(held)
            self._pairs_read = read
        return read

    def _note_pairs(self, held, rows: int) -> None:
        cfg = self.cfg
        self._pairs_unread.append(
            (held, rows * cfg.num_experts_per_tok * cfg.num_moe_layers)
        )
        if len(self._pairs_unread) >= 64:
            self.moe_pairs()   # nobody asks: keep the line short

    def moe_dispatch_for(
        self, rows: int, decode: bool = False
    ) -> Optional[str]:
        """The dispatch ``forward`` traces a program of ``rows`` tokens
        with on this runner's mesh (:func:`moe_dispatch`, given what
        ``forward`` is given; ``decode``: one row a slot over the
        cache); None for a model without experts."""
        if not self.cfg.is_moe:
            return None
        return moe_dispatch(
            rows, self.cfg, self.mesh.devices.flat[0].platform, self.mesh,
            decode=decode,
        )

    @staticmethod
    def _named_jit(fn, name: str):
        """``jax.jit`` of a ``functools.partial`` under a name of its
        own: a partial has none, and the program would be
        ``jit__unknown`` in every log and profiler trace, whatever its
        bucket."""
        fn.__name__ = name
        return jax.jit(fn)

    def _prefill_impl(
        self, params, tokens, true_len, *, attn_impl="xla", routing=False
    ):
        """tokens [1, Tb]; returns (last_logits [V], k, v [L, Tb, heads,
        width]); for a model that keeps something a slot beside its rows
        then that, after ``true_len`` tokens (``KVCache.slot_share``;
        not after the bucket: the padding moves no state and writes no
        ring row); under a share of the experts the count of the
        router's pairs on held experts; with ``routing`` last of all
        what ``forward(routing_out=True)`` adds."""
        Tb = tokens.shape[1]
        cache = KVCache.create(self.cfg, 1, Tb)
        positions = jnp.arange(Tb, dtype=jnp.int32)[None, :]
        logits, cache, *extras = forward(
            params, self.cfg, tokens, positions, cache,
            attn_impl=attn_impl,
            mesh=self.mesh,
            count_held_pairs=bool(self.cfg.experts_held),
            routing_out=routing,
            # a prefill keeps one row of its bucket: the final norm and
            # the vocabulary head run on that row alone
            logits_at=(true_len - 1)[None],
            **(
                {"true_len": true_len[None]} if self.cfg.beside_rows
                else {}
            ),
        )
        last = logits[0, 0]
        beside = cache.slot_share()
        mixer = () if beside is None else (beside,)
        return (last, cache.k[:, 0], cache.v[:, 0], *mixer, *extras)

    def prefill(self, token_ids, true_len: int, routing: bool = False):
        """Run prefill at the bucket for ``true_len``. ``token_ids`` must be
        padded to the bucket length already (any pad id).

        ``routing``: the same program with one more output, each layer's
        chosen experts and router logits (``forward``), returned last.
        For a comparison with a reference; the engine never asks.

        A model that keeps something a slot beside its rows
        (``cfg.beside_rows``) returns one more after ``k, v``: what the
        prompt leaves of it, for :meth:`insert`'s ``mixer`` and opaque
        to whoever carries it there (``KVCache.slot_share``)."""
        Tb = len(token_ids)
        assert Tb in self.prefill_buckets, (Tb, self.prefill_buckets)
        fns = self._prefills_routing if routing else self._prefills
        fn = fns.get(Tb)
        if fn is None:
            fn = self._named_jit(
                partial(
                    self._prefill_impl, attn_impl=self.attn_impl_for(Tb),
                    **({"routing": True} if routing else {}),
                ),
                f"prefill_{Tb}" + ("_routing" if routing else ""),
            )
            fns[Tb] = fn
        tokens = np.asarray(token_ids, np.int32)[None, :]
        last, k, v, *extras = fn(self.params, tokens, np.int32(true_len))
        mixer = (extras.pop(0),) if self.cfg.beside_rows else ()
        if self.cfg.experts_held:
            self._note_pairs(extras[0], Tb)
        return (last, k, v, *mixer, *((extras[-1],) if routing else ()))

    def _prefill_embeds_impl(
        self, params, tokens, true_len, embeds, mask, *, attn_impl="xla"
    ):
        """Prefill with vision-token splicing (models/vlm.py): embedding
        rows where ``mask`` is set are overridden by ``embeds``."""
        Tb = tokens.shape[1]
        cache = KVCache.create(self.cfg, 1, Tb)
        positions = jnp.arange(Tb, dtype=jnp.int32)[None, :]
        logits, cache = forward(
            params, self.cfg, tokens, positions, cache,
            attn_impl=attn_impl,
            mesh=self.mesh,
            embeds_override=(embeds, mask),
            logits_at=(true_len - 1)[None],   # as _prefill_impl
        )
        last = logits[0, 0]
        return last, cache.k[:, 0], cache.v[:, 0]

    def prefill_with_embeds(
        self, token_ids, true_len: int, embeds, mask
    ):
        """Like :meth:`prefill` but with per-token embedding overrides
        (``embeds`` [Tb, D], ``mask`` [Tb] bool, both bucket-padded)."""
        Tb = len(token_ids)
        assert Tb in self.prefill_buckets, (Tb, self.prefill_buckets)
        fn = self._prefill_embeds.get(Tb)
        if fn is None:
            fn = self._named_jit(
                partial(
                    self._prefill_embeds_impl,
                    attn_impl=self.attn_impl_for(Tb),
                ),
                f"prefill_embeds_{Tb}",
            )
            self._prefill_embeds[Tb] = fn
        tokens = np.asarray(token_ids, np.int32)[None, :]
        return fn(
            self.params, tokens, np.int32(true_len),
            np.asarray(embeds)[None, :], np.asarray(mask, bool)[None, :],
        )

    def _prefix_prefill_impl(
        self, params, prefix_k, prefix_v, prefix_len, tokens, true_len,
        *, total_bucket, attn_impl="xla",
    ):
        """Continue prefill from a cached prefix (prefix-granular host
        KV cache): seed the scratch cache with the prefix K/V, run the
        suffix at absolute positions ``prefix_len + j``. Pad slots the
        prefix carried above ``prefix_len`` are overwritten by the
        suffix's own writes before any query can attend them (same
        invisible-pad argument as bucketed prefill).

        prefix_k/v: [L, Pb, H, hd]; tokens: [1, Tsb];
        returns (last_logits [V], k, v [L, total_bucket, H, hd]).
        """
        Pb = prefix_k.shape[1]
        cache = KVCache.create(self.cfg, 1, total_bucket)
        cache = KVCache(
            k=cache.k.at[:, 0, :Pb].set(prefix_k),
            v=cache.v.at[:, 0, :Pb].set(prefix_v),
        )
        Tsb = tokens.shape[1]
        positions = (
            prefix_len + jnp.arange(Tsb, dtype=jnp.int32)
        )[None, :]
        logits, cache, *held = forward(
            params, self.cfg, tokens, positions, cache,
            attn_impl=attn_impl,
            mesh=self.mesh,
            count_held_pairs=bool(self.cfg.experts_held),
            logits_at=(true_len - 1)[None],   # as _prefill_impl
        )
        last = logits[0, 0]
        return (last, cache.k[:, 0], cache.v[:, 0], *held)

    def prefill_with_prefix(
        self, prefix_k, prefix_v, prefix_len: int,
        suffix_ids, suffix_true_len: int, total_bucket: int,
    ):
        """suffix_ids must be pre-padded to a prefill bucket."""
        if self.cfg.beside_rows:
            raise ValueError(
                f"{self.cfg.name}: a prefill cannot go on from cached rows "
                f"(prefix reuse, chunked prefill): {self.cfg.beside_rows.span}"
            )
        Pb = prefix_k.shape[1]
        Tsb = len(suffix_ids)
        key = (Pb, Tsb, total_bucket)
        fn = self._prefix_prefills.get(key)
        if fn is None:
            # continuation attention kernel follows the TOTAL width:
            # a 512-token chunk against a 32k cache is exactly the
            # [T, S] blow-up flash exists to avoid (q_offset shifts the
            # kernel's causal diagonal)
            fn = self._named_jit(
                partial(
                    self._prefix_prefill_impl,
                    total_bucket=total_bucket,
                    attn_impl=self.attn_impl_for(total_bucket),
                ),
                f"prefix_prefill_{Pb}_{Tsb}_{total_bucket}",
            )
            self._prefix_prefills[key] = fn
        tokens = np.asarray(suffix_ids, np.int32)[None, :]
        last, k, v, *held = fn(
            self.params,
            prefix_k,
            prefix_v,
            np.int32(prefix_len),
            tokens,
            # logits cover the suffix only
            np.int32(suffix_true_len),
        )
        if held:
            self._note_pairs(held[0], Tsb)
        return last, k, v

    # -- embeddings -------------------------------------------------------

    def _embed_impl(self, params, tokens, true_lens):
        """tokens [N, Tb], true_lens [N] -> l2-normalized mean-pooled
        embeddings [N, D] (one batched forward for the whole request)."""
        Tb = tokens.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(Tb, dtype=jnp.int32)[None, :], tokens.shape
        )
        # every row is pooled: no ``logits_at``
        hidden, _ = forward(
            params, self.cfg, tokens, positions, return_hidden=True,
            mesh=self.mesh,
        )
        mask = (
            jnp.arange(Tb)[None, :] < true_lens[:, None]
        )[..., None].astype(jnp.float32)
        pooled = jnp.sum(hidden * mask, axis=1) / jnp.maximum(
            jnp.sum(mask, axis=1), 1.0
        )
        norm = jnp.linalg.norm(pooled, axis=-1, keepdims=True)
        return pooled / jnp.maximum(norm, 1e-9)

    def embed(self, batch_token_ids, true_lens) -> jax.Array:
        """batch_token_ids: [N][Tb] (pre-padded to one bucket length)."""
        Tb = len(batch_token_ids[0])
        assert Tb in self.prefill_buckets, (Tb, self.prefill_buckets)
        # bucket the batch dim too, bounding jit specializations
        N = len(batch_token_ids)
        Nb = 1
        while Nb < N:
            Nb *= 2
        padded = list(batch_token_ids) + [
            [0] * Tb for _ in range(Nb - N)
        ]
        lens = list(true_lens) + [0] * (Nb - N)
        key = (Nb, Tb)
        fn = self._embeds.get(key)
        if fn is None:
            fn = jax.jit(self._embed_impl)
            self._embeds[key] = fn
        out = fn(
            self.params,
            jnp.asarray(padded, jnp.int32),
            jnp.asarray(lens, jnp.int32),
        )
        return out[:N]

    # -- insert -----------------------------------------------------------

    def _insert_impl(
        self, state, k, v, slot, true_len, first_token,
        temperature, top_k, top_p, seed, seeded, bias_ids, bias_vals,
        mixer=None,
    ):
        return DecodeState(
            cache=state.cache.with_slot(slot, k, v, mixer),
            # ``first_token`` is the [1] that the first-token program
            # returned: indexed here, not by a program of its own
            last_tokens=state.last_tokens.at[slot].set(first_token[0]),
            positions=state.positions.at[slot].set(true_len),
            active=state.active.at[slot].set(True),
            sampling=state.sampling.set_slot(
                slot, temperature, top_k, top_p, seed, seeded,
                bias_ids, bias_vals,
            ),
        )

    def insert(
        self, state: DecodeState, k, v, slot: int, true_len: int,
        first_token, temperature: float, top_k: int, top_p: float,
        seed: int = 0, seeded: bool = False, logit_bias=None,
        mixer=None,
    ) -> DecodeState:
        """Place a prefill's rows, and what it left beside them
        (``mixer``: what :meth:`prefill` returned after ``k, v``, for a
        model that keeps such a thing; ``KVCache.with_slot`` knows what
        it is), in ``slot`` and make the slot live. ``first_token``: an
        int, or the ``[1]`` array of tokens :meth:`sample_first`
        returned, still on the device."""
        Tb = k.shape[1]
        fn = self._inserts.get(Tb)
        if fn is None:
            fn = jax.jit(self._insert_impl, donate_argnums=(0,))
            self._inserts[Tb] = fn
        bias_ids, bias_vals = bias_arrays(logit_bias)
        if not isinstance(first_token, jax.Array):
            first_token = np.asarray([first_token], np.int32)
        return fn(
            state, k, v, np.int32(slot), np.int32(true_len),
            first_token, np.float32(temperature),
            np.int32(top_k), np.float32(top_p),
            np.uint32(seed), np.bool_(seeded),
            bias_ids, bias_vals, mixer,
        )

    def deactivate(self, state: DecodeState, slot: int) -> DecodeState:
        """Switch ``slot`` off now, by a program of its own. The engine
        hands the slots its requests freed to the next decode step
        instead (:meth:`decode_step`'s ``freed``), and calls this only
        before a step that takes no such argument."""
        return dataclasses.replace(
            state, active=state.active.at[slot].set(False)
        )

    def slot_kv(self, state: DecodeState, slot: int, width: int):
        """Copy a slot's KV rows ``[:width]`` out of the decode cache
        (host KV cache's finish-time store). Dispatches eagerly, so the
        returned arrays survive the next decode step's donation of
        ``state``; callers pass a bucketed ``width`` to bound the slice
        executables compiled."""
        return (
            state.cache.k[:, slot, :width],
            state.cache.v[:, slot, :width],
        )

    # -- decode -----------------------------------------------------------

    def _decode_impl(self, params, state, key, freed, routing=False):
        # the slots whose requests ended since the last step go off
        # first: what follows reads ``active`` as the host knows it
        active = state.active & ~freed
        tokens = state.last_tokens[:, None]
        positions = state.positions[:, None]
        # one row a slot already: nothing for ``logits_at`` to drop
        logits, cache, *extras = forward(
            params, self.cfg, tokens, positions, state.cache,
            attn_impl="ring" if self.sp_mode else "xla",
            mesh=self.mesh,
            live=active,
            routing_out=routing,
            count_experts_read=self.cfg.is_moe,
        )
        # the experts' count goes last: whoever asks for the routing
        # finds it where it was
        extras.reverse()
        sampled, tok_lp, top_ids, top_lps = sample(
            logits[:, 0], state.sampling, jax.random.wrap_key_data(key),
            state.positions,
        )
        # the host reads these every step; on a multi-host mesh an
        # unconstrained output can land dp/tp-sharded and span
        # non-addressable devices — force replication (an allgather over
        # a few hundred bytes)
        rep = self._replicated
        sampled, tok_lp, top_ids, top_lps = (
            jax.lax.with_sharding_constraint(x, rep)
            for x in (sampled, tok_lp, top_ids, top_lps)
        )
        # Inactive slots keep feeding their last token at a frozen position;
        # their cache writes are confined to their own rows and invisible
        # through the causal mask of any future tenant. Under the decode
        # kernel they attend nothing (``live``), and what they sample is
        # dropped here either way.
        next_tokens = jnp.where(active, sampled, state.last_tokens)
        at_capacity = state.positions + 1 >= self.max_seq_len
        new_positions = jnp.where(
            active, jnp.minimum(state.positions + 1, self.max_seq_len - 1),
            state.positions,
        )
        return (
            DecodeState(
                cache=cache,
                last_tokens=next_tokens,
                positions=new_positions,
                active=active & ~at_capacity,
                sampling=state.sampling,
            ),
            (sampled, tok_lp, top_ids, top_lps, *extras),
        )

    def decode_step(
        self, state: DecodeState, key, routing: bool = False, freed=None
    ):
        """One decode step. Returns ``(state', (tokens [B], token_logprob
        [B], top_ids [B, TOPLP], top_logprobs [B, TOPLP]))`` — the
        logprob extras ride the same device round-trip as the tokens.
        ``key``: :func:`draw_words`. ``freed``: ``bool[B]`` (NumPy), the
        slots to switch off before the step; None for none.
        ``routing``: as :meth:`prefill`'s, a fifth in the tuple. A model
        with experts adds one last: the held experts the step read,
        summed over its layers (int32 scalar: ``forward``'s
        ``count_experts_read``)."""
        if routing and self._decode_routing is None:
            impl = partial(self._decode_impl, routing=True)
            impl.__name__ = "_decode_routing"
            self._decode_routing = jax.jit(impl, donate_argnums=(1,))
        fn = self._decode_routing if routing else self._decode
        return fn(
            self.params, state, draw_words(key),
            self._none_freed if freed is None else freed,
        )

    # -- generation by diffusion over blocks ------------------------------

    def _denoise_impl(self, params, state, key, freed, probe=False):
        """One pass over every slot's block (``cfg.diffusion_block`` rows
        a slot, at ``positions .. positions + L - 1``, over the cached
        rows below and the block's own, written first).

        A slot with an undecided position makes a **denoise pass**: a
        candidate and its confidence for every undecided position
        (``sampling.sample_block``), of which the rule decides some
        (``sampling.decide``); the rows it wrote are provisional. A slot
        with none undecided makes its **commit pass** in the same call:
        the same rows over the block's final tokens, which are the rows
        kept; its block then starts anew ``L`` further on, all mask, or
        the slot goes off where that block would pass ``max_seq_len``.
        So a pass yields 0 to ``L`` tokens a slot, and a block of ``L``
        undecided positions under a rule that decides one a pass takes
        ``L + 1``.

        Returns ``(state', (tokens i32[B, L], decided bool[B, L],
        committed bool[B], logprob f32[B, L], top_ids i32[B, L, TOPLP],
        top_logprobs f32[B, L, TOPLP], experts read i32))``: the block
        and which of it is decided after the pass (a committed slot's:
        the block it committed), and for every position the candidate's
        numbers of this pass, which are a token's own if this pass
        decided it."""
        cfg = self.cfg
        L = cfg.diffusion_block
        active = state.active & ~freed
        decided = state.block_decided
        tokens = jnp.where(decided, state.block_tokens, cfg.mask_token_id)
        positions = (
            state.positions[:, None] + jnp.arange(L, dtype=jnp.int32)[None]
        )
        logits, cache, n_read, *routing = forward(
            params, cfg, tokens, positions, state.cache,
            attn_impl="xla", mesh=self.mesh, live=active,
            count_experts_read=True, routing_out=probe,
        )
        cand, tok_lp, top_ids, top_lps, conf = sample_block(
            logits, state.sampling, jax.random.wrap_key_data(key),
            positions, cfg.mask_token_id,
        )
        committed = jnp.all(decided, axis=1)
        chosen = decide(
            cfg.remasking_strategy, ~decided, conf,
            pass_quota(state.block_pass, L, cfg.denoising_steps),
            cfg.confidence_threshold,
        )
        tokens = jnp.where(chosen, cand, tokens)
        decided = decided | chosen
        # a committed block's rows stay; the next block, all mask, begins
        # behind it, unless it would not fit the cache
        full = state.positions + 2 * L > self.max_seq_len
        advance = committed & active & ~full
        return (
            dataclasses.replace(
                state,
                cache=cache,
                positions=jnp.where(
                    advance, state.positions + L, state.positions
                ),
                active=active & ~(committed & full),
                block_tokens=jnp.where(
                    advance[:, None], cfg.mask_token_id, tokens
                ),
                block_decided=jnp.where(advance[:, None], False, decided),
                block_pass=jnp.where(committed, 0, state.block_pass + 1),
            ),
            (tokens, decided, committed, tok_lp, top_ids, top_lps, n_read,
             *((logits, *routing) if probe else ())),
        )

    def denoise_step(
        self, state: DecodeState, key, freed=None, probe: bool = False
    ):
        """One block pass for all slots (:meth:`_denoise_impl`), the step
        of a model generated by diffusion over blocks. ``key`` and
        ``freed`` as :meth:`decode_step`'s. ``probe``: the same program
        with two more outputs, the pass's logits ``[B, L, V]`` and its
        routing (``forward``'s ``routing_out``), for a comparison with a
        reference; the engine never asks."""
        fn = self._denoise
        if probe:
            if self._denoise_probe is None:
                impl = partial(self._denoise_impl, probe=True)
                impl.__name__ = "_denoise_probe"
                self._denoise_probe = jax.jit(impl, donate_argnums=(1,))
            fn = self._denoise_probe
        return fn(
            self.params, state, draw_words(key),
            self._none_freed if freed is None else freed,
        )

    def _insert_block_impl(
        self, state, k, v, slot, start, tail, n_tail,
        temperature, top_k, top_p, seed, seeded, bias_ids, bias_vals,
    ):
        cfg = self.cfg
        held = jnp.arange(cfg.diffusion_block) < n_tail
        return dataclasses.replace(
            state,
            cache=state.cache.with_slot(slot, k, v),
            positions=state.positions.at[slot].set(start),
            active=state.active.at[slot].set(True),
            sampling=state.sampling.set_slot(
                slot, temperature, top_k, top_p, seed, seeded,
                bias_ids, bias_vals,
            ),
            block_tokens=state.block_tokens.at[slot].set(
                jnp.where(held, tail, cfg.mask_token_id)
            ),
            block_decided=state.block_decided.at[slot].set(held),
            block_pass=state.block_pass.at[slot].set(0),
        )

    def insert_block(
        self, state: DecodeState, k, v, slot: int, prompt_ids,
        temperature: float, top_k: int, top_p: float,
        seed: int = 0, seeded: bool = False, logit_bias=None,
    ) -> DecodeState:
        """Place the rows of a prompt's whole blocks (``k, v``: what
        :meth:`prefill` of its first ``len // L * L`` tokens returned) in
        ``slot``, the prompt's tail as the decided positions of the first
        block, and make the slot live. A prefill yields no token: the
        first comes of the first block's passes."""
        L = self.cfg.diffusion_block
        n_tail = len(prompt_ids) % L
        tail = np.zeros((L,), np.int32)
        tail[:n_tail] = prompt_ids[len(prompt_ids) - n_tail:]
        if self._insert_block is None:
            self._insert_block = jax.jit(
                self._insert_block_impl, donate_argnums=(0,)
            )
        bias_ids, bias_vals = bias_arrays(logit_bias)
        return self._insert_block(
            state, k, v, np.int32(slot), np.int32(len(prompt_ids) - n_tail),
            tail, np.int32(n_tail), np.float32(temperature),
            np.int32(top_k), np.float32(top_p), np.uint32(seed),
            np.bool_(seeded), bias_ids, bias_vals,
        )

    def _sample_first_impl(
        self, last_logits, temperature, top_k, top_p, seed, seeded,
        position, key, bias_ids, bias_vals,
    ):
        st = SamplingState(
            temperature=temperature[None], top_k=top_k[None],
            top_p=top_p[None], seed=seed[None], seeded=seeded[None],
            bias_ids=bias_ids[None], bias_vals=bias_vals[None],
        )
        outs = sample(
            last_logits[None, :], st, jax.random.wrap_key_data(key),
            position[None],
        )
        # host-read outputs must be replicated on multi-host meshes
        rep = self._replicated
        return tuple(
            jax.lax.with_sharding_constraint(x, rep) for x in outs
        )

    def sample_first(
        self, last_logits, temperature, top_k, top_p, seed, seeded,
        position, key, logit_bias=None,
    ):
        """Sample the first generated token from a prefill's last-position
        logits — one row through the same device sampler as decode, so
        the whole sequence shares one sampling semantics (``key``:
        :func:`draw_words`). A runner method
        (not engine-inline) so multi-host followers can replay it
        (engine/multihost.py)."""
        if self._sample_first is None:
            self._sample_first = jax.jit(self._sample_first_impl)
        bias_ids, bias_vals = bias_arrays(logit_bias)
        return self._sample_first(
            last_logits, np.float32(temperature), np.int32(top_k),
            np.float32(top_p), np.uint32(seed), np.bool_(seeded),
            np.int32(position), draw_words(key), bias_ids, bias_vals,
        )

    # -- draft-model support ---------------------------------------------

    def _ingest_impl(self, params, state, tokens, counts):
        """Ingest already-accepted tokens into the cache (draft-model
        catch-up). State invariant matches decode/verify: ``(pos, last)``
        with KV complete below ``pos`` and ``last`` not yet fed — so the
        block fed is ``[last, tokens[0..P-2]]`` (the verify feeding
        pattern), after which ``pos += counts`` and ``last`` becomes each
        row's final ingested token. Rows with count 0 keep (pos, last);
        pad positions land above the new position and stay invisible
        through the causal mask until genuinely overwritten.
        """
        B, P = tokens.shape
        fed = jnp.concatenate(
            [state.last_tokens[:, None], tokens[:, : P - 1]], axis=1
        )
        positions = (
            state.positions[:, None]
            + jnp.arange(P, dtype=jnp.int32)[None, :]
        )
        # the logits are discarded, and the compiler drops the head
        # with them: no ``logits_at``
        _, cache = forward(
            params, self.cfg, fed, positions, state.cache,
            attn_impl="ring" if self.sp_mode else "xla",
            mesh=self.mesh,
            # a recurrent state takes the tokens that count
            **({"true_len": counts} if self.cfg.state_mixer else {}),
        )
        has_any = counts > 0
        last_idx = jnp.maximum(counts - 1, 0)
        new_last = jnp.take_along_axis(
            tokens, last_idx[:, None], axis=1
        )[:, 0]
        return DecodeState(
            cache=cache,
            last_tokens=jnp.where(has_any, new_last, state.last_tokens),
            positions=jnp.minimum(
                state.positions + counts, self.max_seq_len - 1
            ),
            active=state.active,
            sampling=state.sampling,
        )

    def ingest_step(self, state: DecodeState, tokens, counts) -> DecodeState:
        """tokens [B, P] int32 (pad arbitrary), counts [B] int32."""
        P = np.asarray(tokens).shape[1]
        fn = self._ingests.get(P)
        if fn is None:
            fn = jax.jit(self._ingest_impl, donate_argnums=(1,))
            self._ingests[P] = fn
        return fn(
            self.params,
            state,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(counts, jnp.int32),
        )

    def snapshot_sequence(self, state: DecodeState):
        """(positions, last_tokens) device snapshot — restore after a
        speculative proposal run to rewind the draft's sequence state
        (cache entries above the restored positions are masked out).
        COPIES: the decode steps in between donate the state, which would
        invalidate aliased buffers. What of a slot cannot be masked out
        is copied too and put back whole (``KVCache.unmaskable``)."""
        return (
            jnp.array(state.positions), jnp.array(state.last_tokens),
            *state.cache.unmaskable(),
        )

    def restore_sequence(self, state: DecodeState, snap) -> DecodeState:
        positions, last_tokens, *unmaskable = snap
        return dataclasses.replace(
            state, positions=positions, last_tokens=last_tokens,
            cache=state.cache.with_unmaskable(unmaskable),
        )

    # -- speculative decoding (greedy n-gram verify) ----------------------

    def _verify_impl(self, params, state, proposals):
        """Greedy speculative verification.

        proposals: [B, P]; the first P-1 entries are candidate
        continuations (the last is padding so one jitted shape serves
        propose-and-bonus). Feeds ``[last_token, p_0 .. p_{P-2}]`` (P
        positions); per row the longest matching proposal prefix is
        accepted plus one bonus token from the model's own argmax chain.
        Returns ``(state', tokens [B, P], produced [B])`` where
        ``tokens[b, :produced[b]]`` are the newly generated tokens
        (1..P per active row, 0 for inactive).

        Callers must guarantee every active row has
        ``position + P < max_seq_len`` (the engine falls back to plain
        decode near capacity) — the block KV write is contiguous.
        """
        if self.cfg.beside_rows:
            raise ValueError(
                f"{self.cfg.name}: a verify step cannot roll "
                f"{self.cfg.beside_rows.lost} back past a rejected draft"
            )
        B, P = proposals.shape
        tokens = jnp.concatenate(
            [state.last_tokens[:, None], proposals[:, :-1]], axis=1
        )
        positions = (
            state.positions[:, None]
            + jnp.arange(P, dtype=jnp.int32)[None, :]
        )
        # every row's argmax is compared with a proposal: no
        # ``logits_at``
        logits, cache = forward(
            params, self.cfg, tokens, positions, state.cache,
            attn_impl="ring" if self.sp_mode else "xla",
            mesh=self.mesh,
        )
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, P]
        match = proposals[:, : P - 1] == greedy[:, : P - 1]
        n_accept = jnp.sum(
            jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1
        )                                                        # [B] 0..P-1
        produced = jnp.where(state.active, n_accept + 1, 0)      # tokens out
        new_last = jnp.take_along_axis(
            greedy, n_accept[:, None], axis=1
        )[:, 0]
        next_tokens = jnp.where(state.active, new_last, state.last_tokens)
        new_positions = jnp.where(
            state.active,
            jnp.minimum(state.positions + produced, self.max_seq_len - 1),
            state.positions,
        )
        at_capacity = new_positions + 1 >= self.max_seq_len
        return (
            DecodeState(
                cache=cache,
                last_tokens=next_tokens,
                positions=new_positions,
                active=state.active & ~at_capacity,
                sampling=state.sampling,
            ),
            greedy,
            produced,
        )

    def verify_step(
        self, state: DecodeState, proposals
    ) -> Tuple[DecodeState, jax.Array, jax.Array]:
        P = proposals.shape[1]
        fn = self._verifies.get(P)
        if fn is None:
            fn = jax.jit(self._verify_impl, donate_argnums=(1,))
            self._verifies[P] = fn
        return fn(self.params, state, jnp.asarray(proposals, jnp.int32))
