"""Serving engine: a device-resident batched scheduler over slot caches.

The TableNet integration is first-class: pass ``lut_params`` (from
``core.convert.convert_params``, ideally per-layer-planned via
``core.planner.plan_model``) and every converted projection executes via
the paper's LUT path — ``ExecCfg(use_pallas=True)`` routes through the
Pallas kernel on real devices, the jnp oracle otherwise, and
``ExecCfg(lut_grouped=True)`` additionally fuses same-shape projections
(QKV, gate/up) into one grouped dispatch per decode step.  The scheduler
is agnostic to all of it: both steps inherit the choice from the ``Ctx``
they are built with, so the grouped pre-stacked fast path rides through
unchanged.

Scheduler architecture (``BatchingEngine``):

* **Device-resident slot state.**  The cache carries, besides the KV ring,
  per-slot ``slot_active`` / ``slot_remaining`` / ``slot_key`` /
  ``next_tok`` / ``overflow`` leaves.  Both the prefill and the decode
  step are jitted functions ``(params, cache, ...) -> (cache, packed)``
  whose cache argument is **donated** — steady-state decode does zero
  full-cache allocations (XLA aliases every cache buffer in place) and no
  host-side cache surgery ever happens (the old ``_splice_cache``
  full-cache copies are gone).
* **Fused on-device sampling.**  ``SampleCfg`` (greedy / temperature /
  top-k) executes inside the jitted steps.  Non-greedy draws use
  ``fold_in(slot_key, index)`` — ``slot_key`` is derived from the request
  uid at admission and ``index`` is the slot's write offset — so a sampled
  stream is a pure function of (engine seed, uid, position) and identical
  under batched-admit and per-slot-admit schedules.
* **Batched multi-slot prefill.**  Admission right-pads up to
  ``num_slots`` queued prompts into one (num_slots, S_bucket) batch and
  runs ONE prefill that writes each prompt directly into its slot via the
  one-hot slot machinery (``token_mask`` masks pad positions and
  mid-decode slots).  ``admit="per-slot"`` admits one request per prefill
  call instead — same compiled step, more calls (the measured baseline in
  ``benchmarks/serving.py``).
* **One small readback per step.**  Each step returns a packed (B, 3)
  int32 array ``[token, done, overflow]``; ``step()`` reads it back once
  (steady-state decode: exactly one host readback; an admission round
  adds one for its prefill).  Blocking per-slot ``int(...)`` scalar syncs
  are gone.

Paged mode (``page_size=``): the cache stores K/V in fixed-size pages
behind a slot→page table (``repro.serve._cache``); a host-side
:class:`~repro.serve._paging.PageAllocator` maps pages on demand at
admission and before each decode step, and frees them (refcounted) on
retire.  Admission consults a prompt-prefix registry: a request whose
leading full pages match an earlier prompt maps those pages read-only and
prefills only the divergent tail — with at most one copy-on-write page
duplication (executed in-graph at the start of the prefill step) when the
whole prompt matched.  Requests whose prefix would match pages written in
the *same* admission round are deferred one round so they share instead of
re-prefilling.  The donated-cache / one-readback-per-step discipline is
unchanged: the host only uploads the small (B, max_pages) table when it
changes; ``engine.prefill_tokens`` counts actually-prefilled tokens (tails
only, under sharing) and ``engine.alloc.pages_in_use`` exposes physical
page occupancy.

Overflow policy: requests that cannot fit (``prompt + max_new - 1 >
max_len``) raise :class:`CacheOverflowError` at ``submit()``; the packed
``overflow`` column (accumulated by the cache layer whenever a write slot
would fall past ``max_len`` or land in an unmapped page) is checked on
every readback as a backstop, so overflowing tokens can never be silently
dropped.  In paged mode, pool exhaustion defers admission while any slot
is active (retires will free pages) and raises ``CacheOverflowError`` when
nothing can ever free one.

Tracing: the engine marks its host work with ``jax.profiler.TraceAnnotation``
spans, ``serve.step`` around each step and, inside it, ``serve.admit`` (per
admission round; ``serve.plan``, ``serve.prefill``), ``serve.pages``,
``serve.decode`` and ``serve.readback``.  They record only while a profiler
trace is active.  ``engine.prefill_rows`` counts the token rows the
prefills computed, padding included, beside ``prefill_tokens``.

``decode_step`` is what the decode_32k / long_500k dry-run cells lower: one
new token against a seq_len-deep cache, caches seq-sharded over the model
axis (DESIGN.md §4).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.models.layers import Ctx, SampleCfg, sample_tokens
from repro.models.model import model_forward
from repro.models.params import abstract_params, init_params, tree_map_specs
from repro.serve._cache import CacheOverflowError, cache_specs, copy_pages
from repro.serve._paging import PageAllocator, PagePoolExhausted, _prefix_key

__all__ = [
    "BatchingEngine",
    "CacheOverflowError",
    "Request",
    "SampleCfg",
    "abstract_cache",
    "generate",
    "make_cache",
    "make_decode_step",
    "make_prefill_step",
]

# families whose caches support slot-targeted masked prefill writes
_ENGINE_FAMILIES = ("dense", "moe", "vlm")


def make_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    ctx: Ctx,
    dtype=jnp.bfloat16,
    page_size: int | None = None,
    num_pages: int | None = None,
    page_table: str = "identity",
):
    """Materialize a fresh cache.  With ``page_size``, K/V storage is paged
    (see ``repro.serve._cache``): ``page_table="identity"`` statically maps
    slot b's group g to page ``b * max_pages + g`` — a standalone paged
    cache that behaves exactly like the dense rectangle (``generate`` uses
    this); ``page_table="empty"`` starts fully unmapped for an allocator
    (``BatchingEngine``) to fill."""
    specs = cache_specs(
        cfg, batch, max_len, page_size=page_size, num_pages=num_pages
    )
    cache = init_params(specs, jax.random.PRNGKey(0), default_dtype=dtype)
    if ctx.shard.mesh is not None:  # spread over the mesh like abstract_cache
        cache = jax.device_put(
            cache, tree_map_specs(ctx.shard.param_sharding, specs)
        )
    if page_size is not None:
        max_pages = cache["pos"].shape[1] // page_size
        if page_table == "identity":
            n_phys = cache["layers"][next(iter(cache["layers"]))].shape[1]
            if n_phys < batch * max_pages:
                raise ValueError(
                    f"identity page table needs {batch * max_pages} pages; "
                    f"pool has {n_phys}"
                )
            cache["page_table"] = jnp.arange(
                batch * max_pages, dtype=jnp.int32
            ).reshape(batch, max_pages)
        elif page_table == "empty":
            cache["page_table"] = jnp.full((batch, max_pages), -1, jnp.int32)
        else:
            raise ValueError(
                f"page_table must be 'identity' or 'empty': {page_table!r}"
            )
    return cache


def abstract_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    ctx: Ctx,
    dtype=jnp.bfloat16,
    page_size: int | None = None,
    num_pages: int | None = None,
):
    specs = cache_specs(
        cfg, batch, max_len, page_size=page_size, num_pages=num_pages
    )
    return abstract_params(
        specs,
        default_dtype=dtype,
        sharding_fn=(
            ctx.shard.param_sharding if ctx.shard.mesh is not None else None
        ),
    )


def _serve_ctx(ctx: Ctx) -> Ctx:
    return dataclasses.replace(ctx, ex=dataclasses.replace(ctx.ex, remat="none"))


def _slot_keys(cache: dict) -> jax.Array:
    """Per-slot sampling keys at the current write offsets (B, 2) uint32."""
    return jax.vmap(jax.random.fold_in)(cache["slot_key"], cache["index"])


def make_prefill_step(ctx: Ctx) -> Callable:
    """(params, inputs, cache) -> (last-token logits, filled cache)."""
    sctx = _serve_ctx(ctx)

    def prefill(params, inputs, cache):
        logits, cache, _ = model_forward(params, inputs, sctx, cache=cache)
        return logits[:, -1:], cache

    return prefill


def make_decode_step(ctx: Ctx, sample: SampleCfg | None = None) -> Callable:
    """(params, cache, tokens (B,1)) -> (next tokens (B,1), logits, cache).

    With a non-greedy ``sample``, the cache must carry a ``slot_key`` leaf
    ((B, 2) uint32 per-row PRNG keys); sampling runs fused on device.
    """
    scfg = sample or SampleCfg()
    sctx = _serve_ctx(ctx)

    def decode(params, cache, tokens):
        logits, cache, _ = model_forward(
            params, {"tokens": tokens}, sctx, cache=cache
        )
        keys = _slot_keys(cache) if scfg.mode != "greedy" else None
        nxt = sample_tokens(logits[:, -1], scfg, keys)[:, None]
        return nxt, logits, cache

    return decode


def generate(
    params,
    ctx: Ctx,
    prompts: jax.Array,
    max_new: int,
    max_len: int | None = None,
    eos_id: Optional[int] = None,
    enc_embeds: jax.Array | None = None,
    embeds: jax.Array | None = None,
    sample: SampleCfg | None = None,
    key: jax.Array | None = None,
    page_size: int | None = None,
) -> jax.Array:
    """Reference generation loop used by tests/examples.

    Semantics are aligned with :class:`BatchingEngine`: each row stops at
    its first ``eos_id`` token (the EOS itself is emitted); since the
    return value is rectangular (B, max_new), positions past a row's EOS
    are padded with ``eos_id``.  Non-greedy ``sample`` draws with
    ``fold_in(fold_in(key, row), position)`` per row.  Raises
    :class:`CacheOverflowError` up front when ``prompt + max_new - 1``
    writes cannot fit in ``max_len`` (a non-windowed cache would silently
    drop the overflowing tokens otherwise — the pre-PR4 bug).  With
    ``page_size``, K/V storage is paged behind an identity-mapped page
    table — same semantics, paged layout.
    """
    B, S = prompts.shape
    scfg = sample or SampleCfg()
    pre = S + (embeds.shape[1] if embeds is not None else 0)
    T = max_len or (pre + max_new)
    if ctx.cfg.sliding_window is None and pre + max_new - 1 > T:
        raise CacheOverflowError(
            f"prompt ({pre} tokens) + max_new ({max_new}) needs "
            f"{pre + max_new - 1} cache slots but max_len is {T}; raise "
            "max_len — overflowing one-hot writes would drop tokens"
        )
    cache = make_cache(ctx.cfg, B, T, ctx, page_size=page_size)
    if scfg.mode != "greedy":
        base = key if key is not None else jax.random.PRNGKey(0)
        cache["slot_key"] = jax.vmap(
            lambda r: jax.random.fold_in(base, r)
        )(jnp.arange(B, dtype=jnp.int32))
    prefill = jax.jit(make_prefill_step(ctx), donate_argnums=(2,))
    decode = jax.jit(make_decode_step(ctx, scfg), donate_argnums=(1,))
    inputs = {"tokens": prompts}
    if enc_embeds is not None:
        inputs["enc_embeds"] = enc_embeds
    if embeds is not None:
        inputs["embeds"] = embeds
    logits, cache = prefill(params, inputs, cache)
    keys = _slot_keys(cache) if scfg.mode != "greedy" else None
    tok = sample_tokens(logits[:, -1], scfg, keys)[:, None]
    out = [tok]
    done = np.zeros((B,), bool)
    for _ in range(max_new - 1):
        if eos_id is not None:
            done = done | (np.asarray(tok[:, 0]) == eos_id)
            if done.all():
                break
        tok, _, cache = decode(params, cache, tok)
        if eos_id is not None:
            tok = jnp.where(jnp.asarray(done)[:, None], eos_id, tok)
        out.append(tok)
    toks = jnp.concatenate(out, axis=1)
    if toks.shape[1] < max_new:  # every row hit EOS early: pad rectangle
        pad = jnp.full((B, max_new - toks.shape[1]), eos_id, jnp.int32)
        toks = jnp.concatenate([toks, pad], axis=1)
    return toks


# ---------------------------------------------------------------------------
# Device-resident batched scheduler
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    uid: int
    prompt: Any  # (S,) int32
    max_new: int
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False


@functools.lru_cache(maxsize=32)
def _engine_steps(
    ctx: Ctx, scfg: SampleCfg, eos_id: Optional[int], paged: bool = False
):
    """Compiled engine steps, shared across engine instances (lru-cached so
    repeated engine construction — benchmarks, tests — never recompiles).

    prefill: (params, cache, tokens, lens, admit, uids, max_news, base_key)
             -> (cache, packed); the paged variant takes three extra arrays
             (starts, copy_src, copy_dst): per-slot first-prefilled logical
             position (everything before it is mapped from shared pages)
             and at most one COW page duplication applied in-graph before
             the forward.
    decode:  (params, cache) -> (cache, packed)
    with packed (B, 3) int32 = [sampled token, done, overflow] — the single
    small array the host reads back per step.  Both donate their cache.
    """
    # force logits="all": the batched prefill gathers each slot's logits at
    # its own last REAL position (lens - 1); under logits="last" the model
    # would return only the right-padded final position's head — pad logits
    sctx = dataclasses.replace(
        ctx, ex=dataclasses.replace(ctx.ex, remat="none", logits="all")
    )

    def _sample(last, cache):
        keys = _slot_keys(cache) if scfg.mode != "greedy" else None
        return sample_tokens(last, scfg, keys)

    def _packed(tok, done, cache):
        return jnp.stack(
            [tok, done.astype(jnp.int32), cache["overflow"].astype(jnp.int32)],
            axis=1,
        )

    def _run_prefill(params, cache, tokens, lens, admit):
        """Shared tail: masked forward + per-slot last-real-token sampling."""
        S = tokens.shape[1]
        adm1 = admit[:, None]
        mask = (jnp.arange(S, dtype=jnp.int32)[None, :] < lens[:, None]) & adm1
        logits, cache, _ = model_forward(
            params, {"tokens": tokens, "token_mask": mask}, sctx, cache=cache
        )
        last = jnp.take_along_axis(
            logits, jnp.maximum(lens - 1, 0)[:, None, None], axis=1
        )[:, 0]
        tok = _sample(last, cache)
        eos_hit = (tok == eos_id) if eos_id is not None else jnp.zeros_like(admit)
        done = admit & (eos_hit | (cache["slot_remaining"] <= 0))
        cache = dict(
            cache,
            slot_active=(cache["slot_active"] | admit) & ~done,
            next_tok=jnp.where(adm1, tok[:, None], cache["next_tok"]),
        )
        return cache, _packed(tok, done, cache)

    def prefill(params, cache, tokens, lens, admit, uids, max_news, base_key):
        fresh_keys = jax.vmap(lambda u: jax.random.fold_in(base_key, u))(uids)
        adm1 = admit[:, None]
        cache = dict(
            cache,
            index=jnp.where(admit, 0, cache["index"]),
            pos=jnp.where(adm1, 0, cache["pos"]),
            valid=cache["valid"] & ~adm1,
            overflow=cache["overflow"] & ~admit,
            slot_key=jnp.where(adm1, fresh_keys, cache["slot_key"]),
            slot_remaining=jnp.where(admit, max_news - 1, cache["slot_remaining"]),
        )
        return _run_prefill(params, cache, tokens, lens, admit)

    def prefill_paged(
        params, cache, tokens, lens, admit, uids, max_news, base_key,
        starts, copy_src, copy_dst,
    ):
        T = cache["pos"].shape[1]
        fresh_keys = jax.vmap(lambda u: jax.random.fold_in(base_key, u))(uids)
        adm1 = admit[:, None]
        tpos = jnp.arange(T, dtype=jnp.int32)[None, :]
        shared = tpos < starts[:, None]  # slots mapped from the prefix registry
        # COW duplications first: the divergent tail below overwrites only
        # private copies, never pages other slots still reference
        layers = {
            name: copy_pages(leaf, copy_src, copy_dst)
            for name, leaf in cache["layers"].items()
        }
        cache = dict(
            cache,
            layers=layers,
            index=jnp.where(admit, starts, cache["index"]),
            # shared-prefix slots are valid with their absolute positions;
            # the tail is written by the masked forward below
            pos=jnp.where(adm1, jnp.where(shared, tpos, 0), cache["pos"]),
            valid=jnp.where(adm1, shared, cache["valid"]),
            overflow=cache["overflow"] & ~admit,
            slot_key=jnp.where(adm1, fresh_keys, cache["slot_key"]),
            slot_remaining=jnp.where(admit, max_news - 1, cache["slot_remaining"]),
        )
        return _run_prefill(params, cache, tokens, lens, admit)

    def decode(params, cache):
        active = cache["slot_active"]
        logits, cache, _ = model_forward(
            params,
            {"tokens": cache["next_tok"], "token_mask": active[:, None]},
            sctx,
            cache=cache,
        )
        tok = _sample(logits[:, -1], cache)
        remaining = cache["slot_remaining"] - active.astype(jnp.int32)
        eos_hit = (tok == eos_id) if eos_id is not None else jnp.zeros_like(active)
        done = active & (eos_hit | (remaining <= 0))
        cache = dict(
            cache,
            slot_remaining=remaining,
            slot_active=active & ~done,
            next_tok=jnp.where(active[:, None], tok[:, None], cache["next_tok"]),
        )
        return cache, _packed(tok, done, cache)

    return (
        jax.jit(prefill_paged if paged else prefill, donate_argnums=(1,)),
        jax.jit(decode, donate_argnums=(1,)),
    )


def _bucket(n: int, cap: int) -> int:
    """Right-pad prompts to a power-of-two bucket (bounds recompilation)."""
    b = 4
    while b < n:
        b *= 2
    return min(b, cap)


class BatchingEngine:
    """Fixed-slot continuous batching, fully device-resident: finished
    sequences are swapped out for queued requests between decode steps via
    batched masked prefill (see the module docstring for the scheduler
    architecture, paging/prefix-sharing, sampling determinism, readback and
    overflow contracts).
    """

    def __init__(
        self,
        params,
        ctx: Ctx,
        num_slots: int,
        max_len: int,
        eos_id: Optional[int] = None,
        sample: SampleCfg | None = None,
        seed: int = 0,
        admit: str = "batched",
        prefill_bucket: int | None = None,
        page_size: int | None = None,
        num_pages: int | None = None,
        share_prefixes: bool = True,
    ):
        if ctx.cfg.family not in _ENGINE_FAMILIES:
            raise NotImplementedError(
                f"BatchingEngine needs slot-targeted cache writes; family "
                f"{ctx.cfg.family!r} has recurrent/cross caches without them"
            )
        if admit not in ("batched", "per-slot"):
            raise ValueError(f"admit must be 'batched' or 'per-slot': {admit!r}")
        self.params, self.ctx = params, ctx
        self.num_slots, self.max_len = num_slots, max_len
        self.eos_id = eos_id
        self.sample = sample or SampleCfg()
        self.admit_mode = admit
        self.page_size = page_size
        self.queue: list[Request] = []
        self.slots: list[Optional[Request]] = [None] * num_slots
        self._windowed = ctx.cfg.sliding_window is not None
        if page_size is not None:
            self.cache = make_cache(
                ctx.cfg, num_slots, max_len, ctx,
                page_size=page_size, num_pages=num_pages, page_table="empty",
            )
            self._T = self.cache["pos"].shape[1]
            pages_per_slot = self._T // page_size
            self.alloc: Optional[PageAllocator] = PageAllocator(
                num_pages or num_slots * pages_per_slot,
                page_size,
                num_slots,
                pages_per_slot,
                # ring contents are position-dependent: never share them
                share=share_prefixes and not self._windowed,
            )
        else:
            self.cache = make_cache(ctx.cfg, num_slots, max_len, ctx)
            self._T = self.cache["pos"].shape[1]  # min(window, max_len) for SWA
            self.alloc = None
        self.prefill_bucket = prefill_bucket
        if prefill_bucket is not None and prefill_bucket > self._T:
            raise ValueError(
                f"prefill_bucket {prefill_bucket} exceeds cache capacity {self._T}"
            )
        self.cache.update(
            overflow=jnp.zeros((num_slots,), bool),
            slot_active=jnp.zeros((num_slots,), bool),
            slot_remaining=jnp.zeros((num_slots,), jnp.int32),
            slot_key=jnp.zeros((num_slots, 2), jnp.uint32),
            next_tok=jnp.zeros((num_slots, 1), jnp.int32),
        )
        self._base_key = jax.random.PRNGKey(seed)
        self._prefill, self._decode = _engine_steps(
            ctx, self.sample, eos_id, paged=page_size is not None
        )
        self.readbacks = 0  # host syncs: 1/decode step + 1/admission prefill
        self.prefill_tokens = 0  # tokens actually prefilled (tails only)
        self.prefill_rows = 0  # token rows prefills computed, padding included
        self._slot_len = [0] * num_slots  # host mirror of per-slot index

    def submit(self, req: Request):
        plen = int(req.prompt.shape[0])
        if plen < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        cap = self.prefill_bucket or self._T
        if plen > cap:
            raise ValueError(
                f"request {req.uid}: prompt ({plen}) exceeds the prefill "
                f"capacity ({cap} tokens)"
            )
        if (
            self.ctx.cfg.sliding_window is None
            and plen + req.max_new - 1 > self.max_len
        ):
            raise CacheOverflowError(
                f"request {req.uid}: prompt ({plen}) + max_new ({req.max_new}) "
                f"needs {plen + req.max_new - 1} cache slots but max_len is "
                f"{self.max_len}; overflowing writes would drop tokens"
            )
        self.queue.append(req)

    def _check(self, packed) -> np.ndarray:
        """The ONE host readback per step; backstop overflow check."""
        with TraceAnnotation("serve.readback"):
            arr = np.asarray(packed)
            self.readbacks += 1
            if arr[:, 2].any():
                raise CacheOverflowError(
                    "cache overflow flagged for slots "
                    f"{arr[:, 2].nonzero()[0].tolist()}"
                )
            return arr

    def _plan_batch(self, free: list[int], limit: int):
        """Pop up to ``limit`` admittable requests, assigning slots (and,
        when paged, page mappings).  Prefix-sharing candidates whose donor
        is being prefilled in this same round are deferred one round so
        they map its registered pages instead of re-prefilling."""
        placed: list[tuple[Request, int, Any]] = []
        pending: set[bytes] = set()
        while self.queue and len(placed) < limit:
            req = self.queue.pop(0)
            if req.max_new <= 0:
                req.done = True  # nothing requested; don't pay a prefill
                continue
            s = free[len(placed)]
            if self.alloc is None:
                placed.append((req, s, None))
                continue
            pnp = np.asarray(req.prompt, np.int32)
            keys = (
                [
                    _prefix_key(pnp, m * self.page_size)
                    for m in range(1, len(pnp) // self.page_size + 1)
                ]
                if self.alloc.share
                else []
            )
            if any(
                k in pending and not self.alloc.has_prefix(k) for k in keys
            ):
                self.queue.insert(0, req)  # share with this round's donor
                break  # once it registers, next round
            plan = (
                self.alloc.admit_windowed(s)
                if self._windowed
                else self.alloc.admit(s, pnp)
            )
            if plan is None:  # pool dry: wait for retires to free pages
                self.queue.insert(0, req)
                break
            pending.update(keys)
            placed.append((req, s, plan))
        return placed

    def _admit(self):
        while self.queue and any(s is None for s in self.slots):
            with TraceAnnotation("serve.admit") as span:
                if not self._admit_round(span):
                    return

    def _admit_round(self, span) -> bool:
        """Plan one round, prefill every placed request in one call and read
        the first tokens back; False when nothing could be placed."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        limit = 1 if self.admit_mode == "per-slot" else len(free)
        with TraceAnnotation("serve.plan"):
            placed = self._plan_batch(free, limit)
            if not placed:
                if (
                    self.alloc is not None
                    and self.queue
                    and all(r is None for r in self.slots)
                ):
                    req = self.queue[0]
                    raise CacheOverflowError(
                        f"request {req.uid}: page pool exhausted with no "
                        "active slots to retire; raise num_pages"
                    )
                return False
            B = self.num_slots
            tails = [
                np.asarray(r.prompt, np.int32)[(p.start if p else 0):]
                for r, _, p in placed
            ]
            S = self.prefill_bucket or _bucket(
                max(len(t) for t in tails), self._T
            )
            tokens = np.zeros((B, S), np.int32)
            lens = np.ones((B,), np.int32)
            admit = np.zeros((B,), bool)
            uids = np.zeros((B,), np.int32)
            max_news = np.ones((B,), np.int32)
            starts = np.zeros((B,), np.int32)
            copy_src = np.full((B,), -1, np.int32)
            copy_dst = np.full((B,), -1, np.int32)
            for (req, s, plan), tail in zip(placed, tails):
                tokens[s, : len(tail)] = tail
                lens[s], admit[s] = len(tail), True
                uids[s], max_news[s] = req.uid, req.max_new
                if plan is not None:
                    starts[s] = plan.start
                    copy_src[s], copy_dst[s] = plan.copy_src, plan.copy_dst
        n_tokens = int(sum(len(t) for t in tails))
        if TraceAnnotation.is_enabled():
            span.set_metadata(
                rows=B * S, tokens=n_tokens,
                uids=" ".join(str(r.uid) for r, _, _ in placed),
            )
        if self.alloc is not None:
            self.cache["page_table"] = jnp.asarray(self.alloc.table)
            with TraceAnnotation("serve.prefill"):
                self.cache, packed = self._prefill(
                    self.params, self.cache, tokens, lens, admit, uids,
                    max_news, self._base_key, starts, copy_src, copy_dst,
                )
            for (req, s, plan), tail in zip(placed, tails):
                # the prefill writing these pages has been issued: safe
                # to register them for future admissions to map
                self.alloc.register(s, np.asarray(req.prompt, np.int32))
                self._slot_len[s] = int(plan.start) + len(tail)
        else:
            with TraceAnnotation("serve.prefill"):
                self.cache, packed = self._prefill(
                    self.params, self.cache, tokens, lens, admit, uids,
                    max_news, self._base_key,
                )
            for (req, s, _), tail in zip(placed, tails):
                self._slot_len[s] = len(tail)
        self.prefill_tokens += n_tokens
        self.prefill_rows += B * S
        arr = self._check(packed)
        for req, s, _ in placed:
            req.generated.append(int(arr[s, 0]))
            if arr[s, 1]:  # EOS at prefill or max_new == 1: free the
                req.done = True  # slot now; keep admitting into it
                if self.alloc is not None:
                    self.alloc.retire(s)
            else:
                self.slots[s] = req
        return True

    def lower_decode(self):
        """The engine's decode step lowered for its current params and cache
        (``.compile()`` it to inspect the program each step runs)."""
        return self._decode.lower(self.params, self.cache)

    def step(self) -> bool:
        """One decode step over all active slots; returns True if any active."""
        with TraceAnnotation("serve.step"):
            self._admit()
            if all(r is None for r in self.slots):
                return False
            if self.alloc is not None:
                with TraceAnnotation("serve.pages"):
                    self._map_pages()
            with TraceAnnotation("serve.decode"):
                self.cache, packed = self._decode(self.params, self.cache)
            arr = self._check(packed)
            for s, req in enumerate(self.slots):
                if req is None:
                    continue
                self._slot_len[s] += 1
                req.generated.append(int(arr[s, 0]))
                if arr[s, 1]:
                    req.done = True
                    self.slots[s] = None
                    if self.alloc is not None:
                        self.alloc.retire(s)
            return True

    def _map_pages(self):
        """Map the page each active slot's next decode write lands in, and
        upload the table if that changed it."""
        dirty = False
        for s, req in enumerate(self.slots):
            if req is not None:
                try:
                    # the decode writes this slot's KV at its current
                    # length: map that page before tracing
                    dirty |= self.alloc.ensure_page(s, self._slot_len[s])
                except PagePoolExhausted as e:
                    raise CacheOverflowError(str(e)) from None
        if dirty:
            self.cache["page_table"] = jnp.asarray(self.alloc.table)

    def run(self) -> list[Request]:
        all_reqs = list(self.queue)
        while self.step():
            pass
        return all_reqs
