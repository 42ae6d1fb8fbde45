"""Serve granite_8b at its published widths on a TPU v5e, end to end.

    python chip_smoke.py              # one chip: dense, weight tables, TL1
    python chip_smoke.py --chips 4    # four chips: the sharded engines only

The quickest proof that the system starts on the chip.  It drives the main
serving path through the entry points a user calls -- ``init_params`` ->
``plan_model`` -> ``convert_params`` -> ``repro.serve.BatchingEngine`` with
greedy sampling -- so a few requests are admitted, prefilled, decoded and
retired.  The model is granite_8b at every published width (d_model 4096,
32 query / 8 KV heads of 128, d_ff 14336, vocab 49152, tied embeddings),
cut to 4 layers because 36 bf16 layers do not fit one chip's 16 GB.  The
weights are random, made from ``SEED``.

One chip, three families in one process:

1. ``dense``  -- bf16 weights;
2. ``weight`` -- the paper's weight-side tables (8-bit fixed-point bitplane
   codes, 4-element chunks, 16-entry int8 tables), planned under a byte
   budget that fits HBM beside the cache;
3. ``tl1``    -- TL1 activation-side tables (ternary weights, 8-bit codes).

Both table families serve with ``ExecCfg(use_pallas=True, lut_grouped=True)``
so the Pallas kernels are what runs.  Each family prints one JSON line for
serving (compile seconds, tokens, decode steps, peak device bytes, and
whether the compiled decode step holds a ``tpu_custom_call``) and one for a
logits check on one prefill.  ``--chips 4`` runs only the dense and
weight-table engines over a 4-device ``model`` mesh (``no_fsdp`` rules) and
checks their prefill logits against the same model on device 0.

The last line is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero before any phase: it has no CPU path.  Any failed
phase raises and exits non-zero.  One process holds the chip; the script
starts no other.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

LAYERS = 4  # 36 bf16 layers of granite_8b exceed one chip's 16 GB
SEED = 0
SLOTS = 4  # engine batch slots
REQUESTS = 6  # > SLOTS: retiring requests free slots for queued ones
PROMPT_LENS = (17, 32)  # one prefill bucket of 32 tokens
# The sharded weight tables run the jnp oracle, which materializes its
# (rows, planes, chunks, p) fp32 gather: 30 GB per device for a prefill of
# SLOTS x 32 tokens.  Its prompts stay in the bucket of 4 (3.75 GB).
ORACLE_PROMPT_LENS = (3, 4)
MAX_NEW = 8
MAX_LEN = 64  # cache positions per slot: longest prompt + MAX_NEW fits
HEADROOM = 4 * 2**30  # HBM kept free of tables: activations, temporaries
# 8-bit fixed point with 4 fractional bits holds the post-RMSNorm
# activations (about N(0, 1)) to +-8 in steps of 1/16
ACT_BITS, ACT_FRAC = 8, 4


def log(**row):
    print(json.dumps(row), flush=True)


def require(ok: bool, what: str):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def tpu_devices(chips: int):
    """The TPU devices, or exit naming what JAX found instead."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}, {len(devs)} devices); "
            f"there is no CPU path"
        )
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPUs, found {len(devs)}"
        )
    return devs


def granite_4l():
    from repro.configs.base import get_config

    cfg = dataclasses.replace(get_config("granite_8b"), num_layers=LAYERS)
    published = dict(
        d_model=4096, num_heads=32, num_kv_heads=8, head_dim=128,
        d_ff=14336, vocab_size=49152, tie_embeddings=True,
    )
    require(
        all(getattr(cfg, k) == v for k, v in published.items()),
        f"granite_8b widths differ from the published {published}",
    )
    return cfg


def prompts(cfg, lens=PROMPT_LENS):
    import numpy as np

    rng = np.random.default_rng(SEED)
    lo, hi = lens
    return [
        rng.integers(0, cfg.vocab_size, int(rng.integers(lo, hi + 1))).astype(np.int32)
        for _ in range(REQUESTS)
    ]


def weight_plan(params, cfg):
    """Weight-table plan under the HBM left beside the bf16 parameters,
    the cache and ``HEADROOM``; the kernels serve at most
    ``MAX_SELECT_ENTRIES``-entry int8 tables."""
    import jax
    import jax.numpy as jnp

    from repro.core.planner import plan_model
    from repro.core.quantize import FixedPointFormat
    from repro.kernels.common import MAX_SELECT_ENTRIES
    from repro.launch.hlo_analysis import peaks
    from repro.serve import cache_specs
    from repro.models.params import abstract_params

    kind = jax.devices()[0].device_kind
    param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    cache = abstract_params(
        cache_specs(cfg, SLOTS, MAX_LEN), default_dtype=jnp.bfloat16
    )
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    budget = peaks(kind)["hbm_bytes"] - param_bytes - cache_bytes - HEADROOM
    return plan_model(
        params,
        budget,
        fmt=FixedPointFormat(ACT_BITS, ACT_FRAC, signed=True),
        modes=("bitplane",),
        table_formats=("i8",),
        max_entries=MAX_SELECT_ENTRIES,
    )


def tl1_plan(params):
    from repro.core.planner import plan_model

    return plan_model(params, math.inf, families=("tl1",), tl1_act_bits=8)


def exec_cfg(family: str, use_pallas: bool = True):
    from repro.models.layers import ExecCfg

    if family == "dense":
        return ExecCfg(remat="none")
    return ExecCfg(remat="none", use_pallas=use_pallas, lut_grouped=True)


def serve(family: str, params, ctx, cfg, devices, lens=PROMPT_LENS) -> dict:
    """Admit, prefill, decode and retire ``REQUESTS`` greedy requests."""
    from repro.serve import BatchingEngine, Request

    eng = BatchingEngine(params, ctx, num_slots=SLOTS, max_len=MAX_LEN)
    t0 = time.perf_counter()
    decode_hlo = eng.lower_decode().compile().as_text()
    compile_s = time.perf_counter() - t0
    reqs = [
        Request(uid=i, prompt=p, max_new=MAX_NEW)
        for i, p in enumerate(prompts(cfg, lens))
    ]
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    steps = 0
    while eng.step():
        steps += 1
    serve_s = time.perf_counter() - t0
    row = dict(
        phase="serve",
        family=family,
        devices=len(devices),
        decode_compile_s=compile_s,
        serve_s_incl_prefill_compile=serve_s,
        requests=len(reqs),
        prompt_tokens=[len(r.prompt) for r in reqs],
        tokens=sum(len(r.generated) for r in reqs),
        decode_steps=steps,
        peak_bytes=[(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices],
        decode_tpu_custom_call="tpu_custom_call" in decode_hlo,
        first_stream=reqs[0].generated,
    )
    log(**row)
    require(
        all(r.done and len(r.generated) == MAX_NEW for r in reqs),
        f"{family}: not every request generated {MAX_NEW} tokens",
    )
    require(
        all(0 <= t < cfg.vocab_size for r in reqs for t in r.generated),
        f"{family}: token outside the vocabulary",
    )
    if ctx.ex.use_pallas:
        require(row["decode_tpu_custom_call"], f"{family}: decode step holds no kernel")
    return row


def reference_logits(params, tokens, cfg):
    """Plain f32 ``jax.numpy`` forward of the llama-architecture decoder
    (RMSNorm, half-split RoPE, causal GQA, SwiGLU, tied head), written
    independently of ``repro.models``."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    B, S = tokens.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    half = hd // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * cfg.rope_theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half
    )
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]

    def rope(t):
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

    def rms(t, scale):
        return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + cfg.norm_eps) * scale

    causal = jnp.tril(jnp.ones((S, S), bool))
    x = p["embed"][tokens]
    for i in range(cfg.num_layers):
        b = jax.tree.map(lambda a: a[i], p["blocks"])
        at, ff = b["attn"], b["ffn"]
        h = rms(x, b["ln1"]["scale"])
        q = rope((h @ at["wq"]["w"]).reshape(B, S, H, hd))
        k = rope((h @ at["wk"]["w"]).reshape(B, S, K, hd))
        v = (h @ at["wv"]["w"]).reshape(B, S, K, hd)
        k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * hd)
        x = x + o @ at["wo"]["w"]
        h = rms(x, b["ln2"]["scale"])
        gated = jax.nn.silu(h @ ff["w_gate"]["w"]) * (h @ ff["w_up"]["w"])
        x = x + gated @ ff["w_down"]["w"]
    return rms(x, p["ln_f"]["scale"]) @ p["embed"].T


# Tolerances of the logits checks: max |got - want| over max |want|.
# The dense path serves bf16 weights, activations and residual stream (a
# relative step of 2**-8 per rounding) against an f32 reference computed
# from the same weights at full matmul precision; over 4 layers and the
# 4096-wide head the rounding stays well under 3e-2.  A path that rounded to
# fp8 (2**-4 per step) or dropped a term would not.
DENSE_TOL = 3e-2
# The table families check the table path in fp32 activations (their bf16
# embedding and norm scales upcast; tables and codes as served): in bf16, the
# two programs round intermediates to bf16 in different places (XLA keeps
# excess precision inside its fusions), and the 8-bit activation codes turn
# each rounding flip into a whole code step -- 7.7e-2 between Pallas and
# oracle over 4 layers on the chip, though every linear matched bit for bit.
# In fp32 the paths differ only in the order of fp32 table sums; a wrong
# entry selected or a dropped plane or chunk would move logits by O(1).  The
# oracle runs layer by layer (``layerwise_logits``).
LUT_TOL = 1e-3
CHECK_LEN = {"dense": 16, "weight": 4, "tl1": 16}  # tokens in the checked prefill


def rel_err(got, want) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def layerwise_logits(params, tokens, ctx):
    """The model's forward with each block's attention and MLP as programs
    of their own, no scan over layers.  On the chip, the TL1 jnp oracle
    compiled as the whole scanned model read 1.57e-2 away from this at 16
    tokens, while the Pallas path matched it bit for bit (PERF.md)."""
    import jax
    import jax.numpy as jnp

    from repro.models import layers as L

    cfg = ctx.cfg
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    attn = jax.jit(lambda p, h: L.attention(p, h, ctx, pos)[0])
    mlp = jax.jit(lambda p, h: L.mlp(p, h, ctx))
    x = jnp.take(params["embed"], tokens, axis=0)
    for i in range(cfg.num_layers):
        b = jax.tree.map(lambda a: a[i], params["blocks"])
        x = x + attn(b["attn"], L.apply_norm(b["ln1"], x, cfg))
        x = x + mlp(b["ffn"], L.apply_norm(b["ln2"], x, cfg))
    return L.apply_norm(params["ln_f"], x, cfg) @ params["embed"].T


def f32_activations(params):
    """``params`` with its bf16 leaves (embedding, norm scales) in fp32, so
    the forward computes in fp32; table leaves keep their dtypes."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a, params
    )


def check(family: str, params, ctx, cfg, params_ref=None, ctx_ref=None) -> dict:
    """Logits of one prefill against the family's reference: the f32
    reference forward (dense), the jnp oracle (table families), or the same
    model on one device (``params_ref``/``ctx_ref``, the sharded engines)."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import model_forward

    n = CHECK_LEN[family]
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), (1, n), 0, cfg.vocab_size)

    def forward(c):
        return jax.jit(lambda p, t: model_forward(p, {"tokens": t}, c)[0])

    if family != "dense":  # see LUT_TOL
        params = f32_activations(params)
        if params_ref is not None:
            params_ref = f32_activations(params_ref)
    if params_ref is not None:
        got = forward(ctx)(params, tokens)
        want = forward(ctx_ref)(params_ref, tokens)
        # sharded bf16 matmuls add bf16 partial sums across devices, which
        # rounds like the bf16-vs-f32 gap (DENSE_TOL); the p-sharded weight
        # tables need no cross-device sum (LUT_TOL)
        tol = DENSE_TOL if family == "dense" else LUT_TOL
        against = "same model on one device"
    elif family == "dense":
        got = forward(ctx)(params, tokens)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference_logits, static_argnums=2)(params, tokens, cfg)
        tol, against = DENSE_TOL, "f32 jnp reference, highest precision"
    else:
        oracle = dataclasses.replace(ctx, ex=exec_cfg(family, use_pallas=False))
        with jax.default_matmul_precision("highest"):
            got = forward(ctx)(params, tokens)
            want = layerwise_logits(params, tokens, oracle)
        tol = LUT_TOL
        against = "jnp oracle layer by layer, same converted params, fp32 activations"
    got = got.astype(jnp.float32)[..., : cfg.vocab_size]
    want = want.astype(jnp.float32)[..., : cfg.vocab_size]
    finite = bool(jnp.all(jnp.isfinite(got)))
    err = rel_err(got, want)
    row = dict(
        phase="check",
        family=family,
        against=against,
        prefill_tokens=n,
        logits_shape=list(got.shape),
        finite=finite,
        rel_err=err,
        tol=tol,
        argmax_agree=float(jnp.mean(jnp.argmax(got, -1) == jnp.argmax(want, -1))),
    )
    log(**row)
    require(finite and got.shape == (1, n, cfg.vocab_size), f"{family}: bad logits")
    require(err <= tol, f"{family}: logits error {err:.3g} over tolerance {tol}")
    return row


def convert(family: str, dense, cfg):
    from repro.core.convert import convert_params

    mplan = weight_plan(dense, cfg) if family == "weight" else tl1_plan(dense)
    t0 = time.perf_counter()
    params, report = convert_params(dense, plan=mplan)
    log(
        phase="convert",
        family=family,
        plan=mplan.summary(),
        table_bytes=mplan.total_lut_bytes,
        converted=report.converted,
        convert_s=time.perf_counter() - t0,
    )
    return params


def one_chip(cfg, dense, devices):
    from repro.models.layers import Ctx

    for family in ("dense", "weight", "tl1"):
        params = dense if family == "dense" else convert(family, dense, cfg)
        ctx = Ctx(cfg, ex=exec_cfg(family))
        serve(family, params, ctx, cfg, devices[:1])
        check(family, params, ctx, cfg)
        del params


def spread(tree, devices) -> list[int]:
    """Bytes of ``tree`` held on each device."""
    import jax

    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            held[s.device] += s.data.size * s.data.dtype.itemsize
    return [held[d] for d in devices]


def four_chips(cfg, dense, devices):
    """Dense and weight-table engines sharded over a 4-device ``model``
    mesh, each checked against the same model unsharded on device 0.  The
    sharded weight tables run the jnp oracle, the table path GSPMD can
    partition."""
    import jax
    import jax.numpy as jnp

    from repro.dist.sharding import RULE_SETS, ShardCtx, converted_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.models.layers import Ctx
    from repro.models.model import model_specs
    from repro.models.params import abstract_params
    from repro.serve import make_cache

    shard = ShardCtx(make_host_mesh(data=1, model=4), RULE_SETS["no_fsdp"])
    dense_abs = abstract_params(
        model_specs(cfg), default_dtype=jnp.bfloat16, sharding_fn=shard.param_sharding
    )
    for family in ("dense", "weight"):
        params1 = dense if family == "dense" else convert(family, dense, cfg)
        ex = exec_cfg(family, use_pallas=False)
        ctx1, ctx4 = Ctx(cfg, ex=ex), Ctx(cfg, shard=shard, ex=ex)
        if family == "dense":
            shardings = jax.tree.map(lambda a: a.sharding, dense_abs)
        else:
            shardings = converted_shardings(params1, dense_abs, shard)
        params4 = jax.device_put(params1, shardings)
        held = spread(params4, devices)
        cache_held = spread(make_cache(cfg, SLOTS, MAX_LEN, ctx4)["layers"], devices)
        log(phase="placement", family=family, param_bytes=held, cache_bytes=cache_held)
        for what, b in (("params", held), ("cache", cache_held)):
            require(
                min(b) > 0 and max(b) < sum(b) / 2,
                f"{family}: {what} not spread over the 4 devices: {b}",
            )
        lens = PROMPT_LENS if family == "dense" else ORACLE_PROMPT_LENS
        serve(family, params4, ctx4, cfg, devices, lens)
        check(family, params4, ctx4, cfg, params_ref=params1, ctx_ref=ctx1)
        del params4, params1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the sharded dense and weight-table engines",
    )
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devices = tpu_devices(args.chips)[: args.chips]
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.backend import use_compile_cache
    from repro.launch.hlo_analysis import peaks
    from repro.models.model import model_specs
    from repro.models.params import init_params

    use_compile_cache(os.path.join(ROOT, ".jax_cache"))
    peaks(devices[0].device_kind)  # the chip must have published peaks
    cfg = granite_4l()
    t0 = time.perf_counter()
    dense = init_params(model_specs(cfg), jax.random.PRNGKey(SEED), jnp.bfloat16)
    log(
        phase="init",
        model=f"{cfg.name}, {LAYERS} of 36 layers",
        params=sum(a.size for a in jax.tree.leaves(dense)),
        init_s=time.perf_counter() - t0,
    )
    if args.chips == 4:
        four_chips(cfg, dense, devices)
    else:
        one_chip(cfg, dense, devices)
    d = jax.devices()[0]
    device = {
        "platform": d.platform, "kind": d.device_kind, "count": len(jax.devices())
    }
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
