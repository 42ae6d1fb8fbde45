"""Subprocess worker for distribution tests: runs under 16 fake CPU devices.

Usage: python tests/dist_worker.py <mode>
Prints one JSON line with results; exit code 0 on success.
"""
# ruff: noqa: E402 -- the fake-device XLA_FLAGS must be set before jax imports
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import json
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def small_mesh():
    return jax.make_mesh((2, 2, 4), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)


def mode_train_step_executes():
    """Sharded end-to-end train step on a 2x2x4 mesh matches 1-device run."""
    from repro.configs.base import get_config
    from repro.data.synthetic import LMStreamConfig, lm_batch
    from repro.dist.sharding import ShardCtx
    from repro.models.layers import Ctx, ExecCfg
    from repro.models.model import model_specs
    from repro.models.params import abstract_params, init_params
    from repro.train.trainer import TrainConfig, make_train_step

    cfg = get_config("qwen2_moe_a2_7b", reduced=True)  # exercises shard_map MoE
    mesh = small_mesh()
    ctx_d = Ctx(cfg, shard=ShardCtx(mesh), ex=ExecCfg(remat="none"))
    ctx_1 = Ctx(cfg, ex=ExecCfg(remat="none"))
    tc = TrainConfig(microbatches=1, compute_dtype=jnp.float32)

    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    batch = lm_batch(LMStreamConfig(cfg.vocab_size, 16, 8, seed=0), 0)
    from repro.optim.adamw import init_opt_state

    opt = init_opt_state(params)

    # distribute params per sharding rules
    sharded_params = jax.tree.map(lambda a: a, params)
    abs_p = abstract_params(
        model_specs(cfg), default_dtype=jnp.float32,
        sharding_fn=ctx_d.shard.param_sharding,
    )
    sharded_params = jax.tree.map(
        lambda a, s: jax.device_put(a, s.sharding), params, abs_p
    )
    step_d = jax.jit(make_train_step(ctx_d, tc))
    step_1 = jax.jit(make_train_step(ctx_1, tc))
    p_d, o_d, m_d = step_d(sharded_params, init_opt_state(sharded_params), batch)
    p_1, o_1, m_1 = step_1(params, opt, batch)
    dl = abs(float(m_d["loss"]) - float(m_1["loss"]))
    # parameters after one step agree
    diffs = [
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(p_d), jax.tree.leaves(p_1))
    ]
    return {"loss_diff": dl, "max_param_diff": max(diffs)}


def mode_moe_mesh():
    """moe_ffn on the 2x2x4 mesh, dense AND LUT experts: evenly-divisible
    batches shard tokens over (pod, data) and the returned aux loss must be
    exactly the pmean of the shard-local aux losses (it is genuinely
    replicated, so the P() out-spec is sound); tiny decode batches
    ((B*S) % data_size != 0) drop data sharding and must reproduce the
    single-device output AND aux bit-closely."""
    from repro.configs.base import get_config
    from repro.core.convert import convert_params
    from repro.dist.sharding import ShardCtx
    from repro.models.layers import Ctx, ExecCfg
    from repro.models.moe import _route, moe_ffn, moe_specs
    from repro.models.params import init_params

    cfg = get_config("qwen2_moe_a2_7b", reduced=True)
    mesh = small_mesh()  # dp = pod x data = 4, tp = model = 4
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0))
    lut, rep = convert_params(p, chunk_size=2, convert_experts=True)
    assert rep.grouped >= 1  # gate/up pre-stacked
    ctx1 = Ctx(cfg, ex=ExecCfg(remat="none"))
    ctxm = Ctx(cfg, shard=ShardCtx(mesh), ex=ExecCfg(remat="none"))

    key = jax.random.PRNGKey(1)
    x_even = jax.random.normal(key, (4, 8, cfg.d_model)) * 0.5  # 32 tok / 4 shards
    x_tiny = jax.random.normal(key, (1, 1, cfg.d_model)) * 0.5  # 1 % 4 != 0
    # the aux contract under data sharding: pmean of the per-shard locals
    shards = x_even.reshape(4, -1, cfg.d_model)  # (pod, data)-major row blocks
    aux_want = float(np.mean([float(_route(s, p["router"], cfg)[2]) for s in shards]))

    out = {}
    for name, prm in [("dense", p), ("lut", lut)]:
        y1, _ = moe_ffn(prm, x_even, ctx1)
        ym, am = moe_ffn(prm, x_even, ctxm)
        out[f"{name}_even_out_diff"] = float(jnp.abs(y1 - ym).max())
        out[f"{name}_even_aux_err"] = abs(float(am) - aux_want)
        y1t, a1t = moe_ffn(prm, x_tiny, ctx1)
        ymt, amt = moe_ffn(prm, x_tiny, ctxm)
        out[f"{name}_tiny_out_diff"] = float(jnp.abs(y1t - ymt).max())
        out[f"{name}_tiny_aux_diff"] = abs(float(a1t) - float(amt))
    return out


def mode_compression():
    from repro.dist.compression import compressed_psum

    mesh = small_mesh()
    key = jax.random.PRNGKey(0)
    g_pods = jax.random.normal(key, (2, 64, 32))  # per-pod gradients

    def per_pod(g, err):
        out, new_err = compressed_psum({"w": g[0]}, {"w": err[0]}, "pod")
        return out, jax.tree.map(lambda e: e[None], new_err)

    # jitted: JAX has no eager path for a partly manual shard_map with
    # check_vma off
    out, new_err = jax.jit(
        jax.shard_map(
            per_pod,
            mesh=mesh,
            in_specs=(P("pod"), P("pod")),
            out_specs=(P(), P("pod")),
            axis_names={"pod"},
            check_vma=False,
        )
    )(g_pods, jnp.zeros((2, 64, 32)))
    # expected: mean across pods within int8 quantisation error
    want = np.asarray(g_pods.mean(0))
    got = np.asarray(out["w"])
    scale = float(jnp.abs(g_pods).max()) / 127.0
    err_mag = float(np.abs(got - want).max())
    # error feedback: residual equals what quantisation dropped locally
    errs = np.asarray(new_err["w"])  # (2, 64, 32) per-pod residuals
    return {
        "reduce_err": err_mag,
        "quant_step": scale,
        "err_nonzero": float(np.abs(errs).max()),
        "err_bounded": float(np.abs(errs).max()) <= scale * 0.51,
    }


def mode_elastic_ckpt():
    from repro.dist import checkpoint as ckpt

    mesh = small_mesh()
    big = jax.device_put(
        jnp.arange(16 * 32, dtype=jnp.float32).reshape(16, 32),
        NamedSharding(mesh, P(("pod", "data"), "model")),
    )
    tree = {"w": big}
    d = tempfile.mkdtemp()
    ckpt.save_checkpoint(d, 1, tree)
    # restore onto a DIFFERENT (smaller) mesh => elastic reshard
    mesh2 = jax.make_mesh((2, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
    like = {
        "w": jax.ShapeDtypeStruct(
            (16, 32), jnp.float32, sharding=NamedSharding(mesh2, P("data", "model"))
        )
    }
    out = ckpt.restore_checkpoint(d, 1, like)
    ok = bool(np.array_equal(np.asarray(jax.device_get(out["w"])),
                             np.asarray(jax.device_get(big))))
    n_shards = len(out["w"].sharding.device_set)
    return {"restored_equal": ok, "new_mesh_devices": n_shards}


def mode_compressed_train():
    """Train step with pod-compressed grads lowers and runs; grads close to
    uncompressed."""
    from repro.configs.base import get_config
    from repro.data.synthetic import LMStreamConfig, lm_batch
    from repro.dist.sharding import ShardCtx
    from repro.models.layers import Ctx, ExecCfg
    from repro.models.model import model_specs
    from repro.models.params import init_params
    from repro.train.trainer import TrainConfig, init_train_state, make_train_step

    cfg = get_config("granite_8b", reduced=True)
    mesh = small_mesh()
    ctx = Ctx(cfg, shard=ShardCtx(mesh), ex=ExecCfg(remat="none"))
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
    batch = lm_batch(LMStreamConfig(cfg.vocab_size, 16, 8, seed=0), 0)

    tc_c = TrainConfig(microbatches=1, compute_dtype=jnp.float32,
                       compress_pod_grads=True)
    tc_p = TrainConfig(microbatches=1, compute_dtype=jnp.float32)
    pc, oc, mc = jax.jit(make_train_step(ctx, tc_c))(
        params, init_train_state(ctx, tc_c, params), batch
    )
    pp, op, mp = jax.jit(make_train_step(ctx, tc_p))(
        params, init_train_state(ctx, tc_p, params), batch
    )
    dl = abs(float(mc["loss"]) - float(mp["loss"]))
    gn = abs(float(mc["grad_norm"]) - float(mp["grad_norm"]))
    return {"loss_diff": dl, "gnorm_rel_diff": gn / (float(mp["grad_norm"]) + 1e-9)}


if __name__ == "__main__":
    mode = sys.argv[1]
    out = globals()[f"mode_{mode}"]()
    print("RESULT " + json.dumps(out))
