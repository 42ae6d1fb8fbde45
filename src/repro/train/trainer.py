"""Fault-tolerant training loop.

Fault-tolerance model (designed for 1000+ nodes, exercised here on the CPU
fake mesh):
  * checkpoint/restart — atomic sharded checkpoints every N steps
    (``dist.checkpoint``); on any step failure the loop restores the last
    committed step and replays.  Data is deterministic in (seed, step), so
    replayed steps are bit-idempotent.
  * preemption — a SIGTERM/flag-file request triggers a checkpoint + clean
    exit at the next step boundary.
  * elastic scaling — restore reshards onto whatever mesh the restarted job
    has (checkpoints store full logical arrays).
  * stragglers — steps are timed; the mitigation at scale is deterministic
    step replay on respawned workers (same (seed, step) => same batch) plus
    the synchronous collectives' built-in barrier; the trainer logs p50/p99
    step times so stragglers are visible.
  * gradient compression — optional int8+error-feedback all-reduce across
    the "pod" axis (the slow DCI hop); see ``dist.compression``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import signal
import time
from functools import partial
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import checkpoint as ckpt
from repro.dist.compression import compressed_psum
from repro.models.layers import Ctx
from repro.models.model import model_forward
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.optim.schedule import warmup_cosine
from repro.train.losses import cross_entropy


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    adamw: AdamWConfig = AdamWConfig()
    microbatches: int = 1
    aux_coef: float = 0.001
    compute_dtype: Any = jnp.bfloat16
    checkpoint_every: int = 100
    keep_last: int = 3
    out_dir: str = "/tmp/repro_run"
    compress_pod_grads: bool = False
    seed: int = 0


def _cast_for_compute(params, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 and a.ndim >= 2 else a,
        params,
    )


def make_loss_fn(ctx: Ctx, tc: TrainConfig):
    cfg = ctx.cfg

    def loss_fn(params, batch):
        inputs = {k: v for k, v in batch.items() if k != "labels"}
        logits, _, aux = model_forward(
            _cast_for_compute(params, tc.compute_dtype), inputs, ctx
        )
        labels = batch["labels"]
        if logits.shape[1] != labels.shape[1]:  # VLM: no loss on image tokens
            pad = logits.shape[1] - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.full(labels.shape[:1] + (pad,), -1, labels.dtype), labels], 1
            )
        loss, count = cross_entropy(logits, labels, cfg.vocab_size)
        return loss + tc.aux_coef * aux, (loss, aux)

    return loss_fn


def make_grad_fn(ctx: Ctx, tc: TrainConfig):
    """Microbatched (scan-accumulated) gradients; optional pod compression."""
    if tc.compress_pod_grads and ctx.shard.mesh is not None:
        # inside the pod-manual shard_map, "pod" is no longer a GSPMD axis:
        # the inner forward's sharding rules must not mention it
        from repro.dist.sharding import ShardCtx, rules_without_axis

        inner_rules = rules_without_axis(ctx.shard.rules, "pod")
        inner_ctx = dataclasses.replace(
            ctx, shard=ShardCtx(ctx.shard.mesh, inner_rules)
        )
        loss_fn = make_loss_fn(inner_ctx, tc)
    else:
        loss_fn = make_loss_fn(ctx, tc)

    def grads_of(params, batch):
        if tc.microbatches == 1:
            (tot, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            return grads, loss, aux

        def micro(carry, mb):
            acc = carry
            (_, (loss, aux)), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb
            )
            acc = jax.tree.map(jnp.add, acc, g)
            return acc, (loss, aux)

        nm = tc.microbatches
        mbs = jax.tree.map(
            lambda a: a.reshape((nm, a.shape[0] // nm) + a.shape[1:]), batch
        )
        zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
        acc, (losses, auxs) = jax.lax.scan(
            micro, zeros, mbs, unroll=True if ctx.ex.inner_unroll else 1
        )
        grads = jax.tree.map(lambda g: g / nm, acc)
        return grads, losses.mean(), auxs.mean()

    if not tc.compress_pod_grads:
        return lambda p, b, err: (*grads_of(p, b), err)

    def compressed(params, batch, err):
        mesh = ctx.shard.mesh
        assert mesh is not None and "pod" in mesh.shape, "pod axis required"

        def per_pod(params, batch, err):
            # mark params pod-VARYING: otherwise the autodiff transpose
            # inserts an implicit (uncompressed!) psum over "pod" for
            # grads of replicated inputs — pcast keeps the partials local
            # so the only cross-pod traffic is the int8 payload below
            params = jax.tree.map(
                lambda a: jax.lax.pcast(a, "pod", to="varying"), params
            )
            g, loss, aux = grads_of(params, batch)
            # error-feedback state has an explicit leading pod dim
            g, new_err = compressed_psum(
                g, jax.tree.map(lambda e: e[0], err), "pod"
            )
            new_err = jax.tree.map(lambda e: e[None], new_err)
            return g, jax.lax.pmean(loss, "pod"), jax.lax.pmean(aux, "pod"), new_err

        b_specs = jax.tree.map(lambda _: P("pod"), batch)
        n_specs = jax.tree.map(lambda _: P(), params)
        e_specs = jax.tree.map(lambda _: P("pod"), err)
        # jitted: JAX has no eager path for a partly manual shard_map with
        # check_vma off (a no-op when the caller already traces)
        return jax.jit(
            jax.shard_map(
                per_pod,
                mesh=mesh,
                in_specs=(n_specs, b_specs, e_specs),
                out_specs=(n_specs, P(), P(), e_specs),
                axis_names={"pod"},
                check_vma=False,
            )
        )(params, batch, err)

    return compressed


def make_train_step(ctx: Ctx, tc: TrainConfig) -> Callable:
    grad_fn = make_grad_fn(ctx, tc)

    def train_step(params, opt_state, batch):
        err = opt_state.get("err")
        grads, loss, aux, err = grad_fn(params, batch, err)
        lr = warmup_cosine(
            opt_state["step"],
            peak_lr=tc.peak_lr,
            warmup_steps=tc.warmup_steps,
            total_steps=tc.total_steps,
        )
        params, new_opt, gnorm = adamw_update(grads, opt_state, params, lr, tc.adamw)
        if err is not None:
            new_opt["err"] = err
        metrics = {"loss": loss, "aux": aux, "grad_norm": gnorm, "lr": lr}
        return params, new_opt, metrics

    return train_step


def init_train_state(ctx: Ctx, tc: TrainConfig, params):
    opt = init_opt_state(params)
    if tc.compress_pod_grads:
        n_pods = ctx.shard.axis_size("pod")
        opt["err"] = jax.tree.map(
            lambda a: jnp.zeros((n_pods,) + a.shape, jnp.float32), params
        )
    return opt


class Trainer:
    """Drives the loop with checkpoint/restart + preemption handling."""

    def __init__(self, ctx: Ctx, tc: TrainConfig, params, data: Iterator[dict],
                 donate: bool = True):
        self.ctx, self.tc = ctx, tc
        self.data = data
        self.step_fn = jax.jit(
            make_train_step(ctx, tc), donate_argnums=(0, 1) if donate else ()
        )
        self.params = params
        self.opt_state = init_train_state(ctx, tc, params)
        self.metrics_log: list[dict] = []
        self._preempted = False
        self.start_step = 0
        self._maybe_restore()

    # -- fault tolerance ------------------------------------------------------
    def _ckpt_dir(self) -> str:
        return os.path.join(self.tc.out_dir, "checkpoints")

    def _maybe_restore(self):
        last = ckpt.latest_step(self._ckpt_dir())
        if last is not None:
            state = {"params": self.params, "opt": self.opt_state}
            state = ckpt.restore_checkpoint(self._ckpt_dir(), last, state)
            self.params, self.opt_state = state["params"], state["opt"]
            self.start_step = last
        return self.start_step

    def _save(self, step: int):
        ckpt.save_checkpoint(
            self._ckpt_dir(), step, {"params": self.params, "opt": self.opt_state},
            keep_last=self.tc.keep_last,
        )

    def request_preemption(self, *_):
        self._preempted = True

    # -- main loop ------------------------------------------------------------
    def run(self, num_steps: Optional[int] = None, max_failures: int = 3) -> list[dict]:
        total = num_steps if num_steps is not None else self.tc.total_steps
        step = self.start_step
        failures = 0
        os.makedirs(self.tc.out_dir, exist_ok=True)
        mfile = open(os.path.join(self.tc.out_dir, "metrics.jsonl"), "a")
        try:
            signal.signal(signal.SIGTERM, self.request_preemption)
        except ValueError:
            pass  # not on the main thread (tests)
        while step < total:
            batch = next(self.data)
            t0 = time.perf_counter()
            try:
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, batch
                )
                loss = float(metrics["loss"])
                if not (loss == loss):  # NaN — treat as failure
                    raise FloatingPointError(f"NaN loss at step {step}")
            except Exception:
                failures += 1
                if failures > max_failures:
                    raise
                # restore-and-replay: deterministic data makes this idempotent
                self.start_step = 0
                restored = self._maybe_restore()
                step = restored
                continue
            dt = time.perf_counter() - t0
            step += 1
            rec = {
                "step": step, "time_s": round(dt, 4),
                **{k: float(v) for k, v in metrics.items()},
            }
            self.metrics_log.append(rec)
            mfile.write(json.dumps(rec) + "\n")
            mfile.flush()
            if step % self.tc.checkpoint_every == 0 or step == total or self._preempted:
                self._save(step)
            if self._preempted:
                break
        mfile.close()
        return self.metrics_log
