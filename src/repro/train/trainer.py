"""Training loop substrate.

``make_train_step`` builds the jittable step: bf16 compute over fp32 master
weights, optional gradient-accumulation microbatching (lax.scan keeps the
data-parallel gradient reduce out of the inner loop — one reduce per step,
overlapping XLA's scheduler), global-norm clip, AdamW.

``Trainer`` is the host loop: data pipeline, checkpointing, straggler
watchdog (EWMA step timing), and elastic restart hooks (train/elastic.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.optim.adamw import AdamW, AdamWConfig, OptState
from repro.optim.schedule import cosine_with_warmup


class TrainState(NamedTuple):
    params: dict                     # fp32 master
    opt: OptState
    step: jax.Array                  # () int32


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    adamw: AdamWConfig = AdamWConfig()
    microbatches: int = 1            # gradient accumulation factor
    compute_dtype: str = "bfloat16"


def make_optimizer(tcfg: TrainerConfig) -> AdamW:
    return AdamW(tcfg.adamw, cosine_with_warmup(
        tcfg.peak_lr, tcfg.warmup_steps, tcfg.total_steps))


def init_train_state(model, rng: jax.Array, tcfg: TrainerConfig) -> TrainState:
    params_bf16 = model.init(rng)
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params_bf16)
    opt = make_optimizer(tcfg).init(params)
    return TrainState(params=params, opt=opt, step=jnp.zeros((), jnp.int32))


def train_state_shapes(model, tcfg: TrainerConfig) -> TrainState:
    """Abstract TrainState (ShapeDtypeStructs) for the dry-run / resharding."""
    p_shapes = {k: jax.ShapeDtypeStruct(v.shape, jnp.float32)
                for k, v in model.init_shapes().items()}
    opt = OptState(mu=p_shapes, nu=dict(p_shapes),
                   count=jax.ShapeDtypeStruct((), jnp.int32))
    return TrainState(params=p_shapes, opt=opt,
                      step=jax.ShapeDtypeStruct((), jnp.int32))


def make_train_step(model, tcfg: TrainerConfig) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics)."""
    optimizer = make_optimizer(tcfg)
    compute_dtype = jnp.dtype(tcfg.compute_dtype)

    def loss_fn(params_master: dict, batch: dict):
        params = jax.tree.map(lambda p: p.astype(compute_dtype), params_master)
        total, metrics = model.loss(params, batch)
        return total, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch: dict):
        if tcfg.microbatches > 1:
            def split(x):
                b = x.shape[0]
                mb = tcfg.microbatches
                return x.reshape(mb, b // mb, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc_body(carry, mb_batch):
                gacc, lacc = carry
                (loss, metrics), grads = grad_fn(state.params, mb_batch)
                gacc = jax.tree.map(jnp.add, gacc, grads)
                return (gacc, lacc + loss), None

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 state.params)
            (gsum, lsum), _ = jax.lax.scan(acc_body, (zeros, jnp.zeros((), jnp.float32)),
                                           micro)
            grads = jax.tree.map(lambda g: g / tcfg.microbatches, gsum)
            loss = lsum / tcfg.microbatches
            metrics = {"loss": loss, "total_loss": loss,
                       "aux_loss": jnp.zeros((), jnp.float32)}
        else:
            (loss, metrics), grads = grad_fn(state.params, batch)

        new_params, new_opt, opt_metrics = optimizer.update(
            grads, state.opt, state.params)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return TrainState(params=new_params, opt=new_opt, step=state.step + 1), metrics

    return train_step


def make_train_step_compressed(model, tcfg: TrainerConfig, mesh,
                               state_shardings, batch_shardings,
                               k_compress: str = "int8"):
    """Cross-pod training with int8 error-feedback gradient compression.

    Gradients are computed per-pod under normal GSPMD (the intra-pod
    data/model axes behave exactly as in ``make_train_step``); the *inter-pod*
    mean — the bytes that cross the slow DCN links — runs inside shard_map
    over the ``pod`` axis as quantize -> psum(int32) -> dequantize with an
    error-feedback residual carried in the train state (optim/grad_compress).

    Returns (train_step(state, err_state, batch) -> (state, err_state,
    metrics)).  Requires a mesh with a ``pod`` axis.
    """
    from jax.sharding import PartitionSpec as P

    from repro.optim.grad_compress import (CompressionState,
                                           compressed_cross_pod_mean)

    assert "pod" in mesh.shape, "compressed sync needs a 'pod' mesh axis"
    optimizer = make_optimizer(tcfg)
    compute_dtype = jnp.dtype(tcfg.compute_dtype)

    def loss_fn(params_master: dict, batch: dict):
        params = jax.tree.map(lambda p: p.astype(compute_dtype), params_master)
        total, metrics = model.loss(params, batch)
        return total, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)


    def train_step(state: TrainState, err: dict, batch: dict):
        def pod_local(params, pod_batch, pod_err):
            pod_err = jax.tree.map(lambda e: e[0], pod_err)     # (1,*s) -> (*s)
            (loss, metrics), grads = grad_fn(params, pod_batch)
            grads, new_err_state = compressed_cross_pod_mean(
                grads, CompressionState(error=pod_err), "pod")
            new_err = jax.tree.map(lambda e: e[None], new_err_state.error)
            loss = jax.lax.pmean(loss, "pod")
            return grads, new_err, loss

        # params replicated across pods; batch sharded over pod; error local.
        # Only the pod axis is manual (``axis_names``); the other mesh axes
        # stay in GSPMD's hands inside the body (intra-pod FSDP/TP
        # unchanged).
        p_spec = jax.tree.map(lambda _: P(), state.params)
        b_spec = jax.tree.map(lambda _: P("pod"), batch)
        e_spec = jax.tree.map(lambda _: P("pod"), err)
        grads, new_err, loss = jax.shard_map(
            pod_local, mesh=mesh,
            in_specs=(p_spec, b_spec, e_spec),
            out_specs=(p_spec, e_spec, P()),
            axis_names=frozenset({"pod"}),
            check_vma=False,
        )(state.params, batch, err)

        new_params, new_opt, opt_metrics = optimizer.update(
            grads, state.opt, state.params)
        metrics = {"loss": loss}
        metrics.update(opt_metrics)
        return (TrainState(params=new_params, opt=new_opt, step=state.step + 1),
                new_err, metrics)

    return train_step


def init_compression_errors(model, mesh, n_pods: int) -> dict:
    """Per-pod error-feedback residuals, stacked on a leading pod dim."""
    shapes = model.init_shapes()
    return {k: jnp.zeros((n_pods,) + v.shape, jnp.float32)
            for k, v in shapes.items()}


# ---------------------------------------------------------------------------
# Host loop
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StragglerWatch:
    """EWMA step-time watchdog: flags steps slower than ratio x the EWMA.
    At scale the runner uses flags to rebalance host data shards / trigger
    backup workers; here it records events for tests and logs."""

    ratio: float = 2.0
    alpha: float = 0.1
    ewma: Optional[float] = None
    events: list = dataclasses.field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        slow = self.ewma is not None and dt > self.ratio * self.ewma
        if slow:
            self.events.append((step, dt, self.ewma))
        self.ewma = dt if self.ewma is None else (1 - self.alpha) * self.ewma + self.alpha * dt
        return slow


class Trainer:
    def __init__(self, model, tcfg: TrainerConfig, *, checkpointer=None,
                 log_every: int = 10):
        self.model = model
        self.tcfg = tcfg
        self.checkpointer = checkpointer
        self.log_every = log_every
        self.watch = StragglerWatch()
        self._step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))

    def fit(self, state: TrainState, data_iter, num_steps: int,
            checkpoint_every: int = 0):
        history = []
        for i in range(num_steps):
            batch = next(data_iter)
            t0 = time.perf_counter()
            state, metrics = self._step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self.watch.observe(int(state.step), dt)
            history.append({k: float(v) for k, v in metrics.items()})
            if self.log_every and (i % self.log_every == 0):
                print(f"step {int(state.step):5d} loss {history[-1]['loss']:.4f} "
                      f"({dt*1e3:.1f} ms)")
            if (self.checkpointer is not None and checkpoint_every
                    and int(state.step) % checkpoint_every == 0):
                self.checkpointer.save(int(state.step), state)
        return state, history
