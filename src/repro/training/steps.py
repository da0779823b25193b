"""Train steps.

``make_train_step``: the production step — loss + grad + AdamW; under
pjit the DP gradient reduction is emitted by SPMD autodiff and overlaps
with the backward per-layer (scanned layers + latency-hiding scheduler).

``make_compressed_train_step``: the int8-wire variant — shard_map over
the DP axis computes UNREDUCED per-shard gradients, syncs them with the
compressed ring all-reduce (distributed/compression.py), then applies
the optimizer identically on every shard.  Supported for replicated-
parameter (pure-DP) meshes; the word-length idea of the paper applied
to gradient traffic."""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as Ps

from repro.configs.base import ModelConfig
from repro.distributed import compression
from repro.models import lm
from repro.optim import AdamWConfig, adamw_init, adamw_update


class TrainState(NamedTuple):
    params: dict
    opt: dict


def train_state_init(params) -> TrainState:
    return TrainState(params=params, opt=adamw_init(params))


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig):
    """Returns step(state, batch) -> (state, metrics)."""

    def step(state: TrainState, batch: dict):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, cfg, batch), has_aux=True)(
            state.params)
        params, opt, opt_m = adamw_update(opt_cfg, grads, state.opt,
                                          state.params)
        metrics = dict(metrics, loss=loss, **opt_m)
        return TrainState(params, opt), metrics

    return step


def make_compressed_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                               mesh: Mesh, axis: str = "data"):
    """Pure-DP step with int8-ring gradient sync (params replicated)."""
    n = mesh.shape[axis]

    def step(state: TrainState, batch: dict):
        p_spec = jax.tree.map(lambda _: Ps(), state.params)
        b_spec = jax.tree.map(lambda _: Ps(axis), batch)

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(p_spec, b_spec),
                           out_specs=(p_spec, Ps()),
                           check_vma=False)
        def local_grads(params, local_batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: lm.loss_fn(p, cfg, local_batch),
                has_aux=True)(params)
            # per-shard gradients, NOT psum'd — sync happens compressed
            return grads, jax.lax.pmean(loss, axis)

        grads, loss = local_grads(state.params, batch)
        grads = compression.compressed_psum(grads, mesh, axis)
        grads = jax.tree.map(lambda g: g / n, grads)
        params, opt, opt_m = adamw_update(opt_cfg, grads, state.opt,
                                          state.params)
        return TrainState(params, opt), dict(loss=loss, **opt_m)

    return step
