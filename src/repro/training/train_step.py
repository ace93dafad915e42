"""Jitted distributed train step: value_and_grad → clip → AdamW, with
optional cross-pod int8 gradient compression.

GSPMD handles the in-pod gradient reduction (batch is sharded over
('pod','data'); XLA inserts reduce-scatter/all-gather pairs it can overlap
with backprop).  When ``compress_pods`` is on, the 'pod' axis is excluded from
the automatic reduction by running loss/grad inside shard_map with the pod
axis manual — gradients then cross pods as int8 (training.compress).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding as sh
from repro.models.registry import ModelApi

from . import compress, optimizer as opt


def build_train_step(api: ModelApi, mesh: Mesh, acfg: opt.AdamWConfig,
                     compress_pods: bool = False, microbatch: int = 0):
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def loss_fn(params, batch):
        return api.train_loss(params, mesh=mesh, **batch)

    def _vg(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        # pin gradient dtypes to the parameter dtypes (x64 contexts can let
        # f64 cotangents leak out of mixed-precision einsum backward passes)
        grads = jax.tree.map(lambda g, q: g.astype(q.dtype), grads, params)
        return loss.astype(jnp.float32), grads

    def grads_of(params, batch):
        if microbatch and microbatch > 1:
            # gradient accumulation over microbatches (sequential scan)
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatch, b // microbatch, *x.shape[1:])

            mb = jax.tree.map(split, batch)

            def acc_step(carry, mb_i):
                loss_acc, g_acc = carry
                loss_i, g_i = _vg(params, mb_i)
                return (loss_acc + loss_i,
                        jax.tree.map(jnp.add, g_acc, g_i)), None

            zero = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc_step, (jnp.zeros((), jnp.float32), zero), mb)
            inv = 1.0 / microbatch
            return loss * inv, jax.tree.map(lambda g: g * inv, grads)
        return _vg(params, batch)

    def train_step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        if compress_pods and "pod" in mesh.shape and mesh.shape["pod"] > 1:
            grads = _pod_compress(grads, mesh)
        params, opt_state, gnorm = opt.apply_updates(acfg, params, grads, opt_state)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "lr": opt.lr_at(acfg, opt_state["step"] - 1)}

    return train_step


def _pod_compress(grads, mesh: Mesh):
    """int8 all-reduce of the cross-pod gradient component.

    Grads arriving here are already averaged over 'pod' by GSPMD when the
    batch is pod-sharded; for the explicit-compression path we instead mark
    the batch pod-replicated and do the pod reduction ourselves in int8.
    """
    spec = P()  # gradients handled as pod-replicated blocks per shard

    def red(g):
        fn = jax.shard_map(
            lambda x: compress.compressed_psum_mean(x, "pod"),
            mesh=mesh,
            in_specs=P("pod"),
            out_specs=P("pod"),
            check_vma=False,
        )
        flat = g.reshape(-1)
        n = flat.shape[0]
        npod = mesh.shape["pod"]
        pad = (-n) % npod
        out = fn(jnp.pad(flat, (0, pad)).reshape(npod, -1))
        return out.reshape(-1)[:n].reshape(g.shape)

    return jax.tree.map(red, grads)


def jit_train_step(api: ModelApi, mesh: Mesh, acfg: opt.AdamWConfig,
                   batch_specs: dict, compress_pods: bool = False,
                   microbatch: int = 0, donate: bool = True):
    """jit with explicit in/out shardings — the dry-run entry point."""
    pspecs = api.param_specs(mesh)
    sspecs = opt.state_specs(pspecs)
    step = build_train_step(api, mesh, acfg, compress_pods, microbatch)
    in_sh = (
        sh.tree_shardings(mesh, pspecs),
        sh.tree_shardings(mesh, sspecs),
        {k: NamedSharding(mesh, v) for k, v in batch_specs.items()},
    )
    out_sh = (
        sh.tree_shardings(mesh, pspecs),
        sh.tree_shardings(mesh, sspecs),
        {"loss": NamedSharding(mesh, P()), "grad_norm": NamedSharding(mesh, P()),
         "lr": NamedSharding(mesh, P())},
    )
    return jax.jit(
        step, in_shardings=in_sh, out_shardings=out_sh,
        donate_argnums=(0, 1) if donate else (),
    )
