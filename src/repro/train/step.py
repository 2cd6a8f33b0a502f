"""Train-step builder: loss + grad + AdamW update, with optional microbatch
gradient accumulation (scan) and int8 error-feedback gradient compression.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.model import Model

from . import optimizer as opt_mod
from .optimizer import OptConfig


def make_train_step(model: Model, opt_cfg: OptConfig, *,
                    microbatches: int = 1, compress_grads: bool = False,
                    mesh=None):
    """Returns train_step(train_state, batch) -> (train_state, metrics).

    train_state = {"params", "opt"}; batch = {"tokens", "labels", ...}.
    """

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, remat=True)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        # split the leading batch dim into microbatches and scan-accumulate
        def reshape(x):
            b = x.shape[0]
            assert b % microbatches == 0, (b, microbatches)
            return x.reshape(microbatches, b // microbatches, *x.shape[1:])
        mb = jax.tree.map(reshape, batch)

        def body(acc, micro):
            (loss, metrics), grads = grad_fn(params, micro)
            acc_g, acc_l = acc
            acc_g = jax.tree.map(jnp.add, acc_g, grads)
            return (acc_g, acc_l + loss), metrics

        zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss_sum), metrics = jax.lax.scan(body, (zero_g, 0.0), mb)
        grads = jax.tree.map(lambda g: g / microbatches, grads)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss_sum / microbatches, metrics, grads

    def _pin_like_params(tree):
        """Constrain a params-shaped pytree (params, grads, float moments)
        to the ``dist.sharding`` parameter rules so compiled outputs carry
        the SAME shardings the inputs arrived with — step N+1 then consumes
        step N's donated buffers with zero resharding. Identity off-mesh."""
        if mesh is None:
            return tree
        from repro.dist import sharding as dist_sharding

        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            dist_sharding.param_shardings(mesh, tree))

    def train_step(train_state, batch):
        params, opt_state = train_state["params"], train_state["opt"]
        loss, metrics, grads = compute_grads(params, batch)
        if compress_grads:
            from repro.dist.compression import compress_decompress
            grads, cerr = compress_decompress(grads)
            metrics = {**metrics, "compress_err": cerr}
        new_params, new_opt, opt_metrics = opt_mod.apply_updates(
            opt_cfg, params, grads, opt_state)
        new_params = _pin_like_params(new_params)
        if mesh is not None and opt_cfg.moment_dtype != "int8":
            # float moments mirror the parameter tree leaf-for-leaf; int8
            # moments are (q, scale) pairs with their own treedef — those
            # stay wherever the update computed them
            new_opt = {**new_opt, "m": _pin_like_params(new_opt["m"]),
                       "v": _pin_like_params(new_opt["v"])}
        return ({"params": new_params, "opt": new_opt},
                {"loss": loss, **metrics, **opt_metrics})

    return train_step


def init_train_state(model: Model, opt_cfg: OptConfig, key, *, mesh=None):
    """Fresh {params, opt} state; with ``mesh`` the params AND the float
    optimizer moments are placed by the ``dist.sharding`` parameter rules
    (row/col TP + output-projection flip), matching what the mesh-aware
    train step pins — so the very first step already runs reshard-free."""
    params = model.init(key)
    opt = opt_mod.init_opt_state(opt_cfg, params)
    if mesh is not None:
        from repro.dist import sharding as dist_sharding

        shardings = dist_sharding.param_shardings(mesh, params)
        # donate: no unsharded copy stays on the default device
        params = jax.device_put(params, shardings, donate=True)
        if opt_cfg.moment_dtype != "int8":
            opt = {**opt,
                   "m": jax.device_put(opt["m"], shardings, donate=True),
                   "v": jax.device_put(opt["v"], shardings, donate=True)}
    return {"params": params, "opt": opt}
