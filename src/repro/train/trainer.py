"""Training step factory: grad-accumulation microbatching, fp32 grad
accumulators, AdamW update, metrics.

Gradient accumulation is the memory-term lever:
activation temp scales with the microbatch, while the collective term is
unchanged (grads are reduced once per step, after accumulation).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import transformer as tf
from repro.optim import adamw
from repro.sharding import partition
from repro.utils.scanutil import maybe_scan


def make_train_step(cfg, oc: adamw.OptConfig, mesh, *, accum_steps: int = 1):
    """Build the jittable train step: value_and_grad over the (blockwise
    when ``cfg.blockwise``) chunked loss, ``accum_steps`` microbatches
    summed into fp32 accumulators, then one AdamW update.

    The returned ``train_step(params, opt_state, batch) -> (params,
    opt_state, metrics)`` raises ``ValueError`` if the global batch is not
    divisible by ``accum_steps``; with a ``mesh`` the loss runs under the
    sharded ``residual_spec`` constraint path.
    """
    bspec = partition.residual_spec(cfg) if mesh is not None else None

    def lossf(p, batch):
        return tf.loss_fn(
            p,
            cfg,
            batch["tokens"],
            batch["labels"],
            frontend=batch.get("frontend"),
            batch_spec=bspec,
        )

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(lossf)(params, batch)
        else:
            bsz = batch["tokens"].shape[0]
            if bsz % accum_steps:
                raise ValueError(
                    f"global batch {bsz} is not divisible by "
                    f"accum_steps={accum_steps}; pick accum_steps that "
                    f"divides the batch (microbatch = batch / accum_steps)"
                )
            micro = jax.tree.map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:]),
                batch,
            )

            def body(carry, mb):
                gsum, lsum = carry
                loss, grads = jax.value_and_grad(lossf)(params, mb)
                gsum = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, grads
                )
                return (gsum, lsum + loss), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), _ = maybe_scan(
                body, (g0, jnp.zeros((), jnp.float32)), micro
            )
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            loss = lsum / accum_steps
        params2, opt2, metrics = adamw.update(params, grads, opt_state, oc)
        metrics["loss"] = loss
        return params2, opt2, metrics

    return train_step


def make_eval_step(cfg, mesh):
    """Build the jittable eval step: ``eval_step(params, batch) -> loss``
    over the same chunked loss the train step differentiates."""
    bspec = partition.residual_spec(cfg) if mesh is not None else None

    def eval_step(params, batch):
        return tf.loss_fn(
            params,
            cfg,
            batch["tokens"],
            batch["labels"],
            frontend=batch.get("frontend"),
            batch_spec=bspec,
        )

    return eval_step
