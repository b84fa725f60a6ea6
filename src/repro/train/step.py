"""Train/serve step builders: the programs the dry-run lowers.

``TrainStepBuilder`` binds (model, mesh, rules, optimizer) into jittable
steps with full in/out shardings:

* ``train_step(state, batch)``   — fwd + bwd + AdamW, grad accumulation
  via microbatch scan when ``accum > 1`` (compute/communication overlap
  falls out: XLA overlaps the per-microbatch reduce-scatters with the
  next microbatch's compute);
* ``prefill_step(params, batch, cache)``;
* ``decode_step(params, token, pos, cache)``.

All steps run under ``mesh`` with logical rules active, so the
``constrain`` annotations in model code take effect.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import axes as AX
from repro.distributed import partitioning as PT
from repro.models.zoo import Model
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update, opt_state_axes


class TrainStep:
    """The jitted train step, and running totals of the counters its metrics
    carry (``COUNTERS``; ``expert_rows``: the pairs routed to the experts
    this chip holds, summed over layers).

    A step's counters stay on the device: no call waits for the step.  The
    last ``KEEP`` steps' values are kept one by one, the older ones folded
    into a running total on the device.
    """

    COUNTERS = ("expert_rows",)
    KEEP = 1024

    def __init__(self, jitted) -> None:
        self.jitted = jitted
        self.lower = jitted.lower
        self.steps = 0
        self._older: Dict[str, jax.Array] = {}
        self._recent: Dict[str, list] = {}

    def __call__(self, state, batch):
        state, metrics = self.jitted(state, batch)
        self.steps += 1
        for name in self.COUNTERS:
            if name in metrics:
                recent = self._recent.setdefault(name, [])
                recent.append(metrics[name])
                if len(recent) >= 2 * self.KEEP:
                    fold = jnp.sum(jnp.stack(recent[:-self.KEEP]))
                    self._older[name] = self._older.get(name, 0) + fold
                    del recent[:-self.KEEP]
        return state, metrics

    def total(self, name: str, last: Optional[int] = None) -> Optional[int]:
        """The counter summed over every step so far, or over the ``last``
        steps (at most ``KEEP``); None if no step has counted it."""
        recent = self._recent.get(name)
        if not recent:
            return None
        if last is not None:
            if last > len(recent):
                raise ValueError(f"only the last {len(recent)} steps are kept, {last} asked for")
            return int(sum(int(v) for v in recent[len(recent) - last:]))
        return int(self._older.get(name, 0)) + int(sum(int(v) for v in recent))


class TrainStepBuilder:
    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        strategy: str = "tp",
        opt: Optional[AdamWConfig] = None,
        remat_policy: str = "full",
        accum: int = 1,
        zero2: bool = False,
    ) -> None:
        """``zero2``: under an fsdp strategy, gather parameters ONCE per
        step (outside the microbatch scan) instead of per microbatch, and
        reduce-scatter each microbatch's grads into an fsdp-sharded fp32
        accumulator — ZeRO-2-style.  Collective volume drops from
        ~3·accum·P to ~P + accum·P at the cost of keeping the gathered
        (TP-sharded) parameters resident for the step."""
        self.model = model
        self.mesh = mesh
        self.strategy = strategy
        self.rules = PT.get_rules(strategy)
        self.opt = opt or AdamWConfig()
        self.remat_policy = remat_policy
        self.accum = accum
        self.zero2 = zero2 and "fsdp" in strategy

    # ----------------------------------------------------------------- helpers
    def _activate(self):
        AX.set_logical_rules(self.rules, self.mesh)

    def param_shardings(self, abstract_params, axes_tree):
        return PT.shardings_for_tree(self.mesh, self.rules, abstract_params, axes_tree)

    def state_shardings(self, abstract_params, axes_tree):
        p_shard = self.param_shardings(abstract_params, axes_tree)
        return {
            "params": p_shard,
            "opt": {
                "mu": p_shard,
                "nu": p_shard,
                "master": p_shard,
                "count": NamedSharding(self.mesh, P()),
            },
            "step": NamedSharding(self.mesh, P()),
        }

    def batch_shardings(self, batch_tree):
        ax = PT.batch_axes_for(batch_tree)
        return PT.shardings_for_tree(self.mesh, self.rules, batch_tree, ax)

    def cache_shardings(self, cache_tree):
        ax = PT.cache_axes_for(cache_tree)
        return PT.shardings_for_tree(self.mesh, self.rules, cache_tree, ax)

    def memories_shardings(self, mem_tree):
        ax = PT.memories_axes_for(mem_tree)
        return PT.shardings_for_tree(self.mesh, self.rules, mem_tree, ax)

    # -------------------------------------------------------------- train step
    def init_state(self, rng) -> Dict[str, Any]:
        params, _ = self.model.init(rng)
        return {"params": params, "opt": adamw_init(params), "step": jnp.zeros((), jnp.int32)}

    def train_step_fn(self, gathered_sh=None, grad_sh=None):
        model, opt_cfg, remat, accum = self.model, self.opt, self.remat_policy, self.accum
        zero2 = self.zero2 and gathered_sh is not None

        def loss_fn(params, batch):
            loss, metrics = model.loss_fn(params, batch, remat)
            return loss, metrics

        def step(state, batch):
            self._activate()
            params = state["params"]
            if zero2:
                # one all-gather per STEP: constrain to the TP-only
                # sharding outside the microbatch scan
                params_use = jax.lax.with_sharding_constraint(params, gathered_sh)
            else:
                params_use = params
            if accum <= 1:
                (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                    params_use, batch
                )
                if zero2:
                    grads = jax.lax.with_sharding_constraint(grads, grad_sh)
            else:
                # microbatch scan: batch leaves are (accum*b, ...) and are
                # resliced per microstep; grads accumulate in fp32 (under
                # zero2 the accumulator is fsdp-sharded, so each micro-
                # batch's grads reduce-scatter into it).
                def micro(carry, mb):
                    acc = carry
                    (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        params_use, mb
                    )
                    if zero2:
                        g = jax.lax.with_sharding_constraint(g, grad_sh)
                    acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32) / accum, acc, g
                    )
                    return acc, (l, m)

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                if zero2:
                    zeros = jax.lax.with_sharding_constraint(zeros, grad_sh)
                mbs = jax.tree.map(
                    lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]),
                    batch,
                )
                grads, (losses, metricses) = jax.lax.scan(micro, zeros, mbs)
                loss = losses.mean()
                metrics = jax.tree.map(lambda m: m.mean(0), metricses)
            new_params, new_opt, stats = adamw_update(opt_cfg, grads, state["opt"], params)
            new_state = {"params": new_params, "opt": new_opt,
                         "step": state["step"] + 1}
            metrics = dict(metrics, loss=loss, **stats)
            return new_state, metrics

        return step

    def jit_train_step(self, abstract_params, axes_tree, abstract_batch):
        state_sh = self.state_shardings(abstract_params, axes_tree)
        batch_sh = self.batch_shardings(abstract_batch)
        gathered_sh = grad_sh = None
        if self.zero2:
            tp_rules = dict(self.rules)
            tp_rules["embed"] = None      # gather the fsdp dim, keep TP
            gathered_sh = PT.shardings_for_tree(
                self.mesh, tp_rules, abstract_params, axes_tree)
            grad_sh = state_sh["params"]
        return TrainStep(jax.jit(
            self.train_step_fn(gathered_sh=gathered_sh, grad_sh=grad_sh),
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        ))

    # -------------------------------------------------------------- serve steps
    def prefill_step_fn(self):
        model = self.model

        def step(params, batch, cache):
            self._activate()
            return model.prefill(params, batch, cache)

        return step

    def decode_step_fn(self):
        model = self.model

        def step(params, token, pos, cache, *extras):
            self._activate()
            return model.decode_step(params, token, pos, cache, *extras)

        return step
