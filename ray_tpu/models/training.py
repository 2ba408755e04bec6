"""Sharded train-state + pjit train step for the model zoo.

One jitted program per (model, mesh, rules): init lands params *already
sharded* on the mesh (no host materialization of a 7B model), and the train
step donates the state buffers so params/opt-state update in place in HBM.
XLA inserts all collectives (grad psum over dp, all-gathers for fsdp,
ppermute rings for sp) from the sharding annotations.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu._private import tracing
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    LogicalAxisRules,
    logical_to_pspec,
    spec_tree_to_shardings,
)


def default_optimizer(
    lr: float = 3e-4, weight_decay: float = 0.1, warmup: int = 100,
    decay_steps: int = 10000, grad_clip: float = 1.0,
) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup, max(decay_steps, warmup + 1)
    )
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def _opt_state_shardings(optimizer, param_shapes, param_shardings, mesh):
    """Shardings for the optimizer state: param-like leaves inherit the
    param sharding; scalars (step counts) are replicated."""
    replicated = NamedSharding(mesh, P())
    opt_shapes = jax.eval_shape(optimizer.init, param_shapes)
    return optax.tree_map_params(
        optimizer,
        lambda _, sh: sh,
        opt_shapes,
        param_shardings,
        transform_non_params=lambda _: replicated,
    )


class ShardedTrainer:
    """Builds sharded init/step functions for a functional model.

    model is given as (init_fn(key)->params, loss_fn(params,batch)->scalar,
    param_spec_tree).  This is deliberately model-agnostic: the llm, vision,
    and RL stacks all drive training through this one class.
    """

    def __init__(
        self,
        init_fn: Callable[[jax.Array], Any],
        loss_fn: Callable[[Any, Any], jnp.ndarray],
        param_specs: Any,
        *,
        mesh: Mesh,
        optimizer: Optional[optax.GradientTransformation] = None,
        rules: Optional[LogicalAxisRules] = None,
        batch_spec: Optional[Any] = None,
        accum_steps: int = 1,
        donate_batch: bool = False,
    ):
        self.mesh = mesh
        self.rules = rules or DEFAULT_RULES
        self.optimizer = optimizer or default_optimizer()
        self._init_fn = init_fn
        self._loss_fn = loss_fn
        # gradient accumulation: the step takes the FULL effective batch
        # and scans accum_steps microbatches, summing grads before ONE
        # optimizer update — activation memory is per-microbatch, so the
        # effective batch (and MXU occupancy) can exceed what fits in one
        # forward (reference capability: torch grad accumulation inside
        # the user loop; here it is a trainer feature so the whole
        # accumulation compiles into one XLA program)
        self.accum_steps = max(1, int(accum_steps))

        self.param_shardings = spec_tree_to_shardings(
            param_specs, mesh, self.rules
        )
        param_shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
        self.opt_shardings = _opt_state_shardings(
            self.optimizer, param_shapes, self.param_shardings, mesh
        )
        replicated = NamedSharding(mesh, P())
        self.state_shardings = {
            "params": self.param_shardings,
            "opt_state": self.opt_shardings,
            "step": replicated,
        }
        if batch_spec is None:
            # derived through the rule table (not a device-axis literal)
            # so a rules override moves the batch layout with the params
            batch_spec = logical_to_pspec(("batch",), self.rules, mesh=mesh)
        # batch_spec may be one PartitionSpec (applied to every leaf) or a
        # pytree of them matching the batch structure.
        self.batch_sharding = jax.tree.map(
            lambda sp: NamedSharding(mesh, sp),
            batch_spec,
            is_leaf=lambda x: isinstance(x, P),
        )

        self._jit_init = jax.jit(
            self._state_init, out_shardings=self.state_shardings
        )
        # State (params + opt state) is always donated: the update runs
        # in place in HBM, so the parameter copy never serializes the
        # step tail behind the gradient collectives.  ``donate_batch``
        # additionally donates the input buffers — opt-IN because many
        # callers (benches, the H2D stager's reused staging arrays)
        # legitimately feed the same batch buffers to every step.
        self._jit_step = jax.jit(
            self._train_step,
            donate_argnums=(0, 1) if donate_batch else (0,),
            out_shardings=(self.state_shardings, replicated),
        )

    # --- jitted bodies -----------------------------------------------------
    def _state_init(self, key):
        params = self._init_fn(key)
        return {
            "params": params,
            "opt_state": self.optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    def _train_step(self, state, batch):
        # the whole program under the name scope ``train.step``: the
        # profiler's trace carries it on every device instruction
        # (docs/observability.md); the module stays ``jit__train_step``
        return tracing.scoped("train.step", self._step_body, state, batch)

    def _step_body(self, state, batch):
        if self.accum_steps > 1:
            a = self.accum_steps
            for x in jax.tree.leaves(batch):
                if x.ndim == 0 or x.shape[0] % a:
                    raise ValueError(
                        f"batch leaf shape {getattr(x, 'shape', ())} is "
                        f"not divisible into accum_steps={a} microbatches "
                        "(every leaf needs a leading batch dim that is a "
                        "multiple of accum_steps)")
            micro = jax.tree.map(
                lambda x: x.reshape((a, x.shape[0] // a) + x.shape[1:]),
                batch)

            def body(carry, mb):
                gsum, lsum = carry
                loss_i, g = jax.value_and_grad(self._loss_fn)(
                    state["params"], mb)
                return (jax.tree.map(jnp.add, gsum, g),
                        lsum + loss_i), None

            zeros = jax.tree.map(jnp.zeros_like, state["params"])
            (gsum, lsum), _ = jax.lax.scan(
                body, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / a, gsum)
            loss = lsum / a
        else:
            loss, grads = jax.value_and_grad(self._loss_fn)(
                state["params"], batch
            )
        with tracing.scope("optimizer"):
            updates, opt_state = self.optimizer.update(
                grads, state["opt_state"], state["params"]
            )
            params = optax.apply_updates(state["params"], updates)
            gnorm = optax.global_norm(grads)
        new_state = {
            "params": params,
            "opt_state": opt_state,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss, "grad_norm": gnorm}

    # --- public API --------------------------------------------------------
    def init_state(self, key: jax.Array):
        with self.mesh:
            return self._jit_init(key)

    def shard_batch(self, batch):
        if jax.process_count() > 1:
            # multi-host SPMD: each process passes its LOCAL rows; they
            # concatenate in rank order into one global array (same
            # contract as jax.distributed data loading)
            from jax.experimental import multihost_utils

            def _globalize(x, sh):
                return multihost_utils.host_local_array_to_global_array(
                    x, self.mesh, sh.spec)

            if isinstance(self.batch_sharding, NamedSharding):
                return jax.tree.map(
                    lambda x: _globalize(x, self.batch_sharding), batch)
            return jax.tree.map(_globalize, batch, self.batch_sharding)
        if isinstance(self.batch_sharding, NamedSharding):
            return jax.tree.map(
                lambda x: jax.device_put(x, self.batch_sharding), batch
            )
        return jax.tree.map(jax.device_put, batch, self.batch_sharding)

    def step(self, state, batch) -> Tuple[Any, Dict[str, jnp.ndarray]]:
        with self.mesh:
            return self._jit_step(state, batch)

    def compile(self, state, batch):
        """AOT-compile the step (returns the Lowered/Compiled for cost
        introspection in benchmarks)."""
        with self.mesh:
            return self._jit_step.lower(state, batch).compile()


def make_llama_trainer(
    cfg, mesh: Mesh, *, optimizer=None, rules=None, seq_len=None,
    accum_steps: int = 1
) -> ShardedTrainer:
    """Convenience: a ShardedTrainer for ``ray_tpu.models.llama``."""
    from ray_tpu.models.llama import llama_init, llama_loss, llama_param_specs

    # Batch leaves (tokens, optional mask — both [b, s]) are sharded over
    # batch only: the raw token length (s) differs from the activation
    # length (s-1 after the shift), so sp-sharding happens via activation
    # constraints inside the program.  A single spec applies to all
    # leaves; it is derived from the same rule table the loss constrains
    # activations with ("batch" consumes only the mesh's data axes).
    batch_spec = logical_to_pspec(("batch",), rules, mesh=mesh)
    return ShardedTrainer(
        functools.partial(llama_init, cfg=cfg),
        # the rule table reaches the loss too: params AND activations
        # shard from one table, the named-sharding discipline
        functools.partial(llama_loss, cfg=cfg, mesh=mesh, rules=rules),
        llama_param_specs(cfg),
        mesh=mesh,
        optimizer=optimizer,
        rules=rules,
        batch_spec=batch_spec,
        accum_steps=accum_steps,
    )
