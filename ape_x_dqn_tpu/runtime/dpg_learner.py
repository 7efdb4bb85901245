"""Ape-X DPG learner: critic + policy + Polyak targets in one jit.

The continuous-control counterpart of runtime/learner.SingleChipLearner
(SURVEY.md §2.1 config 5, §2.2 "DPG actor-critic"): one donated XLA graph
fuses prioritized sequence sampling, the critic TD update, the
deterministic-policy-gradient actor update (through the *updated*
critic), Polyak soft target updates (models/base.soft_update, tau from
LearnerConfig), and the |TD| priority write-back. The reference would run
these as separate GPU kernels; fusing them keeps the whole cycle a single
device dispatch.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ape_x_dqn_tpu.models.base import soft_update
from ape_x_dqn_tpu.obs import learning as learn_obs
from ape_x_dqn_tpu.ops.losses import ContinuousBatch, make_dpg_losses
from ape_x_dqn_tpu.replay.prioritized import ReplayState
from ape_x_dqn_tpu.runtime.learner import (
    BATCH, HEALTH, LOSS_GRAD, OPTIMIZER, SAMPLE, TARGET_SYNC, WRITE_BACK)


class DPGTrainState(NamedTuple):
    actor_params: Any
    critic_params: Any
    target_actor: Any
    target_critic: Any
    actor_opt: Any
    critic_opt: Any
    replay: ReplayState
    rng: jax.Array
    step: jax.Array  # int32 grad-step counter


def continuous_item_spec(obs_shape, obs_dtype, action_dim: int) -> dict:
    """Item pytree spec for one flat n-step transition (continuous)."""
    return {
        "obs": jax.ShapeDtypeStruct(obs_shape, obs_dtype),
        "action": jax.ShapeDtypeStruct((action_dim,), jnp.float32),
        "reward": jax.ShapeDtypeStruct((), jnp.float32),
        "next_obs": jax.ShapeDtypeStruct(obs_shape, obs_dtype),
        "discount": jax.ShapeDtypeStruct((), jnp.float32),
    }


class DPGLearner:  # apexlint: parity(no train_step_k/sample_k/learn_k — K-chunked sampling is rejected by the constructor's ValueError gates; no evict_region/add_at — the cold tier is frame-ring only and DPG obs are low-dim)
    """Jitted endpoints for the Ape-X DPG learner."""

    def __init__(self, actor_apply: Callable, critic_apply: Callable,
                 replay, lcfg):
        if getattr(lcfg, "sample_chunk", 1) > 1:
            # loud, not silent: the K-batch relaxation is implemented
            # by runtime/learner.py's cycle, which this learner does
            # not share
            raise ValueError(
                "learner.sample_chunk > 1 is not implemented by the "
                "DPG learner — set sample_chunk=1")
        if getattr(lcfg, "sample_prefetch", False):
            # same rule for the double-buffered sampling pipeline: this
            # learner's fused train step has no split sample/learn
            # stages to pipeline
            raise ValueError(
                "learner.sample_prefetch is not implemented by the "
                "DPG learner — set sample_prefetch=False")
        self.actor_apply = actor_apply
        self.critic_apply = critic_apply
        self.replay = replay
        self.lcfg = lcfg
        self.critic_optimizer = optax.chain(
            optax.clip_by_global_norm(lcfg.max_grad_norm),
            optax.adam(lcfg.critic_lr, eps=lcfg.adam_eps))
        self.actor_optimizer = optax.chain(
            optax.clip_by_global_norm(lcfg.max_grad_norm),
            optax.adam(lcfg.policy_lr, eps=lcfg.adam_eps))
        self.critic_loss, self.policy_loss = make_dpg_losses(
            actor_apply, critic_apply)

    # -- state ------------------------------------------------------------

    def init(self, actor_params: Any, critic_params: Any, replay_state,
             rng: jax.Array) -> DPGTrainState:
        return DPGTrainState(
            actor_params=actor_params,
            critic_params=critic_params,
            target_actor=jax.tree.map(jnp.copy, actor_params),
            target_critic=jax.tree.map(jnp.copy, critic_params),
            actor_opt=self.actor_optimizer.init(actor_params),
            critic_opt=self.critic_optimizer.init(critic_params),
            replay=replay_state,
            rng=rng,
            step=jnp.int32(0))

    # -- core step (pure) -------------------------------------------------

    def _train_step(self, state: DPGTrainState
                    ) -> tuple[DPGTrainState, dict]:
        rng, sk = jax.random.split(state.rng)
        with jax.named_scope(SAMPLE):
            items, idx, is_w = self.replay.sample(
                state.replay, sk, self.lcfg.batch_size)
        with jax.named_scope(BATCH):
            batch = ContinuousBatch(
                obs=items["obs"], actions=items["action"],
                rewards=items["reward"], next_obs=items["next_obs"],
                discounts=items["discount"])

        with jax.named_scope(LOSS_GRAD):
            (c_loss, c_aux), c_grads = jax.value_and_grad(
                self.critic_loss, has_aux=True)(
                state.critic_params, state.target_critic,
                state.target_actor, batch, is_w)
        with jax.named_scope(OPTIMIZER):
            c_updates, critic_opt = self.critic_optimizer.update(
                c_grads, state.critic_opt, state.critic_params)
            critic_params = optax.apply_updates(state.critic_params,
                                                c_updates)

        # policy ascends the UPDATED critic (standard DDPG ordering)
        with jax.named_scope(LOSS_GRAD):
            (p_loss, p_aux), p_grads = jax.value_and_grad(
                self.policy_loss, has_aux=True)(
                state.actor_params, critic_params, batch)
        with jax.named_scope(OPTIMIZER):
            p_updates, actor_opt = self.actor_optimizer.update(
                p_grads, state.actor_opt, state.actor_params)
            actor_params = optax.apply_updates(state.actor_params,
                                               p_updates)

        with jax.named_scope(TARGET_SYNC):
            tau = self.lcfg.tau
            target_actor = soft_update(state.target_actor, actor_params,
                                       tau)
            target_critic = soft_update(state.target_critic,
                                        critic_params, tau)

        with jax.named_scope(WRITE_BACK):
            replay_state = self.replay.update_priorities(
                state.replay, idx, c_aux["td_abs"])
        with jax.named_scope(HEALTH):
            metrics = {
                "loss": c_loss,
                "policy_loss": p_loss,
                "q_mean": c_aux["q_mean"],
                "td_abs_mean": c_aux["td_abs"].mean(),
                "a_abs_mean": p_aux["a_abs_mean"],
                # learning-health scalars over the CRITIC update (the TD
                # learner); fused path, so staleness is identically 0
                "diag": {**learn_obs.sgd_diag(c_aux, is_w, c_grads,
                                              c_updates, critic_params),
                         **learn_obs.replay_health(
                             self.replay, state.replay, idx, None)},
            }
        new_state = DPGTrainState(
            actor_params, critic_params, target_actor, target_critic,
            actor_opt, critic_opt, replay_state, rng, state.step + 1)
        return new_state, metrics

    # -- jitted endpoints --------------------------------------------------

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def train_step(self, state: DPGTrainState):
        return self._train_step(state)

    @partial(jax.jit, static_argnums=(0, 2), donate_argnums=1)
    def train_many(self, state: DPGTrainState, n: int):
        """n grad-steps in one dispatch via lax.scan (driver hot loop)."""
        def body(s, _):
            s, m = self._train_step(s)
            return s, m
        state, metrics = jax.lax.scan(body, state, None, length=n)
        return state, jax.tree.map(lambda x: x[-1], metrics)

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add(self, state: DPGTrainState, items: Any,
            td_abs: jax.Array) -> DPGTrainState:
        return state._replace(
            replay=self.replay.add(state.replay, items, td_abs))

    @partial(jax.jit, static_argnums=0, donate_argnums=1)
    def add_many(self, state: DPGTrainState, items: Any,
                 td_abs: jax.Array) -> DPGTrainState:
        """Coalesced ingest: g staged blocks in one donated dispatch —
        unrolled over the static g axis (NOT lax.scan; see
        SingleChipLearner.add_many for the CPU scan pathology)."""
        rs = state.replay
        for j in range(td_abs.shape[0]):
            rs = self.replay.add(
                rs, jax.tree.map(lambda x, j=j: x[j], items), td_abs[j])
        return state._replace(replay=rs)

    def publish_params(self, state: DPGTrainState) -> dict:
        """Donation-safe {actor, critic} param copies for the inference
        server (the server evaluates mu(s) and Q(s, mu(s)) per query)."""
        return {"actor": jax.tree.map(jnp.copy, state.actor_params),
                "critic": jax.tree.map(jnp.copy, state.critic_params)}
