"""R2D2 sequence learner: sample -> unroll -> update -> priorities, one jit.

The recurrent counterpart of runtime/learner.DQNLearner (SURVEY.md §3.4,
config 4): sequences with stored LSTM state are items in the generic
device-resident prioritized replay, and one donated XLA graph fuses
stratified sequence sampling, the burn-in unroll, the n-step double-DQN
sequence loss with value rescaling, the optimizer update, the eta-mix
priority write-back, and the periodic target sync. The LSTM unroll is a
`lax.scan` inside the jit (models/lstm_q.py), so the whole train step is
a single device dispatch regardless of sequence length.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax

from ape_x_dqn_tpu.obs import learning as learn_obs
from ape_x_dqn_tpu.ops.losses import make_r2d2_loss
from ape_x_dqn_tpu.replay.sequence import batch_to_sequence_batch
from ape_x_dqn_tpu.runtime.learner import (SingleChipLearner, TrainState,
                                           make_optimizer)


class SequenceLearner(SingleChipLearner):
    """Jitted endpoints for the R2D2 sequence-replay learner.

    Reuses TrainState (the replay field holds sequence items,
    replay/sequence.sequence_item_spec) and inherits ALL step/K-batch/
    train_many/add machinery from SingleChipLearner — only the
    sequence-batch construction + R2D2 loss live here, so the K-batch
    semantics cannot drift from the flat-DQN learner's (round-4
    verdict missing #5).
    """

    def __init__(self, net_apply_seq: Callable, replay, lcfg, rcfg,
                 optimizer: optax.GradientTransformation | None = None,
                 compute_dtype=None):
        """net_apply_seq(params, obs[B,T,...], (c,h)) -> (q[B,T,A], state).
        compute_dtype: the net's (cfg.network.compute_dtype), so that
        conv1's input is prepared once per SGD step and not in each of
        the loss's four net applications; None leaves uint8 for the net
        to scale."""
        self.compute_dtype = compute_dtype
        self.burn_in = rcfg.burn_in
        self.net_apply_seq = net_apply_seq
        self.replay = replay
        self.lcfg = lcfg
        self.optimizer = optimizer or make_optimizer(lcfg)
        self.loss_fn = make_r2d2_loss(
            net_apply_seq, burn_in=rcfg.burn_in, n_step=lcfg.n_step,
            gamma=lcfg.gamma, huber_delta=lcfg.huber_delta,
            double=lcfg.double_dqn, rescale=lcfg.value_rescale,
            priority_eta=rcfg.priority_eta)

    def _make_batch(self, items: Any):
        return batch_to_sequence_batch(items, self.compute_dtype,
                                       self.burn_in)

    def _sgd_step(self, params, target_params, opt_state, step,
                  items, is_w):
        """One unroll/loss/optimizer/target-sync update on an already-
        sampled sequence batch (shared by the exact per-step path and
        the K-batch relaxation). Returns the eta-mixed per-sequence
        |TD| priorities (aux['td_abs'])."""
        batch = self._make_batch(items)
        (loss, aux), grads = jax.value_and_grad(
            self.loss_fn, has_aux=True)(
            params, target_params, batch, is_w)
        updates, opt_state = self.optimizer.update(
            grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        step = step + 1
        sync = (step % self.lcfg.target_sync_every == 0)
        target_params = jax.tree.map(
            lambda t, p: jnp.where(sync, p, t), target_params, params)
        metrics = {
            "loss": loss,
            "q_mean": aux["q_mean"],
            "td_abs_mean": aux["td_abs"].mean(),
            "valid_frac": aux["valid_frac"],
            "grad_norm": optax.global_norm(grads),
            # learning-health scalars; td quantiles here are over the
            # eta-mixed per-sequence priorities (the write-back signal)
            "diag": learn_obs.sgd_diag(aux, is_w, grads, updates,
                                       params),
        }
        return params, target_params, opt_state, step, aux["td_abs"], \
            metrics
