"""Training losses, each designed to live inside a single learner jit.

Reference parity (SURVEY.md §3.3–§3.4, §2.2 "Double-DQN Huber loss"):
- n-step double-DQN Huber loss with importance-sampling weights — the
  reference's fused CUDA training step becomes one XLA graph here.
- R2D2 sequence loss: stored-state unroll, burn-in with a stop-gradient
  on the recurrent state, n-step targets inside the sequence, value
  rescaling, and the eta-mix max/mean sequence priority.
- Ape-X DPG critic/policy losses with Polyak targets.

All losses return (scalar_loss, aux) where aux carries the |TD| priorities
the learner writes back into the sum-tree.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from ape_x_dqn_tpu.ops import value_rescale


def huber(x: jax.Array, delta: float = 1.0) -> jax.Array:
    """Huber of a residual (delegates to optax to keep one definition)."""
    return optax.losses.huber_loss(x, jnp.zeros_like(x), delta=delta)


class TransitionBatch(NamedTuple):
    """A batch of n-step transitions (time-collapsed, SURVEY.md §3.3).

    rewards are the already-accumulated n-step discounted returns R_n;
    discounts are gamma^n * (1 - terminal) for the bootstrap term.
    """

    obs: jax.Array        # [B, ...]
    actions: jax.Array    # [B] int32
    rewards: jax.Array    # [B] f32   (n-step return)
    next_obs: jax.Array   # [B, ...]  (s_{t+n})
    discounts: jax.Array  # [B] f32   (gamma^n, 0 at terminal)


def dqn_td_error(q_s: jax.Array, q_sp_online: jax.Array,
                 q_sp_target: jax.Array, batch: TransitionBatch,
                 double: bool = True,
                 rescale: bool = False) -> jax.Array:
    """Per-sample TD error for the (double) n-step DQN target."""
    q_sa = jnp.take_along_axis(
        q_s, batch.actions[:, None].astype(jnp.int32), axis=-1)[:, 0]
    if double:
        a_star = jnp.argmax(q_sp_online, axis=-1)
        q_boot = jnp.take_along_axis(
            q_sp_target, a_star[:, None], axis=-1)[:, 0]
    else:
        q_boot = jnp.max(q_sp_target, axis=-1)
    if rescale:
        target = value_rescale.h(
            batch.rewards + batch.discounts * value_rescale.h_inv(q_boot))
    else:
        target = batch.rewards + batch.discounts * q_boot
    return q_sa - jax.lax.stop_gradient(target)


def make_dqn_loss(net_apply: Callable, double: bool = True,
                  huber_delta: float = 1.0, rescale: bool = False):
    """Build loss(params, target_params, batch, is_weights) -> (loss, aux)."""

    def loss_fn(params: Any, target_params: Any, batch: TransitionBatch,
                is_weights: jax.Array):
        q_s = net_apply(params, batch.obs)
        q_sp_online = net_apply(params, batch.next_obs)
        q_sp_target = net_apply(target_params, batch.next_obs)
        td = dqn_td_error(q_s, q_sp_online, q_sp_target, batch,
                          double=double, rescale=rescale)
        per_sample = huber(td, huber_delta)
        loss = jnp.mean(is_weights * per_sample)
        # learning-health diagnostics (obs/learning.py): the online-max
        # vs target-net bootstrap gap is the overestimation Double-DQN
        # exists to shrink (van Hasselt 2016). XLA CSEs the argmax /
        # gather with dqn_td_error's identical internals.
        a_star = jnp.argmax(q_sp_online, axis=-1)
        boot_t = jnp.take_along_axis(
            q_sp_target, a_star[:, None], axis=-1)[:, 0]
        aux = {"td_abs": jnp.abs(td), "loss_per_sample": per_sample,
               "q_mean": q_s.mean(), "td_mean": td.mean(),
               "q_max": q_s.max(), "target_q_mean": boot_t.mean(),
               "q_gap": (jnp.max(q_sp_online, axis=-1) - boot_t).mean()}
        return loss, aux

    return loss_fn


# ---------------------------------------------------------------------------
# R2D2 sequence loss


class SequenceBatch(NamedTuple):
    """Fixed-length sequences with stored recurrent state (SURVEY.md §3.4)."""

    # [B, L, ...] — what conv1 reads: the compute dtype, already scaled
    # (replay/sequence.batch_to_sequence_batch, once per SGD step), or
    # uint8 stacks from a caller that has not prepared them, which the
    # net then scales in each application
    obs: jax.Array
    actions: jax.Array    # [B, L] int32
    rewards: jax.Array    # [B, L] f32 (per-step, undiscounted)
    terminals: jax.Array  # [B, L] f32 (1 at true terminal steps)
    mask: jax.Array       # [B, L] f32 (1 on valid steps; 0 on padding)
    init_state: tuple     # (c, h) each [B, H] — state before obs[:, 0]


def nstep_targets_in_sequence(rewards: jax.Array, terminals: jax.Array,
                              bootstrap: jax.Array, mask: jax.Array,
                              n_step: int, gamma: float,
                              rescale: bool) -> tuple[jax.Array, jax.Array]:
    """n-step targets at every t using values bootstrap[t+n] within [0, L).

    bootstrap[t] is the (already action-selected) bootstrap value estimate
    at time t in the *rescaled* space if rescale else raw. Positions whose
    t+n falls off the sequence end are reported invalid via the returned
    validity mask.
    """
    b, length = rewards.shape
    if rescale:
        bootstrap = value_rescale.h_inv(bootstrap)
    t_idx = jnp.arange(length)[None, :]
    ret = jnp.zeros((b, length))
    disc = jnp.ones((b, length))
    alive = jnp.ones((b, length))
    # static unroll over n (n is 3-5): R_n[t] = sum_k gamma^k r[t+k] * alive.
    # jnp.roll wraps, so every rolled quantity is masked to real in-range
    # data — wrapped rewards/terminals from the sequence head must never
    # leak into windows hanging off the tail.
    for k in range(n_step):
        m_k = (jnp.roll(mask, -k, axis=1)
               * (t_idx + k < length).astype(jnp.float32))
        ret = ret + disc * alive * jnp.roll(rewards, -k, axis=1) * m_k
        alive = alive * (1.0 - jnp.roll(terminals, -k, axis=1) * m_k)
        disc = disc * gamma
    boot_n = jnp.roll(bootstrap, -n_step, axis=1)
    target = ret + disc * alive * boot_n
    if rescale:
        target = value_rescale.h(target)
    # A position trains iff it is real data AND its target is fully
    # determined: either the bootstrap at t+n is real in-range data, or a
    # terminal inside [t, t+n) zeroed the bootstrap (alive == 0) and the
    # return is grounded — without the latter the last n transitions of
    # every episode (including the terminal-reward step) would never be
    # trained on while still serving as bootstrap values for earlier steps.
    mask_boot = jnp.roll(mask, -n_step, axis=1)
    boot_ok = (t_idx < length - n_step).astype(jnp.float32) * mask_boot
    terminated = 1.0 - alive
    valid = mask * jnp.clip(boot_ok + terminated, 0.0, 1.0)
    return target, valid


def dense_read(double: bool) -> Callable:
    """How the sequence loss reads two dense Q arrays [B, T, A], the
    online and the target net's on the trained steps: -> read(params,
    target_params, q_online, q_target, actions [B, T]) -> (Q(s, a),
    the bootstrap value, the online Q), each [B, T] but the last."""

    def read(params: Any, target_params: Any, q_online: jax.Array,
             q_target: jax.Array, actions: jax.Array):
        del params, target_params
        q_sa = jnp.take_along_axis(
            q_online, actions[..., None].astype(jnp.int32), axis=-1)[..., 0]
        if double:
            a_star = jnp.argmax(q_online, axis=-1)
            boot = jnp.take_along_axis(
                q_target, a_star[..., None], axis=-1)[..., 0]
        else:
            boot = jnp.max(q_target, axis=-1)
        return q_sa, boot, q_online

    return read


def column_read(head_at: Callable, double: bool) -> Callable:
    """`dense_read` for a net whose Q-values are the columns of a matrix
    over its head's input (models/q_head.py) and that hands that input
    back beside Q: its application gives (q [B, T, A], x [B, T,
    hidden]), and `head_at(params, x, ids [B, T]) -> [B, T]` reads one
    column a token. The loss reads columns where it reads columns: the
    online Q(s, a), the only place a gradient enters, and under
    double-Q the target net's bootstrap at a*. The online net's whole
    slice is read without gradient (the argmax, the two diagnostics),
    the target's not at all, so of a step's four [tokens, hidden] x
    [hidden, A] products one is left (XLA drops the target's) and the
    loss holds ONE float32 [tokens, A] array where the dense read holds
    three. Without double-Q the bootstrap is a max over the target's
    whole slice, which stays."""

    def read(params: Any, target_params: Any, online: tuple, target: tuple,
             actions: jax.Array):
        q_online = jax.lax.stop_gradient(online[0])
        q_sa = head_at(params, online[1], actions)
        if double:
            boot = head_at(target_params, target[1],
                           jnp.argmax(q_online, axis=-1))
        else:
            boot = jnp.max(target[0], axis=-1)
        return q_sa, boot, q_online

    return read


def make_r2d2_loss(net_apply_seq: Callable, burn_in: int, n_step: int,
                   gamma: float, huber_delta: float = 1.0,
                   double: bool = True, rescale: bool = True,
                   priority_eta: float = 0.9,
                   reader: Callable = dense_read):
    """Build the R2D2 sequence loss.

    net_apply_seq(params, obs[B,T,...], state) -> (q[B,T,A], final_state)

    `reader(double)`: how Q(s, a), the bootstrap and the online Q come
    out of what the two nets' applications over the trained steps gave.
    `dense_read`, the default, takes two [B, T, A] arrays, and the loss
    holds three float32 [tokens, A] arrays (the two and the online one's
    cotangent). `column_read` over a net's `head_at` (net_apply_seq then
    gives ((q, x), final_state)) holds ONE, the online Q without
    gradient: compiled for a described v5e, `train_many(2)`'s temp
    stays 4.29 GiB in `trinity_mini_offline` and goes 3.29 -> 3.32 GiB
    in `smallthinker_offline` (PERF.md section 6, PR 49; the peak there
    is the first expert block's backward pass either way). Everything
    that does not touch Q's last axis is the same for both.
    """
    read = reader(double)

    def loss_fn(params: Any, target_params: Any, batch: SequenceBatch,
                is_weights: jax.Array):
        # named scopes are op metadata only (the program XLA builds is
        # the same): the benchmark sums device time by them
        # (learner.burn_in_share, benchmarks/harness/scope_stats.py)
        state0 = tuple(batch.init_state)
        if burn_in > 0:
            with jax.named_scope("r2d2.burn_in"):
                _, state_b = net_apply_seq(
                    params, batch.obs[:, :burn_in], state0)
                state_b = jax.tree.map(jax.lax.stop_gradient, state_b)
                _, state_bt = net_apply_seq(
                    target_params, batch.obs[:, :burn_in], state0)
        else:
            state_b = state0
            state_bt = state0
        with jax.named_scope("r2d2.unroll"):
            obs_t = batch.obs[:, burn_in:]
            out_online, _ = net_apply_seq(params, obs_t, state_b)  # [B,T,A]
            out_target, _ = net_apply_seq(target_params, obs_t, state_bt)

        actions = batch.actions[:, burn_in:]
        rewards = batch.rewards[:, burn_in:]
        terminals = batch.terminals[:, burn_in:]
        mask = batch.mask[:, burn_in:]

        q_sa, boot, q_online = read(params, target_params, out_online,
                                    out_target, actions)
        target, valid = nstep_targets_in_sequence(
            rewards, terminals, boot, mask, n_step, gamma, rescale)
        td = (q_sa - jax.lax.stop_gradient(target)) * valid
        per_step = huber(td, huber_delta)
        denom = jnp.maximum(valid.sum(axis=1), 1.0)
        per_seq = per_step.sum(axis=1) / denom
        loss = jnp.mean(is_weights * per_seq)

        td_abs = jnp.abs(td)
        max_td = td_abs.max(axis=1)
        mean_td = td_abs.sum(axis=1) / denom
        priorities = priority_eta * max_td + (1 - priority_eta) * mean_td
        # learning-health diagnostics: valid-masked means so padding
        # never dilutes the statistics (td is already valid-masked)
        vsum = jnp.maximum(valid.sum(), 1.0)
        aux = {"td_abs": priorities, "q_mean": q_sa.mean(),
               "valid_frac": valid.mean(),
               "td_mean": td.sum() / vsum,
               "q_max": q_online.max(),
               "target_q_mean": (target * valid).sum() / vsum,
               "q_gap": ((jnp.max(q_online, axis=-1) - boot)
                         * valid).sum() / vsum}
        return loss, aux

    return loss_fn


# ---------------------------------------------------------------------------
# Ape-X DPG losses


class ContinuousBatch(NamedTuple):
    obs: jax.Array        # [B, D]
    actions: jax.Array    # [B, A] f32
    rewards: jax.Array    # [B] f32 (n-step return)
    next_obs: jax.Array   # [B, D]
    discounts: jax.Array  # [B] f32


def make_dpg_losses(actor_apply: Callable, critic_apply: Callable):
    """Build (critic_loss, policy_loss) closures for Ape-X DPG."""

    def critic_loss(critic_params: Any, target_critic: Any,
                    target_actor: Any, batch: ContinuousBatch,
                    is_weights: jax.Array):
        a_next = actor_apply(target_actor, batch.next_obs)
        q_next = critic_apply(target_critic, batch.next_obs, a_next)
        target = batch.rewards + batch.discounts * q_next
        q = critic_apply(critic_params, batch.obs, batch.actions)
        td = q - jax.lax.stop_gradient(target)
        loss = jnp.mean(is_weights * 0.5 * td**2)
        # q_gap here is critic-vs-bootstrap bias (equals td_mean by
        # construction — there is no separate online-max estimate)
        return loss, {"td_abs": jnp.abs(td), "q_mean": q.mean(),
                      "td_mean": td.mean(), "q_max": q.max(),
                      "target_q_mean": target.mean(),
                      "q_gap": (q - target).mean()}

    def policy_loss(actor_params: Any, critic_params: Any,
                    batch: ContinuousBatch):
        a = actor_apply(actor_params, batch.obs)
        q = critic_apply(critic_params, batch.obs, a)
        return -jnp.mean(q), {"a_abs_mean": jnp.abs(a).mean()}

    return critic_loss, policy_loss
