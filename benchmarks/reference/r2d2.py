"""Plain reference: the R2D2 network (Nature-CNN torso -> LSTM ->
dueling heads) and its sequence loss, in float32 `jax.numpy`, written
from Kapturowski, Ostrovski, Quan, Munos, Dabney, "Recurrent Experience
Replay in Distributed Reinforcement Learning" (ICLR 2019), section 2.3
and the Atari hyper-parameter table. No kernels, no cache, no batching
tricks, nothing imported from the system under test; every entry point
runs under `jax.default_matmul_precision("highest")`.

- Torso: as reference/dqn.py writes it (Mnih et al. 2015): three VALID
  convolutions and a dense layer, ReLU after each, uint8 pixels scaled
  to [0, 1]. With no convolutions given the torso is the dense layer
  alone on float observations (the CPU tests' vector observations).
- LSTM (Hochreiter & Schmidhuber 1997), gate by gate:
      i = sigmoid(x W_ii + h W_hi + b_i)     f = sigmoid(x W_if + h W_hf + b_f)
      g = tanh   (x W_ig + h W_hg + b_g)     o = sigmoid(x W_io + h W_ho + b_o)
      c' = f * c + i * g                     h' = o * tanh(c')
  One bias per gate. flax's `OptimizedLSTMCell`, which the system uses,
  keeps that bias on the hidden projections (`hi`, `hf`, `hg`, `ho`:
  kernel [H, H] + bias [H]) and gives the input projections (`ii`,
  `if`, `ig`, `io`: kernel [F, H]) none; `harness/r2d2_params.py` maps
  its eight kernels and four biases onto `Params` by those names.
- Heads: Wang et al. 2016 eq. 9, Q = V + A - mean_a(A).
- Loss, per sequence of L stored steps with stored state (c0, h0):
  burn in both nets over the first `burn_in` steps from (c0, h0), no
  gradient; unroll both over the remaining T = L - burn_in steps;
  a*_t = argmax_a Q_online(t, a) (double Q); for every t the n-step
  target inside the sequence
      y_t = h( sum_{k<n} gamma^k r_{t+k} + gamma^n h^-1(Q_target(t+n, a*_{t+n})) ),
  cut at the first terminal in [t, t+n);  h(x) = sign(x)(sqrt(|x|+1) - 1)
  + eps x with eps = 1e-3 and h^-1 its closed-form inverse;
  delta_t = Q_online(t, a_t) - y_t; loss = mean over the batch of
  w_b * (sum_t Huber(delta_t) / #valid_t); new priority
  p = eta max_t |delta_t| + (1 - eta) mean_t |delta_t|.

Departures from the paper, all of them the system's, followed here so
that the two can be compared (this sandbox has no network: the paper
is quoted from memory):
1. The paper's burn-in is a prefix of l = 40 steps in front of the
   m = 80 trained steps; the system takes it out of the 80 stored
   steps (40 burn-in + 40 trained).
2. The paper says nothing of the last n steps of a sequence, whose
   bootstrap lies outside it. Here a step is trained ("valid") if it
   is real data and either step t+n is real data inside the sequence
   or a terminal in [t, t+n) makes the target need no bootstrap; mean
   and max run over valid steps only, and padding (mask 0) never
   trains.
3. The paper's loss is the squared TD error; the system keeps Ape-X's
   Huber loss (delta = 1), which equals 0.5 delta^2 while |delta| <= 1.
4. delta is taken in the rescaled space (both Q and y are h-space
   values), as the network predicts rescaled values.

A lower precision, to set the comparison's limits by. With
`mantissa_bits` = m the network (not the loss, which the system too
keeps in float32) rounds its weights, its inputs and the result of
every operation - each matrix product, bias add, activation, gate,
state update and the dueling combination, as a network computed in
that precision would, the system's `q = v + a - mean(a)` included - to
m explicit bits of mantissa (`jax.lax.reduce_precision`, float32's
exponent kept): 7 is bfloat16, the precision the configuration states;
5 is two bits less. `None`, the default, is the reference itself:
plain float32, nothing rounded, the same operations in the same order.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

GATES = ("i", "f", "g", "o")
RESCALE_EPS = 1e-3


class Params(NamedTuple):
    conv_kernels: Sequence[jax.Array]   # each [k, k, c_in, c_out]; () = no convs
    conv_biases: Sequence[jax.Array]
    dense_kernel: jax.Array             # [torso features, F]
    dense_bias: jax.Array
    lstm_input_kernels: dict            # gate -> [F, H]
    lstm_hidden_kernels: dict           # gate -> [H, H]
    lstm_biases: dict                   # gate -> [H]
    value_kernel: jax.Array             # [H, 1]
    value_bias: jax.Array
    advantage_kernel: jax.Array         # [H, A]
    advantage_bias: jax.Array


def rounder(mantissa_bits: int | None):
    """-> x rounded to `mantissa_bits` explicit bits of mantissa; the
    identity for None (the reference proper)."""
    if mantissa_bits is None:
        return lambda x: x
    return lambda x: jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=mantissa_bits)


def torso(p: Params, obs: jax.Array, conv_strides: Sequence[int],
          rnd=rounder(None)):
    """obs [N, H, W, stack] uint8 (or [N, D] float) -> [N, F]."""
    if obs.dtype == jnp.uint8:
        x = rnd(obs.astype(jnp.float32) / 255.0)
    else:
        x = rnd(obs.astype(jnp.float32))
    for w, b, s in zip(p.conv_kernels, p.conv_biases, conv_strides):
        x = rnd(jax.lax.conv_general_dilated(
            x, w.astype(jnp.float32), window_strides=(s, s),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST))
        x = rnd(jnp.maximum(x + b, 0.0))
    x = x.reshape(x.shape[0], -1)
    return rnd(jnp.maximum(rnd(x @ p.dense_kernel) + p.dense_bias, 0.0))


def lstm_cell(p: Params, x, c, h, rnd=rounder(None)):
    def gate(name):
        return rnd(rnd(x @ p.lstm_input_kernels[name])
                   + rnd(rnd(h @ p.lstm_hidden_kernels[name])
                         + p.lstm_biases[name]))

    i = rnd(jax.nn.sigmoid(gate("i")))
    f = rnd(jax.nn.sigmoid(gate("f")))
    g = rnd(jnp.tanh(gate("g")))
    o = rnd(jax.nn.sigmoid(gate("o")))
    c = rnd(rnd(f * c) + rnd(i * g))
    return c, rnd(o * rnd(jnp.tanh(c)))


def unroll(p: Params, obs, state, conv_strides: Sequence[int] = (4, 2, 1),
           mantissa_bits: int | None = None):
    """obs [B, T, ...], state (c, h) each [B, H] -> (Q [B, T, A]
    float32, final (c, h))."""
    rnd = rounder(mantissa_bits)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda w: rnd(jnp.asarray(w, jnp.float32)), p)
        b, t = obs.shape[:2]
        feats = torso(p, obs.reshape(b * t, *obs.shape[2:]), conv_strides,
                      rnd)
        feats = feats.reshape(b, t, -1)

        def step(carry, x):
            c, h = lstm_cell(p, x, *carry, rnd)
            return (c, h), h

        state = tuple(rnd(jnp.asarray(s, jnp.float32)) for s in state)
        state, hs = jax.lax.scan(step, state, jnp.swapaxes(feats, 0, 1))
        hs = jnp.swapaxes(hs, 0, 1)                       # [B, T, H]
        v = rnd(rnd(hs @ p.value_kernel) + p.value_bias)
        a = rnd(rnd(hs @ p.advantage_kernel) + p.advantage_bias)
        q = rnd(rnd(v + a) - rnd(a.mean(axis=-1, keepdims=True)))
        return q, state


def h(x, eps: float = RESCALE_EPS):
    return jnp.sign(x) * (jnp.sqrt(jnp.abs(x) + 1.0) - 1.0) + eps * x


def h_inv(x, eps: float = RESCALE_EPS):
    root = jnp.sqrt(1.0 + 4.0 * eps * (jnp.abs(x) + 1.0 + eps))
    return jnp.sign(x) * (((root - 1.0) / (2.0 * eps)) ** 2 - 1.0)


def huber(x, delta: float):
    ax = jnp.abs(x)
    return jnp.where(ax <= delta, 0.5 * x * x, delta * (ax - 0.5 * delta))


def nstep_targets(rewards, terminals, mask, boot, n_step: int,
                  gamma: float):
    """All [B, T]; `boot` is the rescaled bootstrap value at each step.
    -> (y [B, T] rescaled targets, valid [B, T])."""
    t = rewards.shape[1]

    def ahead(x, k):        # x at step t + k, zero past the sequence end
        return jnp.pad(x, ((0, 0), (0, n_step)))[:, k:k + t]

    ret = jnp.zeros_like(rewards)
    alive = jnp.ones_like(rewards)
    for k in range(n_step):
        real = ahead(mask, k)
        ret = ret + gamma ** k * alive * ahead(rewards, k) * real
        alive = alive * (1.0 - ahead(terminals, k) * real)
    y = h(ret + gamma ** n_step * alive * ahead(h_inv(boot), n_step))
    valid = mask * jnp.clip(ahead(mask, n_step) + (1.0 - alive), 0.0, 1.0)
    return y, valid


def sequence_loss(online: Params, target: Params, obs, actions, rewards,
                  terminals, mask, init_c, init_h, weights, *,
                  burn_in: int, n_step: int, gamma: float, eta: float,
                  huber_delta: float = 1.0,
                  conv_strides: Sequence[int] = (4, 2, 1),
                  mantissa_bits: int | None = None):
    """obs [B, L, ...]; actions/rewards/terminals/mask [B, L]; init_c,
    init_h [B, H]; weights [B].
    -> (loss, {"q" [B, L - burn_in, A], "priorities" [B], "valid"
    [B, L - burn_in], "td" [B, L - burn_in]})."""
    with jax.default_matmul_precision("highest"):
        state = (init_c, init_h)
        state_online = state_target = state
        if burn_in:
            _, state_online = unroll(online, obs[:, :burn_in], state,
                                     conv_strides, mantissa_bits)
            state_online = jax.lax.stop_gradient(state_online)
            _, state_target = unroll(target, obs[:, :burn_in], state,
                                     conv_strides, mantissa_bits)
        q, _ = unroll(online, obs[:, burn_in:], state_online, conv_strides,
                      mantissa_bits)
        q_target, _ = unroll(target, obs[:, burn_in:], state_target,
                             conv_strides, mantissa_bits)
        actions, rewards, terminals, mask = (
            x[:, burn_in:] for x in (actions, rewards, terminals, mask))
        pick = lambda table, a: jnp.take_along_axis(     # noqa: E731
            table, a[..., None].astype(jnp.int32), axis=-1)[..., 0]
        boot = pick(q_target, jnp.argmax(q, axis=-1))
        y, valid = nstep_targets(rewards, terminals, mask, boot, n_step,
                                 gamma)
        td = (pick(q, actions) - jax.lax.stop_gradient(y)) * valid
        n_valid = jnp.maximum(valid.sum(axis=1), 1.0)
        per_sequence = huber(td, huber_delta).sum(axis=1) / n_valid
        loss = jnp.mean(weights * per_sequence)
        td_abs = jnp.abs(td)
        priorities = (eta * td_abs.max(axis=1)
                      + (1.0 - eta) * td_abs.sum(axis=1) / n_valid)
        return loss, {"q": q, "priorities": priorities, "valid": valid,
                      "td": td}


def loss_and_gradients(online: Params, *args, **kwargs):
    """-> ((loss, aux), d loss / d online) — `jax.grad` of
    `sequence_loss` itself, every parameter of the online net."""
    return jax.value_and_grad(sequence_loss, has_aux=True)(
        online, *args, **kwargs)


def new_priority(priority, alpha: float, eps: float):
    """What the replay stores for a sequence: (p + eps)^alpha."""
    return (priority + eps) ** alpha
