"""Learning-health plane: in-graph training diagnostics + LearnMonitor
(ISSUE 10, the fourth obs plane).

PRs 2/6/8 watch the *systems* (spans, fleet telemetry, MFU/roofline);
nothing watched whether the RL itself was healthy. This module closes
that in three pieces:

- jit-safe diagnostic helpers (`sgd_diag`, `replay_health`,
  `replay_health_sharded`) the learner cycles call INSIDE their
  existing jits. Everything is a scalar reduction over arrays the
  loss/optimizer already materialized (TD quantiles, overestimation
  gap, grad/update norms, IS-weight effective sample size,
  priority-mass concentration, in-graph sampled-transition age, and the
  descent-time vs write-back-time priority-staleness delta the prefetch
  pipeline accepts by design — ROADMAP item 3 said "quantify, don't
  assume"; this is the instrument). The result rides the learner's
  metrics pytree through the train_many scan, so the host reads it only
  at the block_until_ready sync points the drivers already pay for:
  zero new device syncs on the default path.
- `publish_learn` — one literal `learn_*` gauge emission per
  diagnostic (the obs-names contract: every instrument is a listed,
  greppable row in obs/report.py), plus dynamic `learn/<tenant>/...`
  duplicates so the 57-game rotation becomes 57 attributable tenants
  (tenant = cfg.env.id, same identity the suite runner uses).
- `LearnMonitor` — warn-only anomaly engine, sibling of profiling.py's
  PerfMonitor: an EWMA baseline over the loss plus absolute-threshold
  rules over the diagnostics (loss spike, Q blowup, ESS collapse, dead
  gradients, priority collapse). Fires ONE attributed
  `learning_degradation` JSONL event per (tenant, rule) cooldown and a
  counter — never an exception: a sick learner is survivable and the
  artifact should say so; aborting is the driver's job, not the
  monitor's. The CI gate lives in `obs/report.py --check`, not here.

Disabled obs routes through NullObs and never reaches this module's
host side; the in-graph helpers import jax lazily and add the same
handful of fused scalar reductions whether or not obs is on (at the
CNN's 1.7 M parameters, 27 MB of learner state, their cost is
`learner.health_share` in PERF.md). The two that read parameter-sized trees are not free at every
size, so `sgd_diag` runs them only on a step whose metrics are read.
"""

from __future__ import annotations

import logging
import time
from typing import Any

from ape_x_dqn_tpu.obs.health import make_lock

# Absolute-threshold rule bounds. These are deliberately loose — the
# monitor flags pathology (divergence, collapse), not suboptimality —
# and each is mirrored by the matching healthy-range row in
# obs/report.py INSTRUMENTS so the offline report flags the same line
# the online monitor fires on.
Q_MAX_LIMIT = 1e3          # |q_max| above this = Q blowup (catch/atari
#                            Qs live in clipped-reward units, O(1..100))
ESS_FRAC_MIN = 0.05        # IS effective-sample-size below 5% of batch
UPDATE_RATIO_MIN = 1e-9    # ||update||/||param|| below this = dead grads
TOP_FRAC_MAX = 0.5         # one transition holding half the priority mass


# -- in-graph diagnostics (pure, jit-safe; called inside learner jits) ----

def sgd_diag(aux: dict, is_w, grads, updates, params, *,
             grad_norm=None, want_tree_diag=True, update_of=None) -> dict:
    """Per-SGD-step learning diagnostics as a flat dict of f32 device
    scalars. `aux` is the loss aux (ops/losses.py), `is_w` the IS
    weights actually applied, `grads`/`updates`/`params` the optimizer
    triple.

    On EVERY step: what is batch-sized (TD quantiles, ESS, the `q_*`
    scalars the loss already reduced) and `grad_norm` — the caller's
    own `grad_norm` when it has one (clipping needs it every step
    anyway), else one reduction over `grads`.

    Only when `want_tree_diag`: `update_ratio`, two reductions over
    parameter-sized trees (‖update‖, ‖params‖; 0.0 when not asked). At
    27 MB of state those were noise; at 591 M float32 parameters one
    read of a tree is 2.9 ms of a 275 ms step (PERF.md §6, PR 31), so a
    step whose metrics never leave the program passes False (a Python
    bool: not traced at all) or a traced flag (a `lax.cond`).

    `update_of`: the `lax.cond`'s operands are `updates` and `params`,
    so both must be trees that live in HBM whether or not the branch
    runs. The update tree does not: as an operand it would be written
    out on every step only to be one (+one parameter-sized temp, ISSUE
    31's table). The caller then passes as `updates` the trees the
    update can be rebuilt FROM — the optimizer's new state — and
    `update_of(updates)` rebuilds it inside the branch."""
    import jax
    import jax.numpy as jnp
    import optax

    td = aux["td_abs"].astype(jnp.float32).reshape(-1)
    qs = jnp.percentile(td, jnp.asarray([50.0, 90.0, 99.0]))
    w = is_w.astype(jnp.float32).reshape(-1)
    # Kish effective sample size as a fraction of the batch: 1.0 under
    # uniform weights, ->1/B when one sample dominates (beta pathology)
    ess = jnp.square(w.sum()) / (
        w.size * jnp.maximum((w * w).sum(), 1e-12))
    if grad_norm is None:
        grad_norm = optax.global_norm(grads)

    def ratio(updates, params):
        if update_of is not None:
            updates = update_of(updates)
        return (optax.global_norm(updates) / jnp.maximum(
            optax.global_norm(params), 1e-12)).astype(jnp.float32)

    zero = jnp.float32(0.0)
    if isinstance(want_tree_diag, bool):
        update_ratio = ratio(updates, params) if want_tree_diag else zero
    else:
        update_ratio = jax.lax.cond(
            want_tree_diag, ratio, lambda *_: zero, updates, params)
    return {
        "td_abs_p50": qs[0],
        "td_abs_p90": qs[1],
        "td_abs_p99": qs[2],
        "td_signed_mean": aux.get("td_mean", zero),
        "q_mean": aux.get("q_mean", zero),
        "q_max": aux.get("q_max", zero),
        "target_q_mean": aux.get("target_q_mean", zero),
        # overestimation gap (van Hasselt 2016): online bootstrap vs
        # the double-DQN target-net bootstrap, the quantity Double-DQN
        # exists to shrink — computed in the loss, surfaced here
        "q_gap": aux.get("q_gap", zero),
        "grad_norm": grad_norm,
        "update_ratio": update_ratio,
        "is_ess_frac": ess,
    }


def replay_health(replay, rs, idx, pri_then) -> dict:
    """Replay-side diagnostics at write-back time (single-chip states).

    `idx` is any int array of sampled leaf indices; `pri_then` the
    matching priorities read AT DESCENT time (None on paths where the
    draw and write-back see the same tree — staleness is identically 0
    there and reported as such). Ages are ring distances from the write
    cursor in TRANSITIONS, so flat and frame-ring layouts agree."""
    import jax.numpy as jnp

    cap = int(replay.capacity)
    idx = idx.reshape(-1)
    cursor = replay.cursor_transitions(rs)
    age = jnp.mod(cursor - 1 - idx, cap).astype(jnp.float32)
    ages = jnp.percentile(age, jnp.asarray([50.0, 90.0]))
    out = {"sample_age_p50": ages[0], "sample_age_p90": ages[1]}
    zero = jnp.float32(0.0)
    if not getattr(replay, "has_priorities", True):
        out["prio_staleness_frac"] = zero
        out["priority_top_frac"] = zero
        return out
    if pri_then is None:
        out["prio_staleness_frac"] = zero
    else:
        then = pri_then.reshape(-1).astype(jnp.float32)
        now = replay.leaf_priorities(rs, idx)
        # mean |delta p| relative to the mean descent-time priority:
        # 0 on the fused path, the measured one-dispatch lag under
        # sample_prefetch / K-batch write-back
        out["prio_staleness_frac"] = jnp.abs(now - then).mean() \
            / jnp.maximum(then.mean(), 1e-12)
    # concentration: largest single leaf's share of the total priority
    # mass — ->1.0 means the sampler has collapsed onto one transition
    leaves = rs.tree[cap:]
    out["priority_top_frac"] = leaves.max() \
        / jnp.maximum(rs.tree[1], 1e-12)
    return out


def replay_health_sharded(replay, rs, idx, pri_then) -> dict:
    """`replay_health` for the dist learner's [dp]-leading shard states
    (`replay` is the per-shard replay; `idx` is [dp, n]). Reductions run
    over all shards — under GSPMD the plain jnp ops lower to the psum /
    all-gather collectives, so the result is the global statistic."""
    import jax
    import jax.numpy as jnp

    cap = int(replay.capacity)
    cursor = jax.vmap(replay.cursor_transitions)(rs)  # [dp]
    age = jnp.mod(cursor[:, None] - 1 - idx, cap).astype(jnp.float32)
    ages = jnp.percentile(age.reshape(-1), jnp.asarray([50.0, 90.0]))
    out = {"sample_age_p50": ages[0], "sample_age_p90": ages[1]}
    zero = jnp.float32(0.0)
    if not getattr(replay, "has_priorities", True):
        out["prio_staleness_frac"] = zero
        out["priority_top_frac"] = zero
        return out
    if pri_then is None:
        out["prio_staleness_frac"] = zero
    else:
        then = pri_then.astype(jnp.float32)
        now = jax.vmap(replay.leaf_priorities)(rs, idx)
        out["prio_staleness_frac"] = jnp.abs(now - then).mean() \
            / jnp.maximum(then.mean(), 1e-12)
    leaves = rs.tree[:, cap:]            # [dp, cap]
    mass = rs.tree[:, 1].sum()           # global mass across shards
    out["priority_top_frac"] = leaves.max() / jnp.maximum(mass, 1e-12)
    return out


# -- host-side publication -------------------------------------------------

def publish_learn(obs, vals: dict, tenant: str = "") -> None:
    """Publish one host-read diag snapshot as `learn_*` gauges.

    One LITERAL emission per instrument (tools/apexlint obs-names
    cross-checks each against its obs/report.py INSTRUMENTS row); the
    per-tenant duplicates ride dynamic slash-prefixed keys, which the
    registry namespaces and the report regroups per game."""
    g = vals.get
    obs.gauge("learn_td_abs_p50", g("td_abs_p50", 0.0))
    obs.gauge("learn_td_abs_p90", g("td_abs_p90", 0.0))
    obs.gauge("learn_td_abs_p99", g("td_abs_p99", 0.0))
    obs.gauge("learn_td_signed_mean", g("td_signed_mean", 0.0))
    obs.gauge("learn_q_mean", g("q_mean", 0.0))
    obs.gauge("learn_q_max", g("q_max", 0.0))
    obs.gauge("learn_target_q_mean", g("target_q_mean", 0.0))
    obs.gauge("learn_q_gap", g("q_gap", 0.0))
    obs.gauge("learn_grad_norm", g("grad_norm", 0.0))
    obs.gauge("learn_update_ratio", g("update_ratio", 0.0))
    obs.gauge("learn_is_ess_frac", g("is_ess_frac", 1.0))
    obs.gauge("learn_priority_top_frac", g("priority_top_frac", 0.0))
    obs.gauge("learn_sample_age_p50", g("sample_age_p50", 0.0))
    obs.gauge("learn_sample_age_p90", g("sample_age_p90", 0.0))
    obs.gauge("learn_prio_staleness_frac", g("prio_staleness_frac", 0.0))
    if "shard_td_mean_min" in vals:  # dist learner only
        obs.gauge("learn_shard_td_mean_min", vals["shard_td_mean_min"])
        obs.gauge("learn_shard_td_mean_max", vals["shard_td_mean_max"])
    if tenant:
        for k, v in vals.items():
            obs.gauge(f"learn/{tenant}/{k}", v)


# -- the anomaly engine ----------------------------------------------------

class LearnMonitor:
    """Warn-only learning-anomaly engine (PerfMonitor's sibling).

    One EWMA baseline per tenant over the loss (relative rule: spike =
    loss > spike_mult x baseline after min_samples) plus four absolute
    rules over the diagnostics. Each (tenant, rule) fires at most once
    per cooldown; a fire is a counter bump + one attributed JSONL event
    — never an exception. Like PerfMonitor, the baseline keeps
    absorbing the new regime, so a persistently sick learner alerts
    once per cooldown and then becomes the new normal in the EWMA while
    the absolute rules (and the report's healthy ranges) keep flagging.
    """

    def __init__(self, obs, metrics, spike_mult: float = 10.0,
                 alpha: float = 0.2, min_samples: int = 8,
                 cooldown_s: float = 30.0):
        self._obs = obs
        self._metrics = metrics
        self.spike_mult = spike_mult
        self._alpha = alpha
        self._min_samples = min_samples
        self._cooldown_s = cooldown_s
        self._lock = make_lock("learning.learn_monitor")
        self._loss: dict[str, dict] = {}        # guarded-by: _lock
        self._last_fire: dict[tuple, float] = {}  # guarded-by: _lock
        # fire listeners (the remediation plane subscribes here):
        # called OUTSIDE the monitor lock, once per emitted event, with
        # (rule, value, baseline, step, tenant). Append-only at wiring
        # time, so iteration is safe without the lock.
        self._listeners: list = []

    def add_listener(self, cb) -> None:
        self._listeners.append(cb)

    def observe(self, vals: dict, loss: float, step: int = 0,
                tenant: str = "") -> None:
        loss = float(loss)
        fires: list[tuple[str, float, float]] = []
        now = time.monotonic()
        with self._lock:
            if loss == loss:  # NaN losses skip the EWMA, not the rules
                s = self._loss.setdefault(tenant, {"ewma": loss, "n": 0})
                baseline = s["ewma"]
                if (s["n"] >= self._min_samples and baseline > 0.0
                        and loss > self.spike_mult * baseline):
                    fires.append(("loss_spike", loss, baseline))
                s["ewma"] = (1 - self._alpha) * baseline \
                    + self._alpha * loss
                s["n"] += 1
            for rule, value, bad in (
                ("q_blowup", vals.get("q_max"),
                 lambda v: abs(v) > Q_MAX_LIMIT),
                ("ess_collapse", vals.get("is_ess_frac"),
                 lambda v: v < ESS_FRAC_MIN),
                ("dead_gradients", vals.get("update_ratio"),
                 lambda v: v < UPDATE_RATIO_MIN),
                ("priority_collapse", vals.get("priority_top_frac"),
                 lambda v: v > TOP_FRAC_MAX),
            ):
                if value is None:
                    continue
                value = float(value)
                if value == value and bad(value):
                    fires.append((rule, value, 0.0))
            fires = [f for f in fires
                     if now - self._last_fire.get(
                         (tenant, f[0]), float("-inf"))
                     >= self._cooldown_s]
            for rule, _, _ in fires:
                self._last_fire[(tenant, rule)] = now
        for rule, value, baseline in fires:
            self._obs.count("learning_degradations")
            self._metrics.log(
                step, learning_degradation=rule,
                learn_tenant=tenant or None,
                learn_value=round(value, 6),
                learn_baseline=round(baseline, 6))
            for cb in self._listeners:
                try:
                    cb(rule, value, baseline, step, tenant)
                except Exception:  # noqa: BLE001 - warn-only plane
                    logging.getLogger(__name__).warning(
                        "learning-degradation listener failed",
                        exc_info=True)
