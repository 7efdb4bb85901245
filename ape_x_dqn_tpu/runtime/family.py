"""Model-family dispatch shared by the drivers and remote actor hosts.

A RunConfig's network kind selects one of three runtime families —
flat-DQN ("dqn"), recurrent R2D2 ("r2d2"), continuous Ape-X DPG
("dpg") — which differ in the inference-server protocol (plain Q-values
vs stateful {obs,c,h} vs {a,q} actor-critic), the actor class, the
AOT-warmup example, and what the learner trains on (its loss and how
sampled items become the loss's batch). ApexDriver (runtime/driver.py),
MultihostApexDriver, single_process.py and run_actor_host
(runtime/actor_host.py) must agree on these, so the dispatch lives here
once: `build_learner` is the one place a learner is constructed, and
the table behind `learner_family` the one place a loss is bound. A new
Q-learning family is a loss in ops/losses.py, a row in that table and
its net — no edit to a learner or a driver.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.configs import RunConfig
from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.replay.frame_ring import (frame_ring_mode,
                                             frame_segment_spec)
from ape_x_dqn_tpu.replay.sequence import (sequence_frame_mode,
                                           sequence_item_spec)
from ape_x_dqn_tpu.runtime.actor import (
    Actor, ContinuousActor, RecurrentActor)
from ape_x_dqn_tpu.utils.rng import component_key


def family_of(cfg: RunConfig) -> str:
    return {"lstm_q": "r2d2", "dpg": "dpg"}.get(cfg.network.kind, "dqn")


def actor_class(family: str, vector: bool = False) -> type:
    """Actor implementation per family. vector=True selects the
    K-envs-per-thread vectorized actors (runtime/vector_actor.py),
    whose query contract is the server's `query_batch` (the recurrent
    variant ships {obs, c, h} pytrees with a leading [K] axis)."""
    if vector:
        from ape_x_dqn_tpu.runtime.vector_actor import (
            ContinuousVectorActor, RecurrentVectorActor, VectorActor)
        return {"r2d2": RecurrentVectorActor,
                "dpg": ContinuousVectorActor}.get(family, VectorActor)
    return {"r2d2": RecurrentActor, "dpg": ContinuousActor}.get(
        family, Actor)


def server_apply_fn(family: str, net: Any) -> Callable:
    """The batched forward the inference server jits, per family.

    - dqn:  obs [B, ...]          -> q [B, A]
    - r2d2: {obs, c, h}           -> {q, c, h}   (stateful step)
    - dpg:  obs [B, ...]          -> {a: mu(s), q: Q(s, mu(s))}
      (params are the {actor, critic} dict publish_params produces)
    """
    if family == "r2d2":
        def apply_rec(p, inp):
            q, (c, h) = net.apply(p, inp["obs"], (inp["c"], inp["h"]),
                                  method=net.step)
            return {"q": q, "c": c, "h": h}
        return apply_rec
    if family == "dpg":
        actor_net, critic_net = net

        def apply_dpg(p, obs):
            a = actor_net.apply(p["actor"], obs)
            q = critic_net.apply(p["critic"], obs, a)
            return {"a": a, "q": q}
        return apply_dpg
    return lambda p, obs: net.apply(p, obs)


# -- the learner's side ------------------------------------------------------


def transition_batch(items: Any):
    """Sampled flat n-step items (transition_item_spec's keys, which
    the frame-ring layout rebuilds too) -> losses.TransitionBatch."""
    from ape_x_dqn_tpu.ops.losses import TransitionBatch

    return TransitionBatch(
        obs=items["obs"], actions=items["action"],
        rewards=items["reward"], next_obs=items["next_obs"],
        discounts=items["discount"])


def dqn_family(net_apply: Callable, lcfg):
    """Flat n-step double-DQN. net_apply(params, obs[B,...]) -> q[B,A]."""
    from ape_x_dqn_tpu.ops.losses import make_dqn_loss
    from ape_x_dqn_tpu.runtime.learner import LearnerFamily

    return LearnerFamily(
        name="dqn",
        loss_fn=make_dqn_loss(
            net_apply, double=lcfg.double_dqn,
            huber_delta=lcfg.huber_delta, rescale=lcfg.value_rescale),
        make_batch=transition_batch,
        net_apply=net_apply)


def r2d2_family(net_apply_seq: Callable, lcfg, rcfg, compute_dtype=None):
    """R2D2 stored-state sequences (SURVEY.md §3.4): burn-in unroll,
    n-step double-DQN sequence loss with value rescaling, eta-mixed
    per-sequence priorities (aux['td_abs']).
    net_apply_seq(params, obs[B,T,...], (c,h)) -> (q[B,T,A], state).
    compute_dtype: the net's (cfg.network.compute_dtype), so that
    conv1's input is prepared once per SGD step and not in each of the
    loss's four net applications; None leaves uint8 for the net to
    scale."""
    from ape_x_dqn_tpu.ops.losses import make_r2d2_loss
    from ape_x_dqn_tpu.replay.sequence import batch_to_sequence_batch
    from ape_x_dqn_tpu.runtime.learner import LearnerFamily

    return LearnerFamily(
        name="r2d2",
        loss_fn=make_r2d2_loss(
            net_apply_seq, burn_in=rcfg.burn_in, n_step=lcfg.n_step,
            gamma=lcfg.gamma, huber_delta=lcfg.huber_delta,
            double=lcfg.double_dqn, rescale=lcfg.value_rescale,
            priority_eta=rcfg.priority_eta),
        make_batch=lambda items: batch_to_sequence_batch(
            items, compute_dtype, rcfg.burn_in),
        net_apply=net_apply_seq,
        apply_attr="net_apply_seq",
        metric_keys=("valid_frac",))


# family name -> (cfg, net) -> LearnerFamily. DPG is not a row: its
# learner (two nets, two optimizers, soft targets) is its own class.
_LEARNER_FAMILIES = {
    "dqn": lambda cfg, net: dqn_family(net.apply, cfg.learner),
    "r2d2": lambda cfg, net: r2d2_family(
        net.apply, cfg.learner, cfg.replay,
        compute_dtype=dtype_of(cfg.network.compute_dtype)),
}


def learner_family(cfg: RunConfig, net: Any):
    return _LEARNER_FAMILIES[family_of(cfg)](cfg, net)


def build_learner(cfg: RunConfig, net: Any, replay: Any, mesh: Any = None):
    """The learner for cfg's family over `replay`: the sharded one on a
    mesh (`replay` then holds the PER-SHARD capacity), else the
    single-chip one. States differ with the learner: see each `init`."""
    if family_of(cfg) == "dpg":
        from ape_x_dqn_tpu.runtime.dpg_learner import DPGLearner

        if mesh is not None:
            raise NotImplementedError(
                "the distributed learner covers the DQN and R2D2 "
                "families; DPG nets are small — run dp=tp=1")
        actor_net, critic_net = net
        return DPGLearner(actor_net.apply, critic_net.apply, replay,
                          cfg.learner)
    family = learner_family(cfg, net)
    if mesh is not None:
        from ape_x_dqn_tpu.parallel.dist_learner import DistLearner

        return DistLearner(family, replay, cfg.learner, mesh)
    from ape_x_dqn_tpu.runtime.learner import SingleChipLearner

    return SingleChipLearner(family, replay, cfg.learner)


def warmup_example(family: str, cfg: RunConfig, spec: Any) -> Any:
    """One server request pytree (no batch dim) for AOT warmup —
    shapes/dtypes only, content irrelevant."""
    obs = np.zeros(spec.obs_shape, spec.obs_dtype)
    if family == "r2d2":
        z = np.zeros(cfg.network.lstm_size, np.float32)
        return {"obs": obs, "c": z, "h": z}
    return obs


class FamilySetup(NamedTuple):
    """Per-family initial params, replay item layout, and ingest
    staging geometry — one source of truth for ApexDriver and
    MultihostApexDriver (they must agree with each other and with the
    actors shipping the items)."""
    params: Any
    item_spec: dict
    frame_mode: bool     # dqn family storing single-frame segments
    stage_chunk: int     # staging units per [dp-row] ingest block
    unit_items: int      # transitions per staging unit (fill counting)


def family_setup(cfg: RunConfig, spec: Any, net: Any,
                 obs0: np.ndarray) -> FamilySetup:
    """Initialize params and pick the replay item layout + staging
    chunk for cfg's family.

    frame_ring storage selects single-frame pixel layouts: for the
    flat-dqn family it swaps the item spec to whole frame segments
    (and the driver swaps the replay class); for r2d2 it only changes
    the sequence item content (single frames, stacks rebuilt in the
    learner jit) — same replay, same staging. DPG obs are
    low-dimensional, so frame_ring is rejected there.

    Staging units are transitions (flat), frame segments (frame mode),
    or whole sequences (r2d2) — for r2d2 the chunk scales ingest_batch
    down by seq_length because ingest_batch counts TRANSITIONS, and a
    [dp, ingest_batch] block of SEQUENCES would hold
    dp*ingest_batch*seq_length env steps and starve the learner
    waiting for the first add.
    """
    from ape_x_dqn_tpu.runtime.dpg_learner import continuous_item_spec
    from ape_x_dqn_tpu.runtime.learner import transition_item_spec

    family = family_of(cfg)
    if family == "r2d2":
        z = jnp.zeros((1, cfg.network.lstm_size), jnp.float32)
        params = net.init(component_key(cfg.seed, "net_init"),
                          obs0[None, None], (z, z))
        seq_frame_mode = sequence_frame_mode(cfg.replay.storage,
                                             spec.obs_shape)
        if cfg.replay.storage == "frame_ring" and not seq_frame_mode:
            raise ValueError(
                f"frame_ring sequence storage needs [H, W, stack] "
                f"pixel obs, got {spec.obs_shape}; set "
                f"replay.storage='flat' for vector observations")
        item_spec = sequence_item_spec(
            spec.obs_shape, spec.obs_dtype, cfg.replay.seq_length,
            cfg.network.lstm_size, frame_mode=seq_frame_mode)
        return FamilySetup(
            params, item_spec, False,
            max(cfg.actors.ingest_batch // cfg.replay.seq_length, 1), 1)
    if family == "dpg":
        if cfg.replay.storage == "frame_ring":
            raise NotImplementedError(
                "frame_ring storage is for pixel families (dqn/r2d2); "
                "use storage='flat' for dpg")
        actor_net, critic_net = net
        a0 = jnp.zeros((1, spec.action_dim), jnp.float32)
        params = (
            actor_net.init(component_key(cfg.seed, "actor_init"),
                           obs0[None]),
            critic_net.init(component_key(cfg.seed, "critic_init"),
                            obs0[None], a0))
        item_spec = continuous_item_spec(spec.obs_shape, spec.obs_dtype,
                                         spec.action_dim)
        return FamilySetup(params, item_spec, False,
                           max(cfg.actors.ingest_batch, 1), 1)
    # flat dqn
    params = net.init(component_key(cfg.seed, "net_init"), obs0[None])
    if cfg.replay.storage == "frame_ring":
        if cfg.replay.kind != "prioritized":
            raise NotImplementedError(
                "flat-family frame_ring storage requires prioritized "
                "replay")
        if not frame_ring_mode(cfg.replay.storage, spec.obs_shape):
            raise ValueError(
                f"frame_ring storage needs [H, W, stack] pixel obs, "
                f"got {spec.obs_shape}; set replay.storage='flat' for "
                f"vector observations")
        item_spec = frame_segment_spec(
            cfg.replay.seg_transitions, cfg.learner.n_step,
            spec.obs_shape, spec.obs_dtype)
        return FamilySetup(params, item_spec, True,
                           max(cfg.replay.segs_per_add, 1),
                           cfg.replay.seg_transitions)
    item_spec = transition_item_spec(spec.obs_shape, spec.obs_dtype)
    return FamilySetup(params, item_spec, False,
                       max(cfg.actors.ingest_batch, 1), 1)
