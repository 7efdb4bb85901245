"""Model-family dispatch shared by the drivers and remote actor hosts.

A RunConfig's network kind selects one of four runtime families —
flat-DQN ("dqn"), recurrent R2D2 ("r2d2"), the token-level decoder
Q-network ("decoder_q"), continuous Ape-X DPG ("dpg") — which differ in
the inference-server protocol (plain Q-values vs a query that carries
state vs {a,q} actor-critic), the actor class, the AOT-warmup example,
and what the learner trains on (its loss and how sampled items become
the loss's batch). ApexDriver (runtime/driver.py), MultihostApexDriver,
single_process.py and run_actor_host (runtime/actor_host.py) must agree
on these, so the dispatch lives here once: `build_learner` is the one
place a learner is constructed, and the table behind `learner_family`
the one place a loss is bound.

Two kinds of sequence state. Both sequence families train with
ops/losses.make_r2d2_loss over stored sequences (replay/sequence.py),
and what differs is the state a sequence starts from:

- "r2d2" STORES it: the LSTM's (c, h) from before the first step rides
  with every sequence (`init_c`, `init_h` in the item), the actor's
  query carries it ({obs, c, h} -> {q, c, h}) and the burn-in steps
  only refresh it.
- "decoder_q" stores NONE: a replayed window starts from an empty
  attention cache and its burn-in prefix is its context — the prefix
  pass leaves the net's cache (glm_moe_q: per layer a latent c_kv and
  k_rope; afmoe_q and smallthinker_q: per layer (k, v), every position
  for a full layer and the last window - 1 for a sliding one; ouro_q:
  per (loop step, layer) (k, v); kimi_linear_q: a KDA layer's state
  matrix and convolution tails, THE SAME SIZE HOWEVER LONG THE PREFIX,
  beside an MLA layer's latent row per position; lfm2_moe_q: a conv
  layer's last two rows of its gated input, 8 KiB a sequence however
  long the prefix, beside an attention layer's (k, v) per position),
  the loss stops its
  gradient, the trained steps start from it. The item has no
  state entry at all. For the six older nets the server is stateless
  too: a query carries the last <= L token ids ({obs, ctx, n} -> {q,
  ctx, n}) and the server re-runs the window. The seventh,
  "minicpm_sala_q", is served FROM SLOTS: its state (a lightning
  layer's float32 matrix, a sparse layer's keys, values and compressed
  keys) lives in parallel/inference_server.py between queries, a query
  names its slot ({obs, slot, fresh} -> {q, slot, fresh}) and a step
  costs one token. So is the eighth, "jamba_q" (a Mamba layer's float32
  state and conv tail, the same size at any context, beside two
  attention layers' keys and values).

The decoder_q family has eight nets. Five (network.kind "glm_moe_q",
"afmoe_q", "smallthinker_q", "kimi_linear_q", "lfm2_moe_q") share
models/expert_layer.py (the plan and the application of an expert
layer); the second, third and sixth of them and "ouro_q" - a stack of
dense blocks run several times with the same weights, no expert layer -
share models/windowed_gqa.py (the attention call and its cache); the
first and the fifth share models/mla.py (latent attention; the fifth's scores
go through ops/blockwise_attention.py as the windowed nets' do). The
fifth, "kimi_linear_q", is the family's first with a scan layer
(ops/chunked_delta_rule.py): a net that has one also reports
`kda_chunks` and `kda_state_rms` in its stats, and the family's loss
hands them on (`_routed_loss`). The sixth, "lfm2_moe_q", is the first
whose mixer is a gated short convolution (models/short_conv.py's
filter, which the fifth calls ahead of its scan) and the first whose
head is its embedding (models/q_head.py's read over [A, hidden]): a
net with conv layers reports `conv_positions`, handed on the same way.
A further decoder registers with: its net in models/ with the surface
the family reads (`init`, `apply`, `apply_with_stats`, `param_count`,
`step_transient_bytes`, `num_actions`; a net whose loss may read the
head by column ALSO OFFERS `head_at(params, x, ids)` over the
`stats["head_input"]` its `apply_with_stats` hands back beside Q,
models/q_head.py, and a net that does not offer it keeps the dense
read; a net WITH an expert layer also
`router_trains`, `share` and the stats `expert_rows` and `topk`, a net
without one the stats `block_applications` and `exit_gates`: the
family's loss reads expert statistics only from a net that has a
`share`), a config block in NetworkConfig, and ONE row in
models.DECODERS (models/__init__.py says what reads it). No code here
names a decoder.

A decoder WITH A SLOT STATE registers the same way and OFFERS five
more things, which `keeps_slots` finds by name on the net and never by
the net's name: `slot_state(slots, pool_tokens, max_len)` (the zeroed
device pytree), `slot_state_bytes(...)` (its price, for `hbm_price`),
`extend(params, slot_state, inputs, max_len=)` (one dispatch: a decode
step or a prefill chunk; its outputs are `q`, `counters` and, from a net
that selects, `sel`), `slot_block` (the unit the host's ledger,
parallel/slot_pool.py, hands out: 1 for a net whose state has no
blocks; a net's state may hold no blocks at all for MOST layers -
models/jamba_q.py's Mamba layers keep a row a slot, and only its two
attention layers' pools are handed out by the ledger) and
`slot_lengths(slot_state)` (the positions each session holds, for
whoever audits the state). `actor_state`,
`episode_state`, `server_apply_fn` and `server_slots` then give its
actors the `{slot, fresh}` row and its server the slot path; every
construction site passes `**server_slots(cfg, net)`.

How a further Q-learning family registers: its net in models/ with a
row in `build_network`; its kind in `family_of`; a row in
`_LEARNER_FAMILIES` binding a loss from ops/losses.py and an items ->
batch function; a row in `ACTOR_STATE` (what a query carries beside
the observation, and which of it is stored with a sequence) if it is a
sequence family; its branch of `family_setup` (params, item spec,
staging unit) and of `server_apply_fn`. No edit to a learner or a
driver.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, NamedTuple

import jax.numpy as jnp
import numpy as np

from ape_x_dqn_tpu.configs import RunConfig
from ape_x_dqn_tpu.models import DECODER_NETS
from ape_x_dqn_tpu.models.base import dtype_of
from ape_x_dqn_tpu.replay.frame_ring import (frame_ring_mode,
                                             frame_segment_spec)
from ape_x_dqn_tpu.replay.sequence import (sequence_frame_mode,
                                           sequence_item_spec)
from ape_x_dqn_tpu.runtime.actor import (
    Actor, ContinuousActor, RecurrentActor)
from ape_x_dqn_tpu.utils.rng import component_key


def family_of(cfg: RunConfig) -> str:
    kind = cfg.network.kind
    if kind in DECODER_NETS:
        return "decoder_q"
    return {"lstm_q": "r2d2", "dpg": "dpg"}.get(kind, "dqn")


# families whose replay items are whole sequences (the staging unit is
# a sequence, the loss is make_r2d2_loss)
SEQUENCE_FAMILIES = ("r2d2", "decoder_q")


class ActorState(NamedTuple):
    """What a sequence family's query carries beside the observation.
    `zeros(cfg)` -> {name: array} at an episode's start (no batch dim);
    every name is sent with a query and read back from its reply.
    `stored` names the entries kept with a replayed sequence, as
    `init_<name>` (replay/sequence.py)."""
    zeros: Callable
    stored: tuple[str, ...]


def _lstm_state(cfg: RunConfig) -> dict:
    z = np.zeros(cfg.network.lstm_size, np.float32)
    return {"c": z, "h": z.copy()}


def _token_window(cfg: RunConfig) -> dict:
    return {"ctx": np.zeros(cfg.replay.seq_length, np.int32),
            "n": np.int32(0)}


def _slot(cfg: RunConfig) -> dict:
    # the slot is the env's (`episode_state`); `fresh` says that an
    # episode begins, and the server answers 0
    return {"slot": np.int32(0), "fresh": np.int32(1)}


ACTOR_STATE = {
    "r2d2": ActorState(_lstm_state, ("c", "h")),
    "decoder_q": ActorState(_token_window, ()),
    # a decoder the server keeps in slots (`keeps_slots`): nothing rides
    # the query but the session's name
    "decoder_q.slots": ActorState(_slot, ()),
}


def keeps_slots(net_or_cfg: Any) -> bool:
    """Whether the inference server keeps this net's per-session state
    in slots on the device: the net OFFERS it (`slot_state` and
    `extend`: models/minicpm_sala_q.py), nothing here names a net.
    Takes the net, its class, or a RunConfig."""
    if isinstance(net_or_cfg, RunConfig):
        net_or_cfg = DECODER_NETS.get(net_or_cfg.network.kind)
    return hasattr(net_or_cfg, "slot_state")


def actor_state(cfg: RunConfig) -> ActorState:
    """`ACTOR_STATE`'s row for cfg: the family's, or the slot row for a
    net the server keeps in slots."""
    family = family_of(cfg)
    return ACTOR_STATE[family + ".slots" if keeps_slots(cfg) else family]


def episode_state(cfg: RunConfig, index: int) -> dict:
    """What the env of global index `index` (actor_index x
    envs_per_actor + j; the eval worker's is the fleet's size) sends
    with an episode's first query: the row's zeros, and where the state
    lives in the server, the env's own slot."""
    state = actor_state(cfg).zeros(cfg)
    if "slot" in state:
        state["slot"] = np.int32(index)
    return state


def slot_geometry(cfg: RunConfig, block: int = 1) -> tuple[int, int, int]:
    """-> (slots, the longest session, the pool's positions) of cfg's
    slot server; inference.slots / slot_max_len / slot_pool_tokens say
    them, 0 meaning: a slot an env of the fleet and one for the eval
    worker, an episode and its truncation query, and room for every
    slot's longest session in whole blocks of `block` positions."""
    inf = cfg.inference
    slots = inf.slots or (cfg.actors.num_actors
                          * max(cfg.actors.envs_per_actor, 1) + 1)
    max_len = inf.slot_max_len or cfg.env.max_episode_frames + 1
    return slots, max_len, (inf.slot_pool_tokens
                            or slots * -(-max_len // block) * block)


def server_slots(cfg: RunConfig, net: Any) -> dict:
    """The keyword BatchedInferenceServer takes beside the family's
    `server_apply_fn`: `slots=` for a net the server keeps in slots
    (the ledger, the zeroed device state, the prefill bucket), nothing
    for any other."""
    if not keeps_slots(net):
        return {}
    from ape_x_dqn_tpu.parallel.inference_server import Slots
    from ape_x_dqn_tpu.parallel.slot_pool import SlotPool

    block = net.slot_block
    slots, max_len, pool_tokens = slot_geometry(cfg, block)
    return {"slots": Slots(
        pool=SlotPool(slots, -(-pool_tokens // block), block, max_len),
        state=net.slot_state(slots, pool_tokens, max_len),
        prefill_chunk=cfg.inference.prefill_chunk,
        prefill_rows=cfg.inference.prefill_rows)}


def stored_state_spec(family: str, cfg: RunConfig) -> dict:
    """{name: shape} of the state entries a stored sequence carries."""
    st = actor_state(cfg)
    zeros = st.zeros(cfg)
    return {k: zeros[k].shape for k in st.stored}


def hbm_price(cfg: RunConfig, net: Any) -> dict:
    """What cfg's family tells utils/hbm.check_hbm_fits beside the
    parameter count: the float32 values of state a sequence stores,
    and, from a net whose learner state fills the chip, what a train
    step holds beside that state (the decoder's own
    `step_transient_bytes`; every other net's step sits inside the flat
    headroom)."""
    family = family_of(cfg)
    price = {}
    if family in ACTOR_STATE:
        price["stored_state_floats"] = sum(
            math.prod(s) for s in stored_state_spec(family, cfg).values())
    if keeps_slots(net):
        # what the server holds between queries, as the net prices it
        slots, max_len, pool_tokens = slot_geometry(cfg, net.slot_block)
        price["slot_state"] = net.slot_state_bytes(slots, pool_tokens,
                                                   max_len)
    if hasattr(net, "step_transient_bytes"):
        price["step_transient"] = net.step_transient_bytes(
            cfg.learner.batch_size,
            cfg.replay.seq_length - cfg.replay.burn_in)
    if hasattr(net, "sequence_state_bytes"):
        # what the burn-in leaves, online and target net: for a net
        # whose state does not grow with the prefix (a scan layer's
        # matrix, a conv layer's two rows) the net says so itself; the
        # attention caches of the older nets sit inside their
        # `step_transient_bytes` anchors
        price["step_transient"] += 2 * net.sequence_state_bytes(
            cfg.learner.batch_size, cfg.replay.burn_in)
    return price


def actor_class(family: str) -> type:
    """The family's actor (runtime/actor.py): K = actors.envs_per_actor
    envs a thread, K >= 1, whose query contract is the server's
    `query_batch`. Both sequence families run the same class: what
    their queries carry is `ACTOR_STATE`'s row."""
    return {"r2d2": RecurrentActor, "decoder_q": RecurrentActor,
            "dpg": ContinuousActor}.get(family, Actor)


def server_apply_fn(family: str, net: Any,
                    cfg: RunConfig | None = None) -> Callable:
    """The batched forward the inference server jits, per family.

    - dqn:  obs [B, ...]          -> q [B, A]
    - r2d2: {obs, c, h}           -> {q, c, h}   (stateful step)
    - decoder_q: {obs, ctx, n}    -> {q, ctx, n}: STATELESS — `ctx`
      [B, L] holds the last n <= L token ids before `obs`; the server
      appends `obs` (dropping the oldest id of a full window), re-runs
      the whole window from an empty cache and answers with the
      Q-values at the last position. Fixed shapes, so one compiled
      graph; the cost is a window per step. The six older nets keep
      this protocol (their five kinds of state in slots are ROADMAP
      R2.1's).
    - decoder_q, a net that `keeps_slots` (`cfg` required):
      (params, state, {obs, slot, base, fresh[, n_valid]}) ->
      ({q[, sel], counters, slot, fresh}, state) — the net's own `extend`
      over the slot state the server donates from one dispatch to the
      next; a step costs one token. `slot` comes back as sent and
      `fresh` as 0, so an actor's state round-trips like any other.
      `server_slots(cfg, net)` makes the state and the ledger that go
      with it.
    - dpg:  obs [B, ...]          -> {a: mu(s), q: Q(s, mu(s))}
      (params are the {actor, critic} dict publish_params produces)
    """
    if family == "decoder_q" and keeps_slots(net):
        max_len = slot_geometry(cfg)[1]

        def apply_slots(p, state, inp):
            out, state = net.extend(p, state, inp, max_len=max_len)
            return {**out, "slot": inp["slot"],
                    "fresh": jnp.zeros_like(inp["fresh"])}, state
        return apply_slots
    if family == "decoder_q":
        def apply_window(p, inp):
            ctx, n = inp["ctx"], inp["n"]
            length = ctx.shape[1]
            full = (n >= length)[:, None]
            ctx = jnp.where(full, jnp.roll(ctx, -1, axis=1), ctx)
            at = jnp.minimum(n, length - 1)
            ctx = jnp.where(jnp.arange(length)[None, :] == at[:, None],
                            inp["obs"][:, None].astype(ctx.dtype), ctx)
            q, _ = net.apply(p, ctx, ())
            q = jnp.take_along_axis(q, at[:, None, None], axis=1)[:, 0]
            return {"q": q, "ctx": ctx, "n": at + 1}
        return apply_window
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


def has_scan_layer(net: Any) -> bool:
    """Whether `net` has a chunked-scan layer and reports its two
    counters (`kda_chunks`, `kda_state_rms` in its stats:
    models/kimi_linear_q.py)."""
    return getattr(net, "num_kda_layers", 0) > 0


def has_conv_layer(net: Any) -> bool:
    """Whether `net` has a short-convolution mixer and reports
    `conv_positions` in its stats (models/lfm2_moe_q.py)."""
    return getattr(net, "num_conv_layers", 0) > 0


def reads_by_column(net: Any) -> bool:
    """Whether `net` offers the head's column read (`head_at` over the
    `head_input` in its stats: models/q_head.py)."""
    return hasattr(net, "head_at")


def _routed_loss(net: Any, r2d2: Callable) -> Callable:
    """decoder_q_family's loss for a net WITH an expert layer (its
    docstring says what the counters are). `r2d2(apply[, reader])` ->
    the R2D2 sequence loss over `apply`, reading Q through `reader`
    (ops/losses.dense_read where none is given)."""
    from ape_x_dqn_tpu.models.expert_layer import capacity, fits
    from ape_x_dqn_tpu.ops.losses import column_read

    scan_layer, conv_layer = has_scan_layer(net), has_conv_layer(net)
    by_column = reads_by_column(net)

    def loss_fn(params, target_params, batch, is_weights):
        tally, fitted, seen, scans, columns, convs = [], [], [], [], [], []

        def apply(p, tokens, state):
            q, state, stats = net.apply_with_stats(p, tokens, state)
            if scan_layer:
                scans.append((stats["kda_chunks"], stats["kda_state_rms"]))
            if conv_layer:
                convs.append(stats["conv_positions"])
            tally.append(stats["expert_rows"].astype(jnp.float32))
            fitted.append(fits(stats["expert_rows"],
                               capacity(net.share, tokens.size)))
            seen.append((q, stats["topk"]))
            return ((q, stats["head_input"]) if by_column else q), state

        def head_at(p, x, ids):
            columns.append(ids.size)
            return net.head_at(p, x, ids)

        loss_of = (r2d2(apply, partial(column_read, head_at)) if by_column
                   else r2d2(apply))
        loss, aux = loss_of(params, target_params, batch, is_weights)
        # the loss applies: online burn-in, target burn-in (when
        # burn_in > 0), then online and target over the trained steps
        online = tally[0::2]
        load = sum(online)                          # [layers, held]
        mean = jnp.maximum(load.mean(axis=-1), 1e-9)
        aux = {**aux,
               "moe_rows": sum(t.sum() for t in tally),
               "moe_rows_grad": online[-1].sum(),
               "moe_load_max_over_mean": (
                   (load.max(axis=-1) / mean).mean() if load.size
                   else jnp.float32(1.0)),
               "moe_compact_share": (
                   jnp.concatenate(fitted).mean(dtype=jnp.float32)
                   if load.size else jnp.float32(1.0)),
               "q": seen[-2][0],
               # prefix then trained steps, along the sequence
               "topk_online": jnp.concatenate(
                   [t for _, t in seen[0::2]], axis=2),
               "topk_target": jnp.concatenate(
                   [t for _, t in seen[1::2]], axis=2)}
        if scan_layer:
            # the online net's forward: prefix, then trained steps
            aux["kda_chunks"] = sum(
                n for n, _ in scans[0::2]).astype(jnp.float32)
            aux["kda_state_rms_last"] = scans[-2][1]
        if conv_layer:
            aux["conv_positions"] = sum(convs[0::2]).astype(jnp.float32)
        if by_column:
            aux["head_columns"] = jnp.float32(sum(columns))
        return loss, aux

    return loss_fn


def _looped_loss(net: Any, r2d2: Callable) -> Callable:
    """decoder_q_family's loss for a net WITHOUT an expert layer, whose
    blocks are applied several times a forward pass (models/ouro_q.py):
    `loop_block_applications` the blocks applied per net forward (loop
    steps x layers, counted where they are applied; the online net's
    pass over the trained steps), `loop_exit_mass_last` the mean over
    the trained tokens of the exit distribution's mass on the last loop
    step, the product over the earlier steps of (1 - lambda_t): the one
    place the net's exit gate is seen. `q` as for a routed net."""

    def loss_fn(params, target_params, batch, is_weights):
        seen = []

        def apply(p, tokens, state):
            q, state, stats = net.apply_with_stats(p, tokens, state)
            seen.append((q, stats))
            return q, state

        loss, aux = r2d2(apply)(params, target_params, batch, is_weights)
        # the online net over the trained steps: the last but one
        q, stats = seen[-2]
        stay = 1.0 - stats["exit_gates"][:-1]       # [steps - 1, B, T]
        return loss, {
            **aux,
            "loop_block_applications": stats["block_applications"].astype(
                jnp.float32),
            "loop_exit_mass_last": jnp.prod(stay, axis=0).mean(),
            "q": q}

    return loss_fn


def decoder_q_family(net: Any, lcfg, rcfg):
    """Token-level Q-learning on a decoder: make_r2d2_loss as it
    stands, over stored token sequences with no stored state — the
    loss's burn-in is the prefix pass that leaves the net's latent
    cache (models/glm_moe_q.py). HOW THE LOSS READS THE HEAD is the
    net's to offer, as its transient bytes and its scan's counters are:
    a routed net with `head_at` (`reads_by_column`: AfmoeQNet,
    SmallThinkerQNet, and Lfm2MoeQNet over its tied head) gets
    losses.column_read — the target net's
    bootstrap and the online net's Q(s, a) are one column of `lm_head`
    a token, the online net's whole slice stays without gradient for
    the argmax and `aux["q"]`, of a step's four [tokens, hidden] x
    [hidden, A] products one is left and the loss holds ONE float32
    [tokens, A] array (compiled for a described v5e, `train_many(2)`'s
    temp: 4.29 GiB in `trinity_mini_offline`, 3.32 in
    `smallthinker_offline`; PERF.md section 6, PR 49) — and its aux
    gains `head_columns`, the columns read a step, online + target:
    2 x trained tokens under `double_dqn`, 1 x without it (the target's
    whole slice stays then, a max needs it). A net without `head_at`
    (GlmMoeQNet, OuroQNet, KimiLinearQNet; ROADMAP S5.9 says what each
    waits for) gets losses.dense_read, three such arrays, the program
    it had, and no `head_columns` (`_looped_loss` knows no other read).
    tests/test_decoder_head_columns.py holds the column read to the
    dense form. The net also says how its layers were
    used; the loss's signature has no room for that, so each of its
    four net applications leaves its statistics in a list the family's
    loss reads back inside the same trace. WHICH statistics depends on
    what the net has: expert statistics are read only from a net with
    an expert layer (it has a `share`: `_routed_loss`), a net without
    one reports its loop (`_looped_loss`).

    A routed net's:
    `moe_rows` rows routed to the experts held here, summed over the
    layers and the four applications (one forward each); `moe_rows_grad`
    those of the online net's trained steps, which also pay a
    recomputation and a backward; `moe_load_max_over_mean` the fullest
    held expert over the mean, online net, mean over layers;
    `moe_compact_share` of the step's expert-layer applications (layers
    x the loss's net applications) the share whose rows fit the layer's
    buffers (`expert_layer.capacity`; 1.0 where that is the full
    width): under 1.0 a step paid the full width somewhere. The same
    way the aux hands back what the loss saw and chose — `q` the online
    net's Q-values on the trained steps, `topk_online`/`topk_target`
    [expert layers, B, L, k] the experts selected — for whoever
    differentiates this function to hold it to a reference (the
    benchmark's check); a train step reads none of the three and XLA
    drops them there. A routed net WITH A SCAN LAYER (its stats have
    `kda_chunks`: models/kimi_linear_q.py) adds `kda_chunks`, the chunks
    the delta rule's scan walked in the online net's forward pass
    (prefix and trained steps, summed over the KDA layers in the scan's
    own carry: KDA layers x positions / chunk), and
    `kda_state_rms_last`, the RMS of the state matrices after the last
    trained position (mean over the KDA layers): the one place a state
    that blew up or died is seen. A routed net WITH CONV LAYERS (its
    stats have `conv_positions`: models/lfm2_moe_q.py) adds
    `conv_positions`, the positions that passed a conv operator in the
    online net's forward pass (prefix and trained steps, summed where
    the operator runs: conv layers x sequence length x batch)."""
    from ape_x_dqn_tpu.ops.losses import (
        SequenceBatch, dense_read, make_r2d2_loss)
    from ape_x_dqn_tpu.runtime.learner import LearnerFamily

    def r2d2(apply, reader=dense_read):
        return make_r2d2_loss(
            apply, burn_in=rcfg.burn_in, n_step=lcfg.n_step,
            gamma=lcfg.gamma, huber_delta=lcfg.huber_delta,
            double=lcfg.double_dqn, rescale=lcfg.value_rescale,
            priority_eta=rcfg.priority_eta, reader=reader)

    routed = hasattr(net, "share")
    return LearnerFamily(
        name="decoder_q",
        loss_fn=(_routed_loss if routed else _looped_loss)(net, r2d2),
        make_batch=lambda items: SequenceBatch(
            obs=items["obs"], actions=items["actions"],
            rewards=items["rewards"], terminals=items["terminals"],
            mask=items["mask"], init_state=()),
        net_apply=net.apply,
        apply_attr="net_apply_seq",
        metric_keys=(("valid_frac", "moe_rows", "moe_rows_grad",
                      "moe_load_max_over_mean", "moe_compact_share")
                     + (("kda_chunks", "kda_state_rms_last")
                        if has_scan_layer(net) else ())
                     + (("conv_positions",) if has_conv_layer(net) else ())
                     + (("head_columns",) if reads_by_column(net) else ())
                     if routed else
                     ("valid_frac", "loop_block_applications",
                      "loop_exit_mass_last")))


# family name -> (cfg, net) -> LearnerFamily. DPG is not a row: its
# learner (two nets, two optimizers, soft targets) is its own class.
_LEARNER_FAMILIES = {
    "dqn": lambda cfg, net: dqn_family(net.apply, cfg.learner),
    "r2d2": lambda cfg, net: r2d2_family(
        net.apply, cfg.learner, cfg.replay,
        compute_dtype=dtype_of(cfg.network.compute_dtype)),
    "decoder_q": lambda cfg, net: decoder_q_family(
        net, cfg.learner, cfg.replay),
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
    if family in ACTOR_STATE:
        return {"obs": obs, **actor_state(cfg).zeros(cfg)}
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
    if family == "decoder_q":
        if spec.num_actions != net.num_actions or spec.obs_shape != ():
            from ape_x_dqn_tpu.models import decoder_block

            block = decoder_block(cfg.network)[0]
            raise ValueError(
                f"the decoder holds {net.num_actions} vocabulary rows "
                f"(network.{block}.vocab_size / shard_count) but the "
                f"environment has {spec.num_actions} actions over "
                f"observations {spec.obs_shape}: set env.num_tokens="
                f"{net.num_actions} on a synthetic_tokens environment")
        if cfg.replay.storage == "frame_ring":
            raise ValueError(
                "frame_ring storage is for pixel observations; a token "
                "sequence is stored flat (replay.storage='flat')")
        from ape_x_dqn_tpu.utils.hbm import check_hbm_fits

        # before a single weight is made: a published model whole is
        # 100 GB of float32 and should fail with a budget table
        check_hbm_fits(cfg, spec.obs_shape, spec.obs_dtype,
                       param_count=net.param_count(),
                       **hbm_price(cfg, net))
        params = net.init(component_key(cfg.seed, "net_init"))
        item_spec = sequence_item_spec(
            spec.obs_shape, spec.obs_dtype, cfg.replay.seq_length,
            stored_state_spec(family, cfg))
        return FamilySetup(
            params, item_spec, False,
            max(cfg.actors.ingest_batch // cfg.replay.seq_length, 1), 1)
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
            stored_state_spec(family, cfg), frame_mode=seq_frame_mode)
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
