"""The cycle's own parts carry device-side names (ISSUE 35):
`runtime/learner.py::CYCLE_SCOPES`, seven `jax.named_scope`s that tile a
grad step, and `ops/sum_tree.py`'s two nested in the first and the last.

(a) `train_many`'s lowered text WITH debug info holds every name, the
    descent only under `cycle.sample` and the tree update only under
    `cycle.write_back`, for every family, the sharded learner and DPG;
(b) WITHOUT debug info the text is pinned by SHA-256 (`_lowered`
    below): a scope is op metadata and moves no arithmetic. ISSUE 36
    changed `sum_tree.update` and with it all six programs by design:
    the hashes are re-pinned from that PR's tree (the commit after
    fc151c0), and with `sum_tree.dense_levels` held at 0, the
    all-indexed walk, every program is still PR 35's (4a42c99) to the
    byte: nothing but the tree's update moved. The dense pass's ops
    carry `sum_tree.update` in their name stacks, so the reader of
    `replay.write_back_share` keeps seeing them. ISSUE 42 moved `r2d2`
    alone, by design (the packed store's rows are words, gathered a
    chunk at a time, and the stack rebuild reads them): both its
    hashes are re-pinned from that PR's tree, the second still with no
    dense level; the other seven programs, which run no packed store
    or run it at K = 1, are PR 41's to the byte. ISSUE 44 moved
    `ouro_tiny_q` alone, by design (a group of one: the blockwise
    attention's backward pass as a dq nest and a dk/dv nest); the
    groups of 2 and 7 of `trinity_tiny_q` and `smallthinker_tiny_q`
    keep the one nest and their text. ISSUE 46 added
    `kimi_linear_tiny_q` and moved none of the eight: GLM's attention
    is models/mla.py's now, and ops/blockwise_attention.py takes values
    of another head size than the keys'. ISSUE 47 moved
    `kimi_linear_tiny_q` alone, by design (ops/chunked_delta_rule.py:
    a chunk's solve multiplies whole block diagonal matrices, W and U_0
    come from one product and the chunk's walk is two);
    `kda.scan`, `kda.scan.intra` and `kda.scan.carry` are still in its
    name stacks. ISSUE 49 moved `trinity_tiny_q` and
    `smallthinker_tiny_q`, by design, and nothing else: the family's
    loss reads the head by column for the two nets that offer `head_at`
    (models/q_head.py, ops/losses.column_read), so those two hashes are
    re-pinned from that PR's tree, both columns; `pong`, `r2d2`,
    `dist`, `apex_dpg`, `glm_tiny_q`, `ouro_tiny_q` and
    `kimi_linear_tiny_q` passed UNCHANGED, which was the proof that
    the other seven cells ran the parent's program. ISSUE 50 added
    `lfm2_tiny_q` and moved none of the ten: `kimi_linear_tiny_q`'s
    filter is models/short_conv.py's now, and the two column-reading
    nets' read is one of models/q_head.py's two (the other reads a
    head that is the embedding). ISSUE 51 moved `pong`, `r2d2`, `dist`
    and `apex_dpg`, by design, and nothing else: `sum_tree.sample`
    reads the top of the tree densely where a draw is a row of lanes
    or more (`dense_descent_levels`), so those four first hashes are
    re-pinned from that PR's tree; the six `*_tiny_q` programs draw 4
    sequences a step, keep the indexed walk and pass UNCHANGED
    (UNMOVED_BY_ISSUE_51), which is the proof that the six decoder
    cells run the parent's program. The dense levels' ops carry
    `sum_tree.descent` in their name stacks, so `replay.sample_share`
    keeps seeing them, and with BOTH rules held at 0 every program is
    still the parent's to the byte (the second hashes, untouched).
    ISSUE 54 moved `kimi_linear_tiny_q` alone, by design
    (ops/chunked_delta_rule.py: the chunk's backward pass is a rule of
    its own, a `jax.custom_vjp`, where autodiff ran under a
    `jax.checkpoint`): both its hashes are re-pinned from that PR's
    tree (they are the ones PR 53, the same change refused for a file it
    added to the benchmark, had read), and its name stacks now hold `kda.scan.back` under `kda.scan`
    beside the forward's two; the other TEN programs passed UNCHANGED,
    which is the proof that the nine other cells run the parent's
    program (nothing but Kimi's net reaches the file);
(c) no endpoint bypasses a scope: `train_step`, `train_step_k`,
    `sample_k` + `learn_k` and the prefetching `train_many` open the
    same names, on one chip and on the mesh.
"""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.configs import (
    LearnerConfig, NetworkConfig, ParallelConfig, RunConfig, get_config)
from ape_x_dqn_tpu.envs.base import EnvSpec
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.ops import chunked_delta_rule, sum_tree
from ape_x_dqn_tpu.parallel.dist_learner import DistLearner
from ape_x_dqn_tpu.parallel.mesh import make_mesh
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.runtime.family import learner_family
from ape_x_dqn_tpu.runtime.learner import (
    BATCH, CYCLE_SCOPES, SAMPLE, WRITE_BACK, SingleChipLearner,
    transition_item_spec)
from ape_x_dqn_tpu.runtime.train import apply_overrides

# case -> (preset, overrides, n of train_many, SHA-256[:16] of the text,
# the same with no dense level: PR 35's).
# RELABELS: the family's `make_batch` renames the items' fields and the
# preset has K = 1, so `cycle.batch` is opened around no op
PROGRAMS = {
    # moved by ISSUE 51, with `r2d2`, `dist` and `apex_dpg` and nothing
    # else: 2,048 / 256 / 1,024 a shard / 256 draws a cycle read the top
    # of the tree densely (PR 50's first hashes: ef63e79f3fa20d68,
    # 6c63d1b14997ec19, 8edfe2412a4bc64f, 836445fb85177e4e)
    "pong": ("pong", ["replay.capacity=4096", "replay.min_fill=512"], 8,
             "e75393e30b36dc88", "fa002ec06af372f3"),
    "r2d2": ("r2d2", ["parallel.dp=1", "parallel.tp=1",
                      "replay.capacity=64", "replay.min_fill=8"], 8,
             "1b798c4e84fed946", "96e219598457572d"),
    "glm_tiny_q": ("glm_tiny_q", ["replay.capacity=64"], 2,
                   "a88f0e52e73150b6", "dfb4d0171f649268"),
    # moved by ISSUE 49, with `smallthinker_tiny_q` and nothing else: the
    # loss reads these two nets' head by column (PR 47's: f80b0f6ac1ea7140)
    "trinity_tiny_q": ("trinity_tiny_q", ["replay.capacity=64"], 2,
                       "082554aa406ffca0", "3c5a52828b08e728"),
    # the decoder family's third net, pinned at the PR that added it (ISSUE
    # 39); its second hash is the same tree's with no dense level; moved by
    # ISSUE 49 (PR 47's: 6d3781211705c6a0)
    "smallthinker_tiny_q": ("smallthinker_tiny_q", ["replay.capacity=64"],
                            2, "85c7f305db804121", "13b93cf6b8bd527e"),
    # the family's fourth net, the one without experts, pinned at the PR
    # that added it (ISSUE 41) beside the three that must not move; moved
    # by ISSUE 44 and by nothing else: at its group of one the blockwise
    # attention's backward pass runs as two loop nests (with the rule's
    # constant at 0 the text is PR 41's 12a333f806383e44 again)
    "ouro_tiny_q": ("ouro_tiny_q", ["replay.capacity=64"], 2,
                    "574b3f311835f9a8", "bfb0aedd29a1ffac"),
    # the family's fifth net, the first with a scan layer (the chunked
    # delta rule) and the second through models/mla.py, pinned at the PR
    # that added it (ISSUE 46) beside the eight that must not move: GLM's
    # through the moved MLA, `ouro_tiny_q`'s and the two one-nest nets'
    # through the blockwise attention's value head size
    # (moved by ISSUE 47 and by ISSUE 54, each time alone and through
    # ops/chunked_delta_rule.py; PR 52's: f2232d0ad15b7a42,
    # c5d7cc95256d237b)
    "kimi_linear_tiny_q": ("kimi_linear_tiny_q", ["replay.capacity=64"], 2,
                           "80df8af98cc14ae8", "b4eaea1f0904ba67"),
    # the family's sixth net, the first with a convolution mixer and a
    # head that is its embedding, pinned at the PR that added it (ISSUE
    # 50) beside the ten that must not move: Kimi's through the shared
    # filter, Trinity's and SmallThinker's through models/q_head.py
    "lfm2_tiny_q": ("lfm2_tiny_q", ["replay.capacity=64"], 2,
                    "2072e7df22e882b4", "f11bd9b1f01519e7"),
    "dist": ("pong", ["parallel.dp=2", "parallel.tp=1",
                      "replay.capacity=4096", "replay.min_fill=512"], 8,
             "f0a2402ba816b811", "6668f8be4d7f2de8"),
    "apex_dpg": ("apex_dpg", ["replay.capacity=4096",
                              "replay.min_fill=512"], 8,
                 "e1c130ce8b3309f6", "a376acdbc487b640"),
}
# the programs ISSUE 51 must not move: a draw of under a row of lanes
# (these presets draw 4 sequences a step) keeps the indexed descent
UNMOVED_BY_ISSUE_51 = ("glm_tiny_q", "trinity_tiny_q", "smallthinker_tiny_q",
                       "ouro_tiny_q", "kimi_linear_tiny_q", "lfm2_tiny_q")
RELABELS = ("glm_tiny_q", "trinity_tiny_q", "smallthinker_tiny_q",
            "ouro_tiny_q", "kimi_linear_tiny_q", "lfm2_tiny_q", "apex_dpg")
QUIET = ["actors.num_actors=0", "eval_episodes=0", "eval_every_steps=0"]


@functools.cache
def _lowered(case: str, dense_top: bool = True) -> tuple[str, str]:
    """-> `train_many`'s lowered text (without, with debug info) of the
    learner `ApexDriver` builds for the case; without `dense_top` every
    `sum_tree.update` and every `sum_tree.sample` in it walks all its
    levels by index."""
    from ape_x_dqn_tpu.runtime.driver import ApexDriver

    preset, overrides, n = PROGRAMS[case][:3]
    with pytest.MonkeyPatch.context() as patch:
        if not dense_top:
            patch.setattr(sum_tree, "dense_levels", lambda capacity, n: 0)
            patch.setattr(sum_tree, "dense_descent_levels",
                          lambda capacity, n: 0)
        driver = ApexDriver(apply_overrides(get_config(preset),
                                            overrides + QUIET))
        try:
            low = type(driver.learner).train_many.lower(
                driver.learner, driver.state, n)
            return low.as_text(), low.as_text(debug_info=True)
        finally:
            driver.server.stop()


def _name_stacks(debug_text: str) -> set[str]:
    return set(re.findall(r'loc\("([^"]+)"', debug_text))


def _scopes_in(debug_text: str) -> set[str]:
    stacks = _name_stacks(debug_text)
    return {s for s in CYCLE_SCOPES if any(s in x for x in stacks)}


def _assert_tree_passes_nest(debug_text: str) -> None:
    for stack in _name_stacks(debug_text):
        if sum_tree.DESCENT_SCOPE in stack:
            assert SAMPLE in stack.split(sum_tree.DESCENT_SCOPE)[0], stack
        if sum_tree.UPDATE_SCOPE in stack:
            assert WRITE_BACK in stack.split(sum_tree.UPDATE_SCOPE)[0], \
                stack


def test_the_names_are_seven_disjoint_ones():
    assert len(set(CYCLE_SCOPES)) == 7
    every = CYCLE_SCOPES + (sum_tree.DESCENT_SCOPE, sum_tree.UPDATE_SCOPE)
    # the reader asks "is the name in the op's stack": none may be a
    # substring of another
    for a in every:
        assert not [b for b in every if a != b and a in b], a


@pytest.mark.parametrize("case", list(PROGRAMS))
def test_train_many_names_every_part_of_the_cycle(case):
    _, debug = _lowered(case)
    assert _scopes_in(debug) == set(CYCLE_SCOPES) - (
        {BATCH} if case in RELABELS else set())
    stacks = _name_stacks(debug)
    assert any(sum_tree.DESCENT_SCOPE in s for s in stacks)
    assert any(sum_tree.UPDATE_SCOPE in s for s in stacks)
    # the dense top's ops are the update's: `replay.write_back_share`
    # reads them by this name (the prefix's write is a
    # dynamic_update_slice, and a one-window scatter under `vmap`)
    assert {"reduce_window_sum", "concatenate"} <= {
        s.rsplit("/", 1)[-1] for s in stacks if sum_tree.UPDATE_SCOPE in s}
    # (the descent lays its left children out with the same pair sums)
    assert not [s for s in stacks if s.endswith("/reduce_window_sum")
                and sum_tree.UPDATE_SCOPE not in s
                and sum_tree.DESCENT_SCOPE not in s]
    # the descent's dense levels are the descent's: a select summed over
    # a level's nodes, where the draw is a row of lanes or more
    descent = {s.rsplit("/", 1)[-1] for s in stacks
               if sum_tree.DESCENT_SCOPE in s}
    assert ("reduce_sum" in descent) == (case not in UNMOVED_BY_ISSUE_51)
    _assert_tree_passes_nest(debug)


@pytest.mark.parametrize("case", list(PROGRAMS))
def test_the_program_is_the_parents_to_the_byte(case):
    text, _ = _lowered(case)
    assert not any(s in text for s in CYCLE_SCOPES)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PROGRAMS[case][3], (
            "ISSUE 54 moved this program alone (the delta rule's backward "
            "pass), re-pinned there" if case == "kimi_linear_tiny_q" else
            "ISSUE 51 moved the four programs whose draw is a row of lanes "
            "or more and must move no other: this pin is PR 50's"
            if case in UNMOVED_BY_ISSUE_51
            else "moved by ISSUE 51 (the descent's dense top), re-pinned "
                 "there")


def test_the_delta_rules_scan_keeps_its_three_scopes():
    # what `learner.kda_scan_share` and PERF.md's split of it read, and
    # (ISSUE 54) the backward rule's own scope with its three parts: a
    # `custom_vjp`'s backward function opens them itself, under
    # `kda.scan`, or its time would fall out of that share
    stacks = _name_stacks(_lowered("kimi_linear_tiny_q")[1])
    for scope in (chunked_delta_rule.SCOPE, chunked_delta_rule.INTRA,
                  chunked_delta_rule.CARRY, chunked_delta_rule.BACK,
                  chunked_delta_rule.BACK_TILE,
                  chunked_delta_rule.BACK_SOLVE,
                  chunked_delta_rule.BACK_CARRY):
        assert any(scope + "/" in s for s in stacks), scope
    back = [s for s in stacks if chunked_delta_rule.BACK in s]
    assert all(chunked_delta_rule.SCOPE + "/" in s for s in back)
    # the scan's products are the forward's two kinds and the backward
    # rule's two, each under its scope: none unscoped, and none a
    # transpose of the forward's (autodiff of the chunk left the program)
    assert {s for s in stacks if chunked_delta_rule.SCOPE in s
            and s.endswith("dot_general")} == {
        f"{scope}/dot_general" for scope in (
            chunked_delta_rule.INTRA, chunked_delta_rule.CARRY,
            "/".join((chunked_delta_rule.SCOPE, chunked_delta_rule.BACK,
                      chunked_delta_rule.BACK_CARRY)),
            "/".join((chunked_delta_rule.SCOPE, chunked_delta_rule.BACK,
                      chunked_delta_rule.BACK_SOLVE)))}


@pytest.mark.parametrize("case", list(PROGRAMS))
def test_only_the_trees_two_passes_moved_since_pr35(case):
    # (and, in `r2d2`, the packed store's rows since ISSUE 42): with no
    # dense level in the update (ISSUE 36) nor in the descent (ISSUE 51)
    # every program is still its parent's to the byte
    text, _ = _lowered(case, dense_top=False)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PROGRAMS[case][4]


# -- (c) every endpoint ---------------------------------------------------

SPEC = EnvSpec(obs_shape=(5,), obs_dtype=np.dtype(np.float32),
               discrete=True, num_actions=3)
DP, N, K = 2, 32, 2


def _learner_and_state(dist: bool, prefetch: bool = False):
    cfg = RunConfig(
        network=NetworkConfig(kind="mlp", mlp_hidden=(24,),
                              compute_dtype="float32"),
        learner=LearnerConfig(batch_size=4, n_step=2, sample_chunk=K,
                              sample_prefetch=prefetch),
        parallel=ParallelConfig(dp=DP if dist else 1, tp=1))
    net = build_network(cfg.network, SPEC)
    params = net.init(jax.random.key(0), jnp.zeros((1, 5)))
    item_spec = transition_item_spec(SPEC.obs_shape, jnp.float32)
    family = learner_family(cfg, net)
    if dist:
        learner = DistLearner(family, PrioritizedReplay(capacity=N // DP),
                              cfg.learner, make_mesh(dp=DP, tp=1))
        return learner, learner.init(params, item_spec, jax.random.key(1))
    replay = PrioritizedReplay(capacity=N)
    learner = SingleChipLearner(family, replay, cfg.learner)
    return learner, learner.init(params, replay.init(item_spec),
                                 jax.random.key(1))


def _debug_text(learner, endpoint: str, *args) -> str:
    return getattr(type(learner), endpoint).lower(
        learner, *args).as_text(debug_info=True)


EVERY = set(CYCLE_SCOPES)


@pytest.mark.parametrize("dist", [False, True], ids=["single", "dist"])
@pytest.mark.parametrize("endpoint", ["train_step", "train_step_k",
                                      "train_many_prefetch"])
def test_a_fused_endpoint_opens_all_seven(endpoint, dist):
    learner, state = _learner_and_state(
        dist, prefetch=endpoint == "train_many_prefetch")
    if endpoint == "train_step":
        debug = _debug_text(learner, endpoint, state)
    elif endpoint == "train_step_k":
        debug = _debug_text(learner, endpoint, state, K)
    else:
        # n = 2K + 1: the remainder single, the prologue draw and the
        # double-buffered scan are all in the program
        debug = _debug_text(learner, "train_many", state, 2 * K + 1)
    # one chip, no K-split: the dqn family's batch is the items renamed
    relabels = endpoint == "train_step" and not dist
    assert _scopes_in(debug) == EVERY - ({BATCH} if relabels else set())
    _assert_tree_passes_nest(debug)


@pytest.mark.parametrize("dist", [False, True], ids=["single", "dist"])
def test_the_split_endpoints_divide_the_names_between_them(dist):
    learner, state = _learner_and_state(dist)
    drawn = _debug_text(learner, "sample_k", state, K)
    assert _scopes_in(drawn) == {SAMPLE}
    sample, rng = jax.eval_shape(
        lambda s: type(learner).sample_k(learner, s, K), state)
    learnt = _debug_text(learner, "learn_k", state, sample, K)
    assert _scopes_in(learnt) == EVERY - {SAMPLE}
    stacks = _name_stacks(learnt)
    assert not any(sum_tree.DESCENT_SCOPE in s for s in stacks)
    _assert_tree_passes_nest(drawn)
    _assert_tree_passes_nest(learnt)
