"""The SGD tail passes over a parameter-sized tree only when the pass has
a consumer (ISSUE 31): the target sync is a `lax.cond`, the health norms
over trees run on the step whose metrics leave the program, and the
gradient's norm is computed once.

(a) against a reference learner that overrides `_sgd_update` with the
    parent's tail (the `where` select, `optax.global_norm(updates)` on
    every step), every endpoint leaves a training state equal to the
    bit and returns the same metrics — `diag["update_ratio"]` alone to
    rtol 1e-6, because the update is rebuilt from Adam's new moments;
(b) the jaxpr of `train_many`'s scan body holds no tree-sized select but
    the clip's, squares and sums the gradient tree only, and hands no
    `cond` an operand that lives only to be one (the materialised
    `updates` that costs 2.2 GiB of temp at 591 M parameters).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.extend.core import Literal

from ape_x_dqn_tpu.configs import (
    LearnerConfig, NetworkConfig, ParallelConfig, ReplayConfig, RunConfig,
    get_config)
from ape_x_dqn_tpu.envs import make_env
from ape_x_dqn_tpu.envs.base import EnvSpec
from ape_x_dqn_tpu.models import build_network
from ape_x_dqn_tpu.obs import learning as learn_obs
from ape_x_dqn_tpu.parallel.dist_learner import DistLearner
from ape_x_dqn_tpu.parallel.mesh import make_mesh
from ape_x_dqn_tpu.replay.prioritized import PrioritizedReplay
from ape_x_dqn_tpu.replay.sequence import sequence_item_spec
from ape_x_dqn_tpu.runtime.family import (
    family_of, learner_family, stored_state_spec)
from ape_x_dqn_tpu.runtime import learner as learner_mod
from ape_x_dqn_tpu.runtime.learner import (
    SingleChipLearner, applied_update, make_optimizer, transition_item_spec)

SPEC = EnvSpec(obs_shape=(5,), obs_dtype=np.dtype(np.float32),
               discrete=True, num_actions=3)
DP, N, SYNC, STEPS = 2, 32, 3, 8


@pytest.fixture(autouse=True)
def _tail_branches(request, monkeypatch):
    """The tail branches from TAIL_BRANCH_MIN_BYTES of parameters on and
    these nets are kilobytes, so a test sets the threshold: 0 (every net
    branches) unless it asks for the shipped one with
    `@pytest.mark.parametrize("tail_min_bytes", ...)`."""
    callspec = getattr(request.node, "callspec", None)
    wanted = callspec.params.get("tail_min_bytes", 0) if callspec else 0
    if wanted is not None:
        monkeypatch.setattr(learner_mod, "TAIL_BRANCH_MIN_BYTES", wanted)


@pytest.fixture(autouse=True)
def _drop_executables():
    """Every case builds its own learners, so nothing compiled is used
    twice; dropping it case by case keeps the XLA CPU client's footprint
    where a long-lived process does not die in `backend_compile`
    (tests/conftest.py does the same per module)."""
    yield
    import gc

    gc.collect()
    jax.clear_caches()


class _ParentTail:
    """`_sgd_update` as the parent commit had it: a select over the whole
    target tree and every norm on every step."""
    heed_flag = False

    def _sgd_update(self, params, target_params, opt_state, step,
                    batch, w, want_tree_diag=True):
        (loss, aux), grads = jax.value_and_grad(
            self.family.loss_fn, has_aux=True)(
            params, target_params, batch, w)
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
            **{key: aux[key] for key in self.family.metric_keys},
            "grad_norm": optax.global_norm(grads),
            "diag": learn_obs.sgd_diag(
                aux, w, grads, updates, params,
                want_tree_diag=want_tree_diag if self.heed_flag else True),
        }
        return params, target_params, opt_state, step, aux["td_abs"], \
            metrics


class _RefSingle(_ParentTail, SingleChipLearner):
    pass


class _TrapSingle(_ParentTail, SingleChipLearner):
    """The trap of ISSUE 31's table: the norms under the flag, but over
    `updates` itself."""
    heed_flag = True


class _RefDist(_ParentTail, DistLearner):
    pass


def _setup(kind: str, k: int, prefetch: bool):
    """-> (cfg, net, params, item_spec, items [N, ...], td [N])."""
    rng = np.random.default_rng(0)
    batch = 4
    lcfg = LearnerConfig(batch_size=batch, n_step=2, sample_chunk=k,
                         sample_prefetch=prefetch, target_sync_every=SYNC,
                         lr=1e-3)
    if kind in ("dqn", "dist_dqn"):
        cfg = RunConfig(
            network=NetworkConfig(kind="mlp", mlp_hidden=(24,),
                                  compute_dtype="float32"),
            learner=lcfg,
            parallel=ParallelConfig(dp=DP if kind == "dist_dqn" else 1,
                                    tp=1))
        net = build_network(cfg.network, SPEC)
        params = net.init(jax.random.key(0), jnp.zeros((1, 5)))
        item_spec = transition_item_spec(SPEC.obs_shape, jnp.float32)
        items = {
            "obs": rng.normal(size=(N, 5)).astype(np.float32),
            "action": rng.integers(0, 3, N).astype(np.int32),
            "reward": rng.normal(size=N).astype(np.float32),
            "next_obs": rng.normal(size=(N, 5)).astype(np.float32),
            "discount": np.full(N, 0.99, np.float32),
        }
    elif kind == "r2d2":
        t, lstm = 6, 8
        cfg = RunConfig(
            network=NetworkConfig(kind="lstm_q", lstm_size=lstm,
                                  torso_dense=16,
                                  compute_dtype="float32"),
            replay=ReplayConfig(kind="sequence", seq_length=t, burn_in=2,
                                seq_overlap=3),
            learner=lcfg)
        net = build_network(cfg.network, SPEC)
        z = jnp.zeros((1, lstm), jnp.float32)
        params = net.init(jax.random.key(0), jnp.zeros((1, t, 5)), (z, z))
        item_spec = sequence_item_spec(SPEC.obs_shape, np.float32, t, lstm)
        items = {
            "obs": rng.normal(size=(N, t, 5)).astype(np.float32),
            "actions": rng.integers(0, 3, (N, t)).astype(np.int32),
            "rewards": rng.normal(size=(N, t)).astype(np.float32),
            "terminals": np.zeros((N, t), np.float32),
            "mask": np.ones((N, t), np.float32),
            "init_c": np.zeros((N, lstm), np.float32),
            "init_h": np.zeros((N, lstm), np.float32),
        }
    else:
        assert kind == "glm_tiny_q"
        cfg = get_config("glm_tiny_q")
        # one dense and one expert layer, 11-step sequences: the tail is
        # the same and the CPU compiles it in half the time
        cfg = cfg.replace(
            network=dataclasses.replace(
                cfg.network, glm=dataclasses.replace(
                    cfg.network.glm, num_hidden_layers=2)),
            replay=dataclasses.replace(cfg.replay, seq_length=11,
                                       seq_overlap=5, burn_in=5),
            learner=dataclasses.replace(
                cfg.learner, batch_size=batch, sample_chunk=k,
                sample_prefetch=prefetch, target_sync_every=SYNC))
        spec = make_env(cfg.env).spec
        net = build_network(cfg.network, spec)
        params = net.init(jax.random.PRNGKey(0))
        t, v = cfg.replay.seq_length, cfg.env.num_tokens
        item_spec = sequence_item_spec(
            spec.obs_shape, spec.obs_dtype, t,
            stored_state_spec(family_of(cfg), cfg))
        items = {
            "obs": rng.integers(0, v, (N, t)).astype(np.int32),
            "actions": rng.integers(0, v, (N, t)).astype(np.int32),
            "rewards": (rng.integers(0, 4, (N, t)) == 0).astype(
                np.float32),
            "terminals": np.zeros((N, t), np.float32),
            "mask": np.ones((N, t), np.float32),
        }
    td = (rng.random(N) + 0.1).astype(np.float32)
    return cfg, net, params, item_spec, items, td


def _learner_and_state(kind: str, k: int, prefetch: bool, ref):
    """`ref`: False the learner as it is, True the parent's tail,
    "trap" the single-chip learner with `updates` under the flag."""
    cfg, net, params, item_spec, items, td = _setup(kind, k, prefetch)
    family = learner_family(cfg, net)
    replay = PrioritizedReplay(capacity=N)
    if kind == "dist_dqn":
        learner = (_RefDist if ref else DistLearner)(
            family, PrioritizedReplay(capacity=N // DP), cfg.learner,
            make_mesh(dp=DP, tp=1))
        state = learner.init(params, item_spec, jax.random.key(1))
        state = learner.add(
            state,
            jax.tree.map(lambda x: x.reshape(DP, N // DP, *x.shape[1:]),
                         items),
            td.reshape(DP, N // DP))
    else:
        cls = {False: SingleChipLearner, True: _RefSingle,
               "trap": _TrapSingle}[ref]
        learner = cls(family, replay, cfg.learner)
        state = learner.init(params, replay.init(item_spec),
                             jax.random.key(1))
        state = learner.add(state, items, td)
    return learner, state


def _bits(x):
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
        x = jax.random.key_data(x)
    return np.asarray(x)


def _assert_same(got, want):
    """Both (state, metrics): every leaf equal to the bit, but
    diag.update_ratio (rebuilt from Adam's moments) to rtol 1e-6."""
    g_paths, g_def = jax.tree_util.tree_flatten_with_path(got)
    w_paths, w_def = jax.tree_util.tree_flatten_with_path(want)
    assert g_def == w_def
    for (path, g), (_, w) in zip(g_paths, w_paths):
        name = jax.tree_util.keystr(path)
        if name.endswith("['update_ratio']"):
            assert float(w) > 0.0
            np.testing.assert_allclose(_bits(g), _bits(w), rtol=1e-6,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(_bits(g), _bits(w),
                                          err_msg=name)


def _copy(tree):
    return jax.tree.map(jnp.copy, tree)


def _train_many(learner, state, k):
    return learner.train_many(state, STEPS)


def _train_step(learner, state, k):
    """Four single steps across a sync (every third); the target is the
    online net to the bit on the syncing step and is not touched on the
    others."""
    target0 = _copy(state.target_params)
    out = None
    for step in range(1, SYNC + 2):
        state, metrics = learner.train_step(state)
        if step == SYNC:
            at_sync = _copy(state.params)
        want = target0 if step < SYNC else at_sync
        jax.tree.map(
            lambda t, w: np.testing.assert_array_equal(_bits(t), _bits(w)),
            state.target_params, want)
        out = (state, metrics) if out is None else \
            (state, jax.tree.map(lambda a, b: a + b, out[1], metrics))
    return out


def _train_step_k(learner, state, k):
    return learner.train_step_k(state, max(k, 2))


def _learn_k(learner, state, k):
    sample, rng = learner.sample_k(state, max(k, 2))
    return learner.learn_k(state._replace(rng=rng), sample, max(k, 2))


ENDPOINTS = {
    # name -> (driver, K, prefetch)
    "train_many_k1": (_train_many, 1, False),
    "train_many_k4": (_train_many, 4, False),
    "train_many_k1_prefetch": (_train_many, 1, True),
    "train_many_k4_prefetch": (_train_many, 4, True),
    # n=8 is two macro-steps at K=4; 7 also runs the remainder singles,
    # whose metrics are dropped whole
    "train_many_k4_remainder": (
        lambda learner, state, k: learner.train_many(state, STEPS - 1),
        4, False),
    "train_step": (_train_step, 1, False),
    "train_step_k": (_train_step_k, 4, False),
    "learn_k": (_learn_k, 4, False),
}


# the remainder path is the cycle's, not a family's: two kinds hold it
CASES = [(kind, endpoint)
         for kind in ("dqn", "r2d2", "glm_tiny_q", "dist_dqn")
         for endpoint in ENDPOINTS
         if endpoint != "train_many_k4_remainder"
         or kind in ("dqn", "dist_dqn")]


# under the shipped threshold these nets keep the select and every
# step's norms: the parent's tail but for the rebuilt update
SMALL = [(kind, endpoint) for kind in ("dqn", "r2d2", "dist_dqn")
         for endpoint in ("train_many_k1", "train_many_k4")]


@pytest.mark.parametrize(
    "kind,endpoint,tail_min_bytes",
    [(*c, 0) for c in CASES] + [(*c, None) for c in SMALL],
    ids=[f"{k}-{e}" for k, e in CASES]
    + [f"{k}-{e}-small" for k, e in SMALL])
def test_tail_equals_the_parents_to_the_bit(kind, endpoint, tail_min_bytes):
    drive, k, prefetch = ENDPOINTS[endpoint]
    got = drive(*_learner_and_state(kind, k, prefetch, ref=False), k)
    want = drive(*_learner_and_state(kind, k, prefetch, ref=True), k)
    assert int(got[0].step) > SYNC          # a sync was crossed
    assert set(got[1]) == set(want[1])
    assert set(got[1]["diag"]) == set(want[1]["diag"])
    _assert_same(got, want)


def test_applied_update_is_the_optimizers_own():
    """The update rebuilt from the NEW optimizer state is the one
    `make_optimizer` returned on that step, clipped or not."""
    lcfg = LearnerConfig(lr=3e-4, max_grad_norm=2.0)
    opt = make_optimizer(lcfg)
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.normal(size=(7, 5)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    state = opt.init(params)
    for scale in (0.01, 10.0, 1.0):
        grads = jax.tree.map(
            lambda p: jnp.asarray(scale * rng.normal(size=p.shape),
                                  jnp.float32), params)
        updates, state = opt.update(grads, state, params)
        jax.tree.map(
            lambda u, r: np.testing.assert_allclose(u, r, rtol=1e-6,
                                                    atol=0.0),
            updates, applied_update(lcfg, state))


# -- the structure of train_many's scan body -------------------------------

def _in(v, variables) -> bool:
    return not isinstance(v, Literal) and v in variables


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "jaxpr"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


def _walk(jaxpr, in_cond=False, root=None):
    """-> (equation, whether a `cond` encloses it, its inputs as the
    variables of the outermost jaxpr they come from) for every equation
    under `jaxpr`. A call (`jnp.where` is a pjit) hands its operands
    through; a nested scan or while starts a scope of its own."""
    root = root or {}
    for eqn in jaxpr.eqns:
        ins = [v if isinstance(v, Literal) else root.get(v, v)
               for v in eqn.invars]
        yield eqn, in_cond, ins
        name = eqn.primitive.name
        for sub in _sub_jaxprs(eqn):
            if name == "cond":
                yield from _walk(sub, True, dict(zip(sub.invars, ins[1:])))
            elif name not in ("scan", "while") \
                    and len(sub.invars) == len(ins):
                yield from _walk(sub, in_cond, dict(zip(sub.invars, ins)))
            else:
                yield from _walk(sub, in_cond)


def _scan_body(learner, state, n):
    """-> (train_many's one scan's body, the carried leaves of the
    parameter-sized trees on their way in and out: params, target, Adam's
    two moments)."""
    jaxpr = jax.make_jaxpr(lambda s: learner.train_many(s, n))(state)
    (scan,) = [eqn for eqn in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
               if eqn.primitive.name == "scan"]
    body = scan.params["jaxpr"].jaxpr
    # TrainState flattens to params, target, (count, mu, nu), replay, ...
    # and leads the carry with or without a prefetched sample behind it
    n_p = len(jax.tree.leaves(state.params))
    assert jax.tree.leaves(state)[2 * n_p].shape == ()   # Adam's count
    at = [*range(2 * n_p), *range(2 * n_p + 1, 4 * n_p + 1)]
    first = scan.params["num_consts"]
    return body, ({body.invars[first + i] for i in at}
                  | {body.outvars[i] for i in at})


def _update_vars(body, carried):
    """-> (the update trees' leaves: the other operand of each
    `params + update` (optax.apply_updates); every parameter leaf on the
    way), followed from the carried parameters through the K steps."""
    params, updates = set(carried), set()
    for eqn in body.eqns:
        if eqn.primitive.name != "add" \
                or any(isinstance(v, Literal) for v in eqn.invars):
            continue
        a, b = eqn.invars
        for p, u in ((a, b), (b, a)):
            if p in params and u not in params \
                    and u.aval.shape == p.aval.shape:
                updates.add(u)
                params.add(eqn.outvars[0])
    return updates, params


def _passes_outside_conds(body, trees, shapes):
    """What the body does to parameter-sized arrays outside any cond:
    -> (selects that read one of `trees`; `trees` squared and summed;
    selects with a parameter-shaped result; distinct parameter-shaped
    arrays squared and summed — two sums over one array are one pass
    once XLA has merged them, the clip's norm and the metric's).
    The first two are exact; the last two count by shape, so they also
    count what the loss does to a batch of a parameter's shape, the
    same in every tail."""
    made_by = {out: (eqn, ins) for eqn, _, ins in _walk(body)
               for out in eqn.outvars}
    tree_selects, tree_squares, selects, squared = 0, 0, 0, set()
    for eqn, in_cond, ins in _walk(body):
        if in_cond:
            continue
        if eqn.primitive.name == "select_n":
            tree_selects += any(_in(v, trees) for v in ins[1:])
            selects += eqn.outvars[0].aval.shape in shapes
        if eqn.primitive.name == "reduce_sum" and _in(ins[0], made_by):
            src, src_ins = made_by[ins[0]]
            if src.primitive.name == "square" \
                    or (src.primitive.name == "integer_pow"
                        and src.params["y"] == 2) \
                    or (src.primitive.name == "mul"
                        and src_ins[0] is src_ins[1]):
                tree_squares += _in(src_ins[0], trees)
                if _in(src_ins[0], made_by) \
                        and src_ins[0].aval.shape in shapes:
                    squared.add(src_ins[0])
    return tree_selects, tree_squares, selects, len(squared)


def _cond_tree_operands(body, trees):
    """-> per cond of the body that takes any of `trees`, those it
    takes."""
    return [took for eqn in body.eqns if eqn.primitive.name == "cond"
            for took in [{v for v in eqn.invars[1:] if _in(v, trees)}]
            if took]


@pytest.mark.parametrize("k,prefetch", [(1, False), (4, False), (4, True)],
                         ids=["k1", "k4", "k4_prefetch"])
@pytest.mark.parametrize("kind", ["dqn", "r2d2", "glm_tiny_q"])
def test_scan_body_passes_over_a_tree_only_for_a_consumer(kind, k, prefetch):
    def census(ref):
        learner, state = _learner_and_state(kind, k, prefetch, ref=ref)
        body, carried = _scan_body(learner, state, 2 * k)
        updates, params = _update_vars(body, carried)
        assert len(updates) == k * n_leaves
        trees = carried | updates | params
        return (_passes_outside_conds(body, trees, shapes),
                _cond_tree_operands(body, trees), updates)

    leaves = jax.tree.leaves(_setup(kind, k, prefetch)[2])
    n_leaves, shapes = len(leaves), {x.shape for x in leaves}
    (tree_selects, tree_squares, selects, squared), conds, updates = \
        census(ref=False)
    # outside a cond no carried tree, parameter or update is selected
    # from or squared and summed
    assert (tree_selects, tree_squares) == (0, 0)
    # K target syncs, and the health norms of the one step that reports
    assert len(conds) == k + 1
    # no cond takes the update tree: it is made by Adam, used by the
    # apply and dead after it, so as an operand it would be written out
    # on every step only to be one (+2.2 GiB of temp at 591 M parameters)
    assert not set().union(*conds) & updates
    _, trap_conds, trap_updates = census(ref="trap")
    assert len(set().union(*trap_conds) & trap_updates) == n_leaves

    # the parent's tail under the same census: its select over the target
    # and its norms of the updates and the parameters are seen, and are
    # all that differs — what is left squared and summed here is the
    # gradient, what is left selected is the clip
    (r_tree_selects, r_tree_squares, r_selects, r_squared), r_conds, _ = \
        census(ref=True)
    assert (r_tree_selects, r_tree_squares) == (k * n_leaves,
                                                2 * k * n_leaves)
    assert not r_conds
    assert r_selects - selects == k * n_leaves
    assert r_squared - squared == 2 * k * n_leaves


@pytest.mark.parametrize("tail_min_bytes", [None])
def test_a_small_net_keeps_the_select_and_every_steps_norms(tail_min_bytes):
    """Under TAIL_BRANCH_MIN_BYTES the scan body is the parent's: no
    cond takes a tree, the sync selects, the reporting step's norms are
    not behind the flag."""
    learner, state = _learner_and_state("dqn", 4, False, ref=False)
    n_leaves = len(jax.tree.leaves(state.params))
    shapes = {x.shape for x in jax.tree.leaves(state.params)}
    body, carried = _scan_body(learner, state, 8)
    updates, params = _update_vars(body, carried)
    trees = carried | updates | params
    assert not _cond_tree_operands(body, trees)
    ref, ref_state = _learner_and_state("dqn", 4, False, ref=True)
    ref_body, ref_carried = _scan_body(ref, ref_state, 8)
    ref_updates, ref_params = _update_vars(ref_body, ref_carried)
    got = _passes_outside_conds(body, trees, shapes)
    want = _passes_outside_conds(
        ref_body, ref_carried | ref_updates | ref_params, shapes)
    # every step's sync selects, as the parent's; the last step's norms
    # are traced (the other three steps' only in the parent's jaxpr, where
    # XLA prunes them), ‖params‖ over the new parameters themselves and
    # ‖update‖ over a tree rebuilt from the moments
    assert got[0] == want[0] == 4 * n_leaves
    assert got[2] == want[2]
    assert (got[1], want[1]) == (n_leaves, 2 * 4 * n_leaves)


@pytest.mark.parametrize("tail_min_bytes", [None])
@pytest.mark.parametrize("preset,sets,branches", [
    ("pong", [], False),
    ("atari57_apex", [], False),
    ("r2d2", [], True),
    ("glm47_flash_q", ["network.glm.num_hidden_layers=5",
                       "network.glm.shard_count=8",
                       "env.num_tokens=19360"], True),
    ("trinity_mini_q", ["network.afmoe.num_hidden_layers=5",
                        "network.afmoe.num_dense_layers=1",
                        "network.afmoe.layer_types=('sliding_attention',"
                        "'sliding_attention','sliding_attention',"
                        "'sliding_attention','full_attention')",
                        "network.afmoe.shard_count=16",
                        "network.afmoe.vocab_shard_count=8",
                        "env.num_tokens=25024"], True),
    ("smallthinker_21b_q", ["network.smallthinker.num_hidden_layers=4",
                            "network.smallthinker.rope_layout=(0,1,1,1)",
                            "network.smallthinker.sliding_window_layout="
                            "(0,1,1,1)",
                            "network.smallthinker.shard_count=8",
                            "env.num_tokens=18992"], True),
])
def test_each_cells_net_takes_the_tail_measured_for_it(preset, sets, branches,
                                                       tail_min_bytes):
    """PERF.md §6 (PR 31): 6.8 MB of parameters lost 2.8% to the
    branches, 15 MB gained 1.0%, 2.4 GB 3.9%. The threshold sits between
    the first two; a preset that crosses it changes what was measured."""
    from ape_x_dqn_tpu.runtime.train import apply_overrides

    cfg = apply_overrides(get_config(preset), sets)
    spec = make_env(cfg.env).spec
    net = build_network(cfg.network, spec)
    if family_of(cfg) == "decoder_q":
        count = net.param_count()
    else:
        obs = jnp.zeros((1, *spec.obs_shape), spec.obs_dtype)
        if family_of(cfg) == "r2d2":
            z = jnp.zeros((1, cfg.network.lstm_size), jnp.float32)
            shapes = jax.eval_shape(net.init, jax.random.key(0),
                                    obs[None], (z, z))
        else:
            shapes = jax.eval_shape(net.init, jax.random.key(0), obs)
        count = sum(x.size for x in jax.tree.leaves(shapes))
    assert (4 * count >= learner_mod.TAIL_BRANCH_MIN_BYTES) == branches
