"""models/expert_layer.py's routed path in float32 on the CPU: the
buffers sized by `capacity`, the compact path and the full width it
falls back to, each against a plain loop over the held experts (every
held expert applied to every token, weighted by whether and how much
the token selected it). Sizes: hidden 16, 8 experts of 8, top-2, 512
tokens, so that a share of 2, 4 or 8 ways has a capacity below its
1,024 assignments."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ape_x_dqn_tpu.models import expert_layer
from ape_x_dqn_tpu.models.expert_layer import (
    CAPACITY_SLACK, ROW_TILE, ExpertShare, _balanced_scores, capacity,
    expert_ffn)

HIDDEN, INTER, EXPERTS, TOP_K = 16, 8, 8, 2
B, T = 2, 256
N = B * T
# `expert_ffn`'s lowered text (CPU backend, float32, the whole layer at
# these sizes) at the commit before the capacity: SHA-256, 16 hex
WHOLE_LAYER_TEXT = "45dbab8fa00b0392"


def share_of(ways: int, index: int = 0, **fields) -> ExpertShare:
    held = EXPERTS // ways
    return ExpertShare(**{
        "experts": EXPERTS, "top_k": TOP_K, "held": held,
        "first": index * held, "norm_topk": True, "scale": 2.5,
        "router_trains": True, **fields})


def layer_params(share: ExpertShare, seed: int = 0) -> dict:
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    normal = lambda k, *shape: 0.3 * jax.random.normal(  # noqa: E731
        k, shape, jnp.float32)
    return {
        "gate": normal(keys[0], HIDDEN, EXPERTS),
        "e_score_correction_bias": 0.1 * normal(keys[1], EXPERTS),
        "experts": {
            "gate_proj": normal(keys[2], share.held, HIDDEN, INTER),
            "up_proj": normal(keys[3], share.held, HIDDEN, INTER),
            "down_proj": normal(keys[4], share.held, INTER, HIDDEN)},
        "shared_experts": {
            "gate_proj": normal(keys[5], HIDDEN, INTER),
            "up_proj": normal(keys[6], HIDDEN, INTER),
            "down_proj": normal(keys[7], INTER, HIDDEN)}}


def inputs(seed: int = 1) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(seed), (B, T, HIDDEN))


def forced(seed: int = 2) -> jax.Array:
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, 1000)
    return _balanced_scores(tokens, jnp.arange(T), 1, EXPERTS)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def loop_ffn(p: dict, x: jax.Array, share: ExpertShare, balanced=None):
    flat = x.reshape(-1, HIDDEN)
    s = jax.nn.sigmoid(jnp.dot(flat, p["gate"],
                               precision=jax.lax.Precision.HIGHEST))
    select = (s + jax.lax.stop_gradient(p["e_score_correction_bias"])
              if balanced is None else balanced.reshape(-1, EXPERTS))
    _, ids = jax.lax.top_k(select, share.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if share.norm_topk:
        w = w / w.sum(axis=-1, keepdims=True)
    if not share.router_trains:
        w = jax.lax.stop_gradient(w)
    w = w * share.scale
    e = p["experts"]
    out = swiglu(flat, *(p["shared_experts"][m] for m in (
        "gate_proj", "up_proj", "down_proj")))
    for j in range(share.held):
        mine = (w * (ids == share.first + j)).sum(axis=-1)
        out = out + mine[:, None] * swiglu(
            flat, e["gate_proj"][j], e["up_proj"][j], e["down_proj"][j])
    return out.reshape(x.shape)


def both(share, p, x, balanced):
    """(output, gradients w.r.t. (p, x) of a fixed random projection of
    it, rows) of the layer and of the loop."""
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def layer(p, x):
        out, rows, _ = expert_ffn(p, x, jnp.float32, share, balanced)
        return (out * probe).sum(), (out, rows)

    def loop(p, x):
        out = loop_ffn(p, x, share, balanced)
        return (out * probe).sum(), out

    (_, (out, rows)), grads = jax.jit(jax.value_and_grad(
        layer, argnums=(0, 1), has_aux=True))(p, x)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        loop, argnums=(0, 1), has_aux=True))(p, x)
    return (out, grads, rows), (want, want_grads)


def assert_equal_to_the_loop(share, p, x, balanced):
    (out, grads, rows), (want, want_grads) = both(share, p, x, balanced)
    np.testing.assert_allclose(out, want, atol=2e-5)
    flat, tree = jax.tree.flatten(grads)
    for got, ref in zip(flat, tree.flatten_up_to(want_grads)):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=1e-4)
    gate = np.asarray(grads[0]["gate"])
    assert np.abs(gate).max() > 0          # the router's gradient is live
    np.testing.assert_array_equal(
        grads[0]["e_score_correction_bias"], 0)
    return int(rows.sum())


@pytest.mark.parametrize("balanced", [True, False],
                         ids=["forced", "own"])
@pytest.mark.parametrize("ways,index", [
    (w, i) for w in (2, 4, 8) for i in range(w)])
def test_a_share_equals_the_loop_over_its_experts(ways, index, balanced):
    share = share_of(ways, index)
    c = capacity(share, N)
    assert c < TOP_K * N
    p, x = layer_params(share, seed=index), inputs()
    rows = assert_equal_to_the_loop(share, p, x,
                                    forced() if balanced else None)
    assert 0 < rows <= c                    # the compact path took it


def selection_of(rows_here: int, share: ExpertShare) -> jax.Array:
    """Selection scores [B, T, experts] that route exactly `rows_here`
    assignments to a share holding experts 0 and 1: top-2 is (0, 1) for
    the first tokens, (0, 4) for one more if the count is odd, (4, 5)
    for the rest."""
    assert (share.first, share.held, share.top_k) == (0, 2, 2)
    token = np.arange(N)[:, None]
    expert = np.arange(EXPERTS)[None, :]
    two, one = rows_here // 2, rows_here % 2
    chosen = np.where(token < two, (expert == 0) | (expert == 1),
                      np.where(token < two + one,
                               (expert == 0) | (expert == 4),
                               (expert == 4) | (expert == 5)))
    return jnp.asarray(chosen.reshape(B, T, EXPERTS), jnp.float32)


@pytest.mark.parametrize("over", [-37, 0, 1, "all"])
def test_rows_up_to_and_past_the_capacity_equal_the_loop(over):
    """At exactly C rows the compact path, at C + 1 the full width:
    either way every row reaches its expert (a row dropped at the
    boundary would show against the loop)."""
    share = share_of(4)
    c = capacity(share, N)
    want_rows = TOP_K * N if over == "all" else c + over
    p, x = layer_params(share), inputs()
    rows = assert_equal_to_the_loop(share, p, x,
                                    selection_of(want_rows, share))
    assert rows == want_rows


def sorted_for(share, p, flat, rows_here):
    """(w, order, inverse, rows) as `expert_ffn` hands them to
    `_routed`, under `selection_of(rows_here)`."""
    ids, w = expert_layer.route(
        p, flat, share, selection_of(rows_here, share).reshape(N, EXPERTS))
    slot = jnp.where(ids < share.held, ids, share.held)
    order = jnp.argsort(slot.reshape(-1), stable=True).astype(jnp.int32)
    rows = jnp.bincount(slot.reshape(-1), length=share.held + 1)[
        :share.held].astype(jnp.int32)
    assert int(rows.sum()) == rows_here
    return w, order, expert_layer._inverse(order), rows


def test_the_compact_path_alone_would_drop_what_the_branch_keeps():
    """The test above has teeth: `_compact` fed C + 1 rows loses one."""
    share = share_of(4, router_trains=False)
    c = capacity(share, N)
    p, x = layer_params(share), inputs()
    flat = x.reshape(N, HIDDEN)

    def alone(rows_here):
        return expert_layer._compact(
            c, jnp.float32, jax.nn.silu, flat, p["experts"],
            *sorted_for(share, p, flat, rows_here))

    def branching(rows_here):
        return expert_layer._routed(c, jnp.float32, jax.nn.silu, flat,
                                    p["experts"],
                                    *sorted_for(share, p, flat, rows_here))

    np.testing.assert_allclose(alone(c), branching(c), atol=1e-6)
    lost = np.abs(np.asarray(alone(c + 1)) - np.asarray(branching(c + 1)))
    assert (lost.max(axis=-1) > 1e-3).sum() == 1


def test_padded_rows_read_zeros_and_give_their_tokens_no_cotangent():
    """Fewer rows than the buffer holds: the rows past the last group
    were gathered from tokens that selected no expert held here; the
    routed path gives those tokens an output and a cotangent of exactly
    zero, whatever the grouped matmul's transpose left in those rows,
    and every gradient is finite."""
    share = share_of(4, router_trains=False)
    c = capacity(share, N)
    rows_here = c - 100
    p, x = layer_params(share), inputs()
    flat = x.reshape(N, HIDDEN)
    w, order, inverse, rows = sorted_for(share, p, flat, rows_here)
    padded = np.unique(np.asarray(order[rows_here:c]) // TOP_K)
    served = np.unique(np.asarray(order[:rows_here]) // TOP_K)
    assert len(padded) and not set(padded) & set(served)
    probe = jax.random.normal(jax.random.PRNGKey(5), flat.shape)

    def f(flat, e, w):
        out = expert_layer._routed(c, jnp.float32, jax.nn.silu, flat, e, w,
                                   order, inverse, rows)
        return (out * probe).sum(), out

    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                         has_aux=True)(flat, p["experts"], w)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(leaf).all()
    np.testing.assert_array_equal(np.asarray(out)[padded], 0)
    np.testing.assert_array_equal(np.asarray(grads[0])[padded], 0)
    assert np.abs(np.asarray(grads[0])[served]).min(axis=-1).max() > 0
    np.testing.assert_array_equal(
        np.asarray(grads[2]).reshape(-1)[np.asarray(order[rows_here:])], 0)


@pytest.mark.parametrize("experts,top_k,held", [
    (8, 2, 8), (8, 2, 6), (8, 2, 4), (8, 2, 1), (64, 4, 8), (128, 8, 8)])
def test_capacity(experts, top_k, held):
    share = ExpertShare(experts, top_k, held, 0, True, 1.0, held == experts)
    sizes = [1, 7, 64, 100, 512, 2048, 6144, 12288]
    got = [capacity(share, n) for n in sizes]
    assert got == sorted(got)                        # monotone in n
    for n, c in zip(sizes, got):
        worst = top_k * n
        assert 0 < c <= worst
        if held == experts or CAPACITY_SLACK * held >= experts:
            assert c == worst        # one path: today's, no branch
        if c < worst:
            expected = worst * held / experts
            assert c % ROW_TILE == 0
            assert CAPACITY_SLACK * expected <= c
            assert c < CAPACITY_SLACK * expected + ROW_TILE


def test_the_cells_capacities():
    """The two decoder cells' shares at their trained segments and
    prefixes (ISSUE 33: 12,288 of 131,072 rows a pass in Trinity's,
    6,144 of 32,768 in GLM's)."""
    trinity = ExpertShare(128, 8, 8, 0, True, 1.0, False)
    assert (capacity(trinity, 2 * 6144), capacity(trinity, 2 * 2048)) == (
        9216, 3072)
    glm = ExpertShare(64, 4, 8, 0, True, 1.0, False)
    assert (capacity(glm, 16 * 384), capacity(glm, 16 * 128)) == (4608, 1536)


def lowered(share: ExpertShare) -> str:
    p = layer_params(share)
    return jax.jit(lambda p, x: expert_ffn(
        p, x, jnp.float32, share, None)).lower(p, inputs()).as_text()


def test_a_whole_layer_lowers_to_the_text_it_had_before_the_capacity():
    """held == experts: no branch, and the program is byte for byte
    what `expert_ffn` lowered to before this path had a capacity."""
    text = lowered(share_of(1))
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == WHOLE_LAYER_TEXT


def test_a_share_below_its_worst_case_lowers_to_a_branch():
    text = lowered(share_of(4))
    assert "stablehlo.case" in text or "stablehlo.if" in text


# -- what became an argument (ISSUE 39): the tensor the plan is read
# from, the scoring, the activation, a layer without a shared expert ----

def routed_only_params(share: ExpertShare, seed: int = 0) -> dict:
    p = layer_params(share, seed)
    return {"gate": p["gate"], "experts": p["experts"]}


def loop_routed_only(p, x, read, share, balanced, scoring, act):
    """The plain loop for a routed-only layer whose router reads `read`
    [B, T, HIDDEN]: every held expert applied to every row of x."""
    flat, r = x.reshape(-1, HIDDEN), read.reshape(-1, HIDDEN)
    logits = jnp.dot(r, p["gate"], precision=jax.lax.Precision.HIGHEST)
    if scoring == expert_layer.SIGMOID:
        s = jax.nn.sigmoid(logits)
        select = s if balanced is None else balanced.reshape(-1, EXPERTS)
        _, ids = jax.lax.top_k(select, share.top_k)
        w = jnp.take_along_axis(s, ids, axis=-1)
        w = w / w.sum(axis=-1, keepdims=True)
    else:
        select = (logits if balanced is None
                  else balanced.reshape(-1, EXPERTS))
        _, ids = jax.lax.top_k(select, share.top_k)
        w = jax.nn.softmax(jnp.take_along_axis(logits, ids, axis=-1), -1)
    w = w * share.scale
    out = jnp.zeros_like(flat)
    e = p["experts"]
    for j in range(share.held):
        mine = (w * (ids == share.first + j)).sum(axis=-1)
        out = out + mine[:, None] * (
            (act(flat @ e["gate_proj"][j]) * (flat @ e["up_proj"][j]))
            @ e["down_proj"][j])
    return out.reshape(x.shape)


@pytest.mark.parametrize("ways", [1, 4], ids=["whole", "share"])
@pytest.mark.parametrize("balanced", [True, False], ids=["forced", "own"])
@pytest.mark.parametrize("scoring,act", [
    (expert_layer.SOFTMAX_SELECTED, jax.nn.relu),
    (expert_layer.SOFTMAX_SELECTED, jax.nn.silu),
    (expert_layer.SIGMOID, jax.nn.relu)],
    ids=["softmax-relu", "softmax-silu", "sigmoid-relu"])
def test_a_plan_from_another_tensor_equals_the_loop(scoring, act, balanced,
                                                    ways):
    """The plan read off ANOTHER tensor than the rows the experts are
    fed, either scoring, either activation, no shared expert: output
    and every gradient (the rows', the read tensor's, the router's, the
    experts') against the loop, on the one path of a whole layer and
    through the compact path of a share."""
    share = share_of(ways)
    p = routed_only_params(share)
    if scoring == expert_layer.SIGMOID:
        p["e_score_correction_bias"] = jnp.zeros(EXPERTS)
    x, read = inputs(), inputs(seed=7)
    scores = forced() if balanced else None
    probe = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def layer(p, x, read):
        planned = expert_layer.plan(
            p, read.reshape(N, HIDDEN), share,
            None if scores is None else scores.reshape(N, EXPERTS), scoring)
        out, rows, ids = expert_ffn(p, x, jnp.float32, share,
                                    planned=planned, act=act)
        return (out * probe).sum(), (out, rows)

    def loop(p, x, read):
        out = loop_routed_only(p, x, read, share, scores, scoring, act)
        return (out * probe).sum(), out

    (_, (out, rows)), grads = jax.jit(jax.value_and_grad(
        layer, argnums=(0, 1, 2), has_aux=True))(p, x, read)
    (_, want), want_grads = jax.jit(jax.value_and_grad(
        loop, argnums=(0, 1, 2), has_aux=True))(p, x, read)
    np.testing.assert_allclose(out, want, atol=2e-5)
    flat, tree = jax.tree.flatten(grads)
    for got, exp in zip(flat, tree.flatten_up_to(want_grads)):
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, exp, atol=2e-4, rtol=1e-4)
    # the router's gradient arrives through the tensor it READ
    assert np.abs(np.asarray(grads[2])).max() > 0
    assert np.abs(np.asarray(grads[0]["gate"])).max() > 0
    assert int(rows.sum()) <= capacity(share, N)
    # the stream the plan is read from decides the result
    from_rows = expert_layer.plan(
        p, x.reshape(N, HIDDEN), share,
        None if scores is None else scores.reshape(N, EXPERTS), scoring)
    own, _, _ = expert_ffn(p, x, jnp.float32, share, planned=from_rows,
                           act=act)
    assert np.abs(np.asarray(own) - np.asarray(out)).max() > 1e-3


@pytest.mark.parametrize("over", [0, 1, "all"])
def test_overflow_takes_the_full_width_with_a_plan_relu_and_no_shared(over):
    """`test_rows_up_to_and_past_the_capacity_equal_the_loop` with what
    became an argument: at C rows the compact path, past it the full
    width, nothing dropped."""
    share = share_of(4)
    c = capacity(share, N)
    want_rows = TOP_K * N if over == "all" else c + over
    p, x, read = routed_only_params(share), inputs(), inputs(seed=7)
    scores = selection_of(want_rows, share)
    planned = expert_layer.plan(
        p, read.reshape(N, HIDDEN), share, scores.reshape(N, EXPERTS),
        expert_layer.SOFTMAX_SELECTED)
    out, rows, _ = jax.jit(lambda p, x, planned: expert_ffn(
        p, x, jnp.float32, share, planned=planned, act=jax.nn.relu))(
        p, x, planned)
    assert int(rows.sum()) == want_rows
    np.testing.assert_allclose(out, loop_routed_only(
        p, x, read, share, scores, expert_layer.SOFTMAX_SELECTED,
        jax.nn.relu), atol=2e-5)


def test_softmax_over_the_selected_sums_to_one_and_ignores_the_rest():
    share = share_of(1)._replace(scale=1.0)
    p, x = routed_only_params(share), inputs().reshape(N, HIDDEN)
    ids, w = expert_layer.route(p, x, share, None,
                                expert_layer.SOFTMAX_SELECTED)
    np.testing.assert_allclose(w.sum(axis=-1), 1.0, atol=1e-6)
    logits = x @ p["gate"]
    np.testing.assert_array_equal(
        np.sort(ids, -1), np.sort(jax.lax.top_k(logits, TOP_K)[1], -1))
    # an unselected logit moved by any amount below the k-th changes nothing
    low = jnp.argmin(logits, axis=-1)
    bumped = logits.at[jnp.arange(N), low].add(-5.0)
    w2 = jax.nn.softmax(jnp.take_along_axis(bumped, ids, -1), -1)
    np.testing.assert_allclose(w, w2, atol=1e-6)


def test_the_cells_capacities_smallthinker():
    """`smallthinker_offline`'s share at its trained segment and its
    prefix: the rows expected (9,216 and 3,072) x 1.5, in tiles of 128."""
    st = ExpertShare(64, 6, 8, 0, True, 1.0, False)
    assert (capacity(st, 12288), capacity(st, 4096)) == (13824, 4608)
    assert capacity(st, 12288) < 6 * 12288
