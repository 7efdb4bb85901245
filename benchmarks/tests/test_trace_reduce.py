"""The trace reduction on a recorded trace: 100 ms of a `pong_live`
traced run on one v5e chip (PR 22), trimmed to the device's `XLA Ops`
and `XLA Modules` lines and the harness's own `bench.*` host
annotations. The numbers below were read off that trace once and are
what every later reduction must still give."""

import os

import pytest

from benchmarks.harness import trace_reduce, xplane_meta

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "pong_live_100ms.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    assert os.path.getsize(TRACE) < 1 << 20
    return trace_reduce.reduce(TRACE)


def test_window_busy_and_idle_share(tr):
    assert tr["window_s"] == pytest.approx(0.1)
    assert len(tr["devices"]) == 1
    assert tr["devices"][0]["plane"] == "/device:TPU:0"
    assert tr["busy_s_mean"] == pytest.approx(0.019765485, abs=1e-9)
    assert tr["idle_share_worst"] == pytest.approx(0.80234515, abs=1e-8)


def test_modules(tr):
    mods = tr["devices"][0]["modules"]
    # one paced train_many(8) dispatch, one coalesced add_many, six
    # server forwards inside these 100 ms
    assert mods["jit_train_many"] == {"count": 1, "total_ns": 16332982,
                                      "median_ns": 16332982}
    assert mods["jit_add_many"]["count"] == 1
    assert mods["jit__lambda"]["count"] == 6
    assert mods["jit__lambda"]["median_ns"] == 49190


def test_op_table_is_self_time_labelled_with_category_and_source(tr):
    top = tr["device_ops"][:4]
    assert [name for name, _ in top] == [
        "fusion.2098 [custom fusion] frame_ring.py:388",
        "fusion.2096 [custom fusion] frame_ring.py:388",
        "copy.211 [data formatting] learner.py:145",
        "copy.208 [data formatting] learner.py:145"]
    assert top[0][1] == pytest.approx(0.001424171, abs=1e-9)
    dev = tr["devices"][0]
    # the while loop's own time, not that of the ops nested in it
    assert dev["opcode_ns"]["while"] == 90898
    assert dev["opcode_ns"]["copy"] == 3176199
    # self times partition busy time exactly: no op is counted twice
    assert sum(dev["opcode_ns"].values()) == dev["busy_ns"]
    assert sum(dev["category_ns"].values()) == dev["busy_ns"]
    assert dev["collective_ns"] == 0


def test_categories_and_sources_come_from_the_event_metadata(tr):
    dev = tr["devices"][0]
    assert dev["category_ns"]["convolution fusion"] == 5834622
    assert dev["category_ns"]["custom fusion"] == 8564002
    by_source = {os.path.basename(k): v
                 for k, v in dev["source_ns"].items()}
    # the frame-row gather of FrameRingReplay._gather
    assert by_source["frame_ring.py:388"] == 3486624
    assert by_source["linear.py:700"] > by_source["frame_ring.py:388"]


def test_metadata_reader_against_known_ops():
    ops = xplane_meta.op_metadata(TRACE)["/device:TPU:0"]
    gather = next(v for k, v in ops.items()
                  if k.startswith("%fusion.2098 = "))
    assert gather["hlo_category"] == "custom fusion"
    assert gather["source"].endswith(
        "ape_x_dqn_tpu/replay/frame_ring.py:388")
    assert len(ops) > 1000


def test_idle_gaps_are_attributed_to_host_annotations(tr):
    assert len(tr["devices"][0]["gaps"]) == 6
    # 32 closed-loop clients are always inside a query, so every hole
    # is time the chip waited while clients waited for the server
    assert tr["idle_gaps"] == [["bench.client_query",
                                pytest.approx(0.080203948, abs=1e-9)]]
    assert tr["host_annotations"]["bench.client_ship"]["count"] == 7


def test_op_key():
    text = ("%fusion.12 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p.1), "
            "kind=kOutput, calls=%fused_computation.3")
    assert trace_reduce.op_key(text) == ("fusion.12", "fusion")
    assert trace_reduce.op_key(
        "%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %x)"
    ) == ("all-reduce-start.1", "all-reduce")
    assert trace_reduce.op_key("odd name")[1] == "unknown"


def test_a_trace_with_no_device_plane_is_an_error(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    found = [os.path.join(r, f) for r, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce(found[0])
