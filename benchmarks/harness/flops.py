"""Operations a train step needs, computed from shapes — one function
per network family, looked up by the configuration file's `family`.

These are the yardstick for `learner.mfu`: operations the algorithm
requires, not what XLA happens to execute (its `cost_analysis()`
undercounts conv FLOPs 10-50x on this chip, PERF.md section 6).
"""

from __future__ import annotations

from typing import Callable


def nature_cnn_dueling_dqn(sizes: dict) -> float:
    """FLOP per double-DQN train step of the dueling Nature-CNN
    (Mnih et al. 2015 torso, Wang et al. 2016 heads) at `sizes`
    (`batch_size`, `num_actions`, `frame` = [H, W, stack],
    `cnn_channels`/`cnn_kernels`/`cnn_strides`, `torso_dense`).

    The loss runs the online net on obs with gradient (forward +
    backward = 3x a forward), the online net on next_obs and the
    target net on next_obs (1x each): 5x one forward's MACs, 2 FLOP
    per MAC. Elementwise, optimizer and replay work is left out — it
    is bound by bandwidth and latency, not by FLOPs. At batch 512, 18
    actions: 47.89 GFLOP/step."""
    h, w, c_in = sizes["frame"]
    macs = 0
    for c_out, k, s in zip(sizes["cnn_channels"], sizes["cnn_kernels"],
                           sizes["cnn_strides"]):
        h, w = (h - k) // s + 1, (w - k) // s + 1   # VALID padding
        macs += h * w * c_out * k * k * c_in
        c_in = c_out
    dense = sizes["torso_dense"]
    macs += h * w * c_in * dense
    macs += dense * (sizes["num_actions"] + 1)      # dueling heads
    return 2.0 * macs * sizes["batch_size"] * 5.0


TRAIN_STEP_FLOPS: dict[str, Callable[[dict], float]] = {
    "nature_cnn_dueling_dqn": nature_cnn_dueling_dqn,
}
