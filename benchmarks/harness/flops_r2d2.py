"""Operations per train step of the recurrent family
`r2d2_lstm_dueling` (Nature-CNN torso -> LSTM -> dueling heads under
the R2D2 sequence loss), from shapes. Importing this module registers
the count in `harness/flops.py`'s one table, which is where
`layer_metrics/learner.mfu.py` looks a family up; the traffic kind that
runs the family imports it and binds the configuration file's
`sequence_sizes` (the reader passes `sizes` alone)."""

from __future__ import annotations

from benchmarks.harness.flops import TRAIN_STEP_FLOPS

FAMILY = "r2d2_lstm_dueling"


def frame_forward_macs(sizes: dict, lstm_size: int) -> int:
    """MACs of one frame through torso, one LSTM step and the heads."""
    h, w, c_in = sizes["frame"]
    macs = 0
    for c_out, k, s in zip(sizes["cnn_channels"], sizes["cnn_kernels"],
                           sizes["cnn_strides"]):
        h, w = (h - k) // s + 1, (w - k) // s + 1       # VALID padding
        macs += h * w * c_out * k * k * c_in
        c_in = c_out
    dense = sizes["torso_dense"]
    macs += h * w * c_in * dense
    macs += 4 * (dense + lstm_size) * lstm_size         # four gates
    macs += lstm_size * (sizes["num_actions"] + 1)      # dueling heads
    return macs


def r2d2_lstm_dueling(sizes: dict, sequence_sizes: dict) -> float:
    """FLOP per R2D2 train step. Per sequence the loss runs, in
    frame-forward equivalents: the burn-in through the online and the
    target net without gradient (2 x burn_in), the trained segment
    through the online net with gradient (forward + backward = 3x) and
    through the target net (1x): 4 x (L - burn_in). 2 FLOP per MAC.
    Elementwise, optimizer and replay work is left out, as for the CNN
    family. At the published widths (batch 64, L 80, burn-in 40, LSTM
    512, 6 actions): 15,360 forwards x 11,443,712 MACs = 351.55
    GFLOP/step."""
    length = sequence_sizes["seq_length"]
    burn_in = sequence_sizes["burn_in"]
    forwards = sizes["batch_size"] * (2 * burn_in + 4 * (length - burn_in))
    return 2.0 * forwards * frame_forward_macs(
        sizes, sequence_sizes["lstm_size"])


def register(sequence_sizes: dict) -> None:
    """Put the family in the table, bound to `sequence_sizes`."""
    TRAIN_STEP_FLOPS[FAMILY] = lambda sizes: r2d2_lstm_dueling(
        sizes, sequence_sizes)
