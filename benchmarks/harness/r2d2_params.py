"""The system's flax pytree (models/lstm_q.ApeXLSTMQNet) onto the plain
arrays of benchmarks/reference/r2d2.py — the counterpart of
`correctness.reference_params` for the recurrent family. Kernel layouts
agree (HWIO convs, [in, out] dense), so this is renaming only."""

from __future__ import annotations

import numpy as np

from benchmarks.reference import r2d2 as ref


def reference_params(sys_params) -> ref.Params:
    p = sys_params["params"]
    torso, lstm, head = p["torso"], p["lstm"], p["head"]
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    # the MLP torso of the vector-observation tests is one Dense named
    # "torso"; the pixel torso is NatureCNNTorso (Conv_0.. + torso_out)
    convs = sorted(k for k in torso if k.startswith("Conv_"))
    dense = torso["torso_out"] if convs else torso
    return ref.Params(
        conv_kernels=[f32(torso[k]["kernel"]) for k in convs],
        conv_biases=[f32(torso[k]["bias"]) for k in convs],
        dense_kernel=f32(dense["kernel"]), dense_bias=f32(dense["bias"]),
        # flax's OptimizedLSTMCell: input projections `i<gate>` carry no
        # bias, hidden projections `h<gate>` carry the gate's one bias
        lstm_input_kernels={g: f32(lstm["i" + g]["kernel"])
                            for g in ref.GATES},
        lstm_hidden_kernels={g: f32(lstm["h" + g]["kernel"])
                             for g in ref.GATES},
        lstm_biases={g: f32(lstm["h" + g]["bias"]) for g in ref.GATES},
        value_kernel=f32(head["value"]["kernel"]),
        value_bias=f32(head["value"]["bias"]),
        advantage_kernel=f32(head["advantage"]["kernel"]),
        advantage_bias=f32(head["advantage"]["bias"]))


def system_gradients(ref_grads: ref.Params, like) -> dict:
    """The reference's gradients renamed back into the system's pytree
    (`like`: the system's params, for the structure), so the two trees
    compare leaf by leaf."""
    p = like["params"]
    convs = sorted(k for k in p["torso"] if k.startswith("Conv_"))
    dense = {"kernel": ref_grads.dense_kernel,
             "bias": ref_grads.dense_bias}
    if convs:
        torso = {k: {"kernel": ref_grads.conv_kernels[i],
                     "bias": ref_grads.conv_biases[i]}
                 for i, k in enumerate(convs)}
        torso["torso_out"] = dense
    else:
        torso = dense
    lstm = {}
    for g in ref.GATES:
        lstm["i" + g] = {"kernel": ref_grads.lstm_input_kernels[g]}
        lstm["h" + g] = {"kernel": ref_grads.lstm_hidden_kernels[g],
                         "bias": ref_grads.lstm_biases[g]}
    head = {"value": {"kernel": ref_grads.value_kernel,
                      "bias": ref_grads.value_bias},
            "advantage": {"kernel": ref_grads.advantage_kernel,
                          "bias": ref_grads.advantage_bias}}
    return {"params": {"torso": torso, "lstm": lstm, "head": head}}
