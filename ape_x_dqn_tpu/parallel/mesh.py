"""Device mesh construction.

The reference scales with NCCL data-parallel collectives (SURVEY.md §2.3
item 2); here the learner scales over a `jax.sharding.Mesh` with named
axes and XLA-inserted collectives over ICI:

- "dp": data parallel — replay shards + batch shards + gradient psum.
- "tp": tensor parallel — large dense kernels column/row-sharded.

An Ape-X system has no pipeline/sequence/expert parallelism to express
(SURVEY.md §2.4): networks are small CNNs/LSTMs, so dp x tp is the
complete, honest mesh. R2D2 sequences shard across the batch axis (dp),
never time.

There is no expert axis yet (`EXPERT_AXIS` is not in `AXIS_NAMES`):
the decoder family's expert layer (models/glm_moe_q.py) runs one
chip's share without the all-to-all that would bring it the other
shares' tokens and sum the router's gradient over the shares, and
while that is so the layer holds its router fixed
(`has_expert_exchange`, ROADMAP "Reach" R2.2).
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


AXIS_NAMES = ("dp", "tp")
EXPERT_AXIS = "ep"  # the name the expert axis takes when it lands


def has_expert_exchange() -> bool:
    """Whether the mesh can exchange tokens between the chips that
    share an expert layer. models.build_network asks: a share without
    the exchange sees only its own experts' terms of the router's
    gradient, so its router gets none (glm_moe_q.GlmMoeQNet). The PR
    that adds the axis and the all-to-all makes this true, and the
    stop-gradient goes with it (a test holds the two together)."""
    return EXPERT_AXIS in AXIS_NAMES


def make_mesh(dp: int | None = None, tp: int = 1,
              devices: list | None = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if dp is None:
        dp = n // tp
    assert 1 <= dp * tp <= n, f"dp({dp}) * tp({tp}) > device count ({n})"
    arr = np.asarray(devices[:dp * tp]).reshape(dp, tp)
    return Mesh(arr, axis_names=AXIS_NAMES)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis sharding over dp (replay shards, batches)."""
    return NamedSharding(mesh, P("dp"))
