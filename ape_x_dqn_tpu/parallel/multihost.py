"""Multi-host (multi-process) learner support over JAX's distributed
runtime.

The reference scales its learner across hosts with NCCL/MPI process
groups (SURVEY.md §2.2 "Comm: NCCL", §5 "distributed communication
backend"); the TPU-native equivalent is `jax.distributed` + GSPMD: every
learner process calls `init_multihost` (which wires the coordination
service), builds ONE global `(dp, tp)` mesh over all processes' devices,
and then executes the SAME jitted programs on globally-sharded arrays —
XLA inserts the cross-host collectives (grad psum, publication
all-gather) over ICI within a host and DCN between hosts (Gloo on CPU
test rigs).

The host-side contract this module provides to the multihost driver
(runtime/multihost_driver.py):

- `process_rows(mesh)`: which contiguous dp rows this process owns —
  ingest routes each host's actor experience into its own replay shards
  (no cross-host experience traffic, mirroring the reference's
  per-learner replay locality).
- `make_global(mesh, local)`: wrap this process's [dp_local, ...] block
  into the global [dp, ...] array GSPMD programs consume.
- `global_stats`: ONE packed collective reduction per round of the
  host-local control scalars (ingest readiness, idleness, frame
  counts). Every control-flow decision in the multihost driver derives
  from it or from global jit outputs, which is what keeps all
  processes' call sequences in lockstep — a process branching on a
  host-local value would deadlock the others inside a collective.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_multihost(coordinator: str, num_processes: int,
                   process_id: int) -> None:
    """Join the JAX distributed coordination service. Must run before
    any backend use (the CLI calls it first thing)."""
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def process_rows(mesh: Mesh) -> tuple[int, int]:
    """[start, stop) dp rows owned by this process.

    Mesh rows are process-contiguous because make_mesh reshapes
    jax.devices() (globally ordered by process) into (dp, tp); asserts
    that a row never straddles processes (tp must divide the local
    device count)."""
    dp = mesh.shape["dp"]
    tp = mesh.shape.get("tp", 1)
    local = jax.local_device_count()
    nproc = jax.process_count()
    assert dp * tp == len(jax.devices()), \
        f"multihost mesh must cover every global device: dp*tp=" \
        f"{dp * tp} != {len(jax.devices())} (make_mesh takes the first " \
        f"dp*tp devices, so a partial mesh would assign this process " \
        f"rows living on another process's chips)"
    assert local % tp == 0, \
        f"tp={tp} must divide local device count {local} (a tensor-" \
        f"parallel row cannot straddle hosts: tp collectives ride ICI)"
    rows_per_proc = dp // nproc
    assert rows_per_proc * nproc == dp, \
        f"dp={dp} must divide by process count {nproc}"
    start = jax.process_index() * rows_per_proc
    return start, start + rows_per_proc


def make_global(mesh: Mesh, local: Any) -> Any:
    """Per-process [dp_local, ...] pytree -> global [dp, ...] arrays
    sharded P('dp') (each process contributes its own rows)."""
    dp = mesh.shape["dp"]
    sharding = NamedSharding(mesh, P("dp"))

    def one(x):
        x = np.asarray(x)
        global_shape = (dp,) + x.shape[1:]
        return jax.make_array_from_process_local_data(
            sharding, x, global_shape)

    return jax.tree.map(one, local)


_reduce_jits: dict[Any, Any] = {}


def global_min_scalar(mesh: Mesh, value: int) -> int:
    """Min of each process's integer scalar — one-off agreement values
    outside the hot loop (e.g. which checkpoint step every process can
    restore; min handles a host whose filesystem lacks the files).

    int32 lanes, NOT f32: checkpoint steps exceed f32's 2^24 exact
    range within hours at the measured learner rate, and a rounded
    step number would name a checkpoint that was never written.
    Values must fit int32 (|v| < 2^31 — ~50 days of grad steps)."""
    assert -(2**31) < value < 2**31, value
    start, stop = process_rows(mesh)
    block = np.full((stop - start, 1), value, np.int32)
    arr = make_global(mesh, block)
    fn = _reduce_jits.get((mesh, "min"))
    if fn is None:
        fn = jax.jit(jnp.min, out_shardings=NamedSharding(mesh, P()))
        _reduce_jits[(mesh, "min")] = fn
    return int(fn(arr))


def global_stats(mesh: Mesh, ready: float, idle: float,
                 frames: float) -> tuple[bool, bool, float]:
    """One packed per-round reduction: (all_ready, all_idle,
    frames_total).

    The lockstep round loop needs three global quantities per round;
    issuing them as separate reductions would cost three sequential DCN
    barrier round-trips, so they ride one [dp, 5] array through a
    single cached jit (a fresh jax.jit wrapper per call would retrace
    every round) that returns both the row-min (flags) and the row-sum
    (frame limbs).

    Exactness: frame counts reach billions at atari57 scale — a
    rounded-down global count would stall the frame-budget termination
    forever. The lanes are int32 (like global_min_scalar; f32 rounds
    integers above 2^24, which a 256-process fleet's limb sums would
    already exceed): the per-process count rides as three base-2^16
    limbs on ONE row per process (zeros on its other rows, so limb sums
    scale with process count, not dp). Each limb < 2^16, so int32
    limb-sums stay exact through 2^15 processes and counts to 2^48, and
    the limbs recombine exactly in Python ints. Flags tile across all
    the process's rows (min is idempotent over copies).
    """
    v = int(frames)
    flags = [int(ready), int(idle)]
    limbs = [(v >> 32) & 0xFFFF, (v >> 16) & 0xFFFF, v & 0xFFFF]
    start, stop = process_rows(mesh)
    block = np.zeros((stop - start, 5), np.int32)
    block[:, :2] = flags
    block[0, 2:] = limbs
    arr = make_global(mesh, block)
    fn = _reduce_jits.get(mesh)
    if fn is None:
        repl = NamedSharding(mesh, P())
        fn = jax.jit(lambda a: (jnp.min(a, axis=0), jnp.sum(a, axis=0)),
                     out_shardings=(repl, repl))
        _reduce_jits[mesh] = fn
    mins, sums = fn(arr)
    mins, sums = np.asarray(mins), np.asarray(sums)
    l2, l1, l0 = (int(s) for s in sums[2:])
    total = float((l2 << 32) + (l1 << 16) + l0)
    return bool(mins[0] >= 1), bool(mins[1] >= 1), total
