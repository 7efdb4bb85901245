"""Checkpoint / resume (SURVEY.md §5).

Orbax-backed manager. The driver (runtime/driver.py) saves params, target
params, optimizer state, RNG, and the grad-step counter on its
``checkpoint_every`` cadence plus once at shutdown, and restores the
latest checkpoint at construction; replay contents are not saved (large,
and Ape-X regenerates them — actors refill the buffer on resume).
``tests/test_checkpoint.py`` asserts the round-trip is bitwise and that a
resumed run continues the grad-step counter.

STORAGE LAYOUT VERSIONING (round 5 FORMAT BREAK, now machine-checked):
replay-bearing checkpoints (``RunConfig.checkpoint_replay=True``)
written before the byte-row storage layout (replay/packing.py — frames
[S*F, pad128(H*W)] instead of [S*F, H, W] planes, packed pixel obs rows
in flat storage) do not restore into the new layout. Every dict payload
saved here is therefore stamped with ``STORAGE_LAYOUT_VERSION``; a
restore that hits a version mismatch — or the Orbax structure mismatch
an unstamped pre-versioning checkpoint produces — fails with a
RuntimeError carrying the documented recovery guidance instead of a raw
Orbax traceback: restart the run fresh, or restore on the old code and
re-save a params-only checkpoint. Param-only checkpoints (the default)
are unaffected by layout breaks either way.

History: v2 = the round-5 byte rows; v3 (PR 29) = the frame ring's
rows are 32-bit words, uint32 [S*F, pad128(H*W) // 4], four pixels of
one frame to a word (replay/frame_ring.py) — the same bytes, but a v2
``frames`` leaf is uint8 [S*F, pad128(H*W)] and does not restore into
it. The packed flat/sequence stores (replay/packing.py) keep words on
the device too since PR 42, but their leaves are saved as the byte rows
they always were (`PixelPacker.checkpoint_rows`, converted on the host
by the driver), so v3 stands and a v3 file restores.
"""

from __future__ import annotations

import os
from typing import Any

import jax
import numpy as np
import orbax.checkpoint as ocp

# Bump on any break in the on-disk layout of checkpointed device state
# (storage rows, ReplayState fields, ...); the history is in the module
# docstring.
STORAGE_LAYOUT_VERSION = 3
_LAYOUT_KEY = "storage_layout_version"

_LAYOUT_GUIDANCE = (
    "this checkpoint was written under an incompatible storage layout "
    "(see utils/checkpoint.py STORAGE LAYOUT VERSIONING). Either restart "
    "the run fresh, or restore the checkpoint on the code version that "
    "wrote it and re-save params-only (checkpoint_replay=False)")


class CheckpointManager:
    def __init__(self, directory: str, max_to_keep: int = 3):
        self._dir = os.path.abspath(directory)
        os.makedirs(self._dir, exist_ok=True)
        self._mngr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep),
        )

    def save(self, step: int, state: Any, wait: bool = False) -> None:
        if isinstance(state, dict) and _LAYOUT_KEY not in state:
            # stamp rides inside the payload so it survives any orbax
            # version / directory relocation the metadata might not
            state = {**state,
                     _LAYOUT_KEY: np.asarray(STORAGE_LAYOUT_VERSION,
                                             np.int32)}
        # orbax's StandardSave accepts 0-d ndarrays but rejects bare
        # numpy scalars (np.generic) such as an np.int32 step counter;
        # promote them so callers don't have to care
        state = jax.tree.map(
            lambda x: np.asarray(x) if isinstance(x, np.generic) else x,
            state)
        # orbax asserts (finalize_thread is None) if a save starts while
        # the previous async save is still finalizing; drain it first.
        # wait_until_finished only clears the handle when called from
        # the thread that issued the previous save — the driver saves
        # from both its train loop and its shutdown path, so a finished
        # thread's handle can linger and still trip the assert; clear it.
        self._mngr.wait_until_finished()
        lock = getattr(self._mngr, "_finalize_thread_lock", None)
        if lock is not None:
            with lock:
                ft = getattr(self._mngr, "_finalize_thread", None)
                if ft is not None and not ft.is_alive():
                    self._mngr._finalize_thread = None
        self._mngr.save(step, args=ocp.args.StandardSave(state))
        if wait:
            self._mngr.wait_until_finished()

    def restore(self, step: int | None = None, template: Any = None) -> Any:
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        if isinstance(template, dict) and _LAYOUT_KEY not in template:
            saved = self._raw_item_keys(step)
            if saved is not None and _LAYOUT_KEY in saved:
                # match the stamped payload; checked + stripped below so
                # callers (driver template building) never see the key
                template = {**template,
                            _LAYOUT_KEY: np.asarray(0, np.int32)}
        try:
            if template is not None:
                out = self._mngr.restore(
                    step, args=ocp.args.StandardRestore(template))
            else:
                # a fresh manager has no handler registered for the
                # saved item; an argless StandardRestore restores from
                # the checkpoint's own metadata
                out = self._mngr.restore(
                    step, args=ocp.args.StandardRestore())
        except (ValueError, KeyError, TypeError) as e:
            # the raw Orbax structure-mismatch traceback names neither
            # the cause nor the way out; translate it
            raise RuntimeError(
                f"checkpoint restore failed at step {step} with a "
                f"structure mismatch ({e!s:.300}) — most likely "
                + _LAYOUT_GUIDANCE) from e
        if isinstance(out, dict) and _LAYOUT_KEY in out:
            ver = int(np.asarray(out.pop(_LAYOUT_KEY)))
            if ver != STORAGE_LAYOUT_VERSION:
                raise RuntimeError(
                    f"checkpoint storage layout v{ver} does not match "
                    f"this code's v{STORAGE_LAYOUT_VERSION} — "
                    + _LAYOUT_GUIDANCE)
        return out

    def latest_step(self) -> int | None:
        return self._mngr.latest_step()

    def item_keys(self, step: int | None = None) -> set[str] | None:
        """Top-level keys of a saved checkpoint's pytree (version stamp
        excluded), or None when unknowable. Lets a restore build its
        template from what was actually SAVED — e.g. toggling
        RunConfig.checkpoint_replay between runs must not brick resume
        with an Orbax structure mismatch (the flag governs saves;
        restores follow the file)."""
        keys = self._raw_item_keys(step)
        if keys is not None:
            keys.discard(_LAYOUT_KEY)
        return keys

    def _raw_item_keys(self, step: int | None = None) -> set[str] | None:
        step = self.latest_step() if step is None else step
        if step is None:
            return None
        # in-memory metadata works once THIS manager has saved; a fresh
        # manager over an existing directory cannot infer the handler
        # (item_metadata returns tree=None), so fall back to orbax's
        # on-disk _METADATA, whose tree_metadata entries carry each
        # leaf's key path
        try:
            meta = self._mngr.item_metadata(step)
            tree = getattr(meta, "tree", meta)
            if tree is not None:
                return set(tree.keys())
        except Exception:
            pass
        import json
        path = os.path.join(self._dir, str(step), "default", "_METADATA")
        try:
            with open(path) as fh:
                tm = json.load(fh)["tree_metadata"]
            return {e["key_metadata"][0]["key"] for e in tm.values()}
        except Exception:  # layout varies across orbax versions
            return None

    def close(self) -> None:
        self._mngr.wait_until_finished()
        self._mngr.close()
