"""The batched ECDSA-P256 verify kernel, jitted over a device mesh.

Two entry points:

- `verify_flat`: one channel's (tx x sig) batch, lanes sharded over the
  mesh's "data" axis. The output mask is replicated, so XLA inserts the
  all-gather of per-shard masks over ICI (SURVEY.md §2.13 P6).
- `verify_channels`: a (channel, lane) stack — the kernel vmapped over a
  leading channel axis, channels sharded over "channel" and lanes over
  "data" (SURVEY.md §2.13 P3; reference channel objects are fully
  independent, core/peer/peer.go:337-408, so a pure batch dim is the
  exact semantic match).

Shapes must divide the mesh: lanes % data-axis == 0 and channels %
channel-axis == 0 (use `pad_lanes` / callers' bucket padding).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from fabric_tpu.parallel.mesh import CHANNEL_AXIS, DATA_AXIS


def pad_lanes(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


class ShardedVerify:
    """Holds the per-mesh jitted programs (one compile per shape, persisted
    in the XLA compilation cache)."""

    def __init__(self, mesh):
        self.mesh = mesh
        self._flat = None
        self._channels = None

    # ------------------------------------------------------------------
    @property
    def data_size(self) -> int:
        return self.mesh.shape[DATA_AXIS]

    @property
    def channel_size(self) -> int:
        return self.mesh.shape.get(CHANNEL_AXIS, 1)

    # ------------------------------------------------------------------
    def _sharding(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P(*spec))

    def _build_flat(self):
        import jax

        from fabric_tpu.ops.p256_kernel import verify_batch_device

        limb = self._sharding(None, DATA_AXIS)  # (20, B)
        mask = self._sharding(DATA_AXIS)  # (B,)
        replicated = self._sharding()
        return jax.jit(
            verify_batch_device,
            in_shardings=(limb,) * 5 + (mask,),
            out_shardings=replicated,  # all-gather of per-shard masks (P6)
        )

    def _build_channels(self):
        import jax

        from fabric_tpu.ops.p256_kernel import verify_batch_device

        if CHANNEL_AXIS in self.mesh.shape:
            limb = self._sharding(CHANNEL_AXIS, None, DATA_AXIS)  # (C, 20, B)
            mask = self._sharding(CHANNEL_AXIS, DATA_AXIS)  # (C, B)
        else:
            limb = self._sharding(None, None, DATA_AXIS)
            mask = self._sharding(None, DATA_AXIS)
        return jax.jit(
            jax.vmap(verify_batch_device),
            in_shardings=(limb,) * 5 + (mask,),
            out_shardings=mask,
        )

    # ------------------------------------------------------------------
    def verify_flat(
        self,
        e: np.ndarray,
        r: np.ndarray,
        s: np.ndarray,
        qx: np.ndarray,
        qy: np.ndarray,
        ok: np.ndarray,
    ) -> np.ndarray:
        """(20, B) limb arrays + (B,) mask -> (B,) bool, B % data == 0."""
        if e.shape[1] % self.data_size:
            raise ValueError(
                f"lane count {e.shape[1]} not divisible by data axis {self.data_size}"
            )
        if self._flat is None:
            self._flat = self._build_flat()
        return np.asarray(self._flat(e, r, s, qx, qy, ok))

    def channels_program(self):
        """The jitted channel-stack program (built once per mesh)."""
        if self._channels is None:
            self._channels = self._build_channels()
        return self._channels

    def dispatch_channels(self, e, r, s, qx, qy, ok):
        """`verify_channels` up to the launch: the (C, B) mask as the
        device array the jitted call returns, still sharded over the mesh
        and maybe still being computed.  A caller that times the launch
        and the wait apart, or reads where the output lives, copies it
        back itself."""
        c, _, b = e.shape
        if b % self.data_size or c % self.channel_size:
            raise ValueError(
                f"stack ({c}, {b}) not divisible by mesh "
                f"({self.channel_size}, {self.data_size})"
            )
        return self.channels_program()(e, r, s, qx, qy, ok)

    def verify_channels(
        self,
        e: np.ndarray,
        r: np.ndarray,
        s: np.ndarray,
        qx: np.ndarray,
        qy: np.ndarray,
        ok: np.ndarray,
    ) -> np.ndarray:
        """(C, 20, B) limb stacks + (C, B) mask -> (C, B) bool."""
        return np.asarray(self.dispatch_channels(e, r, s, qx, qy, ok))


def channel_stack(
    batches: Tuple[Tuple[np.ndarray, ...], ...],
    lanes: int,
    channels: int,
) -> Tuple[np.ndarray, ...]:
    """Pad each channel's (e, r, s, qx, qy, ok) arrays to `lanes` lanes,
    stack to (channels, ...) with dead (ok=False) rows for missing
    channels."""
    import fabric_tpu.ops.bignum as bn

    n_real = len(batches)
    out_limbs = [
        np.zeros((channels, bn.NLIMBS, lanes), dtype=np.uint32) for _ in range(5)
    ]
    out_ok = np.zeros((channels, lanes), dtype=bool)
    for c, batch in enumerate(batches):
        *limb_arrays, ok = batch
        n = ok.shape[0]
        for dst, src in zip(out_limbs, limb_arrays):
            dst[c, :, :n] = src
        out_ok[c, :n] = ok
    assert n_real <= channels
    return (*out_limbs, out_ok)
