"""Multi-channel validation in one device step (SURVEY.md §2.13 P3;
BASELINE config #5: 4 channels x 2k-tx blocks sharded over the mesh).

The reference validates channels in fully independent per-channel
Channel objects (core/peer/peer.go:337-408) — process-level parallelism.
The TPU-native form: collect one block per channel, host-parse each,
flatten every channel's signature jobs to fixed-shape lanes, stack on a
leading channel axis, and run ONE sharded program; per-channel masks
come back in a single device step, then each channel finishes its
host-side phases (principal matching, policy circuits, dup-TxID) exactly
as in the single-channel path.

Observability (common/fabobs.py), per step and per channel, never per
transaction or lane: ``mc.validate`` (``channels``, ``lanes``,
``bucket``) > ``mc.prepare`` (one per channel; children ``mc.parse``,
``mc.collect_sig_jobs``, ``mc.prep_limbs``), ``mc.stack``,
``mc.dispatch`` (the jitted call returning), ``mc.resolve`` (the wait
for the devices and the mask's copy back), ``mc.epilogue`` (one per
channel).  Every span carries ``step=``, the per-channel ones
``channel=``.  ``fabric_verify_lanes_total{rung="device"}`` counts the
real lanes of every channel once the mask is back, as TPUProvider's
resolver does.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, FrozenSet, Set, Tuple

import numpy as np

from fabric_tpu.common import fabobs
from fabric_tpu.crypto.tpu_provider import TPUProvider, _bucket, _on_fresh_stack
from fabric_tpu.parallel.sharded import ShardedVerify, channel_stack, pad_lanes
from fabric_tpu.protos import common_pb2
from fabric_tpu.validation.blockparse import parse_block
from fabric_tpu.common.txflags import ValidationFlags
from fabric_tpu.validation.validator import BlockValidator


def _annotation(name: str):
    """What an executed fabobs span also is: a ``TraceAnnotation`` of its
    name on the profiler's clock (nothing while fabobs is off)."""
    if not fabobs.enabled():
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class MultiChannelValidator:
    """Validates one block per channel in a single sharded device batch."""

    # wall time of the last validate() call's sharded step (the jitted
    # call entered -> masks on the host), for duty-cycle reporting
    last_device_ms = 0.0
    # ids of the devices the last step's output lived on
    last_device_ids: FrozenSet[int] = frozenset()
    # validate() calls so far: the `step=` of the next call's spans
    _step = 0

    def __init__(self, mesh, validators: Dict[str, BlockValidator]):
        self.validators = dict(validators)
        self.sharded = ShardedVerify(mesh)
        # host prep (DER parse, key-limb cache) shared across channels
        self._prep = TPUProvider()
        # stack shapes whose program this validator has traced and lowered
        self._lowered: Set[Tuple[int, ...]] = set()

    def _dispatch(self, stacked):
        """The sharded program's output, still on the devices.  Before the
        first call of a stack shape the program is traced and lowered for
        it on an empty Python stack (tpu_provider._on_fresh_stack: the
        time of that deep recursion hangs on the caller's frames); the
        call itself then finds both cached and only compiles or loads."""
        shape = stacked[0].shape
        if shape not in self._lowered:
            _on_fresh_stack(self.sharded.channels_program().lower, *stacked)
            self._lowered.add(shape)
        return self.sharded.dispatch_channels(*stacked)

    def validate(
        self, blocks: Dict[str, common_pb2.Block]
    ) -> Dict[str, ValidationFlags]:
        channels = sorted(blocks)
        unknown = [c for c in channels if c not in self.validators]
        if unknown:
            raise KeyError(f"no validator for channels {unknown}")
        step = self._step
        self._step = step + 1

        with fabobs.span(
            "mc.validate", step=step, channels=len(channels)
        ) as whole:
            # phase 1+2 host prep per channel
            per_channel = {}
            batches = []  # each channel's (e, r, s, qx, qy, ok), in order
            lanes = real_lanes = 0
            t_prepare = time.perf_counter()
            for ch in channels:
                with fabobs.span("mc.prepare", step=step, channel=ch) as prepare:
                    validator = self.validators[ch]
                    block = blocks[ch]
                    with fabobs.span("mc.parse", step=step, channel=ch):
                        parsed = parse_block(list(block.data.data))
                    with fabobs.span(
                        "mc.collect_sig_jobs", step=step, channel=ch
                    ):
                        jobs, job_identity, keys, sigs, digests = (
                            validator.collect_sig_jobs(parsed)
                        )
                    with fabobs.span("mc.prep_limbs", step=step, channel=ch):
                        limbs = self._prep.prep_limbs(keys, sigs, digests)
                    n = limbs[-1].shape[0]
                    prepare.set(lanes=n)
                per_channel[ch] = (validator, block, parsed, jobs, job_identity, n)
                batches.append(limbs)
                lanes = max(lanes, n)
                real_lanes += n
            prepare_s = time.perf_counter() - t_prepare

            # one fixed-shape device step for every channel
            lanes = pad_lanes(_bucket(max(lanes, 1)), self.sharded.data_size)
            whole.set(lanes=real_lanes, bucket=lanes)
            n_channels = pad_lanes(len(channels), self.sharded.channel_size)
            with fabobs.span("mc.stack", step=step):
                stacked = channel_stack(tuple(batches), lanes, n_channels)
            # three clock reads bound the two spans, `last_device_ms` and
            # the histogram alike
            t_dispatch = time.perf_counter()
            with _annotation("mc.dispatch"):
                out = self._dispatch(stacked)
                t_resolve = time.perf_counter()
            with _annotation("mc.resolve"):
                masks = np.asarray(out)  # the one copy back, sliced below
                t_done = time.perf_counter()
            fabobs.obs_record_span(
                "mc.dispatch", t_dispatch, t_resolve,
                step=step, lanes=real_lanes, bucket=lanes,
            )
            fabobs.obs_record_span(
                "mc.resolve", t_resolve, t_done, step=step, lanes=real_lanes
            )
            self.last_device_ms = (t_done - t_dispatch) * 1000.0
            self.last_device_ids = frozenset(
                d.id for d in out.sharding.device_set
            )
            fabobs.obs_count(
                "fabric_verify_lanes_total", real_lanes, rung="device"
            )
            fabobs.obs_observe(
                "fabric_verify_seconds",
                prepare_s + t_done - t_dispatch, rung="device",
            )

            # per-channel host epilogue
            result: Dict[str, ValidationFlags] = {}
            for c, ch in enumerate(channels):
                validator, block, parsed, jobs, job_identity, n = per_channel[ch]
                with fabobs.span("mc.epilogue", step=step, channel=ch):
                    # masks is already a host ndarray (materialized once above)
                    ok_list = [bool(v) for v in masks[c, :n]]
                    sig_results = validator.finish_sig_results(
                        jobs, job_identity, ok_list
                    )
                    result[ch] = validator.validate(
                        block, parsed, sig_results=sig_results
                    )
        return result
