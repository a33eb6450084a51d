"""One rehearsal of the cell ``sidecar-fleet`` on a CPU, in a
process of its own: the driver forks four peer processes, which a test
process whose threads may hold locks must not do.  The sidecar runs the
host engine with a provider that pads launches to the configuration's
buckets (as TPUProvider does), at --rehearse-on-cpu's 64-tx blocks; no
kernel is compiled.  ``--fault`` plants one fault:

    python -m benchmarks.tests.fleet_rehearsal [--fault NAME] [--control RULE]

The last line of standard output is the run's result line (exit code as
benchmarks/run.py's)."""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmarks import harness as hs  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.drivers import sidecar_fleet as fleet  # noqa: E402

WORKLOAD = "sidecar-fleet"
FAULTS = ("none", "swapped_masks", "peer_dies", "unwarmed_width",
          "exhausted_backlog", "parent_tree")
# a launch of more lanes than one 64-tx request's holds two requests or more
ONE_REQUEST_LANES = 256


def padded_provider(buckets, swap: bool = False):
    """The program's SoftwareProvider, padding its launches to the ladder
    (and, with ``swap``, handing back a coalesced launch's two halves in
    each other's place: two requests' masks swapped)."""
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.serve.registry import bucket_for

    class Padded(SoftwareProvider):
        def bucket_for(self, lanes):
            return bucket_for(lanes, buckets)

        def batch_verify_async(self, keys, sigs, digests):
            out = list(SoftwareProvider.batch_verify(self, keys, sigs, digests))
            if swap and len(out) > ONE_REQUEST_LANES:
                half = len(out) // 2
                out = out[half:] + out[:half]
            # the device's time: the peers' next requests queue meanwhile
            time.sleep(0.02)
            return lambda: out

    return Padded


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=FAULTS, default="none")
    ap.add_argument("--control", default=None)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    extra = ["--control", opts.control] if opts.control else []
    args = bench_run.parse_args(
        ["--workload", WORKLOAD, "--seed", "2147483677", "--seconds",
         str(opts.seconds), "--trace", str(opts.trace), "--rehearse-on-cpu",
         *extra]
    )
    loaded = bench_run.load_cell(WORKLOAD)
    buckets = tuple(loaded["config"]["buckets"])
    loaded["traffic"].update(workers=4, requests_built_per_second=80)
    factory = padded_provider(buckets, swap=opts.fault == "swapped_masks")
    if opts.fault == "unwarmed_width":
        from fabric_tpu.crypto.bccsp import SoftwareProvider

        factory = SoftwareProvider  # launches at their own widths
    elif opts.fault == "exhausted_backlog":
        loaded["traffic"].update(requests_built_per_second=1)
    elif opts.fault == "peer_dies":
        real_main = fleet._peer_main

        def dying(conn, peer, *rest):
            if peer == 1:
                fleet._Peer.window = lambda self, t0, t_end: os._exit(3)
            real_main(conn, peer, *rest)

        fleet._peer_main = dying
    elif opts.fault == "parent_tree":
        from fabric_tpu.serve.server import SidecarServer

        del SidecarServer._warm_served

        def touched(self):
            raise AssertionError("JAX touched on a tree that cannot run the cell")

        hs.Run.start_backend = touched
    r = hs.Run(
        WORKLOAD, loaded["config"], loaded["traffic"], args.seed,
        args.seconds, bool(args.trace), True, time.perf_counter(),
        controls=bench_run.controls_of(args), provider_factory=factory,
        serve_engine="host",
    )
    return bench_run.one_run(r, args, loaded)


if __name__ == "__main__":
    sys.exit(main())
