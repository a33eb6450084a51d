"""One sidecar serving several peers: requests of several clients coalesce
into one launch and each gets exactly its own lanes' verdicts; the ``verify``
warm ladder builds the program the provider serves with, at each bucket and
no other; the batcher's coalescing spans and its launches by bucket; a launch
never grows past the widest warmed bucket, nor takes a request it would pad
past that request's own launch; the dispatcher launches while a launch is
unsettled, and a batch waits for company while the device is busy.  Host
engine or a planted kernel: nothing is compiled here."""

import random
import sys
import threading
import time

import pytest

from conftest import requires_crypto

from fabric_tpu.common.fabobs import obs_installed
from fabric_tpu.crypto.bccsp import SoftwareProvider
from fabric_tpu.parallel.batcher import VerifyBatcher
from fabric_tpu.serve.registry import bucket_for


def _spans(reg, name):
    return [
        e for e in reg.trace_events()
        if e.get("ph") == "X" and e["name"] == name
    ]


class Padded(SoftwareProvider):
    """The host engine, padding launches to a ladder as the device does,
    and recording each launch's width."""

    def __init__(self, buckets):
        super().__init__()
        self.buckets = buckets
        self.launch_lanes = []

    def bucket_for(self, lanes):
        return bucket_for(lanes, self.buckets)

    def batch_verify_async(self, keys, sigs, digests):
        self.launch_lanes.append(len(keys))
        out = SoftwareProvider.batch_verify(self, keys, sigs, digests)
        return lambda: out


@requires_crypto
def test_four_clients_coalesce_and_each_gets_its_own_mask(tmp_path):
    from benchmarks import generator as gen
    from benchmarks import reference as ref
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey
    from fabric_tpu.serve.client import SidecarProvider
    from fabric_tpu.serve.server import SidecarServer

    cfg = {
        "channel": "fleetchan", "block_txs": 64, "orgs": 3,
        "policy_dsl": "OutOf(2, 'Org1MSP.member', 'Org2MSP.member', "
                      "'Org3MSP.member')",
        "poisons_per_kind": 2, "damaged_lanes_per_kind": 8,
    }
    world = gen.build_world(cfg)
    requests = []
    for number in range(4):
        built = gen.build_envelopes(world, cfg, number, 2147483711)
        requests.append(
            gen.block_lanes(world, cfg, built["envelopes"], 2147483711, number)
        )
    provider = Padded((256, 512, 1024))
    # a linger long enough that the four requests, sent at once, meet
    server = SidecarServer(
        str(tmp_path / "fleet.sock"), engine="host", provider=provider,
        warm_ladder="verify", buckets=(256, 512, 1024), linger_s=0.3,
    )
    server.warm()
    server.start()
    masks = {}
    go = threading.Barrier(4)

    def peer(n):
        req = requests[n]
        keys = [None if p is None else ECDSAPublicKey(*p) for p in req["points"]]
        client = SidecarProvider(address=server.address,
                                 fallback=SoftwareProvider())
        try:
            go.wait()
            masks[n] = client.batch_verify(keys, req["sigs"], req["digests"])
            assert client.degraded is False
        finally:
            client.client.close()

    try:
        threads = [threading.Thread(target=peer, args=(n,)) for n in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
    finally:
        server.stop()
    assert max(provider.launch_lanes) >= 2 * requests[0]["lanes"]
    assert sum(provider.launch_lanes) == sum(q["lanes"] for q in requests)
    for n, req in enumerate(requests):
        want = ref.verify_lanes(req["points"], req["sigs"], req["digests"])
        assert masks[n] == want
        assert not all(want)


class Recording(SoftwareProvider):
    def __init__(self):
        super().__init__()
        self.warmed = []

    def warm_bucket(self, lanes):
        self.warmed.append(lanes)


def test_verify_warm_builds_the_served_program_at_each_bucket(tmp_path):
    from fabric_tpu.serve import registry
    from fabric_tpu.serve.server import SidecarServer

    assert not hasattr(registry, "verify_limb_program")
    provider = Recording()
    server = SidecarServer(
        str(tmp_path / "w.sock"), engine="host", provider=provider,
        warm_ladder="verify", buckets=(2048, 4096, 8192),
    )
    try:
        report = server.warm()
    finally:
        server.stop()
    assert provider.warmed == [2048, 4096, 8192]
    assert {b: r["program"] for b, r in report["per_bucket"].items()} == {
        "2048": "served", "4096": "served", "8192": "served",
    }
    assert report["traces"] == 0  # nothing traced through the jax builder
    bucket, program = server.registry.program_for(3000)
    assert bucket == 4096 and program == provider.batch_verify


class _Program:
    """A jitted function as TPUProvider uses it: lowered once a shape,
    then called; records both."""

    def __init__(self):
        self.lowered, self.called = [], []

    def lower(self, *operands):
        self.lowered.append(operands[0].shape)

    def __call__(self, *operands):
        self.called.append(tuple(a.shape for a in operands))
        return operands[-1]


def test_a_warmed_width_is_served_by_the_program_it_warmed():
    from fabric_tpu.crypto.tpu_provider import TPUProvider

    provider = TPUProvider()
    provider._lowered = set()
    bytes_program, limb_program = _Program(), _Program()
    provider._pk = type("Kernel", (), {
        "verify_batch_bytes_jit": bytes_program,
        "verify_batch_jit": limb_program,
    })()
    for bucket in (2048, 4096):
        provider.warm_bucket(bucket)
    assert bytes_program.lowered == [(2048, 32), (4096, 32)]
    warmed_call = bytes_program.called[-1]
    resolve = provider.batch_verify_async(
        [None] * 3000, [b"\x30\x07garbage"] * 3000, [bytes(32)] * 3000
    )
    assert resolve() == [False] * 3000
    # the same program, the same argument shapes, lowered no second time
    assert bytes_program.called[-1] == warmed_call
    assert bytes_program.lowered == [(2048, 32), (4096, 32)]
    assert limb_program.lowered == limb_program.called == []
    assert TPUProvider.bucket_for(3000) == 4096


class _Gated:
    """Async provider whose launches wait on a gate; pads to a ladder."""

    def __init__(self):
        self.gate = threading.Event()
        self.launch_lanes = []

    def bucket_for(self, lanes):
        return bucket_for(lanes, (4, 8, 16))

    def batch_verify_async(self, keys, sigs, digests):
        self.gate.wait(10)
        self.launch_lanes.append(len(keys))
        return lambda: [True] * len(keys)


def test_linger_inflight_and_the_bucket_counter_read_as_launches_happen():
    provider = _Gated()
    with obs_installed() as reg:
        batcher = VerifyBatcher(provider, linger_s=0.0)
        try:
            first = batcher.submit([1] * 3, [b"s"] * 3, [b"d"] * 3)
            time.sleep(0.05)  # the dispatcher holds the first launch
            later = [batcher.submit([1] * 4, [b"s"] * 4, [b"d"] * 4)
                     for _ in range(2)]
            provider.gate.set()
            assert first() == [True] * 3
            assert [r() for r in later] == [[True] * 4] * 2
        finally:
            provider.gate.set()
            batcher.stop()
        launches = _spans(reg, "batcher.launch")
        lingers = _spans(reg, "batcher.linger")
        counted = reg.snapshot()["fabric_batcher_launches_by_bucket_total"]
    assert provider.launch_lanes == [3, 8]
    assert [e["args"]["requests"] for e in launches] == [1, 2]
    # the second was dispatched while the first was still unsettled
    assert [e["args"]["inflight"] for e in launches] == [0, 1]
    assert [e["args"]["joined"] for e in lingers] == [0, 1]
    assert counted["series"] == {"bucket=4": 1, "bucket=8": 1}


def test_a_launch_never_grows_past_the_widest_warm_bucket():
    provider = _Gated()
    batcher = VerifyBatcher(provider, linger_s=0.0, max_launch_lanes=8)
    try:
        first = batcher.submit([1] * 4, [b"s"] * 4, [b"d"] * 4)
        time.sleep(0.05)
        # three more queue behind the held launch: two fit one launch of 8,
        # the third would take it to 12 and leads the next
        later = [batcher.submit([1] * 4, [b"s"] * 4, [b"d"] * 4)
                 for _ in range(3)]
        provider.gate.set()
        assert [r() for r in [first] + later] == [[True] * 4] * 4
    finally:
        provider.gate.set()
        batcher.stop()
    assert provider.launch_lanes == [4, 8, 4]


@pytest.mark.parametrize("cap", [None, 2048])
def test_one_client_lingers_the_same_under_the_cap(cap):
    """A lone 1,498-lane request waits the full linger for company, cap or
    no cap (what a one-client sidecar sees)."""
    provider = Padded((2048, 4096, 8192))
    with obs_installed() as reg:
        batcher = VerifyBatcher(provider, linger_s=0.05, max_launch_lanes=cap)
        try:
            keys = [None] * 1498
            assert batcher.submit(keys, [b""] * 1498, [bytes(32)] * 1498)() \
                == [False] * 1498
        finally:
            batcher.stop()
        (linger,) = _spans(reg, "batcher.linger")
    assert linger["dur"] >= 50e3 and linger["args"]["joined"] == 0


class _Busy:
    """Async provider whose verdicts wait on a gate, as a busy device's
    do; it pads to a ladder unless ``padded`` is False (a host pool)."""

    def __init__(self, padded=True):
        self.gate = threading.Event()
        self.launch_lanes = []
        if not padded:
            self.bucket_for = None

    def bucket_for(self, lanes):
        return bucket_for(lanes, (4, 8, 16))

    def batch_verify_async(self, keys, sigs, digests):
        self.launch_lanes.append(len(keys))
        n = len(keys)

        def resolve():
            self.gate.wait(10)
            return [True] * n

        return resolve

    def launched(self, n):
        deadline = time.monotonic() + 5
        while len(self.launch_lanes) < n and time.monotonic() < deadline:
            time.sleep(0.005)
        return list(self.launch_lanes)


def _submit(batcher, lanes):
    return batcher.submit([1] * lanes, [b"s"] * lanes, [b"d"] * lanes)


def test_the_dispatcher_launches_while_the_last_launch_is_unsettled():
    """Verdicts are waited on by the settler: a request that arrives while
    the last launch runs is dispatched behind it at once."""
    provider = _Busy()
    batcher = VerifyBatcher(provider, linger_s=0.0)
    try:
        first = _submit(batcher, 4)
        assert provider.launched(1) == [4]
        second = _submit(batcher, 4)
        assert provider.launched(2) == [4, 4]
        provider.gate.set()
        assert first() == second() == [True] * 4
    finally:
        provider.gate.set()
        batcher.stop()


class _Held(_Gated):
    """A launch's dispatch waits on the gate: requests queue behind it."""

    def bucket_for(self, lanes):
        return bucket_for(lanes, (4, 8, 16))


def test_a_request_that_would_pad_the_launch_leads_the_next():
    """Three 3-lane requests queued: two share a launch of 8; the third
    would pad it to 16, twice the 4 its own launch takes."""
    provider = _Held()
    batcher = VerifyBatcher(provider, linger_s=0.0)
    try:
        first = _submit(batcher, 3)
        time.sleep(0.05)
        later = [_submit(batcher, 3) for _ in range(3)]
        provider.gate.set()
        assert [r() for r in [first] + later] == [[True] * 3] * 4
    finally:
        provider.gate.set()
        batcher.stop()
    assert provider.launch_lanes == [3, 6, 3]


@pytest.mark.parametrize("padded, launches", [
    (True, [8, 8]), (False, [8, 4, 4]),
])
def test_a_batch_waits_for_company_while_the_device_is_busy(padded, launches):
    """With twice its width in flight, a lone request lingers on until a
    second joins it; a host pool's launches run side by side, so there
    the usual linger ends the wait."""
    provider = _Busy(padded=padded)
    with obs_installed() as reg:
        batcher = VerifyBatcher(provider, linger_s=0.005)
        try:
            busy = _submit(batcher, 8)
            assert provider.launched(1) == [8]
            pair = [_submit(batcher, 4)]
            time.sleep(0.1)  # twenty lingers
            pair.append(_submit(batcher, 4))
            assert provider.launched(len(launches)) == launches
            provider.gate.set()
            assert busy() == [True] * 8
            assert [r() for r in pair] == [[True] * 4] * 2
        finally:
            provider.gate.set()
            batcher.stop()
        lingers = _spans(reg, "batcher.linger")
    if padded:
        assert lingers[1]["args"]["joined"] == 1
        assert lingers[1]["dur"] >= 0.1e6


class _Jittery:
    """Pads to a ladder; each launch's verdicts come back after a random
    short wait, as a busy device's do, and say each lane's own key."""

    def __init__(self):
        self.rng = random.Random(7)
        self.lock = threading.Lock()

    def bucket_for(self, lanes):
        return bucket_for(lanes, (4, 8, 16, 32, 64))

    def batch_verify_async(self, keys, sigs, digests):
        with self.lock:
            wait = self.rng.random() * 0.004
        out = [k % 3 == 0 for k in keys]

        def resolve():
            time.sleep(wait)
            return out

        return resolve


def test_many_submitters_each_get_their_own_lanes_under_a_short_switch_interval():
    """The dispatcher, the settler and 24 submitters share the batcher's
    queue and its launches in flight: every request gets exactly its own
    lanes' verdicts, and every lane is launched once."""
    provider = _Jittery()
    batcher = VerifyBatcher(provider, linger_s=0.001, max_launch_lanes=64)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    wrong, lanes = [], []
    try:
        def submitter(n):
            rng = random.Random(n)
            for _ in range(8):
                keys = [rng.randrange(1000) for _ in range(rng.randint(1, 12))]
                lanes.append(len(keys))
                got = batcher.submit(keys, [b"s"] * len(keys), [b"d"] * len(keys))()
                if got != [k % 3 == 0 for k in keys]:
                    wrong.append(n)

        threads = [threading.Thread(target=submitter, args=(n,)) for n in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        batcher.stop()
    assert wrong == [] and len(lanes) == 24 * 8
    assert batcher.lanes == sum(lanes)
