"""The plain reference against the generator's own plan, and its controls:
the reference with one guarantee broken has to disagree with the reference.
(The same controls were run on the chip at the cells' own size: PERF.md.)"""

import pytest

from benchmarks import generator as gen
from benchmarks import reference as ref

CONFIG = {
    "channel": "testchan", "orgs": 3, "block_txs": 48, "poisons_per_kind": 2,
    "damaged_lanes_per_kind": 4,
    "policy_dsl": "OutOf(2, 'Org1MSP.member', 'Org2MSP.member', 'Org3MSP.member')",
}
POLICY = {"n": 2, "mspids": ["Org1MSP", "Org2MSP", "Org3MSP"]}
SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def world():
    return gen.build_world(CONFIG)


@pytest.fixture(scope="module")
def chain(world):
    entries, prev = [], b""
    for number in range(3):
        entry = gen.build_envelopes(world, CONFIG, number, SEED)
        entry["kept_envelopes"] = list(entry["envelopes"])
        prev = gen.seal_block(entry, number, prev)
        entries.append(entry)
    return entries


def ledger_of(world, chain, rule=None):
    membership = ref.Membership(gen.msp_roots(world))
    ledger = ref.Ledger(rule)
    rows_of = []
    for entry in chain:
        number, envelopes = ref.block_envelopes(entry["raw"])
        rows = ref.check_signatures_and_policy(envelopes, membership, POLICY, rule)
        ledger.commit(number, rows)
        rows_of.append(rows)
    return ledger, rows_of


def test_the_reference_finds_exactly_the_planted_poisons(world, chain):
    ledger, _ = ledger_of(world, chain)
    assert ledger.height == 3
    for entry, got in zip(chain, ledger.filters):
        want = bytearray(CONFIG["block_txs"])
        for i, code in entry["codes"].items():
            want[i] = code
        assert got == bytes(want)
        assert len(entry["codes"]) == 8
        for key, value in entry["state"].items():
            assert ledger.get(gen.CHAINCODE, key) == value


def test_the_wire_reader_sees_what_the_generator_built(chain):
    number, envelopes = ref.block_envelopes(chain[1]["raw"])
    assert number == 1
    assert envelopes == chain[1]["kept_envelopes"]


@pytest.mark.parametrize("rule, flipped_per_block", [
    ("accept_high_s", 2), ("skip_policy", 4), ("skip_mvcc", 2),
])
def test_each_control_disagrees_with_the_reference(world, chain, rule, flipped_per_block):
    truth, _ = ledger_of(world, chain)
    control, _ = ledger_of(world, chain, rule)
    differing = sum(
        1 for a, b in zip(truth.filters, control.filters)
        for x, y in zip(a, b) if x != y
    )
    assert differing == flipped_per_block * len(chain)
    assert truth.state != control.state


def test_a_served_request_and_its_control(world, chain):
    request = gen.block_lanes(world, CONFIG, chain[0]["kept_envelopes"], SEED, 0)
    assert request["lanes"] == 3 * CONFIG["block_txs"] - 2  # 2 short endorsements
    mask = ref.verify_lanes(request["points"], request["sigs"], request["digests"])
    # false: 4 no-key + 4 garbage + 2 bad creator + 2 high-S, less overlaps
    assert 8 <= sum(1 for v in mask if not v) <= 12
    for lane, point in enumerate(request["points"]):
        if point is None or request["sigs"][lane] == b"\x30\x07garbage":
            assert mask[lane] is False
    control = ref.verify_lanes(
        request["points"], request["sigs"], request["digests"], "accept_high_s"
    )
    flipped = [i for i, (a, b) in enumerate(zip(mask, control)) if a != b]
    assert 1 <= len(flipped) <= 2 and all(control[i] for i in flipped)


def test_the_reference_agrees_with_the_programs_software_provider(world, chain):
    """A second witness for the lane rule, at a size a test can hold."""
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey, SoftwareProvider

    request = gen.block_lanes(world, CONFIG, chain[2]["kept_envelopes"], SEED, 2)
    keys = [None if p is None else ECDSAPublicKey(*p) for p in request["points"]]
    theirs = SoftwareProvider().batch_verify(keys, request["sigs"], request["digests"])
    ours = ref.verify_lanes(request["points"], request["sigs"], request["digests"])
    assert [bool(v) for v in theirs] == ours
