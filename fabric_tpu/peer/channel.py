"""Per-channel peer pipeline (reference core/peer/peer.go createChannel
wiring + gossip/privdata/coordinator.go StoreBlock + the MCS block checks).

Block intake order matches the reference (SURVEY.md §3.1):
1. MCS.VerifyBlock: recompute DataHash, check the header chain, verify the
   orderer block signature when a verifier is configured
   (usable-inter-nal/peer/gossip/mcs.go:124);
2. txvalidator.Validate -> TRANSACTIONS_FILTER (signatures + policies,
   TPU-batched);
3. kvledger.commit -> MVCC merge + block store + state/history commit.
"""

from __future__ import annotations

from typing import Callable, Optional

from fabric_tpu.common import fabobs, flogging
from fabric_tpu.crypto.bccsp import Provider, default_provider
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.statedb import BlockPreload
from fabric_tpu.msp.identity import MSPManager
from fabric_tpu.protos import common_pb2, protoutil
from fabric_tpu.validation.blockparse import parse_block
from fabric_tpu.common.txflags import TxValidationCode, ValidationFlags
from fabric_tpu.validation.validator import BlockValidator, ChaincodeRegistry

logger = flogging.must_get_logger("committer")


class BlockVerificationError(Exception):
    pass


def _written_keys(parsed):
    """Every key the block writes, public and hashed, from the parse's
    columnar table: all the SBE gate asks of committed state, and (where a
    tx reads what it writes) all MVCC will."""
    keys, hashed_keys = [], []
    walk = getattr(parsed, "iter_written_keys", None)
    for _tx, ns, coll, key in walk() if walk is not None else ():
        if coll:
            hashed_keys.append((ns, coll, key))
        else:
            keys.append((ns, key))
    return keys, hashed_keys


class Channel:
    def __init__(
        self,
        channel_id: str,
        ledger_dir: str,
        msp_manager: MSPManager,
        registry: ChaincodeRegistry,
        provider: Optional[Provider] = None,
        verify_orderer_sig: Optional[Callable[[common_pb2.Block], bool]] = None,
        apply_config: Optional[Callable[[bytes], None]] = None,
        transient_store=None,  # gossip.coordinator.TransientStore
        fetch_pvt: Optional[Callable] = None,  # (blk, tx, txid, ns, coll) -> bytes|None
        is_eligible: Optional[Callable[[str, str], bool]] = None,
        btl_policy: Optional[Callable[[str, str], int]] = None,
        metrics=None,  # ledger.ledgermetrics.CommitterMetrics
        device_mvcc: bool = False,  # SURVEY P5 device fixpoint resolver
        writeset_check=None,  # legacy v12/v13 write-set guards
        plugin_registry=None,  # dispatcher.PluginRegistry (custom plugins)
        state_mirror=None,  # statecouch.CouchStateAdapter (public mirror)
    ):
        self.metrics = metrics
        self.channel_id = channel_id
        base_provider = provider or default_provider()
        # serve-plane QoS dispatch: a sidecar-routed provider binds this
        # channel's admission class (FABRIC_TPU_SERVE_QOS map) so the
        # shared sidecar sheds priority-aware — a spam channel's batches
        # carry its class, never the paying channel's.  Non-serve
        # providers have no for_channel and pass through unchanged.
        bind = getattr(base_provider, "for_channel", None)
        self.provider = bind(channel_id) if callable(bind) else base_provider
        self.ledger = KVLedger(
            ledger_dir, channel_id, btl_policy=btl_policy,
            device_mvcc=device_mvcc, state_mirror=state_mirror,
        )
        self.verify_orderer_sig = verify_orderer_sig
        self.transient_store = transient_store
        self.fetch_pvt = fetch_pvt
        self.is_eligible = is_eligible

        # the committed rows of the block whose policy stage is running,
        # read in bulk and then handed on to MVCC; None outside that stage
        self._committed: Optional[BlockPreload] = None

        def get_state_metadata(ns: str, coll: str, key) -> Optional[bytes]:
            # asked outside store_block, an empty one: point reads
            committed = self._committed or BlockPreload(self.ledger.state_db)
            if coll:
                return committed.hashed_metadata(ns, coll, key)
            return committed.metadata(ns, key)

        self.validator = BlockValidator(
            channel_id,
            msp_manager,
            self.provider,
            registry,
            tx_exists=self.ledger.tx_exists,
            apply_config=apply_config,
            get_state_metadata=get_state_metadata,
            writeset_check=writeset_check,
            plugin_registry=plugin_registry,
        )

    def prepare_block(self, block: common_pb2.Block):
        """Stage A of the commit pipeline (SURVEY.md §2.13 P4): orderer
        signature check, host parse, and the DEVICE signature batch —
        everything that may overlap the previous block's sequential
        MVCC/commit epilogue. Returns the opaque tuple store_block takes
        as `prepared`."""
        number = block.header.number
        with fabobs.span("prepare.content_check", block=number):
            self._verify_block_content(block)
        with fabobs.span("prepare.parse", block=number):
            parsed = parse_block(list(block.data.data))
        with fabobs.span("prepare.collect_sig_jobs", block=number):
            jobs, job_identity, keys, sigs, digests = (
                self.validator.collect_sig_jobs(parsed)
            )
        # dispatch WITHOUT waiting when the provider has an async seam
        # (device kernels, pool shards, the serve sidecar): the returned
        # resolver rides the prepared tuple and store_block collects the
        # verdicts at stage B — block N's signature math overlaps block
        # N-1's sequential commit epilogue across the full dispatch
        # ladder, not just inside one provider
        dispatch = getattr(self.provider, "batch_verify_async", None)
        if dispatch is None:
            ok_list = self.provider.batch_verify(keys, sigs, digests)
        else:
            ok_list = dispatch(keys, sigs, digests)
        return parsed, jobs, job_identity, ok_list

    def store_block(
        self, block: common_pb2.Block, prepared=None
    ) -> ValidationFlags:
        """The full commit pipeline for one delivered block. Envelopes are
        parsed once and the result shared between validation and commit;
        a pipelined deliver loop passes `prepared` from prepare_block run
        on another thread (P4 overlap).

        Private data is assembled coordinator-style (gossip/privdata/
        coordinator.go:149-209): transient store first, then the peer
        fetcher, with anything still missing recorded for the reconciler."""
        import time as _time

        t0 = _time.perf_counter()
        self._verify_block_position(block)
        if prepared is None:
            prepared = self.prepare_block(block)
        parsed, jobs, job_identity, ok_list = prepared
        number = block.header.number
        if callable(ok_list):
            # async-prepared tuple: resolve the verify dispatch now.  A
            # resolver failure raises here and surfaces through the
            # commit error path (the block is NOT committed — fail
            # closed), same as a synchronous batch_verify failure would.
            with fabobs.span("commit.await_verdicts", block=number):
                ok_list = ok_list()
        committed = BlockPreload(self.ledger.state_db)
        with fabobs.span("commit.validate", block=number) as validate_span:
            committed.load(*_written_keys(parsed))
            sig_results = self.validator.finish_sig_results(
                jobs, job_identity, ok_list
            )
            self._committed = committed
            try:
                flags = self.validator.validate(
                    block, parsed=parsed, sig_results=sig_results
                )
            finally:
                self._committed = None
            validate_span.set(**committed.account())
        t_validate = _time.perf_counter() - t0
        with fabobs.span("commit.rwsets", block=number):
            rwsets = [p.rwset for p in parsed]
            # materializing rwsets may demote txs the native walker accepted
            # but the Python parser rejects (ParsedTx.rwset divergence guard);
            # fold that into the filter BEFORE it is persisted so native and
            # pure-Python peers commit the same TRANSACTIONS_FILTER
            refilter = False
            for p in parsed:
                if p.code == TxValidationCode.BAD_RWSET and (
                    flags.flag(p.index) == TxValidationCode.VALID
                ):
                    flags.set_flag(p.index, TxValidationCode.BAD_RWSET)
                    rwsets[p.index] = None
                    refilter = True
            if refilter:
                block.metadata.metadata[common_pb2.TRANSACTIONS_FILTER] = (
                    flags.tobytes()
                )
        with fabobs.span("commit.assemble_pvt", block=number):
            pvt_data, missing = self._assemble_pvt_data(block, parsed, flags)
        result = self.ledger.commit(
            block, rwsets=rwsets, pvt_data=pvt_data, missing_pvt=missing,
            committed=committed,
        )
        if self.transient_store is not None:
            self.transient_store.purge_by_txids(
                [p.tx_id for p in parsed if p.tx_id]
            )
        timings = getattr(self.ledger, "last_commit_timings", {})
        logger.debug(
            "[%s] committed block [%d] in %dms (state_validation=%dms "
            "block_and_pvtdata_commit=%dms state_commit=%dms)",
            self.channel_id,
            block.header.number,
            int((t_validate + sum(timings.values())) * 1000),
            int(timings.get("state_validation", 0) * 1000),
            int(timings.get("block_and_pvtdata_commit", 0) * 1000),
            int(timings.get("state_commit", 0) * 1000),
        )
        if self.metrics is not None:
            self.metrics.observe_commit(
                self.channel_id,
                result,
                self.ledger.height,
                t_validate + timings.get("state_validation", 0.0),
                timings.get("block_and_pvtdata_commit", 0.0),
                timings.get("state_commit", 0.0),
            )
        return result

    def _assemble_pvt_data(self, block, parsed, flags):
        """(tx_num, ns, coll) -> cleartext KVRWSet bytes for every valid tx
        whose hashed rwset references a collection this peer is eligible
        for; plus MissingEntry records for what could not be found."""
        from fabric_tpu.ledger.pvtdatastore import MissingEntry

        pvt_data = {}
        missing = []
        wanted = []  # (tx_num, tx_id, ns, coll)
        arr = flags.asarray() if flags is not None else None
        for p in parsed:
            if arr is not None and arr[p.index] != 0:  # not VALID
                continue
            if p.rwset is None:
                continue
            for ns_rw in p.rwset.ns_rw_sets:
                for coll in ns_rw.coll_hashed:
                    if not coll.hashed_writes:
                        continue
                    if self.is_eligible is not None and not self.is_eligible(
                        ns_rw.namespace, coll.collection_name
                    ):
                        continue
                    wanted.append(
                        (p.index, p.tx_id, ns_rw.namespace, coll.collection_name)
                    )
        from fabric_tpu.ledger.kvledger import pvt_data_matches_hashes

        by_index = {p.index: p for p in parsed}
        for tx_num, tx_id, ns, coll in wanted:
            rwset = by_index[tx_num].rwset
            data = None
            if self.transient_store is not None and tx_id:
                data = self.transient_store.get(tx_id, ns, coll)
                if data is not None and not pvt_data_matches_hashes(
                    rwset, ns, coll, data
                ):
                    data = None
            if data is None and self.fetch_pvt is not None:
                data = self.fetch_pvt(block.header.number, tx_num, tx_id, ns, coll)
                # fetched from untrusted peers: a hash mismatch is treated
                # as missing, never an error (coordinator.go fetch path)
                if data is not None and not pvt_data_matches_hashes(
                    rwset, ns, coll, data
                ):
                    data = None
            if data is not None:
                pvt_data[(tx_num, ns, coll)] = data
            else:
                missing.append(MissingEntry(tx_num, ns, coll))
        return pvt_data, missing

    def _verify_block_content(self, block: common_pb2.Block) -> None:
        """Position-independent checks (MCS VerifyBlock: DataHash +
        orderer signature) — safe in pipeline stage A, before the
        preceding block committed."""
        if protoutil.block_data_hash(block.data) != block.header.data_hash:
            raise BlockVerificationError(
                "Header.DataHash is different from Hash(block.Data)"
            )
        if self.verify_orderer_sig is not None and not self.verify_orderer_sig(block):
            raise BlockVerificationError("orderer block signature invalid")

    def _verify_block_position(self, block: common_pb2.Block) -> None:
        """Chain-position checks — must run in commit order (stage B)."""
        if block.header.number != self.ledger.height:
            raise BlockVerificationError(
                f"expected block {self.ledger.height}, got {block.header.number}"
            )
        if (
            self.ledger.height > 0
            and block.header.previous_hash != self.ledger.block_store.last_block_hash
        ):
            raise BlockVerificationError("previous-hash mismatch")

    def _verify_block(self, block: common_pb2.Block) -> None:
        self._verify_block_position(block)
        self._verify_block_content(block)

    @property
    def height(self) -> int:
        return self.ledger.height
