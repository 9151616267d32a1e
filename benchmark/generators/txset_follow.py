"""Traffic driver `txset_follow`: a validator that follows. The node
under test is one of three validators (threshold 2) and never the one
whose set wins: for every slot it is handed the tx set and the other
two validators' signed SCP envelopes through the calls the overlay
makes, `Herder.recv_tx_set` and `Herder.recv_scp_envelope`, and does the
rest itself: SCP asks `validate_value` for the set, the set's
signatures go to the device as one batch, the node votes, the slot
externalizes and the ledger closes.

The other two validators are recorded, not run, in the window. In
set-up they are two nodes of this program with the native per-signature
verifier (the plain reference) on one virtual clock, the third
validator absent: each ledger's payments are admitted by both, SCP
between the two decides the slot, both close it, and every envelope
either emits is kept in the order it was emitted, with the slot's tx
set as bytes. The window plays all of them to the node under test, on
the real-time clock, one slot after the other.

Parameters (the traffic file): `amounts`; `recorded_ledgers` (payment
ledgers the publisher records; the first goes through the node before
the window, to load the device program); `min_ledgers` (the window
closes at least that many, however long they take); `corrupted`
(signatures flipped in the set the check refuses); `oracle_sample`
(verdicts of that set, beside the flipped ones, that are held against
the pure-Python oracle; the rest against the publisher's, which applied
them). None of a set's signatures is in the verify cache when the set
arrives (the configuration's first `assumed` line).
"""

import hashlib
import random
import struct
import time

from stellar_core_tpu.crypto.keys import SecretKey, clear_verify_cache
from stellar_core_tpu.crypto.strkey import StrKey
from stellar_core_tpu.herder.tx_set import TxSetFrame
from stellar_core_tpu.history import is_checkpoint_ledger
from stellar_core_tpu.main import Application
from stellar_core_tpu.scp import ValidationLevel
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.xdr.ledger import StellarValue
from stellar_core_tpu.xdr.scp import SCPEnvelope, SCPStatementType
from stellar_core_tpu.xdr.types import CryptoKeyType, EnvelopeType

from benchmark.generators.payments import PaymentTraffic, submit
from benchmark.harness import node
from benchmark.harness.checks import Check
from benchmark.reference import ed25519_oracle
from benchmark.reference.ledger_model import LedgerModel

VALIDATORS = 3
FOLLOWER = 2            # the node under test; 0 and 1 are recorded


def validator_keys(seed: int) -> list:
    return [SecretKey.from_seed(hashlib.sha256(
        b"benchmark-validator-%d-%d" % (int(seed), i)).digest())
        for i in range(VALIDATORS)]


def node_doc(node_cfg: dict, keys: list, index: int) -> dict:
    """The configuration's `node` table as validator `index` of the
    quorum: docs/stellar-core-tpu_testnet_validator.cfg with the
    benchmark's seeds."""
    doc = dict(node_cfg)
    qset = dict(doc.get("QUORUM_SET", {}))
    qset["VALIDATORS"] = [
        StrKey.encode_ed25519_public(k.public_key().raw) for k in keys]
    doc["QUORUM_SET"] = qset
    doc["NODE_SEED"] = StrKey.encode_ed25519_seed(keys[index].seed) \
        + " self"
    return doc


class Slot:
    """What the quorum sent for one slot, and what came of it."""
    __slots__ = ("seq", "set_hash", "set_bytes", "generalized", "envelopes",
                 "header_hash", "payments", "txs", "frame")

    def __init__(self, seq):
        self.seq = seq
        self.envelopes = []        # bytes, in the order they were emitted
        self.payments = []         # (source index, destination, amount)
        self.frame = None


class Publisher:
    """Validators 0 and 1 in set-up: two nodes of the program on one
    virtual clock with the native verifier, wired herder to herder (no
    overlay), validator 2 absent. `close(frames)` gives both the
    frames, cranks until both have closed the next ledger and returns
    its `Slot`."""

    def __init__(self, config: dict, keys: list, workdir: str,
                 slots_ahead: int):
        self.clock = VirtualClock(ClockMode.VIRTUAL_TIME)
        # the recorded close times lie minutes in the past of the node
        # that will follow, however fast set-up runs: the virtual clock
        # starts far enough back for every slot's trigger and timeouts
        self.apps = []
        over = config.get("publisher_overrides")
        self.close_every = None
        for i in (0, 1):
            cfg = node.make_config(node_doc(config["node"], keys, i),
                                   f"{workdir}/validator{i}",
                                   overrides=over)
            if self.close_every is None:
                self.close_every = cfg.EXPECTED_LEDGER_CLOSE_TIME
                self.clock.set_virtual_time(
                    time.time() - 3 * self.close_every * slots_ahead - 60)
            self.apps.append(Application.create(self.clock, cfg,
                                                new_db=True))
        self.emitted = []           # (slot, bytes) in emission order
        for app in self.apps:
            self._wire(app)
        for app in self.apps:
            app.start()

    def _wire(self, app) -> None:
        other = self.apps[1 - self.apps.index(app)]

        def broadcast(env):
            self.emitted.append((env.statement.slotIndex, env.to_bytes()))
            self.clock.post(lambda: other.herder.recv_scp_envelope(env))
        app.herder.broadcast_cb = broadcast

        def fetch_txset(h):
            def fetch():
                ts = other.herder.pending_envelopes.get_tx_set(h)
                if ts is not None:
                    app.herder.recv_tx_set(h, ts)
            self.clock.post(fetch)
        app.herder.pending_envelopes.request_txset = fetch_txset

    @property
    def lcl(self) -> int:
        return min(a.ledger_manager.get_last_closed_ledger_num()
                   for a in self.apps)

    def close(self, frames=()) -> Slot:
        seq = self.lcl + 1
        for app in self.apps:
            submit(app, frames)
        deadline = self.clock.now() + 20 * self.close_every
        while self.lcl < seq:
            if self.clock.now() > deadline:
                raise RuntimeError(f"set-up: the two validators did not "
                                   f"close ledger {seq}")
            if self.clock.crank(False) == 0:
                self.clock.crank(True)
        a, b = self.apps
        slot = Slot(seq)
        slot.header_hash = a.ledger_manager.get_last_closed_ledger_hash()
        if b.ledger_manager.get_last_closed_ledger_hash() \
                != slot.header_hash:
            raise RuntimeError(f"set-up: the validators disagree on "
                               f"ledger {seq}")
        header = a.ledger_manager.get_last_closed_ledger_header()
        slot.set_hash = bytes(header.scpValue.txSetHash)
        frame = a.herder.pending_envelopes.get_tx_set(slot.set_hash)
        slot.set_bytes = frame.to_bytes()
        slot.generalized = frame.is_generalized
        slot.txs = frame.size_tx_total()
        if slot.txs != len(frames):
            raise RuntimeError(f"set-up: ledger {seq} holds {slot.txs} "
                               f"transactions of {len(frames)} given")
        return slot

    def take_envelopes(self, slots: dict) -> None:
        """Hand every envelope emitted so far to its slot."""
        for seq, raw in self.emitted:
            if seq in slots:
                slots[seq].envelopes.append(raw)
        self.emitted = []

    def header_hashes(self) -> dict:
        self.apps[0].ledger_manager.join_completion()
        return {int(seq): bytes(h)
                for seq, h in self.apps[0].database.query_all(
                    "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}

    def shutdown(self) -> None:
        for app in self.apps:
            app.shutdown()
        self.apps = []


def parse_set(slot: Slot, network_id: bytes) -> TxSetFrame:
    """The slot's tx set as the overlay hands it to the herder: parsed
    from its bytes, sharing no object with the node that built it."""
    from stellar_core_tpu.xdr.ledger import (GeneralizedTransactionSet,
                                             TransactionSet)
    kind = GeneralizedTransactionSet if slot.generalized else TransactionSet
    return TxSetFrame(kind.from_bytes(slot.set_bytes), network_id)


def flip_signatures(frame: TxSetFrame, which, network_id: bytes):
    """A copy of `frame` in which the transactions numbered `which` (in
    the set's own order) have one bit of their signature flipped."""
    xdr = type(frame.to_xdr()).from_bytes(frame.to_bytes())
    envs = set_envelopes(xdr, frame.is_generalized)
    for i in which:
        sig = envs[i].value.signatures[0]
        raw = bytes(sig.signature)
        sig.signature = raw[:7] + bytes([raw[7] ^ 0x10]) + raw[8:]
    # the hash is of the contents: a new frame over the edited set
    return TxSetFrame(type(xdr).from_bytes(xdr.to_bytes()), network_id)


class Recording:
    """What set-up kept of validators 0 and 1: the keys, every slot
    from ledger 2 on, the header chain, and the dictionary model as the
    account creation left it."""

    def __init__(self):
        self.keys = self.nid = self.traffic = self.model = None
        self.slots = []
        self.hash_at = {}
        self.first_payment = 0
        self.seconds = self.sign_s = 0.0


def record(config: dict, params: dict, seed: int, workdir: str) -> Recording:
    """Ledger 2 (the tx-set size upgrade), ledger 3 (account creation),
    then `recorded_ledgers` ledgers of one payment an account, closed
    by validators 0 and 1; the nodes are gone when this returns."""
    dep = config["deployment"]
    rec = Recording()
    rec.keys = validator_keys(seed)
    n_rec = int(params["recorded_ledgers"])
    t0 = time.perf_counter()
    pub = Publisher(config, rec.keys, workdir, n_rec + 2)
    try:
        rec.nid = pub.apps[0].config.network_id()
        rec.traffic = t = PaymentTraffic(
            seed, rec.nid, dep["accounts"], params["amounts"],
            dep["starting_balance"])
        if dep["txs_per_ledger"] != len(t.accounts):
            raise ValueError("txset_follow: one payment an account a "
                             "ledger; txs_per_ledger must equal accounts")
        rec.model = LedgerModel()
        slots = rec.slots
        slots.append(pub.close())        # ledger 2: the tx-set size upgrade
        creation = t.creation_frames(
            node.account_seq(pub.apps[0], t.root.raw))
        slots.append(pub.close(creation))
        states = node.account_states(pub.apps[0],
                                     [a.raw for a in t.accounts])
        if len(states) != len(t.accounts):
            raise RuntimeError("account creation did not apply")
        for a in t.accounts:
            a.seq = states[a.raw][1]
            rec.model.create(a.raw, *states[a.raw])
        rec.first_payment = slots[-1].seq + 1
        for _ in range(n_rec):
            s0 = time.perf_counter()
            ledger = t.next_ledger()
            rec.sign_s += time.perf_counter() - s0
            slot = pub.close([f for f, _, _, _ in ledger])
            slot.payments = [(s, d, amount) for _, s, d, amount in ledger]
            slots.append(slot)
            if is_checkpoint_ledger(slot.seq):
                raise ValueError("txset_follow: the recording reaches a "
                                 "checkpoint ledger; record fewer")
        pub.take_envelopes({s.seq: s for s in slots})
        rec.hash_at = pub.header_hashes()
    finally:
        pub.shutdown()
    # what the overlay would hand over: parsed messages
    for s in slots:
        s.frame = parse_set(s, rec.nid)
        s.envelopes = [SCPEnvelope.from_bytes(raw) for raw in s.envelopes]
    rec.seconds = time.perf_counter() - t0
    return rec


def start_follower(config: dict, rec: Recording, workdir: str):
    """Validator 2 of the quorum, the node under test: a started node
    on the real-time clock with a new database."""
    return node.start_node(node.make_config(
        node_doc(config["node"], rec.keys, FOLLOWER), workdir))


def hand_over(app, slot: Slot) -> tuple:
    """Hand the node one slot's messages as the overlay would, the tx
    set and then every envelope of the other two; returns (seconds from
    the `recv_tx_set` call to the ledger committed, envelopes handed
    over after the commit)."""
    herder, lm = app.herder, app.ledger_manager
    if lm.get_last_closed_ledger_num() != slot.seq - 1:
        raise RuntimeError(f"slot {slot.seq} handed over before ledger "
                           f"{slot.seq - 1} was committed")
    t0 = time.perf_counter()
    herder.recv_tx_set(slot.set_hash, slot.frame)
    committed = None
    late = 0
    for env in slot.envelopes:
        herder.recv_scp_envelope(env)
        if committed is not None:
            late += 1
        elif lm.get_last_closed_ledger_num() == slot.seq:
            committed = time.perf_counter()
    if committed is None:
        raise RuntimeError(
            f"the node did not externalize slot {slot.seq} from the "
            f"{len(slot.envelopes)} envelopes of the other two")
    return committed - t0, late


def set_envelopes(xdr, generalized: bool) -> list:
    """The transaction envelopes of a tx set's XDR, in the set's order."""
    if not generalized:
        return list(xdr.txs)
    return [env for phase in xdr.value.phases for comp in phase.value
            for env in comp.value.txs]


def set_tuples(frame: TxSetFrame, network_id: bytes) -> list:
    """(public key, signature, message) of every signature of the set,
    in the set's order, read off the XDR here and not by the program's
    collector: the source account's key, the decorated signature, and
    SHA-256(network id, ENVELOPE_TYPE_TX, the transaction). The cell's
    payments are v1 envelopes signed by their source."""
    tag = struct.pack(">i", EnvelopeType.ENVELOPE_TYPE_TX)
    out = []
    for env in set_envelopes(frame.to_xdr(), frame.is_generalized):
        if env.disc != EnvelopeType.ENVELOPE_TYPE_TX:
            raise ValueError("txset_follow: a set of v1 payments only")
        tx = env.value.tx
        src = tx.sourceAccount
        pub = bytes(src.value if src.disc == CryptoKeyType.KEY_TYPE_ED25519
                    else src.value.ed25519)
        msg = hashlib.sha256(network_id + tag + tx.to_bytes()).digest()
        out.extend((pub, bytes(sig.signature), msg)
                   for sig in env.value.signatures)
    return out


def prepare_naming(rec: Recording, slot: Slot, set_hash: bytes):
    """Validator 0's first PREPARE of `slot` as it was recorded, naming
    `set_hash` instead and signed again with validator 0's key (the
    generator holds it): (value bytes, envelope)."""
    from stellar_core_tpu.herder.scp_driver import scp_envelope_sign_bytes
    me = rec.keys[0].public_key().raw
    for env in slot.envelopes:
        st = env.statement
        if st.pledges.disc == SCPStatementType.SCP_ST_PREPARE and \
                bytes(st.nodeID.value) == me:
            break
    else:
        raise RuntimeError("no PREPARE of validator 0 in the slot")
    env = SCPEnvelope.from_bytes(env.to_bytes())
    pl = env.statement.pledges.value
    sv = StellarValue.from_bytes(bytes(pl.ballot.value))
    sv.txSetHash = set_hash
    value = sv.to_bytes()
    pl.ballot.value = value
    pl.prepared = pl.preparedPrime = None
    pl.nC = pl.nH = 0
    env.signature = rec.keys[0].sign(scp_envelope_sign_bytes(
        rec.nid, env.statement))
    return value, env


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.p = cell.traffic["params"]
        self.app = None
        self.clear_cache = True       # a control leaves it warm

    # ---------------------------------------------------------- set-up --
    def setup(self) -> None:
        cell, p = self.cell, self.p
        dep = cell.config["deployment"]
        rec = self.rec = record(cell.config, p, cell.seed, cell.workdir)
        self.nid, self.slots = rec.nid, rec.slots
        self.traffic, self.model = rec.traffic, rec.model
        slots = self.slots
        cell.note(
            f"set-up: validators 0 and 1 closed ledgers 2..{slots[-1].seq} "
            f"({p['recorded_ledgers']} of {dep['txs_per_ledger']} "
            f"payments) in {rec.seconds:.1f} s, {rec.sign_s:.1f} s of it "
            f"signing; {sum(len(s.envelopes) for s in slots)} envelopes "
            f"recorded, {min(len(s.envelopes) for s in slots)}-"
            f"{max(len(s.envelopes) for s in slots)} a slot")
        self.app = app = start_follower(cell.config, rec,
                                        cell.workdir + "/node")
        self.emitted = []             # what the node under test says
        app.herder.broadcast_cb = self.emitted.append
        cell.watch_app(app)
        self.at = 0                   # next slot to hand over
        # the upgrade and the account creation, with the process's
        # verify cache as the publisher left it; from here on the node
        # meets every signature for the first time
        while slots[self.at].seq < rec.first_payment:
            self._follow(slots[self.at])
        if self.clear_cache:
            clear_verify_cache()
        # one payment ledger outside the window: the device program of
        # the one shape is loaded (or compiled) here
        self._follow(slots[self.at])
        app.ledger_manager.join_completion()
        self.window_from = self.at
        self.counters0 = node.counters(app)
        self.zones0 = node.zones(app)

    def _follow(self, slot: Slot) -> float:
        """The next slot through the node: seconds from `recv_tx_set`
        to committed."""
        took, self.late = hand_over(self.app, slot)
        self.at += 1
        return took

    # ---------------------------------------------------------- window --
    def window(self, seconds: float) -> None:
        app, cell, p = self.app, self.cell, self.p
        close_ms = []
        self.envelopes = 0
        # an envelope's own signature is verified natively (a batch of
        # one), and those counts reach the node's zone at its next
        # close: the window sees the envelopes that followed the warm
        # ledger's commit and not those that follow its own last one
        late_before = self.late
        t_start = time.perf_counter()
        deadline = t_start + seconds
        # (the last recorded ledger is kept for the check)
        while self.at < len(self.slots) - 1:
            slot = self.slots[self.at]
            t0 = time.perf_counter()
            took = self._follow(slot)
            close_ms.append(took * 1e3)
            self.envelopes += len(slot.envelopes)
            cell.spans.add("bench.slot", t0, time.perf_counter(),
                           seq=slot.seq)
            if time.perf_counter() >= deadline and \
                    len(close_ms) >= p["min_ledgers"]:
                break
        else:
            cell.note("the window used every recorded ledger but the one "
                      "the check needs, and ended early")
        # the last ledger's completion tail is work of this window
        t1 = time.perf_counter()
        app.ledger_manager.join_completion()
        t_end = time.perf_counter()
        cell.spans.add("bench.last_tail", t1, t_end)
        self.t_start, self.t_end = t_start, t_end
        self.window_s = t_end - t_start
        self.close_ms = close_ms
        self.followed = self.slots[self.window_from:self.at]
        self.attempted = sum(s.txs for s in self.followed)
        node.add_into(cell.counters, node.counters(app), self.counters0)
        node.add_into(cell.zones, node.zones(app), self.zones0)
        applied = cell.counters.get("ledger.transaction.count", (0, 0))[0]
        self.failed = self.attempted - applied
        cell.traffic_counts.update(
            transactions=applied, signatures=self.attempted,
            ledgers=len(self.followed), scp_envelopes=self.envelopes,
            envelope_verifies=self.envelopes + late_before - self.late)
        cell.note(
            f"{len(close_ms)} ledgers followed in a window of "
            f"{self.window_s:.2f} s (the last tail {t_end - t1:.2f} s): "
            f"recv_tx_set to committed median "
            f"{cell.percentile(close_ms, 50):.0f} ms, longest "
            f"{max(close_ms):.0f} ms; {self.envelopes} envelopes handed "
            f"over, {len(self.emitted)} emitted")

    def end_to_end(self) -> dict:
        return {"applied_tx_per_s":
                (self.attempted - self.failed) / self.window_s,
                "close_ms_p90": self.cell.percentile(self.close_ms, 90)}

    # ------------------------------------------------- after the window --
    def after_window(self) -> None:
        """Inside the traced window, after the measured one: the next
        recorded set with `corrupted` signatures flipped, offered to the
        node by validator 0 in a PREPARE of its own. The node must find
        the value invalid, say nothing and stay where it is; and the
        set's tuples go through the node's device verifier once more,
        for the verdicts themselves."""
        app, p = self.app, self.p
        if self.at >= len(self.slots):
            raise RuntimeError("no recorded ledger left for the check")
        slot = self.slots[self.at]
        rng = random.Random(self.cell.seed ^ 0x5EED)
        self.flipped = sorted(rng.sample(range(slot.txs), p["corrupted"]))
        bad = flip_signatures(slot.frame, self.flipped, self.nid)
        value, env = prepare_naming(self.rec, slot, bad.get_contents_hash())
        said = len(self.emitted)
        lcl = app.ledger_manager.get_last_closed_ledger_num()
        before = node.counters(app)
        app.herder.recv_tx_set(bad.get_contents_hash(), bad)
        app.herder.recv_scp_envelope(env)
        after = node.counters(app)
        self.bad_level = app.herder.scp_driver.validate_value(
            slot.seq, value, False)
        self.bad_said = len(self.emitted) - said
        self.bad_moved = app.ledger_manager.get_last_closed_ledger_num() \
            - lcl
        self.bad_dispatched = after.get(
            "herder.txset.prevalidate.dispatched", (0, 0))[0] - before.get(
            "herder.txset.prevalidate.dispatched", (0, 0))[0]
        self.bad_txs = slot.txs
        # the verdicts themselves, in the set's order
        tuples = set_tuples(bad, self.nid)
        t0 = time.perf_counter()
        self.bad_verdicts = [bool(v) for v in
                             app.batch_verifier.verify_tuples(tuples)]
        self.cell.spans.add("bench.device_check", t0, time.perf_counter(),
                            batch=len(tuples))
        flipped = set(self.flipped)
        sample = set(rng.sample(
            [i for i in range(len(tuples)) if i not in flipped],
            min(p["oracle_sample"], len(tuples) - len(flipped))))
        self.bad_expected = [
            ed25519_oracle.verify(*tuples[i])
            if i in flipped or i in sample else True
            for i in range(len(tuples))]
        self.oracle_checked = len(flipped) + len(sample)

    # ---------------------------------------------------------- checks --
    def check(self) -> list:
        app, cell, t = self.app, self.cell, self.traffic
        c = cell.counters
        checks = []
        app.ledger_manager.join_completion()
        mine = {int(seq): bytes(h) for seq, h in app.database.query_all(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
        last = self.followed[-1].seq
        checks.append(Check(
            f"ledgers 1..{last} whose header hash on the node's disk "
            "differs from the publisher's",
            sum(1 for seq in range(1, last + 1)
                if mine.get(seq) != self.rec.hash_at.get(seq, b"?")), 0))
        for slot in self.slots[:self.at]:
            for s, d, amount in slot.payments:
                self.model.pay(t.accounts[s].raw, t.accounts[d].raw, amount)
        observed = node.account_states(app, [a.raw for a in t.accounts])
        checks.append(Check("accounts whose balance or sequence differs "
                            "from the dictionary model",
                            self.model.differences(observed), 0))
        checks.append(Check("transactions of the window's sets that were "
                            "not applied (ledger.transaction.count)",
                            abs(self.failed), 0))
        got = {k: c.get("herder.txset.prevalidate." + k, (0, 0))[0]
               for k in ("cached", "dispatched", "fallback")}
        checks.append(Check(
            "signatures of the window's sets the validation did not send "
            "to the device (herder.txset.prevalidate.dispatched off "
            f"{self.attempted})",
            abs(self.attempted - got["dispatched"]), 0))
        checks.append(Check(
            "signatures of the window's sets counted neither cached, "
            "dispatched nor fallen back",
            abs(self.attempted - sum(got.values())), 0))
        checks.append(Check("signatures verified natively after a failed "
                            "batch (herder.txset.prevalidate.fallback)",
                            got["fallback"], 0))
        validated = cell.zones.get("herder.txset.validate", (0, 0.0))[0]
        checks.append(Check("sets validated (herder.txset.validate) off "
                            "the ledgers followed",
                            abs(validated - len(self.followed)), 0))
        native = cell.zones.get("crypto.verify.native", (0, 0.0))[0]
        own = cell.traffic_counts["envelope_verifies"]
        checks.append(Check(
            "native verifies inside the window off the SCP envelopes' "
            f"own signatures ({own}: none of a transaction's)",
            abs(native - own), 0))
        # every chunk landed
        import stellar_core_tpu.ops.chunking as chunking
        per_set = self.attempted // len(self.followed)
        bounds = chunking.chunk_bounds(per_set, chunking.MAX_BUCKET)
        runs = c.get("crypto.verify.dispatch.batch", (0, 0))[0]
        landed = c.get("crypto.verify.dispatch.wall", (0, 0))[0]
        checks.append(Check(
            f"device runs off {len(bounds)} a set (chunks of "
            f"{chunking.MAX_BUCKET} lanes)",
            abs(runs - len(bounds) * len(self.followed)), 0))
        checks.append(Check("device runs that did not land",
                            runs - landed, 0))
        faults = node.supervisor_faults(app.batch_verifier.status())
        checks.append(Check("supervisor complaints " + "; ".join(faults),
                            len(faults), 0))
        # the node's own statements: one quorum member's, for the value
        # the other two confirmed, and nothing for the corrupted set
        checks.append(Check(
            "ledgers the node closed without an EXTERNALIZE of its own",
            len(self.followed) - sum(
                1 for e in self.emitted
                if e.statement.pledges.disc
                == SCPStatementType.SCP_ST_EXTERNALIZE
                and self.followed[0].seq <= e.statement.slotIndex
                <= last), 0))
        checks.append(Check(
            f"corrupted set ({len(self.flipped)} of {self.bad_txs} "
            "signatures flipped): validation level off kInvalidValue, "
            "envelopes the node emitted for it, ledgers it closed on it",
            int(self.bad_level != ValidationLevel.kInvalidValue)
            + self.bad_said + abs(self.bad_moved), 0))
        checks.append(Check(
            "signatures of the corrupted set that were not sent to the "
            "device", self.bad_txs - self.bad_dispatched, 0))
        wrong = sum(1 for g, w in zip(self.bad_verdicts, self.bad_expected)
                    if g != w) + abs(len(self.bad_verdicts)
                                     - len(self.bad_expected))
        checks.append(Check(
            f"device verdicts of the corrupted set (of {self.bad_txs}, in "
            f"order; {self.oracle_checked} by the oracle, the rest by the "
            "publisher that applied them) that differ", wrong, 0))
        checks.append(Check(
            "flipped signatures the device called valid",
            sum(1 for i in self.flipped if self.bad_verdicts[i]), 0))
        return checks

    def close(self) -> None:
        if self.app is not None:
            self.app.shutdown()
