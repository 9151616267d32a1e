"""Traffic driver `txset_flood`: a validator that is flooded before it
is asked. `txset_follow`'s node (one of three validators, threshold 2,
never the one whose set wins) with what a running validator has had
before the leader's set arrives: the slot's transactions, from its
peers' flood, in bursts.

For every slot the node under test is handed, through the calls the
overlay makes: the slot's recorded transactions as parsed frames in
bursts of `burst_txs` through `Herder.recv_transactions` (each burst
after the last returns; the order is the one in which the publisher
admitted them), then the tx set through `Herder.recv_tx_set`, then
every SCP envelope of the other two validators in recorded order
through `Herder.recv_scp_envelope`. The node does the rest: a burst's
signatures go through its verify service as one device dispatch, the
verdicts are written through the verify cache, the frames enter its
transaction queue; the set's validation finds its signatures in the
cache and sends the device only what the cache has lost; the node
votes, the slot externalizes, the ledger closes and the queue lets go
of what was applied.

The recording, the node and the hand-over are `txset_follow`'s
(imported, not copied). Parameters (the traffic file): `amounts`,
`recorded_ledgers`, `min_ledgers` as there; `burst_txs`; and for the
checks after the window, on the next recorded slot: `corrupted`
(signatures flipped in the adversarial burst), `duplicates` (frames of
that burst that are already pending), `withheld` (transactions of that
slot the flood never brings, so that its set's validation has exactly
that many to send to the device). The process-wide verify cache is
emptied twice, outside the window both times: after set-up's two
ledgers, as `txset_follow` empties it, and before the checks' slot.
"""

import hashlib
import random
import struct
import time

from stellar_core_tpu.crypto.keys import VERIFY_CACHE_SIZE, clear_verify_cache
from stellar_core_tpu.herder.tx_queue import AddResult
from stellar_core_tpu.main import Application
from stellar_core_tpu.ops import chunking
from stellar_core_tpu.tx.frame import make_frame
from stellar_core_tpu.util.perf import default_registry
from stellar_core_tpu.xdr.scp import SCPStatementType
from stellar_core_tpu.xdr.transaction import TransactionEnvelope
from stellar_core_tpu.xdr.types import EnvelopeType

from benchmark.generators import txset_follow as F
from benchmark.harness import node
from benchmark.harness.checks import Check
from benchmark.reference import ed25519_oracle, flood_model
from benchmark.reference.flood_model import FloodModel

JAX_WORK = ("jax.trace", "jax.lower", "jax.backendCompile")


class Body:
    """One transaction of a recorded slot as the flood carries it: the
    envelope's bytes, and what the model needs to know of it."""
    __slots__ = ("raw", "key", "account", "seq")

    def __init__(self, env):
        self.raw = env.to_bytes()
        self.key = hashlib.sha256(self.raw).digest()
        tx = env.value.tx
        self.account = bytes(tx.sourceAccount.value)
        self.seq = tx.seqNum

    def frame(self, network_id: bytes):
        """What the overlay hands the herder: a frame parsed from the
        bytes, sharing no object with the set or an earlier delivery."""
        return make_frame(TransactionEnvelope.from_bytes(self.raw),
                          network_id)

    def flipped(self) -> "Body":
        """The same transaction with one bit of its signature flipped."""
        env = TransactionEnvelope.from_bytes(self.raw)
        sig = env.value.signatures[0]
        raw = bytes(sig.signature)
        sig.signature = raw[:7] + bytes([raw[7] ^ 0x10]) + raw[8:]
        return Body(env)


def slot_bodies(slot, accounts) -> list:
    """The slot's transactions in the order the publisher admitted them
    (`slot.payments`), read off the set's XDR."""
    by_source = {}
    for env in F.set_envelopes(slot.frame.to_xdr(), slot.generalized):
        b = Body(env)
        by_source[b.account] = b
    return [by_source[accounts[s].raw] for s, _, _ in slot.payments]


def bursts_of(items: list, size: int) -> list:
    return [items[at:at + size] for at in range(0, len(items), size)]


def outcome(res, bad: bool) -> str:
    if res == AddResult.ADD_STATUS_PENDING:
        return flood_model.PENDING
    if res == AddResult.ADD_STATUS_DUPLICATE:
        return flood_model.DUPLICATE
    if res == AddResult.ADD_STATUS_ERROR:
        return flood_model.BAD_SIG if bad else flood_model.BAD_SEQ
    return str(res)


def jax_work() -> int:
    """Traces, lowerings and compiles this process has made."""
    report = default_registry.report()
    return sum(report[z]["count"] for z in JAX_WORK if z in report)


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.p = cell.traffic["params"]
        self.app = None
        self.flood = True             # a control skips the flood

    # ---------------------------------------------------------- set-up --
    def setup(self) -> None:
        cell, p = self.cell, self.p
        if not hasattr(Application, "_load_verify_shapes"):
            # a program from before this cell: its node meets a burst's
            # shape on the crank, so the cell cannot be held to "nothing
            # compiles inside the window"; no result, and at once
            raise RuntimeError(
                "txset_flood: this program's node loads no device shape "
                "when it starts; the cell needs one that does")
        dep = cell.config["deployment"]
        self.burst = int(p["burst_txs"])
        if self.burst != dep["flood"]["burst_txs"]:
            raise ValueError("txset_flood: the traffic's burst is not the "
                             "configuration's")
        rec = self.rec = F.record(cell.config, p, cell.seed, cell.workdir)
        self.nid, self.slots = rec.nid, rec.slots
        self.traffic, self.model = rec.traffic, rec.model
        slots = self.slots
        t0 = time.perf_counter()
        self.bodies = {s.seq: slot_bodies(s, rec.traffic.accounts)
                       for s in slots if s.seq >= rec.first_payment}
        # what the overlay would hand over: parsed messages
        self.frames = {seq: [b.frame(rec.nid) for b in bodies]
                       for seq, bodies in self.bodies.items()}
        cell.note(
            f"set-up: validators 0 and 1 closed ledgers 2..{slots[-1].seq} "
            f"({p['recorded_ledgers']} of {dep['txs_per_ledger']} "
            f"payments) in {rec.seconds:.1f} s, {rec.sign_s:.1f} s of it "
            f"signing; {sum(len(s.envelopes) for s in slots)} envelopes "
            f"recorded; the flood's bodies read off the sets in "
            f"{time.perf_counter() - t0:.1f} s")
        # what the node was handed and what it answered, in order; the
        # dictionary model is run over it after the window (`check`)
        self.log = []
        self.created = {a.raw: rec.model.seq[a.raw]
                        for a in rec.traffic.accounts}
        self.app = app = F.start_follower(cell.config, rec,
                                          cell.workdir + "/node")
        self.emitted = []             # what the node under test says
        app.herder.broadcast_cb = self.emitted.append
        cell.watch_app(app)
        self.at = 0                   # next slot to hand over
        self.flooded_frames = 0
        self.late = 0
        # the upgrade and the account creation are followed, not
        # flooded; from here on the node meets every signature for the
        # first time in a burst
        while slots[self.at].seq < rec.first_payment:
            self._follow(slots[self.at])
        clear_verify_cache()
        # one flooded and followed ledger outside the window
        self._ledger(slots[self.at])
        app.ledger_manager.join_completion()
        self.window_from = self.at
        self.counters0 = node.counters(app)
        self.zones0 = node.zones(app)
        self.jax0 = jax_work()

    def _counter(self, name: str) -> int:
        return self.app.metrics.new_counter(name).count

    def _flood(self, bodies: list, frames: list = None) -> None:
        """`bodies` (as `frames`, or parsed here) through
        `recv_transactions`, a burst at a time."""
        if not self.flood:
            return
        recv = self.app.herder.recv_transactions
        if frames is None:
            frames = [b.frame(self.nid) for b in bodies]
        for part, fs in zip(bursts_of(bodies, self.burst),
                            bursts_of(frames, self.burst)):
            bad = []
            res = recv(fs, bad_sig=bad)
            self.log.append(("burst", part, None, res, bad))
            self.flooded_frames += len(part)

    def _follow(self, slot) -> float:
        """The slot's set and envelopes through the node: seconds from
        `recv_tx_set` to committed."""
        took, self.late = F.hand_over(self.app, slot)
        self.at += 1
        return took

    def _ledger(self, slot, bodies=None) -> float:
        """One slot as the node lives it: the flood (the caller's, where
        it passes `bodies`), then the set and the envelopes; what the
        queue holds after the commit."""
        if bodies is None:
            self._flood(self.bodies[slot.seq], self.frames.pop(slot.seq))
        took = self._follow(slot)
        self.log.append(("close", self.bodies[slot.seq],
                         self.app.herder.tx_queue.size_txs()))
        return took

    # ---------------------------------------------------------- window --
    def window(self, seconds: float) -> None:
        app, cell, p = self.app, self.cell, self.p
        close_ms = []
        self.envelopes = 0
        self.dispatched = []          # per ledger: the set's cache misses
        self.written = []             # ... and verdicts written before it
        late_before = self.late
        flooded_from = self.flooded_frames
        t_start = time.perf_counter()
        deadline = t_start + seconds
        # (the last recorded ledger is kept for the checks)
        while self.at < len(self.slots) - 1:
            slot = self.slots[self.at]
            before = self._counter("herder.txset.prevalidate.dispatched")
            # an upper bound of what the verify cache has been given
            # since it was emptied, once this slot's flood is in: every
            # frame flooded, and two entries for every envelope handed
            # over or emitted (its own signature, its value's)
            self.written.append(
                self.flooded_frames + slot.txs + 2 * (
                    sum(len(s.envelopes) for s in self.slots[:self.at + 1])
                    + len(self.emitted) + 64))
            t0 = time.perf_counter()
            took = self._ledger(slot)
            close_ms.append(took * 1e3)
            t1 = time.perf_counter()
            cell.spans.add("bench.slot", t0, t1, seq=slot.seq)
            cell.spans.add("bench.flood", t0, t1 - took, seq=slot.seq)
            self.envelopes += len(slot.envelopes)
            self.dispatched.append(self._counter(
                "herder.txset.prevalidate.dispatched") - before)
            if time.perf_counter() >= deadline and \
                    len(close_ms) >= p["min_ledgers"]:
                break
        else:
            cell.note("the window used every recorded ledger but the one "
                      "the checks need, and ended early")
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
        self.flooded = self.flooded_frames - flooded_from
        self.bursts = sum(-(-s.txs // self.burst) for s in self.followed) \
            if self.flood else 0
        node.add_into(cell.counters, node.counters(app), self.counters0)
        node.add_into(cell.zones, node.zones(app), self.zones0)
        self.jax_in_window = jax_work() - self.jax0
        applied = cell.counters.get("ledger.transaction.count", (0, 0))[0]
        self.failed = self.attempted - applied
        cell.traffic_counts.update(
            transactions=applied, signatures=self.attempted,
            flooded=self.flooded, bursts=self.bursts,
            ledgers=len(self.followed), scp_envelopes=self.envelopes,
            envelope_verifies=self.envelopes + late_before - self.late)
        cell.note(
            f"{len(close_ms)} ledgers flooded and followed in a window of "
            f"{self.window_s:.2f} s (the last tail {t_end - t1:.2f} s): "
            f"the flood {cell.spans.total('bench.flood') / len(close_ms):.3f}"
            f" s a ledger, recv_tx_set to committed median "
            f"{cell.percentile(close_ms, 50):.0f} ms, longest "
            f"{max(close_ms):.0f} ms; {self.envelopes} envelopes handed "
            f"over, {len(self.emitted)} emitted; cache misses of the sets "
            f"{self.dispatched}")

    def end_to_end(self) -> dict:
        return {"applied_tx_per_s":
                (self.attempted - self.failed) / self.window_s,
                "close_ms_p90": self.cell.percentile(self.close_ms, 90)}

    # ------------------------------------------------- after the window --
    def after_window(self) -> None:
        """Inside the traced window, after the measured one, on the
        next recorded slot: a sound burst; then a burst of the same size
        with `corrupted` signatures flipped and `duplicates` frames of
        the first burst delivered again; then the rest of the slot's
        flood without `withheld` of its transactions; then the set and
        the envelopes."""
        app, p, B = self.app, self.p, self.burst
        if self.at >= len(self.slots):
            raise RuntimeError("no recorded ledger left for the checks")
        slot = self.slots[self.at]
        bodies = self.bodies[slot.seq]
        rng = random.Random(self.cell.seed ^ 0xF100D)
        self.adv = None
        # the process-wide verify cache is emptied once more: by now it
        # is full or nearly so (5,000 verdicts a ledger into 65,535
        # entries) and evicts at random, and what this slot's validation
        # sends to the device has to be the withheld transactions and
        # nothing else, to the signature
        clear_verify_cache()
        c0 = node.counters(app)
        if self.flood:
            first, rest = bodies[:B], bodies[B:]
            n_new = B - p["corrupted"] - p["duplicates"]
            new, rest = rest[:n_new], rest[n_new:]
            held = set(rng.sample(range(len(rest)), p["withheld"]))
            withheld = [b for i, b in enumerate(rest) if i in held]
            rest = [b for i, b in enumerate(rest) if i not in held]
            self._flood(first)
            adv = [(b, True) for b in new] \
                + [(b.flipped(), False) for b in withheld[:p["corrupted"]]] \
                + [(b, True) for b in rng.sample(first, p["duplicates"])]
            rng.shuffle(adv)
            c1 = node.counters(app)
            frames = [b.frame(self.nid) for b, _ in adv]
            bad = []
            t0 = time.perf_counter()
            res = app.herder.recv_transactions(frames, bad_sig=bad)
            self.cell.spans.add("bench.device_check", t0,
                                time.perf_counter(), batch=len(frames))
            c2 = node.counters(app)
            # every verdict of the burst by the pure-Python oracle
            tuples = [_tuple_of(b.raw, self.nid) for b, _ in adv]
            memo = {}
            sound = [memo.setdefault(t, ed25519_oracle.verify(*t))
                     for t in tuples]
            self.log.append(("adversarial", [b for b, _ in adv], sound,
                             res, bad))
            self.adv = {
                "bad": list(bad), "oracle_bad": [not ok for ok in sound],
                "flipped": [not meant for _, meant in adv],
                "runs": _delta(c1, c2, "crypto.verify.dispatch.batch", 0),
                "sigs": _delta(c1, c2, "crypto.verify.dispatch.batch", 1),
                "distinct": len(adv) - p["duplicates"],
                "queued": sum(1 for f, (_, meant) in zip(frames, adv)
                              if not meant and
                              app.herder.tx_queue.is_pending(f.full_hash())),
            }
            self._flood(rest)
        lcl = app.ledger_manager.get_last_closed_ledger_num()
        c3 = node.counters(app)
        self._ledger(slot, bodies=[])
        c4 = node.counters(app)
        self.last = {
            "seq": slot.seq, "txs": slot.txs,
            "moved": app.ledger_manager.get_last_closed_ledger_num() - lcl,
            "cached": _delta(c3, c4, "herder.txset.prevalidate.cached", 0),
            "dispatched": _delta(c3, c4,
                                 "herder.txset.prevalidate.dispatched", 0),
            "fallback": _delta(c3, c4,
                               "herder.txset.prevalidate.fallback", 0),
            "runs": _delta(c3, c4, "crypto.verify.dispatch.batch", 0),
        }
        self.jax_after = jax_work() - self.jax0 - self.jax_in_window
        self.flood_counts = {
            k: _delta(c0, c4, "herder.flood." + k, 0)
            for k in ("received", "admitted", "duplicate", "badSig")}

    # ---------------------------------------------------------- checks --
    def check(self) -> list:
        app, cell, t, p = self.app, self.cell, self.traffic, self.p
        c = cell.counters
        checks = []
        app.ledger_manager.join_completion()
        mine = {int(seq): bytes(h) for seq, h in app.database.query_all(
            "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
        last = self.last["seq"]
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
        ledgers = len(self.followed)
        # ---- the flood, frame by frame: the dictionary model over
        # everything the node was handed, in order
        model = FloodModel()
        for account, seq in self.created.items():
            model.create(account, seq)
        sound_frames = wrong = closes = left = 0
        adv_got, adv_want = [], [None]
        for kind, bodies, *rest in self.log:
            if kind == "close":
                closes += 1
                left += bool(rest[0]) + model.close(
                    [(b.key, b.account, b.seq) for b in bodies])
                continue
            sound, res, bad = rest
            got = [outcome(r, b) for r, b in zip(res, bad)]
            want = model.burst([
                (b.key, b.account, b.seq, sound is None or sound[i])
                for i, b in enumerate(bodies)])
            if kind == "adversarial":
                adv_got, adv_want = got, want
            else:
                sound_frames += len(bodies)
                wrong += sum(1 for g, w in zip(got, want)
                             if g != w or g != flood_model.PENDING) \
                    + abs(len(got) - len(want))
        checks.append(Check(
            f"frames of the sound bursts ({sound_frames} flooded, set-up's "
            "ledger and the checks' included) whose outcome differs from "
            "flood_model's, or is not ADD_STATUS_PENDING", wrong, 0))
        checks.append(Check(
            f"frames flooded inside the window off {self.attempted} "
            f"({ledgers} ledgers in bursts of {self.burst})",
            abs(self.flooded - self.attempted), 0))
        checks.append(Check(
            "ledgers after whose commit the node's queue held a frame, or "
            f"the model's did (of {closes})", left, 0))
        # ---- admission on the device
        sets_missed = sum(self.dispatched)
        batch_n, batch_sum = c.get("crypto.verify.dispatch.batch", (0, 0.0))
        checks.append(Check(
            "signatures the verify service sent to the device at admission "
            f"off {self.attempted} (crypto.verify.dispatch.batch sum "
            f"{batch_sum:.0f} less the sets' {sets_missed} cache misses)",
            abs(batch_sum - sets_missed - self.attempted), 0))
        set_runs = sum(-(-d // chunking.MAX_BUCKET) for d in self.dispatched)
        checks.append(Check(
            f"device runs off {self.bursts} ({-(-self.followed[0].txs // self.burst)} "
            f"bursts a ledger) and {set_runs} for the sets' misses",
            abs(batch_n - self.bursts - set_runs), 0))
        landed = c.get("crypto.verify.dispatch.wall", (0, 0))[0]
        checks.append(Check("device runs that did not land",
                            batch_n - landed, 0))
        occ_n, occ_sum = c.get("crypto.verify_service.occupancy", (0, 0.0))
        native = c.get("crypto.verify_service.flush.native", (None, 0))[0]
        checks.append(Check(
            "verify-service flushes that were neither a burst's nor one "
            "signature of an envelope's run on the host "
            f"(flushes {occ_n}, bursts {self.bursts}, "
            f"crypto.verify_service.flush.native {native}, tuples "
            f"{occ_sum:.0f})",
            abs(occ_n - self.bursts - (native or 0))
            + abs(occ_sum - self.attempted - (native or 0))
            + (native is None), 0))
        checks.append(Check(
            "flushes that fell back to the host after a device failure "
            "(crypto.verify_service.fallback)",
            c.get("crypto.verify_service.fallback", (0, 0))[0], 0))
        # ---- the sets, warm
        got = {k: c.get("herder.txset.prevalidate." + k, (0, 0))[0]
               for k in ("cached", "dispatched", "fallback")}
        checks.append(Check(
            "signatures of the window's sets counted neither cached, "
            f"dispatched nor fallen back ({got['cached']} + "
            f"{got['dispatched']} + {got['fallback']} off {self.attempted})",
            abs(self.attempted - sum(got.values())), 0))
        room = [w <= VERIFY_CACHE_SIZE for w in self.written]
        checks.append(Check(
            "signatures the sets' validation sent to the device while the "
            f"verify cache still had room ({sum(room)} of {ledgers} sets "
            f"arrived before it had been given {VERIFY_CACHE_SIZE} "
            f"verdicts; the others sent {sets_missed}: what a full cache "
            "had lost)",
            sum(d for d, r in zip(self.dispatched, room) if r), 0))
        checks.append(Check(
            "the window's sets the verify cache answered no signature of "
            "(herder.txset.prevalidate.cached 0: no flood came before "
            "them)", int(got["cached"] == 0), 0))
        checks.append(Check("signatures verified natively after a failed "
                            "batch (herder.txset.prevalidate.fallback)",
                            got["fallback"], 0))
        validated = cell.zones.get("herder.txset.validate", (0, 0.0))[0]
        checks.append(Check("sets validated (herder.txset.validate) off "
                            "the ledgers followed",
                            abs(validated - ledgers), 0))
        native_v = cell.zones.get("crypto.verify.native", (0, 0.0))[0]
        own = cell.traffic_counts["envelope_verifies"]
        checks.append(Check(
            "native verifies inside the window off the SCP envelopes' "
            f"own signatures ({own}: none of a transaction's)",
            abs(native_v - own), 0))
        faults = node.supervisor_faults(app.batch_verifier.status())
        checks.append(Check("supervisor complaints " + "; ".join(faults),
                            len(faults), 0))
        # ---- shapes
        loaded = self._counter("crypto.verify.shape.loaded")
        missed = self._counter("crypto.verify.shape.missed")
        checks.append(Check(
            "shapes the node loaded when it started "
            "(crypto.verify.shape.loaded)", loaded, 1, at_least=True))
        checks.append(Check(
            "batches of the node's whole life that met a shape it had not "
            "loaded (crypto.verify.shape.missed)", missed, 0))
        checks.append(Check(
            "traces, lowerings and compiles inside the window "
            f"({self.jax_in_window}) and in the checks after it "
            f"({self.jax_after})", self.jax_in_window + self.jax_after, 0))
        checks.append(Check(
            "ledgers the node closed without an EXTERNALIZE of its own",
            ledgers + 1 - sum(
                1 for e in self.emitted
                if e.statement.pledges.disc
                == SCPStatementType.SCP_ST_EXTERNALIZE
                and self.followed[0].seq <= e.statement.slotIndex
                <= last), 0))
        # ---- the adversarial burst
        # (a driver that played no flood has none: every line fails)
        adv = self.adv or {"bad": [], "oracle_bad": [None], "flipped": [],
                           "runs": 0, "sigs": 0, "distinct": -1,
                           "queued": 0}
        checks.append(Check(
            f"adversarial burst ({len(adv_got)} frames, "
            f"{p['corrupted']} signatures flipped, {p['duplicates']} already "
            "pending): outcomes that differ from flood_model's",
            sum(1 for g, w in zip(adv_got, adv_want) if g != w)
            + abs(len(adv_got) - len(adv_want)), 0))
        checks.append(Check(
            "adversarial burst: bad_sig flags that differ from the "
            "oracle's verdicts, in order; flags off the flipped frames; "
            "flipped frames in the queue",
            sum(1 for g, w in zip(adv["bad"], adv["oracle_bad"]) if g != w)
            + abs(len(adv["bad"]) - len(adv["oracle_bad"]))
            + sum(1 for g, w in zip(adv["bad"], adv["flipped"]) if g != w)
            + adv["queued"], 0))
        checks.append(Check(
            f"adversarial burst: outcomes off {p['corrupted']} bad_sig, "
            f"{p['duplicates']} duplicate, the rest pending",
            abs(adv_got.count(flood_model.BAD_SIG) - p["corrupted"])
            + abs(adv_got.count(flood_model.DUPLICATE)
                  - p["duplicates"])
            + abs(adv_got.count(flood_model.PENDING) - len(adv_got)
                  + p["corrupted"] + p["duplicates"]), 0))
        checks.append(Check(
            f"adversarial burst: device runs off 1, signatures sent off "
            f"{adv['distinct']} (a duplicate is not verified twice)",
            abs(adv["runs"] - 1) + abs(adv["sigs"] - adv["distinct"]), 0))
        fc = self.flood_counts
        checks.append(Check(
            "herder.flood.received / .admitted / .duplicate / .badSig of the "
            f"checks' slot ({fc['received']} / {fc['admitted']} / "
            f"{fc['duplicate']} / {fc['badSig']}) off the model's",
            abs(fc["received"] - (self.last["txs"] - p["withheld"]
                                  + p["corrupted"] + p["duplicates"]))
            + abs(fc["admitted"] - (self.last["txs"] - p["withheld"]))
            + abs(fc["duplicate"] - p["duplicates"])
            + abs(fc["badSig"] - p["corrupted"]), 0))
        # ---- the slot the flood did not finish
        lastd = self.last
        checks.append(Check(
            f"slot {last} with {p['withheld']} of its {lastd['txs']} "
            "transactions withheld from the flood: signatures its "
            f"validation sent to the device ({lastd['dispatched']}) off "
            f"those, cached ({lastd['cached']}) off the rest, fallen back, "
            f"device runs off {-(-p['withheld'] // chunking.MAX_BUCKET)}, "
            "ledgers closed off 1",
            abs(lastd["dispatched"] - p["withheld"])
            + abs(lastd["cached"] - lastd["txs"] + p["withheld"])
            + lastd["fallback"]
            + abs(lastd["runs"] - -(-p["withheld"] // chunking.MAX_BUCKET))
            + abs(lastd["moved"] - 1), 0))
        return checks

    def close(self) -> None:
        if self.app is not None:
            self.app.shutdown()


def _delta(before: dict, after: dict, name: str, part: int):
    return after.get(name, (0, 0.0))[part] - before.get(name, (0, 0.0))[part]


def _tuple_of(raw: bytes, network_id: bytes) -> tuple:
    """(public key, signature, message) of a one-signature v1 envelope,
    read off its XDR here and not by the program's collector."""
    env = TransactionEnvelope.from_bytes(raw)
    tx = env.value.tx
    msg = hashlib.sha256(
        network_id + struct.pack(">i", EnvelopeType.ENVELOPE_TYPE_TX)
        + tx.to_bytes()).digest()
    return (bytes(tx.sourceAccount.value),
            bytes(env.value.signatures[0].signature), msg)
