"""Traffic driver `multisig_replay`: `catchup_replay`'s whole catchups of
the run's archive into fresh nodes, back to back, for a checkpoint of
signature-dense traffic (`multisig_payments.py`: m-of-n, fee-bumped and
20-signature payments, signers installed and rotated by SetOptions).

What it takes from `catchup_replay` is the `Replay`, the window and the
checks of the chain, the accounts and the supervisor; what it replaces
is the publishing, the counts (a transaction carries 1 to 21 signatures,
not one) and the checks that only this deployment has:

- signatures dispatched >= decorated signatures of the replayed ledgers;
- `crypto.prevalidated.miss.unknown` 0: the resolver made every tuple
  apply asked for;
- every chunk settled, none quarantined;
- the adversarial corpus and the tuples of the adversarial envelopes,
  padded with the archive's tuples to `corpus_chunks` whole chunks and a
  remainder, through the warm shape: every verdict equal to the oracle's,
  in order;
- `adversarial_envelopes` envelopes (one of twenty signatures
  bit-flipped; a 2of3 envelope with one good and one bad signature; a
  good signature of a non-signer added; a signature of a rotated-out
  signer; a fee bump with a bad outer signature over a good inner; and
  one sound envelope of every class) through `check_valid` on the check
  node, which has replayed the archive itself, with a table the device
  filled: each verdict and each refusal's codes equal to
  `reference/multisig_model.py`'s.

A program without chunked dispatch cannot run this deployment (its one
batch would be a shape never compiled): the driver says so and leaves
with a non-zero exit code before it builds anything.
"""

import shutil
import sys
import time

try:
    from stellar_core_tpu.ops import chunking
except ImportError:
    print("benchmark: this program dispatches a device batch as one "
          "bucket (no stellar_core_tpu/ops/chunking.py): it cannot run "
          "the multisig-dense deployment. No result.", file=sys.stderr)
    raise SystemExit(4)

from stellar_core_tpu.catchup import CatchupConfiguration, CatchupWork
from stellar_core_tpu.crypto.keys import SecretKey, clear_verify_cache
from stellar_core_tpu.ledger.ledger_txn import LedgerTxn
from stellar_core_tpu.tx.signature_checker import (PrevalidatedVerifier,
                                                   collect_signature_tuples)
from stellar_core_tpu.work import State
from stellar_core_tpu.xdr.results import TransactionResultCode

from benchmark.generators import catchup_replay
from benchmark.generators.multisig_payments import (
    MultisigTraffic, apply_to_model, describe, signature_count)
from benchmark.generators.payments import submit
from benchmark.harness import node
from benchmark.harness.checks import Check
from benchmark.reference import adversarial, multisig_model
from benchmark.reference.multisig_ledger_model import MultisigLedgerModel


class ChunkRecorder(catchup_replay.RecordingVerifier):
    """`harness/recording.py`'s pass-through, for a collect callable
    that also hands out chunks: what went in, what came out, and the
    benchmark's spans at the boundary of the verifier layer. It changes
    nothing: every call goes to the program's supervised
    `batch_verifier`, and `chunks()` stays reachable behind it."""

    def verify_tuples_async(self, items):
        t0 = time.perf_counter()
        handle = self._inner.verify_tuples_async(items)
        t1 = time.perf_counter()
        rec = {"n": len(items), "results": None, "chunks": 0, "failed": 0}
        self.batches.append(rec)
        self._spans.add("bench.verifier.dispatch", t0, t1, batch=len(items))
        return _RecordedCollect(handle, rec, self._spans, t1)


class _RecordedCollect:
    def __init__(self, handle, rec, spans, t_dispatched):
        self._handle = handle
        self._rec = rec
        self._spans = spans
        self._t = t_dispatched
        self._seen = []

    def _settled(self) -> None:
        if self._rec["results"] is None:
            self._rec["results"] = [v for vs in self._seen for v in vs]
            self._spans.add("bench.verifier.in_flight", self._t,
                            time.perf_counter(), batch=self._rec["n"])

    def chunks(self):
        for lo, hi, verdicts in chunking.chunks_of(self._handle,
                                                   self._rec["n"]):
            self._rec["chunks"] += 1
            if verdicts is None:
                self._rec["failed"] += 1
            else:
                self._seen.append([bool(v) for v in verdicts])
            yield lo, hi, verdicts
        self._settled()

    def __call__(self):
        res = self._handle()
        if self._rec["results"] is None:
            self._seen = [[bool(v) for v in res]]
            self._settled()
        return res


class Driver(catchup_replay.Driver):
    def wrap_verifier(self, verifier):
        """`Replay` hands its `RecordingVerifier` through here: put the
        chunk-aware recorder round the same supervised verifier."""
        return ChunkRecorder(verifier._inner, self.cell.spans)

    # ---------------------------------------------------------- set-up --
    def setup(self) -> None:
        cell = self.cell
        dep = cell.config["deployment"]
        self.archive_root = cell.workdir + "/archive"
        self.checkpoint = dep["checkpoint"]
        t0 = time.perf_counter()
        self._publish(dep)
        t1 = time.perf_counter()
        cell.note(f"set-up: {self.decorated_total} signatures signed in "
                  f"{self.sign_s:.1f} s, archive published in "
                  f"{t1 - t0:.1f} s ({len(self.tuples)} tuples resolved)")
        # the check node catches up from the archive itself: that warms
        # the one device shape through the path the window takes, and
        # leaves a node whose ledger state the adversarial envelopes
        # are checked against after the window
        cfg = node.make_config(cell.config["node"],
                               cell.workdir + "/check-node",
                               self.archive_root)
        clear_verify_cache()
        app = self.check_app = node.start_node(cfg)
        archive = next(a for a in app.history_manager.archives
                       if a.has_get())
        work = CatchupWork(app, archive, CatchupConfiguration(to_ledger=0))
        app.work_scheduler.schedule(work)
        while not work.is_done():
            if app.clock.crank(False) == 0:
                app.clock.crank(True)
        work.drain(self.p["drain_timeout_s"])
        self._tmp_dirs = [getattr(work, "_tmp", "")]
        lm = app.ledger_manager
        if work.get_state() != State.WORK_SUCCESS or \
                lm.get_last_closed_ledger_hash() != \
                self.hash_at.get(self.checkpoint):
            raise RuntimeError("warm-up: the check node did not reach the "
                               "publisher's checkpoint")
        faults = node.supervisor_faults(app.batch_verifier.status())
        if faults:
            raise RuntimeError("warm-up: " + "; ".join(faults))
        t2 = time.perf_counter()
        cell.note(f"set-up: the check node's own catchup (first device "
                  f"calls, {chunking.MAX_BUCKET} lanes a chunk) took "
                  f"{t2 - t1:.1f} s")
        self.corpus = adversarial.corpus(cell.seed,
                                         self.p["adversarial_random"])
        self.envelopes = self._adversarial_envelopes(
            self.p["adversarial_envelopes"])

    def _publish(self, dep: dict) -> None:
        cell = self.cell
        cfg = node.make_config(
            cell.config["node"], cell.workdir + "/publisher",
            self.archive_root, put=True,
            overrides=cell.config.get("publisher_overrides"))
        app = node.start_node(cfg)
        try:
            nid = cfg.network_id()
            t = self.traffic = MultisigTraffic(cell.seed, nid, dep)
            self.model = MultisigLedgerModel()
            lm = app.ledger_manager
            t0 = time.perf_counter()
            creation, installs = t.fund(app, self.model)
            # ledger -> (transactions, decorated signatures) it holds
            self.by_ledger = {}
            seq = lm.get_last_closed_ledger_num()
            for at, batch in ((seq - 1, creation), (seq, installs)):
                self.by_ledger[at] = (len(batch), sum(
                    signature_count(f) for f in batch))
            frames = creation + installs
            self.sign_s = 0.0
            self.first_payment_ledger = seq + 1
            for _ in range(dep["payment_ledgers"]):
                s0 = time.perf_counter()
                ledger = t.next_ledger()
                self.sign_s += time.perf_counter() - s0
                batch = [entry[0] for entry in ledger]
                submit(app, batch)
                frames.extend(batch)
                app.manual_close()
                apply_to_model(self.model, t, ledger)
                self.by_ledger[lm.get_last_closed_ledger_num()] = (
                    len(batch), sum(signature_count(f) for f in batch))
            self.last_payment_ledger = lm.get_last_closed_ledger_num()
            cell.note(f"set-up: {len(frames)} transactions admitted and "
                      f"closed by the publisher in "
                      f"{time.perf_counter() - t0:.1f} s")
            if lm.get_last_closed_ledger_num() > self.checkpoint:
                raise ValueError("the deployment's ledgers do not fit "
                                 "the checkpoint")
            while lm.get_last_closed_ledger_num() < self.checkpoint:
                app.manual_close()
            lm.join_completion()     # the checkpoint's publish rides it
            if app.history_manager.published_count < 1:
                raise RuntimeError("the checkpoint was not published")
            self.hash_at = {
                int(seq): bytes(h) for seq, h in app.database.query_all(
                    "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
            # the archive's tuples (for the corpus' padding), resolved as
            # a replaying node resolves them: no ledger state
            self.tuples = collect_signature_tuples(frames, nid)
            self.decorated_total = sum(s for _, s in self.by_ledger.values())
        finally:
            app.shutdown()

    # ---------------------------------------------------------- window --
    def window(self, seconds: float) -> None:
        # catchup_replay's window; its counts take one signature a
        # transaction, so they are made again here
        self.payment_signatures_per_ledger = 0
        super().window(seconds)
        txs = sigs = 0
        for r in self.replays:
            for seq, (n_txs, n_sigs) in self.by_ledger.items():
                if seq <= r.lcl:
                    txs += n_txs
                    sigs += n_sigs
        batched = sum(1 for r in self.replays if r.verifier.batches)
        self.cell.traffic_counts.update(
            transactions=txs, signatures=sigs,
            signatures_in_checkpoints=batched * self.decorated_total)

    # ---------------------------------------------------------- checks --
    def check(self) -> list:
        checks = super().check()
        cell = self.cell
        unknown, _ = cell.counters.get("crypto.prevalidated.miss.unknown",
                                       (0, 0.0))
        checks.append(Check("signature checks of apply whose tuple the "
                            "resolver never made "
                            "(crypto.prevalidated.miss.unknown)",
                            unknown, 0))
        chunks = failed = 0
        for r in self.replays:
            for b in r.verifier.batches:
                chunks += b["chunks"]
                failed += b["failed"]
        want = sum(len(chunking.chunk_bounds(b["n"], chunking.MAX_BUCKET))
                   for r in self.replays for b in r.verifier.batches)
        checks.append(Check("chunks of the window's batches that failed "
                            "or never landed", failed + want - chunks, 0))
        return checks

    def _corpus_check(self) -> list:
        """The adversarial corpus and the adversarial envelopes' tuples,
        padded with the archive's own to whole chunks and a remainder,
        through the warm shape; then the envelopes through `check_valid`
        with the table those verdicts fill."""
        app = self.check_app
        nid = app.config.network_id()
        env_frames = [f for f, _ in self.envelopes]
        env_tuples = collect_signature_tuples(
            env_frames, nid, ledger_state=app.ledger_manager.root)
        items = [(p, s, m) for p, s, m, _ in self.corpus]
        want = [v for _, _, _, v in self.corpus]
        n = self.p["corpus_chunks"] * chunking.MAX_BUCKET \
            + self.p["corpus_remainder"]
        # the archive's tuples again and again where it has fewer
        need = max(0, n - len(items) - len(env_tuples))
        fill = (self.tuples * (need // max(1, len(self.tuples)) + 1))[:need]
        # the corpus straddles the first boundary: half before, half after
        at = max(0, chunking.MAX_BUCKET - len(items) // 2)
        batch = fill[:at] + items + fill[at:] + env_tuples
        wanted = [True] * len(fill[:at]) + want + [True] * len(fill[at:])
        verifier = self.wrap_verifier(catchup_replay.RecordingVerifier(
            app.batch_verifier, self.cell.spans))
        got = [bool(v) for v in verifier.verify_tuples(batch)]
        head = got[:len(wanted)]
        wrong = sum(1 for g, w in zip(head, wanted) if g != w) + \
            abs(len(got) - len(batch))
        faults = node.supervisor_faults(app.batch_verifier.status())
        checks = [
            Check(f"verdicts (of {len(self.corpus)} adversarial and "
                  f"{len(fill)} valid tuples in "
                  f"{len(chunking.chunk_bounds(len(batch), chunking.MAX_BUCKET))}"
                  " chunks) that differ from the oracle's", wrong, 0),
            Check("supervisor complaints after the corpus "
                  + "; ".join(faults), len(faults), 0)]
        # the envelopes: a table the device filled, the sequential checker
        table = PrevalidatedVerifier()
        table.add_results(env_tuples, got[len(wanted):],
                          table.expect(env_tuples))
        accounts = self.traffic.model_accounts()
        differ = 0
        for frame, what in self.envelopes:
            with LedgerTxn(app.ledger_manager.root) as ltx:
                ok = frame.check_valid(ltx, verify=table)
            res = frame.result.result
            code = TransactionResultCode(res.disc).name
            inner = None
            if code.startswith("txFEE_BUMP_INNER"):
                inner = TransactionResultCode(
                    res.value.result.result.disc).name
            m_ok, m_code, m_inner = multisig_model.envelope_verdict(
                accounts, describe(frame))
            # an authorised envelope's code is made at apply
            if ok != m_ok or (not m_ok and (code, inner)
                              != (m_code, m_inner)):
                differ += 1
                self.cell.note(f"envelope {what}: {ok} {code} {inner}, "
                               f"the model {m_ok} {m_code} {m_inner}")
        checks.append(Check(
            f"adversarial envelopes (of {len(self.envelopes)}) whose "
            "verdict or result code differs from the multisig model's",
            differ, 0))
        checks.append(Check(
            "envelope checks whose tuple the resolver never made",
            table.misses_unknown + table.misses_pending, 0))
        return checks

    def _adversarial_envelopes(self, n: int) -> list:
        """[(frame, what)]: the next payment of accounts of the archive,
        built for the check node's state at the checkpoint."""
        t = self.traffic

        def flip(frame, i):
            ds = frame.signatures[i]
            sig = bytes(ds.signature)
            ds.signature = bytes([sig[0] ^ 1]) + sig[1:]
            return frame

        def of_class(name, rotated=None):
            return [i for i, c in enumerate(t.class_of) if c == name
                    and (rotated is None or (i in t.rotated_out) == rotated)]

        def pay(i, keys=None):
            """Account i's next payment; its sequence number is given
            back, so every envelope is the account's next one."""
            frame = t.payment(i, t.payers[(i + 1) % len(t.payers)],
                              t.amounts[0], keys)
            t.payers[i].seq -= 1
            return frame

        stranger = SecretKey.from_seed(b"benchmark-stranger".ljust(32, b"-"))

        def with_master_and(other, accounts):
            """Payments signed by the master key and one key more."""
            return lambda k: pay(accounts[k], [t.payers[accounts[k]].key,
                                               other(accounts[k])])
        makers = [
            ("one of twenty signatures bit-flipped",
             lambda k: flip(pay(of_class("limit20")[k]), (7 * k + 3) % 20)),
            ("2of3 with one good and one bad signature",
             lambda k: flip(pay(of_class("2of3", False)[k]), k % 2)),
            ("a good signature of a non-signer added",
             with_master_and(lambda i: stranger, of_class("single"))),
            ("a signature of a rotated-out signer",
             with_master_and(t.rotated_out.get, of_class("2of3", True))),
            ("a fee bump with a bad outer signature over a good inner",
             lambda k: flip(pay(of_class("3of5-bumped")[k]), 0)),
        ]
        sound = [("a sound single envelope", of_class("single")[-1]),
                 ("a sound 2of3 envelope", of_class("2of3", False)[-1]),
                 ("a sound envelope of a rotated 2of3 account",
                  of_class("2of3", True)[-1]),
                 ("a sound fee-bumped 3of5 envelope",
                  of_class("3of5-bumped")[-1]),
                 ("a sound envelope of twenty signatures",
                  of_class("limit20")[-1])]
        out = [(pay(i), what) for what, i in sound]
        k = 0
        while len(out) < n:
            what, make = makers[k % len(makers)]
            out.append((make(k // len(makers)), what))
            k += 1
        return out

    def close(self) -> None:
        super().close()
        for d in getattr(self, "_tmp_dirs", []):
            shutil.rmtree(d, ignore_errors=True)
