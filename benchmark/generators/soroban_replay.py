"""Traffic driver `soroban_replay`: `multisig_replay`'s whole catchups of
the run's archive into fresh nodes, back to back, for a checkpoint of
Stellar-Asset-Contract transfers (`soroban_transfers.py`) most of which
are relayed: the transaction's source is not the account whose funds
move, so `from` authorizes with an address-credential entry that
carries a second Ed25519 signature, inside the operation.

What it takes from `multisig_replay` is the `Replay`, the chunk
recorder, the window, the check node's own catchup in set-up (which
warms the one device shape through the path the window takes) and the
checks of the chain's end, the accounts, the supervisor, the resolver
and the chunks. What it replaces is the publishing and the counts (a
transfer carries one or two signatures, one of them not in the
envelope), and what it adds are the checks only this deployment has:

- the replayed node's header chain is the publisher's at every ledger;
- every transaction's archived result is the model's
  (`reference/soroban_auth_model.py`): the adversarial transfers failed
  with the host's auth error, everything else succeeded;
- the nonce entries a replay left are the (address, nonce) pairs the
  model used, no more and no fewer;
- `crypto.collect.auth` = the auth signatures of the replayed ledgers:
  each became a tuple of the checkpoint's batch;
- `soroban.auth.verify.prevalidated` + `.fallback` = the auth
  signatures the model asked a verdict for, and `.fallback`, like
  `crypto.verify.native`, is no more than `crypto.prevalidated.miss.
  pending` (what apply outran is counted and exact; anything beyond it
  is a verdict the device made and nobody used);
- the adversarial corpus, the archive's bit-flipped auth tuples and a
  seeded sample of its sound ones, padded with the archive's tuples to
  whole chunks and a remainder, through the warm shape: every verdict
  the pure-Python oracle's, in order.

The oracle takes milliseconds a verify, so the model asks it for the
adversarial transfers and `oracle_sample` sound ones drawn from the
seed, and the native verifier for the rest.

A program that hands apply's verifier to the Soroban host only
natively (before `ApplyContext.verify` was assigned) runs this cell to
its end and is not correct: its `soroban.auth.verify.*` are absent or
all `fallback`.
"""

import random
import sqlite3
import time

from stellar_core_tpu.crypto.keys import PubKeyUtils
from stellar_core_tpu.ops import chunking
from stellar_core_tpu.soroban.host import instance_key
from stellar_core_tpu.tx.signature_checker import collect_signature_tuples
from stellar_core_tpu.xdr.contract import InvokeHostFunctionResultCode
from stellar_core_tpu.xdr.results import (TransactionResultCode,
                                          TransactionResultPair)

from benchmark.generators import catchup_replay, multisig_replay
from benchmark.generators.payments import submit
from benchmark.generators.soroban_transfers import SorobanTraffic, nonce_key
from benchmark.harness import node
from benchmark.harness.checks import Check
from benchmark.reference import ed25519_oracle, soroban_auth_model
from benchmark.reference.soroban_auth_model import SorobanAuthModel


def result_of(pair_bytes: bytes) -> tuple:
    """(SUCCESS or FAILED, the operation's inner code name) of an
    archived TransactionResultPair."""
    res = TransactionResultPair.from_bytes(pair_bytes).result.result
    code = TransactionResultCode(res.disc)
    inner = None
    if code in (TransactionResultCode.txSUCCESS,
                TransactionResultCode.txFAILED) and res.value:
        inner = InvokeHostFunctionResultCode(
            res.value[0].value.value.disc).name
    if code == TransactionResultCode.txSUCCESS:
        return soroban_auth_model.SUCCESS, inner
    return soroban_auth_model.FAILED, inner if \
        code == TransactionResultCode.txFAILED else code.name


# what the host answers a failed authorization with
AUTH_FAILURE = InvokeHostFunctionResultCode.INVOKE_HOST_FUNCTION_TRAPPED.name
SOUND = InvokeHostFunctionResultCode.INVOKE_HOST_FUNCTION_SUCCESS.name


class Driver(multisig_replay.Driver):
    # ---------------------------------------------------------- set-up --
    def _adversarial_envelopes(self, n: int) -> list:
        return []            # this deployment's faults are inside apply

    def _verdict(self, pub: bytes, sig: bytes, msg: bytes) -> bool:
        """The model's verifier: the oracle for the adversarial and the
        sampled signatures, the native verifier for the rest."""
        if sig in self._by_oracle:
            return ed25519_oracle.verify(pub, sig, msg)
        return PubKeyUtils.verify_sig(pub, sig, msg)

    def _publish(self, dep: dict) -> None:
        cell = self.cell
        cfg = node.make_config(
            cell.config["node"], cell.workdir + "/publisher",
            self.archive_root, put=True,
            overrides=cell.config.get("publisher_overrides"))
        app = node.start_node(cfg)
        try:
            nid = cfg.network_id()
            t = self.traffic = SorobanTraffic(cell.seed, nid, dep)
            self.model = SorobanAuthModel(nid, t.contract_id,
                                          verify=self._verdict)
            lm = app.ledger_manager
            t0 = time.perf_counter()
            frames = t.fund(app, self.model)
            # ledger -> (transactions, tuples: envelope and auth
            # signatures) it holds; set-up's own by where they closed
            self.by_ledger = {}
            seq = lm.get_last_closed_ledger_num()
            self.by_ledger[seq - 1] = (len(frames) - 1, len(frames) - 1)
            self.by_ledger[seq] = (1, 1)
            self.sign_s = 0.0
            self.first_payment_ledger = seq + 1
            self.transfers = {}       # txid -> (ledger, Transfer, kind)
            self.auth_signatures = 0
            for _ in range(dep["payment_ledgers"]):
                seq = lm.get_last_closed_ledger_num() + 1
                s0 = time.perf_counter()
                ledger = t.next_ledger(seq)
                self.sign_s += time.perf_counter() - s0
                batch = [f for f, _, _ in ledger]
                submit(app, batch)
                frames.extend(batch)
                app.manual_close()
                auth = sum(1 for _, tr, _ in ledger
                           if tr.credential == "address")
                self.auth_signatures += auth
                self.by_ledger[seq] = (len(batch), len(batch) + auth)
                for f, tr, kind in ledger:
                    self.transfers[f.full_hash()] = (seq, tr, kind)
            self.last_payment_ledger = lm.get_last_closed_ledger_num()
            cell.note(f"set-up: {len(frames)} transactions admitted and "
                      f"closed by the publisher in "
                      f"{time.perf_counter() - t0:.1f} s")
            if self.last_payment_ledger > self.checkpoint:
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
            self.tuples = collect_signature_tuples(frames, nid)
            self.decorated_total = sum(s for _, s in self.by_ledger.values())
            self._run_model(app, nid)
        finally:
            app.shutdown()

    def _run_model(self, app, nid: bytes) -> None:
        """Advance the model by every transfer in the order the
        publisher applied it, and hold each archived result against the
        model's."""
        rng = random.Random(self.cell.seed ^ 0xA17)
        sound = [tr.signature for _, tr, kind in self.transfers.values()
                 if kind is None and tr.signature is not None]
        self._by_oracle = {
            tr.signature for _, tr, kind in self.transfers.values()
            if kind is not None} | set(rng.sample(
                sound, min(len(sound), self.p["oracle_sample"])))
        rows = app.database.query_all(
            "SELECT txid, ledgerseq, txresult FROM txhistory "
            "WHERE ledgerseq >= ? ORDER BY ledgerseq, txindex",
            (self.first_payment_ledger,))
        self.result_differs = abs(len(rows) - len(self.transfers))
        self.model_failed = 0
        t0 = time.perf_counter()
        for txid, seq, pair in rows:
            got = self.transfers.get(bytes(txid))
            if got is None or got[0] != int(seq):
                self.result_differs += 1
                continue
            verdict, why = self.model.apply(int(seq), got[1])
            want = (verdict, SOUND if why is None else AUTH_FAILURE)
            if result_of(bytes(pair)) != want:
                self.result_differs += 1
                self.cell.note(f"ledger {seq} {got[2]}: archived "
                               f"{result_of(bytes(pair))}, the model "
                               f"{verdict} ({why})")
            if why is not None:
                self.model_failed += 1
        self.cell.note(
            f"set-up: the model applied {len(rows)} transfers "
            f"({self.model_failed} failed, {self.model.verified} verdicts "
            f"asked, {len(self._by_oracle)} of the oracle) in "
            f"{time.perf_counter() - t0:.1f} s")
        # nonce key -> the pair it stands for, of every relayed transfer
        self.pair_of = {
            nonce_key(tr.frm, tr.nonce).to_bytes(): (tr.frm, tr.nonce)
            for _, tr, _ in self.transfers.values()
            if tr.credential == "address"}

    # ---------------------------------------------------------- window --
    def window(self, seconds: float) -> None:
        super().window(seconds)
        whole = sum(1 for r in self.replays
                    if r.lcl >= self.last_payment_ledger)
        self.cell.traffic_counts.update(
            whole_replays=whole,
            auth_signatures=whole * self.auth_signatures,
            auth_verdicts=whole * self.model.verified)

    # ---------------------------------------------------------- checks --
    def check(self) -> list:
        cell = self.cell
        counts = cell.traffic_counts
        # the sibling's "no false device verdict" holds for an archive
        # of sound signatures; this one carries bit-flipped auth tuples,
        # and the device must call exactly those false
        flipped = sum(1 for _, _, kind in self.transfers.values()
                      if kind == "bad_signature")
        false_verdicts = sum(
            b["results"].count(False) for r in self.replays
            for b in r.verifier.batches if b["results"] is not None)
        checks = [
            Check("device verdicts of the window's batches that are "
                  f"false, off the archive's {flipped} bit-flipped auth "
                  "tuples a replay",
                  abs(false_verdicts - flipped * counts["whole_replays"]),
                  0) if c.what.startswith("device verdicts of the") else c
            for c in super().check()]

        def counter(name: str) -> int:
            return cell.counters.get(name, (0, 0.0))[0]

        instance = instance_key(self.traffic.contract).to_bytes()
        off_chain = nonces = 0
        for r in self.replays:
            chain, keys = self._on_disk(r)
            off_chain += sum(1 for seq, h in self.hash_at.items()
                             if chain.get(seq) != h)
            pairs = [self.pair_of.get(k, k) for k in keys
                     if k != instance]
            nonces += self.model.nonce_differences(pairs) \
                + len(pairs) - len(set(pairs))
        checks.append(Check(
            f"ledgers (of {len(self.hash_at)} a replay) whose header hash "
            "on the replayed node's disk differs from the publisher's",
            off_chain, 0))
        checks.append(Check(
            f"archived results (of {len(self.transfers)} transfers, "
            f"{self.model_failed} failed in the model) that differ from "
            "the model's", self.result_differs, 0))
        checks.append(Check(
            f"nonce entries (over {len(self.replays)} replays, "
            f"{len(self.model.used)} used in the model) a replay holds "
            "and the model does not, or the reverse", nonces, 0))
        checks.append(Check(
            "auth signatures of the replayed ledgers "
            f"({counts['auth_signatures']}) off crypto.collect.auth",
            abs(counter("crypto.collect.auth")
                - counts["auth_signatures"]), 0))
        prevalidated = counter("soroban.auth.verify.prevalidated")
        fallback = counter("soroban.auth.verify.fallback")
        pending = counter("crypto.prevalidated.miss.pending")
        checks.append(Check(
            "auth verdicts the model asked for "
            f"({counts['auth_verdicts']}) off soroban.auth.verify."
            f"prevalidated + .fallback ({prevalidated} + {fallback})",
            abs(prevalidated + fallback - counts["auth_verdicts"]), 0))
        checks.append(Check(
            "auth signatures verified by the fallback "
            "(soroban.auth.verify.fallback) beyond what apply outran "
            f"(crypto.prevalidated.miss.pending, {pending})",
            max(0, fallback - pending), 0))
        native = cell.zones.get("crypto.verify.native", (0, 0.0))[0]
        checks.append(Check(
            "native verifies inside the window (crypto.verify.native) "
            f"beyond what apply outran ({pending})",
            max(0, native - pending), 0))
        return checks

    def _on_disk(self, replay) -> tuple:
        """({ledger: header hash}, [contract-data key bytes]) of the
        replayed node's database."""
        db = sqlite3.connect(f"{replay.workdir}/stellar.db")
        try:
            chain = {int(seq): bytes(h) for seq, h in db.execute(
                "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
            keys = [bytes(k) for k, in db.execute(
                "SELECT key FROM contractdata")]
        finally:
            db.close()
        return chain, keys

    def _corpus_check(self) -> list:
        """The adversarial corpus, the archive's bit-flipped auth tuples
        and a sample of its sound ones, padded with the archive's own
        to whole chunks and a remainder, through the warm shape."""
        app = self.check_app
        nid = app.config.network_id()
        rng = random.Random(self.cell.seed ^ 0xC0)
        flipped = [tr for _, tr, kind in self.transfers.values()
                   if kind == "bad_signature"]
        sound = [tr for _, tr, kind in self.transfers.values()
                 if kind is None and tr.signature in self._by_oracle]
        auth = [(tr.signer, tr.signature,
                 soroban_auth_model.auth_payload(
                     nid, self.traffic.contract_id, tr))
                for tr in flipped + rng.sample(
                    sound, min(len(sound), self.p["corpus_sound"]))]
        items = [(p, s, m) for p, s, m, _ in self.corpus] + auth
        want = [v for _, _, _, v in self.corpus] + \
            [ed25519_oracle.verify(*t) for t in auth]
        n = self.p["corpus_chunks"] * chunking.MAX_BUCKET \
            + self.p["corpus_remainder"]
        need = max(0, n - len(items))
        fill = (self.tuples * (need // max(1, len(self.tuples)) + 1))[:need]
        # the corpus straddles the first boundary: half before, half after
        at = max(0, chunking.MAX_BUCKET - len(items) // 2)
        batch = fill[:at] + items + fill[at:]
        # the archive's own tuples are sound but for its bit-flipped
        # auth tuples, whose verdict the oracle has just given
        false = {t for t, ok in zip(auth, want[len(self.corpus):])
                 if not ok}
        wanted = [t not in false for t in fill[:at]] + want + \
            [t not in false for t in fill[at:]]
        verifier = self.wrap_verifier(catchup_replay.RecordingVerifier(
            app.batch_verifier, self.cell.spans))
        got = [bool(v) for v in verifier.verify_tuples(batch)]
        wrong = sum(1 for g, w in zip(got, wanted) if g != w) + \
            abs(len(got) - len(batch))
        faults = node.supervisor_faults(app.batch_verifier.status())
        return [
            Check(f"verdicts (of {len(self.corpus)} adversarial, "
                  f"{len(flipped)} bit-flipped and {len(auth) - len(flipped)}"
                  f" sound auth tuples and {len(fill)} of the archive in "
                  f"{len(chunking.chunk_bounds(len(batch), chunking.MAX_BUCKET))}"
                  " chunks) that differ from the oracle's", wrong, 0),
            Check("supervisor complaints after the corpus "
                  + "; ".join(faults), len(faults), 0)]
