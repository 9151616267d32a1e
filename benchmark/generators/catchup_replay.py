"""Traffic driver `catchup_replay`: for the whole window, repeat a full
offline catchup (`catchup current --new-db` semantics: `CatchupWork`
to the archive's last checkpoint, cranked to completion, then
`work.drain()`) of the run's archive into a fresh node, node start-up
included, as an operator pays it. Ledgers are counted as they close.
The replay in flight when the time is up runs to its end: a window is a
whole number of replays.

Set-up publishes the archive from the seed with a node of the
configuration's deployment and the native verifier (the plain
sequential reference whose header hashes every replay must reproduce),
and warms the one device shape the replays use.

Parameters (the traffic file): `adversarial_random` (valid and
bit-flipped tuples ahead of the adversarial tail of the corpus that
goes through the warm bucket after the window), `drain_timeout_s`.
"""

import shutil
import time

from stellar_core_tpu.catchup import CatchupConfiguration, CatchupWork
from stellar_core_tpu.crypto.keys import clear_verify_cache
from stellar_core_tpu.tx.signature_checker import collect_signature_tuples
from stellar_core_tpu.work import State

from benchmark.generators.payments import PaymentTraffic, submit
from benchmark.harness import node
from benchmark.harness.checks import Check
from benchmark.harness.recording import RecordingVerifier
from benchmark.reference import adversarial
from benchmark.reference.ledger_model import LedgerModel


class Replay:
    """One catchup of the archive into a fresh node."""

    def __init__(self, driver, index: int):
        cell = driver.cell
        self.index = index
        self.workdir = f"{cell.workdir}/replay-{index}"
        self.t0 = time.perf_counter()
        # a catching-up node has never seen these signatures: what the
        # process-wide verify cache learnt from the last replay must not
        # answer for this one (a real catchup is a new process)
        clear_verify_cache()
        cfg = node.make_config(cell.config["node"], self.workdir,
                               driver.archive_root)
        self.app = node.start_node(cfg)
        cell.watch_app(self.app)
        archive = next(a for a in self.app.history_manager.archives
                       if a.has_get())
        self.verifier = driver.wrap_verifier(
            RecordingVerifier(self.app.batch_verifier, cell.spans))
        self.work = CatchupWork(self.app, archive,
                                CatchupConfiguration(to_ledger=0),
                                batch_verifier=self.verifier)
        self.app.work_scheduler.schedule(self.work)
        self.lm = self.app.ledger_manager
        self.lcl = self.lm.get_last_closed_ledger_num()
        self.finished = False       # ran to the end of the work
        self.observed = None
        self.status = None

    def crank(self) -> int:
        """One crank of the node's clock; returns ledgers it closed."""
        clock = self.app.clock
        if clock.crank(False) == 0:
            clock.crank(True)
        now = self.lm.get_last_closed_ledger_num()
        closed, self.lcl = now - self.lcl, now
        return closed

    def done(self) -> bool:
        return self.work.is_done()

    def finish(self, raw_keys, drain_timeout: float) -> None:
        """What `cmd_catchup` does once the work is done: drain the
        device batch, report, shut the node down — plus the reading of
        the replayed accounts for the dictionary model."""
        self.work.drain(drain_timeout)
        self.finished = self.work.is_done()
        self.state = self.work.get_state()
        self.lcl_hash = self.lm.get_last_closed_ledger_hash()
        if self.finished:
            self.observed = node.account_states(self.app, raw_keys)
        self.status = self.app.batch_verifier.status()
        self.counters = node.counters(self.app)
        self.zones = node.zones(self.app)
        self.app.shutdown()
        self.t1 = time.perf_counter()


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.p = cell.traffic["params"]
        self.replays = []
        self.check_app = None

    def wrap_verifier(self, verifier):
        """Tests replace this to break the verifier under a replay."""
        return verifier

    # ---------------------------------------------------------- set-up --
    def setup(self) -> None:
        cell = self.cell
        dep = cell.config["deployment"]
        self.archive_root = cell.workdir + "/archive"
        self.checkpoint = dep["checkpoint"]
        t0 = time.perf_counter()
        self._publish(dep)
        t1 = time.perf_counter()
        cell.note(f"set-up: archive published in {t1 - t0:.1f} s")
        # warm the device shape of a whole checkpoint's batch through a
        # node of the replaying configuration; the same node serves the
        # corpus check after the window
        cfg = node.make_config(cell.config["node"],
                               cell.workdir + "/check-node")
        self.check_app = node.start_node(cfg)
        verdicts = self.check_app.batch_verifier.verify_tuples(self.tuples)
        if not all(verdicts):
            raise RuntimeError("warm-up: the device refused a signature "
                               "of the archive")
        faults = node.supervisor_faults(
            self.check_app.batch_verifier.status())
        if faults:
            raise RuntimeError("warm-up: " + "; ".join(faults))
        t2 = time.perf_counter()
        cell.note(f"set-up: first device call of {len(self.tuples)} "
                  f"signatures took {t2 - t1:.1f} s")
        self.corpus = adversarial.corpus(cell.seed,
                                         self.p["adversarial_random"])

    def _publish(self, dep: dict) -> None:
        cell = self.cell
        cfg = node.make_config(
            cell.config["node"], cell.workdir + "/publisher",
            self.archive_root, put=True,
            overrides=cell.config.get("publisher_overrides"))
        app = node.start_node(cfg)
        try:
            nid = cfg.network_id()
            t = self.traffic = PaymentTraffic(
                cell.seed, nid, dep["accounts"], dep["amounts"],
                dep["starting_balance"])
            self.model = LedgerModel()
            frames = t.fund(app, self.model)
            for _ in range(dep["payment_ledgers"]):
                ledger = t.next_ledger()
                submit(app, [f for f, _, _, _ in ledger])
                frames.extend(f for f, _, _, _ in ledger)
                app.manual_close()
                for _, s, d, amount in ledger:
                    self.model.pay(t.accounts[s].raw, t.accounts[d].raw,
                                   amount)
            lm = app.ledger_manager
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
            self.tuples = collect_signature_tuples(frames, nid)
            self.payment_signatures_per_ledger = dep["txs_per_ledger"]
            self.first_payment_ledger = 4
            self.last_payment_ledger = 3 + dep["payment_ledgers"]
        finally:
            app.shutdown()

    # ---------------------------------------------------------- window --
    def window(self, seconds: float) -> None:
        """Whole replays, one after the other, until `seconds` have
        passed; the one in flight then runs to its end, so a window is
        a whole number of replays (the same work from every seed) and
        every phase of a catchup — start-up, download, parse, apply,
        the checkpoint's completion tail, shutdown — is in it in its
        true proportion."""
        cell = self.cell
        raw_keys = [a.raw for a in self.traffic.accounts]
        drain = self.p["drain_timeout_s"]
        closed = 0
        t_start = time.perf_counter()
        deadline = t_start + seconds
        while time.perf_counter() < deadline:
            replay = Replay(self, len(self.replays))
            self.replays.append(replay)
            while not replay.done():
                closed += replay.crank()
            replay.finish(raw_keys, drain)
            cell.spans.add("bench.replay", replay.t0, replay.t1,
                           index=replay.index)
        t_end = time.perf_counter()
        self.t_start, self.t_end = t_start, t_end
        self.window_s = t_end - t_start
        self.attempted = closed
        self.failed = 0
        for r in self.replays:
            node.add_into(cell.counters, r.counters)
            node.add_into(cell.zones, r.zones)
        replayed = sum(
            max(0, min(r.lcl, self.last_payment_ledger)
                - self.first_payment_ledger + 1) for r in self.replays)
        batched = sum(1 for r in self.replays if r.verifier.batches)
        per_checkpoint = (self.last_payment_ledger
                          - self.first_payment_ledger + 1) \
            * self.payment_signatures_per_ledger
        cell.traffic_counts.update(
            ledgers=closed, payment_ledgers=replayed,
            signatures_in_checkpoints=batched * per_checkpoint,
            transactions=replayed * self.payment_signatures_per_ledger,
            signatures=replayed * self.payment_signatures_per_ledger)

    def after_window(self) -> None:
        pass

    def end_to_end(self) -> dict:
        return {"catchup_ledgers_per_s": self.attempted / self.window_s}

    # ---------------------------------------------------------- checks --
    def check(self) -> list:
        cell = self.cell
        checks = []
        bad_end = bad_hash = bad_model = 0
        for r in self.replays:
            if r.lcl_hash != self.hash_at.get(r.lcl):
                bad_hash += 1
            if not r.finished or r.state != State.WORK_SUCCESS \
                    or r.lcl != self.checkpoint:
                bad_end += 1
            bad_model += self.model.differences(r.observed or {})
        compared = len(self.replays)
        self.failed = bad_end + bad_hash
        checks.append(Check("replays that ended other than WORK_SUCCESS at "
                            f"checkpoint {self.checkpoint}", bad_end, 0))
        checks.append(Check("replays whose last closed ledger's hash "
                            "differs from the publisher's", bad_hash, 0))
        cell.note(f"{len(self.replays)} whole replays in the window")
        checks.append(Check(f"accounts (over {compared} finished replays) "
                            "whose balance or sequence differs from the "
                            "dictionary model", bad_model, 0))
        false_verdicts = undrained = on_device = 0
        for r in self.replays:
            for b in r.verifier.batches:
                if b["results"] is None:
                    undrained += 1
                else:
                    false_verdicts += b["results"].count(False)
                    on_device += b["n"]
        checks.append(Check("device verdicts of the window's batches that "
                            "are false", false_verdicts, 0))
        checks.append(Check("device batches that never settled",
                            undrained, 0))
        checks.append(Check(
            "signatures the verifier was given, against the payment "
            "signatures replayed", on_device,
            cell.traffic_counts["signatures"], at_least=True))
        faults = []
        dispatches = 0
        for r in self.replays:
            faults.extend(node.supervisor_faults(r.status))
            dispatches += r.status.get("dispatches", 0)
        batches = sum(len(r.verifier.batches) for r in self.replays)
        checks.append(Check("supervisor complaints " + "; ".join(faults),
                            len(faults), 0))
        checks.append(Check("batches given to the verifier that the "
                            "supervisor did not dispatch to the device",
                            batches - dispatches, 0))
        checks.extend(self._corpus_check())
        return checks

    def _corpus_check(self) -> list:
        """The adversarial corpus, padded with the archive's own
        signatures to the size of a checkpoint's batch, through the
        already-warm device shape: every verdict must equal the
        oracle's."""
        n = len(self.tuples)
        items = [(p, s, m) for p, s, m, _ in self.corpus]
        want = [v for _, _, _, v in self.corpus]
        fill = self.tuples[:max(0, n - len(items))]
        items += fill
        want += [True] * len(fill)
        verifier = self.wrap_verifier(
            RecordingVerifier(self.check_app.batch_verifier,
                              self.cell.spans))
        got = [bool(v) for v in verifier.verify_tuples(items)]
        wrong = sum(1 for g, w in zip(got, want) if g != w) + \
            abs(len(got) - len(want))
        faults = node.supervisor_faults(
            self.check_app.batch_verifier.status())
        return [Check(f"verdicts (of {len(self.corpus)} adversarial and "
                      f"{len(fill)} valid tuples in one batch) that differ "
                      "from the oracle's", wrong, 0),
                Check("supervisor complaints after the corpus "
                      + "; ".join(faults), len(faults), 0)]

    def close(self) -> None:
        if self.check_app is not None:
            self.check_app.shutdown()
        for r in self.replays:
            shutil.rmtree(getattr(r.work, "_tmp", ""), ignore_errors=True)
