"""Traffic driver `range_replay`: `multisig_replay`'s whole catchups of
the run's multi-signer archive into fresh nodes, back to back, for an
archive of more than one checkpoint, replayed by ONE `CatchupWork`
(`catchup current --new-db`: genesis to the archive's last checkpoint).

What it takes from `multisig_replay` is everything but the publishing
and the counts: the `Replay`, the window, the check node's own catchup
(over every checkpoint, in set-up), the checks of the chain's end, the
accounts, the supervisor, the chunks, the corpus and the envelopes. What
it replaces or adds, because `catchup_replay` and `multisig_replay`
publish one checkpoint and count one batch a replay:

- the publisher closes through `deployment.checkpoints` checkpoints and
  must have published that many;
- the rotation takes turns in a pool of `rotation.pool` accounts, so an
  account rotates again in a later checkpoint and enough never rotate;
- a replay is `checkpoints` batches and, at this deployment's size, two
  chunks a batch: any other number is a failed check;
- the header chain every replay left on disk is the publisher's at
  EVERY ledger, not only at the last;
- `crypto.collect.carried` > 0 in every replay: a later checkpoint's
  signers were resolved from what the one before it had in flight. A
  program whose resolver knows only the node's state and the
  checkpoint's own operations reads 0 here and has
  `crypto.prevalidated.miss.unknown` > 0: not correct, by both.

Nothing that depends on timing decides `correct`.
"""

import sqlite3
import time

from stellar_core_tpu.history.archive import checkpoint_containing
from stellar_core_tpu.ops import chunking
from stellar_core_tpu.tx.signature_checker import collect_signature_tuples

from benchmark.generators import multisig_replay
from benchmark.generators.multisig_payments import (
    MultisigTraffic, apply_to_model, signature_count)
from benchmark.generators.payments import submit
from benchmark.harness import node
from benchmark.harness.checks import Check
from benchmark.reference.multisig_ledger_model import MultisigLedgerModel


class RangeTraffic(MultisigTraffic):
    """`MultisigTraffic` whose rotation carries on for as many ledgers
    as the deployment has: `rotation.pool` accounts of the rotating
    class, drawn from the seed, take turns, `accounts_per_ledger` a
    rotation ledger."""

    def __init__(self, seed: int, network_id: bytes, dep: dict):
        super().__init__(seed, network_id, dep)
        rot = dep["rotation"]
        pool = [i for i, n in enumerate(self.class_of) if n == rot["class"]]
        self._rng.shuffle(pool)
        pool = pool[:rot["pool"]]
        per = rot["accounts_per_ledger"]
        self.rotating = {
            ledger: [pool[(k * per + j) % len(pool)] for j in range(per)]
            for k, ledger in enumerate(rot["payment_ledgers"])}


class Driver(multisig_replay.Driver):
    # ---------------------------------------------------------- set-up --
    def _publish(self, dep: dict) -> None:
        cell = self.cell
        cfg = node.make_config(
            cell.config["node"], cell.workdir + "/publisher",
            self.archive_root, put=True,
            overrides=cell.config.get("publisher_overrides"))
        app = node.start_node(cfg)
        try:
            nid = cfg.network_id()
            t = self.traffic = RangeTraffic(cell.seed, nid, dep)
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
            # (a rehearsal reaches the second checkpoint over empty
            # ledgers; the deployment has none)
            for _ in range(dep.get("idle_ledgers", 0)):
                app.manual_close()
            self.sign_s = 0.0
            self.first_payment_ledger = lm.get_last_closed_ledger_num() + 1
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
            if self.last_payment_ledger > self.checkpoint or \
                    checkpoint_containing(self.first_payment_ledger) \
                    == self.checkpoint:
                raise ValueError("the deployment's ledgers do not span "
                                 "the range up to its checkpoint")
            while lm.get_last_closed_ledger_num() < self.checkpoint:
                app.manual_close()
            lm.join_completion()     # the last checkpoint's publish rides it
            self.published = app.history_manager.published_count
            self.hash_at = {
                int(seq): bytes(h) for seq, h in app.database.query_all(
                    "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
            # the archive's tuples (for the corpus' padding), resolved in
            # one piece: every signer is named by some operation of it
            self.tuples = collect_signature_tuples(frames, nid)
            # decorated signatures by the checkpoint that holds them
            self.decorated_in = {}
            for seq, (_, sigs) in self.by_ledger.items():
                cp = checkpoint_containing(seq)
                self.decorated_in[cp] = self.decorated_in.get(cp, 0) + sigs
            self.decorated_total = sum(self.decorated_in.values())
        finally:
            app.shutdown()

    # ---------------------------------------------------------- window --
    def window(self, seconds: float) -> None:
        super().window(seconds)
        # a replay dispatches one batch a checkpoint, in their order
        by_cp = [sigs for _, sigs in sorted(self.decorated_in.items())]
        self.cell.traffic_counts["signatures_in_checkpoints"] = sum(
            sum(by_cp[:len(r.verifier.batches)]) for r in self.replays)

    # ---------------------------------------------------------- checks --
    def check(self) -> list:
        checks = super().check()
        want_batches = self.p["checkpoints"]
        checks.append(Check(
            f"checkpoints the publisher published ({self.published}) off "
            f"the deployment's {want_batches}",
            abs(self.published - want_batches), 0))
        odd = uncarried = off_chain = 0
        for r in self.replays:
            sizes = [b["n"] for b in r.verifier.batches]
            chunks = sum(b["chunks"] for b in r.verifier.batches)
            if len(sizes) != want_batches or chunks != sum(
                    len(chunking.chunk_bounds(n, chunking.MAX_BUCKET))
                    for n in sizes) or any(b["failed"]
                                           for b in r.verifier.batches):
                odd += 1
            if not r.counters.get("crypto.collect.carried", (0, 0.0))[0]:
                uncarried += 1
            off_chain += self._off_chain(r)
            self.cell.note(f"replay {r.index}: batches of {sizes} tuples, "
                           f"{chunks} chunks landed")
        checks.append(Check(
            f"replays that were other than {want_batches} batches, every "
            "chunk of them landed and none failed", odd, 0))
        checks.append(Check(
            "replays in which no candidate key came from the signer keys "
            "carried from the checkpoint before (crypto.collect.carried)",
            uncarried, 0))
        checks.append(Check(
            f"ledgers (of {len(self.hash_at)} a replay) whose header hash "
            "on the replayed node's disk differs from the publisher's",
            off_chain, 0))
        return checks

    def _off_chain(self, replay) -> int:
        """Ledgers of the publisher's chain that the replayed node's
        database holds under another hash, or not at all."""
        db = sqlite3.connect(f"{replay.workdir}/stellar.db")
        try:
            mine = {int(seq): bytes(h) for seq, h in db.execute(
                "SELECT ledgerseq, ledgerhash FROM ledgerheaders")}
        finally:
            db.close()
        return sum(1 for seq, h in self.hash_at.items()
                   if mine.get(seq) != h)
