"""Traffic driver `closed_loop`: one client that submits a ledger's
worth of pre-signed payments one at a time through
`herder.recv_transaction`, triggers a manual close, and repeats — the
upstream max-TPS loadgen procedure (`generateload mode=pay` +
`manualclose` on the standalone configuration).

Parameters (the traffic file): `txs_per_ledger`, `amounts`,
`presigned_ledgers` (how many ledgers of envelopes set-up signs: two
checkpoint periods; the window ends early, and says so, if it runs
out), `corrupted_after` (how many envelopes with a flipped signature
bit are sent after the window),
`device_check_batch` (how many of the last closes' signatures, corrupted
ones among them, go through the node's device verifier after the
window).
"""

import time

from stellar_core_tpu.crypto.keys import clear_verify_cache
from stellar_core_tpu.herder.tx_queue import AddResult
from stellar_core_tpu.history import is_checkpoint_ledger
from stellar_core_tpu.tx.frame import make_frame
from stellar_core_tpu.tx.signature_checker import collect_signature_tuples
from stellar_core_tpu.xdr.transaction import TransactionEnvelope

from benchmark.generators.payments import PaymentTraffic
from benchmark.harness import node
from benchmark.harness.checks import Check
from benchmark.reference.ledger_model import LedgerModel


def _flip_signature_bit(frame, network_id):
    """A copy of `frame` whose signature has one bit flipped."""
    env = TransactionEnvelope.from_bytes(frame.envelope.to_bytes())
    sig = env.value.signatures[0]
    raw = bytes(sig.signature)
    sig.signature = raw[:7] + bytes([raw[7] ^ 0x10]) + raw[8:]
    return make_frame(env, network_id)


class Driver:
    def __init__(self, cell):
        self.cell = cell
        self.p = cell.traffic["params"]
        self.app = None

    # ---------------------------------------------------------- set-up --
    def setup(self) -> None:
        cell, p = self.cell, self.p
        dep = cell.config["deployment"]
        cfg = node.make_config(cell.config["node"], cell.workdir + "/node")
        self.app = app = node.start_node(cfg)
        cell.watch_app(app)
        self.traffic = PaymentTraffic(
            cell.seed, cfg.network_id(), dep["accounts"], p["amounts"],
            dep["starting_balance"])
        self.model = LedgerModel()
        t = self.traffic
        if p.get("txs_per_ledger", len(t.accounts)) != len(t.accounts):
            raise ValueError("closed_loop sends one payment per account "
                             "per ledger: txs_per_ledger must equal "
                             "the configuration's accounts")
        t.fund(app, self.model)
        # every envelope the window may need, signed now
        self.ledgers = [t.next_ledger()
                        for _ in range(p["presigned_ledgers"])]
        # the node's device verifier: warm the one shape the check
        # after the window uses (and the shape batched admission of a
        # 1,000-payment ledger would use), so nothing compiles later
        self.device_batch = p["device_check_batch"]
        warm = collect_signature_tuples(
            [f for f, _, _, _ in self.ledgers[-1]],
            cfg.network_id())[:self.device_batch]
        verdicts = app.batch_verifier.verify_tuples(warm)
        if not all(verdicts):
            raise RuntimeError("warm-up batch: the device refused a "
                               "valid signature")
        clear_verify_cache()
        app.ledger_manager.join_completion()
        self.counters0 = node.counters(app)
        self.zones0 = node.zones(app)

    # ---------------------------------------------------------- window --
    def window(self, seconds: float) -> None:
        """Whole checkpoint periods: the client keeps going until
        `seconds` have passed AND the node has closed a checkpoint
        ledger and finished its deferred tail. So every window holds
        the same ledgers (4..63 at today's speed: the 60 payment
        ledgers of the first checkpoint), and what a node does once in
        64 ledgers — the checkpoint's completion tail stalls the next
        close for seconds — is in every window in its true proportion,
        not in some windows and not in others."""
        app, cell = self.app, self.cell
        recv = app.herder.recv_transaction
        pending = AddResult.ADD_STATUS_PENDING
        lm = app.ledger_manager
        close_ms = []          # per close: trigger -> committed
        lat_ms = []            # per tx: recv_transaction call -> commit
        attempted = failed = 0
        self.closed = 0
        t_start = time.perf_counter()
        deadline = t_start + seconds
        for ledger in self.ledgers:
            t_sub = []
            t0 = time.perf_counter()
            for frame, _, _, _ in ledger:
                t_sub.append(time.perf_counter())
                if recv(frame) != pending:
                    failed += 1
            t1 = time.perf_counter()
            before = lm.get_last_closed_ledger_num()
            app.manual_close()
            t2 = time.perf_counter()
            if lm.get_last_closed_ledger_num() != before + 1:
                raise RuntimeError("manual close did not close a ledger")
            attempted += len(ledger)
            self.closed += 1
            close_ms.append((t2 - t1) * 1e3)
            lat_ms.extend((t2 - ts) * 1e3 for ts in t_sub)
            cell.spans.add("bench.submit", t0, t1, txs=len(ledger))
            cell.spans.add("bench.close", t1, t2)
            if is_checkpoint_ledger(before + 1):
                # the checkpoint's deferred tail (tx history, meta, the
                # debug-meta segment's compression) is work of this
                # window
                t3 = time.perf_counter()
                lm.join_completion()
                cell.spans.add("bench.checkpoint_tail", t3,
                               time.perf_counter())
                if time.perf_counter() >= deadline:
                    break
        else:
            cell.note("the window used every pre-signed ledger and "
                      "ended before a checkpoint")
            lm.join_completion()
        t_end = time.perf_counter()
        self.window_s = t_end - t_start
        self.t_start, self.t_end = t_start, t_end
        self.attempted, self.failed = attempted, failed
        self.close_ms, self.lat_ms = close_ms, lat_ms
        self.counters1 = node.counters(app)
        self.zones1 = node.zones(app)
        node.add_into(cell.counters, self.counters1, self.counters0)
        node.add_into(cell.zones, self.zones1, self.zones0)
        cell.traffic_counts.update(
            transactions=attempted - failed, signatures=attempted,
            ledgers=self.closed)
        sp = cell.spans
        subs = sorted(e - b for b, e, _ in sp.named("bench.submit"))
        cell.note(
            f"{self.closed} closes in a window of {self.window_s:.2f} s: "
            f"submit {sp.total('bench.submit'):.2f} s (longest loops "
            f"{subs[-1]:.2f} {subs[-2 % len(subs)]:.2f}), close "
            f"{sp.total('bench.close'):.2f} s (longest "
            f"{max(close_ms):.0f} ms, median "
            f"{cell.percentile(close_ms, 50):.0f} ms), checkpoint tail "
            f"{sp.total('bench.checkpoint_tail'):.2f} s")

    def end_to_end(self) -> dict:
        applied = self.attempted - self.failed
        return {"applied_tx_per_s": applied / self.window_s,
                "close_ms_p90": self.cell.percentile(self.close_ms, 90),
                "submit_applied_ms_p95":
                    self.cell.percentile(self.lat_ms, 95)}

    # ------------------------------------------------- after the window --
    def after_window(self) -> None:
        """Inside the traced window, after the measured one: the last
        closed ledger's signatures, some with a flipped bit, through
        the node's own device verifier (the program's supervised
        `batch_verifier`). It shows that in this deployment the device
        path is alive while the window itself never touches it."""
        app = self.app
        nid = app.config.network_id()
        frames = [f for f, _, _, _ in self.ledgers[self.closed - 1]]
        n_bad = self.p["corrupted_after"]
        good = frames[:self.device_batch - n_bad]
        # envelopes of the NEXT ledger (sequence numbers still good), so
        # that the flipped signature bit is the only thing wrong
        nxt = self.ledgers[self.closed] if self.closed < len(self.ledgers) \
            else self.traffic.next_ledger()
        self.bad_frames = [_flip_signature_bit(f, nid)
                           for f, _, _, _ in nxt[:n_bad]]
        tuples = collect_signature_tuples(good + self.bad_frames, nid)
        self.tail_expected = [True] * len(good) + [False] * n_bad
        t0 = time.perf_counter()
        self.tail_verdicts = [bool(v) for v in
                              app.batch_verifier.verify_tuples(tuples)]
        self.cell.spans.add("bench.device_check", t0, time.perf_counter(),
                            batch=len(tuples))

    def check(self) -> list:
        app, t = self.app, self.traffic
        checks = []
        tx_count = self.counters1.get("ledger.transaction.count", (0, 0))[0] \
            - self.counters0.get("ledger.transaction.count", (0, 0))[0]
        checks.append(Check("transactions refused at admission",
                            self.failed, 0))
        checks.append(Check("acknowledged minus applied "
                            "(ledger.transaction.count)",
                            abs(self.attempted - self.failed - tx_count), 0))
        checks.append(Check("transactions left in the queue",
                            len(app.herder.tx_queue.get_transactions()), 0))
        # the dictionary model, advanced by the ledgers that closed
        for ledger in self.ledgers[:self.closed]:
            for _, s, d, amount in ledger:
                self.model.pay(t.accounts[s].raw, t.accounts[d].raw, amount)
        observed = node.account_states(app, [a.raw for a in t.accounts])
        checks.append(Check("accounts whose balance or sequence differs "
                            "from the dictionary model",
                            self.model.differences(observed), 0))
        # corrupted envelopes after the window: each must be refused
        accepted = sum(
            1 for f in self.bad_frames
            if app.herder.recv_transaction(f) == AddResult.ADD_STATUS_PENDING)
        checks.append(Check("corrupted envelopes admitted", accepted, 0))
        wrong = sum(1 for got, want in zip(self.tail_verdicts,
                                           self.tail_expected)
                    if got != want)
        checks.append(Check("device verdicts that differ from the "
                            "expected ones (valid / bit flipped)",
                            wrong + abs(len(self.tail_verdicts)
                                        - len(self.tail_expected)), 0))
        status = app.batch_verifier.status()
        faults = node.supervisor_faults(status)
        checks.append(Check("supervisor complaints " + "; ".join(faults),
                            len(faults), 0))
        # the window itself sends nothing to the device; the check
        # after it is exactly one dispatch
        in_window = self.counters1.get("crypto.verify.dispatch.batch",
                                       (0, 0))[0] - \
            self.counters0.get("crypto.verify.dispatch.batch", (0, 0))[0]
        self.cell.note(f"device dispatches inside the window: {in_window}")
        return checks

    def close(self) -> None:
        if self.app is not None:
            self.app.shutdown()
