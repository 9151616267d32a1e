"""Plain reference for a validator's transaction queue under its peers'
flood: a dictionary of pending frames, advanced by the bursts the node
is handed and by the sets its quorum closes.

Imports nothing of the program. A frame is described by what the flood
carries of it: `key` (SHA-256 of the envelope's bytes), the source
account, its sequence number, and whether its signature verifies (the
pure-Python oracle's verdict, or the publisher's, which applied it).
The semantics are the ones the configuration states:

- a frame already pending is a duplicate and changes nothing;
- a frame whose signature does not verify never enters the queue;
- a frame enters only with the sequence number that follows the
  account's last applied one and those of its pending frames;
- a frame admitted stays until the ledger whose set names it is
  committed, and is gone then.
"""

PENDING = "pending"
DUPLICATE = "duplicate"
BAD_SIG = "bad_sig"
BAD_SEQ = "bad_seq"


class FloodModel:
    def __init__(self):
        self.queue = {}        # key -> (account, sequence number)
        self.seq = {}          # account -> last applied sequence number
        self.depth = {}        # account -> frames pending
        self.counts = {PENDING: 0, DUPLICATE: 0, BAD_SIG: 0, BAD_SEQ: 0}

    def create(self, account: bytes, seq: int) -> None:
        self.seq[account] = seq

    def burst(self, frames) -> list:
        """Outcome of each (key, account, seq, sound) of one burst, in
        order."""
        out = []
        for key, account, seq, sound in frames:
            if key in self.queue:
                what = DUPLICATE
            elif not sound:
                what = BAD_SIG
            elif seq != self.seq[account] + self.depth.get(account, 0) + 1:
                what = BAD_SEQ
            else:
                what = PENDING
                self.queue[key] = (account, seq)
                self.depth[account] = self.depth.get(account, 0) + 1
            self.counts[what] += 1
            out.append(what)
        return out

    def close(self, applied) -> int:
        """A ledger whose set holds the frames `applied` ((key, account,
        seq) each, flooded here or not) is committed. Returns how many
        frames are still pending."""
        for key, account, seq in applied:
            if self.queue.pop(key, None) is not None:
                self.depth[account] -= 1
            self.seq[account] = max(self.seq[account], seq)
        # what an applied sequence number overtook can never apply
        for key, (account, seq) in list(self.queue.items()):
            if seq <= self.seq[account]:
                del self.queue[key]
                self.depth[account] -= 1
        return len(self.queue)
