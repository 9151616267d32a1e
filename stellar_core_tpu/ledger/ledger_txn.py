"""Nested ledger transactions.

Reference: src/ledger/LedgerTxn.{h,cpp} (design essay at LedgerTxn.h:20-120)
— a parent/child stack of in-memory entry deltas over a root store, with
commit folding a child's delta into its parent and the root writing SQL.

Copy discipline (the reference's "activation" rules, adapted): every
value flowing DOWN the chain (`_lookup`) is a shared snapshot that must
never be mutated; `load()` makes exactly ONE owned copy at the loading
level and records it in the delta.  The previous value of every touched
key is captured at first touch (`_prev`) so `get_changes`/`get_delta`
need no chain re-walks and no further copies — the round-1 design
cloned on every chain hop and re-fetched prevs at commit, which
profiling showed was ~46% of catchup apply time.

Headers follow the same rule: a child clones the parent header only on
`load_header()`, and commit passes ownership up without another copy.

Order-book queries resolve root offers through the SQL index
(sellingasset/buyingasset/price/offerid columns) with child deltas
overlaid, mirroring LedgerTxn::loadBestOffer / the reference's
loadBestOffersIntoCache SQL (ledger/LedgerTxnOfferSQL.cpp) rather than
scanning the book.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterable, List, Optional, Tuple

from ..util.checks import releaseAssert
from ..xdr.ledger_entries import (Asset, LedgerEntry, LedgerEntryType,
                                  LedgerKey, OfferEntry, TrustLineAsset,
                                  ledger_entry_key)
from ..xdr.ledger import LedgerHeader


def _copy_entry(e: LedgerEntry) -> LedgerEntry:
    return e.clone()


def _copy_header(h: LedgerHeader) -> LedgerHeader:
    return h.clone()


def key_bytes(key: LedgerKey) -> bytes:
    return key.to_bytes()


def entry_key_bytes(entry: LedgerEntry) -> bytes:
    return ledger_entry_key(entry).to_bytes()


_OFFER_KB_PREFIX = struct.pack(">i", int(LedgerEntryType.OFFER))


class LedgerDelta:
    """Init/live/dead classification of one committed LedgerTxn, the
    shape consumed by BucketList.add_batch and tx meta."""

    def __init__(self, init: List[LedgerEntry], live: List[LedgerEntry],
                 dead: List[LedgerKey]):
        self.init = init
        self.live = live
        self.dead = dead


class AbstractLedgerTxnParent:
    """Interface shared by LedgerTxn and the roots."""

    def _lookup(self, kb: bytes) -> Optional[LedgerEntry]:
        """Shared snapshot of the current value (None = absent).
        Callers MUST NOT mutate the returned object."""
        raise NotImplementedError

    def get_entry(self, kb: bytes) -> Optional[LedgerEntry]:
        """Back-compat shared read; same contract as _lookup."""
        return self._lookup(kb)

    def get_header(self) -> LedgerHeader:
        raise NotImplementedError

    def commit_child(self, delta: Dict[bytes, Optional[LedgerEntry]],
                     prev: Dict[bytes, Optional[LedgerEntry]],
                     header: Optional[LedgerHeader]) -> None:
        raise NotImplementedError

    def _offer_deltas(self, acc: Dict[bytes, Optional[LedgerEntry]]) -> None:
        """Overlay this level's pending OFFER changes into `acc`
        (child-first: existing keys are not overwritten)."""
        return None

    def best_offer(self, selling: Asset, buying: Asset,
                   exclude) -> Optional[Tuple[bytes, LedgerEntry]]:
        """Best committed offer for the pair, skipping keys in
        `exclude`; shared snapshot."""
        return None

    def offers_by_account(self, account_id) -> Dict[bytes, LedgerEntry]:
        return {}

    def iter_offers(self) -> Iterable[Tuple[bytes, LedgerEntry]]:
        """Yield (key_bytes, offer entry) shared snapshots."""
        return iter(())

    def prefetch(self, keys) -> int:
        """Warm whatever cache this parent keeps; no-op by default."""
        return 0

    def child_open(self, child: "LedgerTxn") -> None:
        releaseAssert(getattr(self, "_child", None) is None,
                      "parent already has an open child LedgerTxn")
        self._child = child

    def child_closed(self) -> None:
        self._child = None


class LedgerTxn(AbstractLedgerTxnParent):
    """One nesting level. Create with an open parent; exactly one child
    may be open at a time (reference: sealing rules, LedgerTxn.h:60-90)."""

    def __init__(self, parent: AbstractLedgerTxnParent):
        self._parent = parent
        parent.child_open(self)
        self._child = None
        # kb -> entry object (live, owned by this txn) or None (erased)
        self._delta: Dict[bytes, Optional[LedgerEntry]] = {}
        # kb -> shared snapshot of the value in the parent chain at first
        # touch (None = did not exist).  Never mutated, never cloned.
        self._prev: Dict[bytes, Optional[LedgerEntry]] = {}
        self._header: Optional[LedgerHeader] = None
        self._open = True

    # ------------------------------------------------------------- queries --
    def _check_open(self) -> None:
        releaseAssert(self._open, "LedgerTxn is closed")
        releaseAssert(self._child is None,
                      "LedgerTxn has an open child; parent is sealed")

    def _lookup(self, kb: bytes) -> Optional[LedgerEntry]:
        d = self._delta
        if kb in d:
            return d[kb]
        return self._parent._lookup(kb)

    def entry_exists(self, key: LedgerKey) -> bool:
        return self._lookup(key.to_bytes()) is not None

    def load(self, key: LedgerKey) -> Optional[LedgerEntry]:
        """Load for modification: the returned object is the live record;
        mutating it mutates this txn's pending state."""
        return self.load_by_bytes(key.to_bytes())

    def load_by_bytes(self, kb: bytes) -> Optional[LedgerEntry]:
        """load() addressed by canonical key bytes (hot paths keep the
        serialized key cached — e.g. per-account, tx_utils)."""
        self._check_open()
        d = self._delta
        if kb in d:
            return d[kb]
        p = self._parent._lookup(kb)
        if p is None:
            return None
        if kb not in self._prev:
            self._prev[kb] = p
        e = p.clone()
        # recorded loads count as modifications: stamp the closing seq
        # (reference: LedgerTxn sealing's maybeUpdateLastModified)
        e.lastModifiedLedgerSeq = self.get_header().ledgerSeq
        d[kb] = e
        return e

    def load_with_state_snapshot(self, key: LedgerKey):
        """load() plus a pre-image clone equal to what a nested child
        txn would snapshot at first touch: the recorded object if this
        level already touched the key (stamped, post earlier
        mutations), else the parent chain's shared object (original
        lastModified). Lets per-item meta (STATE, UPDATED) be built
        without a LedgerTxn per item — the lean fee phase."""
        self._check_open()
        kb = key.to_bytes()
        if kb in self._delta:
            cur = self._delta[kb]
            if cur is None:
                return None, None
        else:
            cur = self._parent._lookup(kb)
            if cur is None:
                return None, None
        prev = cur.clone()
        return self.load_by_bytes(kb), prev

    def load_without_record(self, key: LedgerKey) -> Optional[LedgerEntry]:
        """Read-only snapshot (reference: loadWithoutRecord) — does not
        join the delta.  The returned object is SHARED: do not mutate."""
        self._check_open()
        return self._lookup(key.to_bytes())

    # ----------------------------------------------------------- mutations --
    def create(self, entry: LedgerEntry) -> LedgerEntry:
        self._check_open()
        kb = entry_key_bytes(entry)
        d = self._delta
        if kb in d:
            releaseAssert(d[kb] is None, "create: entry already exists")
        else:
            p = self._parent._lookup(kb)
            releaseAssert(p is None, "create: entry already exists")
            if kb not in self._prev:
                self._prev[kb] = p
        entry.lastModifiedLedgerSeq = self.get_header().ledgerSeq
        d[kb] = entry
        return entry

    def erase(self, key: LedgerKey) -> None:
        self._check_open()
        kb = key.to_bytes()
        d = self._delta
        if kb in d:
            releaseAssert(d[kb] is not None, "erase: entry does not exist")
            # every delta key has a _prev record (load/create/commit set it)
            if self._prev[kb] is None:
                # created at this level: erasing cancels it entirely
                del d[kb]
                del self._prev[kb]
            else:
                d[kb] = None
            return
        p = self._parent._lookup(kb)
        releaseAssert(p is not None, "erase: entry does not exist")
        self._prev[kb] = p
        d[kb] = None

    # -------------------------------------------------------------- header --
    def load_header(self) -> LedgerHeader:
        self._check_open()
        if self._header is None:
            self._header = self._parent.get_header().clone()
        return self._header

    def get_header(self) -> LedgerHeader:
        return self._header if self._header is not None \
            else self._parent.get_header()

    # ------------------------------------------------------ commit/rollback --
    def commit(self) -> None:
        self._check_open()
        self._parent.commit_child(self._delta, self._prev, self._header)
        self._open = False
        self._parent.child_closed()

    def rollback(self) -> None:
        releaseAssert(self._open, "LedgerTxn is closed")
        if self._child is not None:
            self._child.rollback()
        self._open = False
        self._delta.clear()
        self._prev.clear()
        self._parent.child_closed()

    def get_root(self):
        """The LedgerTxnRoot (or in-memory root) under this chain."""
        return self._parent.get_root()

    def __enter__(self) -> "LedgerTxn":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._open:
            self.rollback()
        return False

    def commit_child(self, delta: Dict[bytes, Optional[LedgerEntry]],
                     prev: Dict[bytes, Optional[LedgerEntry]],
                     header: Optional[LedgerHeader]) -> None:
        my_prev = self._prev
        my_delta = self._delta
        for kb, e in delta.items():
            if kb not in my_prev:
                # the child observed the parent chain ABOVE this level
                # for keys this level never touched
                my_prev[kb] = prev[kb]
            if e is None and my_prev[kb] is None:
                # created and erased within the composite txn: no-op
                my_delta.pop(kb, None)
            else:
                my_delta[kb] = e
        if header is not None:
            self._header = header     # adopt: the child is closed now

    # ---------------------------------------------------------------- delta --
    def get_delta(self) -> LedgerDelta:
        """Classify pending changes vs the PARENT chain (valid before
        commit; LedgerManager calls this to feed buckets/meta).
        Entries are the live objects — consume before further writes."""
        init, live, dead = [], [], []
        prev = self._prev
        for kb, e in self._delta.items():
            if e is None:
                dead.append(LedgerKey.from_bytes(kb))
            elif prev.get(kb) is None:
                init.append(e)
            else:
                live.append(e)
        return LedgerDelta(init, live, dead)

    def get_changes(self):
        """LedgerEntryChange list vs the parent chain, the tx-meta shape
        (reference: LedgerTxn::getChanges).  Uses the first-touch
        snapshots — no chain re-walk, no copies."""
        from ..xdr.ledger import LedgerEntryChange, LedgerEntryChangeType
        changes = []
        prev_map = self._prev
        for kb, e in self._delta.items():
            prev = prev_map.get(kb)
            if e is None:
                changes.append(LedgerEntryChange(
                    LedgerEntryChangeType.LEDGER_ENTRY_STATE, prev))
                changes.append(LedgerEntryChange(
                    LedgerEntryChangeType.LEDGER_ENTRY_REMOVED,
                    LedgerKey.from_bytes(kb)))
            elif prev is None:
                changes.append(LedgerEntryChange(
                    LedgerEntryChangeType.LEDGER_ENTRY_CREATED, e))
            else:
                changes.append(LedgerEntryChange(
                    LedgerEntryChangeType.LEDGER_ENTRY_STATE, prev))
                changes.append(LedgerEntryChange(
                    LedgerEntryChangeType.LEDGER_ENTRY_UPDATED, e))
        return changes

    # ---------------------------------------------------------- order book --
    def _offer_deltas(self, acc: Dict[bytes, Optional[LedgerEntry]]) -> None:
        for kb, e in self._delta.items():
            if kb.startswith(_OFFER_KB_PREFIX) and kb not in acc:
                acc[kb] = e
        self._parent._offer_deltas(acc)

    def iter_offers(self):
        acc: Dict[bytes, Optional[LedgerEntry]] = {}
        self._offer_deltas(acc)
        for kb, e in acc.items():
            if e is not None:
                yield kb, e
        root = self._root()
        for kb, e in root.iter_offers():
            if kb not in acc:
                yield kb, e

    def _root(self):
        p = self._parent
        while isinstance(p, LedgerTxn):
            p = p._parent
        return p

    def load_best_offer(self, selling: Asset,
                        buying: Asset) -> Optional[LedgerEntry]:
        """Best (lowest price, then lowest offerId) offer selling
        `selling` for `buying`, loaded for modification."""
        self._check_open()
        acc: Dict[bytes, Optional[LedgerEntry]] = {}
        self._offer_deltas(acc)
        best_kb, best = None, None
        for kb, e in acc.items():
            if e is None:
                continue
            of: OfferEntry = e.data.value
            if of.selling != selling or of.buying != buying:
                continue
            if best is None or _offer_less(of, best.data.value):
                best_kb, best = kb, e
        hit = self._root().best_offer(selling, buying, acc)
        if hit is not None and (best is None or _offer_less(
                hit[1].data.value, best.data.value)):
            best_kb, best = hit
        if best_kb is None:
            return None
        return self.load(LedgerKey.from_bytes(best_kb))

    def load_offers_by_account(self, account_id) -> List[LedgerEntry]:
        self._check_open()
        acc: Dict[bytes, Optional[LedgerEntry]] = {}
        self._offer_deltas(acc)
        hits = dict(self._root().offers_by_account(account_id))
        for kb, e in acc.items():
            hits.pop(kb, None)
            if e is not None and e.data.value.sellerID == account_id:
                hits[kb] = e
        return [self.load(LedgerKey.from_bytes(kb)) for kb in hits]


def _offer_less(a: OfferEntry, b: OfferEntry) -> bool:
    # price fraction compare without floats: a.n/a.d < b.n/b.d
    lhs = a.price.n * b.price.d
    rhs = b.price.n * a.price.d
    if lhs != rhs:
        return lhs < rhs
    return a.offerID < b.offerID


class InMemoryLedgerTxnRoot(AbstractLedgerTxnParent):
    """Dict-backed root (reference: InMemoryLedgerTxnRoot, used by
    --in-memory mode and tests).  Entries are stored as objects and
    handed out shared; commits adopt the child's objects."""

    point_reads = 0     # see LedgerTxnRoot: there is no store to read

    def __init__(self, header: Optional[LedgerHeader] = None):
        self._entries: Dict[bytes, LedgerEntry] = {}
        self._header = header or LedgerHeader()
        self._child = None
        self.hot_archive = None   # see LedgerTxnRoot
        self._contract_key_index: Optional[List[bytes]] = None

    def get_root(self) -> "InMemoryLedgerTxnRoot":
        return self

    def contract_entry_keys(self):
        """Canonically ordered CONTRACT_DATA/CONTRACT_CODE key bytes
        (the eviction scan's walk order)."""
        return sorted(
            kb for kb in self._entries
            if LedgerKey.from_bytes(kb).disc in
            (LedgerEntryType.CONTRACT_DATA, LedgerEntryType.CONTRACT_CODE))

    def contract_key_index(self) -> List[bytes]:
        """Sorted contract-key index, built once and maintained by every
        commit (the bounded eviction scan's walk — see _eviction_scan)."""
        if self._contract_key_index is None:
            self._contract_key_index = list(self.contract_entry_keys())
        return self._contract_key_index

    def _lookup(self, kb: bytes) -> Optional[LedgerEntry]:
        return self._entries.get(kb)

    def get_header(self) -> LedgerHeader:
        return self._header

    def commit_child(self, delta, prev, header) -> None:
        for kb, e in delta.items():
            if e is None:
                self._entries.pop(kb, None)
            else:
                self._entries[kb] = e
        _index_apply_delta(self._contract_key_index, delta)
        if header is not None:
            self._header = header

    def _offer_deltas(self, acc) -> None:
        return None

    def iter_offers(self):
        for kb, e in self._entries.items():
            if kb.startswith(_OFFER_KB_PREFIX):
                yield kb, e

    def best_offer(self, selling, buying, exclude):
        best_kb, best = None, None
        for kb, e in self.iter_offers():
            if kb in exclude:
                continue
            of = e.data.value
            if of.selling != selling or of.buying != buying:
                continue
            if best is None or _offer_less(of, best.data.value):
                best_kb, best = kb, e
        return None if best_kb is None else (best_kb, best)

    def offers_by_account(self, account_id) -> Dict[bytes, LedgerEntry]:
        return {kb: e for kb, e in self.iter_offers()
                if e.data.value.sellerID == account_id}

    def entry_count(self) -> int:
        return len(self._entries)


_CONTRACT_KB_PREFIXES = (
    struct.pack(">i", LedgerEntryType.CONTRACT_DATA),
    struct.pack(">i", LedgerEntryType.CONTRACT_CODE),
)


def _index_apply_delta(idx: Optional[List[bytes]], delta) -> None:
    """Maintain a sorted contract-key index across a commit —
    O(changes · log n). No-op until the index is first built, so
    non-soroban workloads never pay for it."""
    if idx is None:
        return
    import bisect
    for kb, e in delta.items():
        if kb[:4] not in _CONTRACT_KB_PREFIXES:
            continue
        pos = bisect.bisect_left(idx, kb)
        present = pos < len(idx) and idx[pos] == kb
        if e is None:
            if present:
                del idx[pos]
        elif not present:
            idx.insert(pos, kb)


_TABLE_FOR_TYPE = {
    LedgerEntryType.ACCOUNT: "accounts",
    LedgerEntryType.TRUSTLINE: "trustlines",
    LedgerEntryType.OFFER: "offers",
    LedgerEntryType.DATA: "accountdata",
    LedgerEntryType.CLAIMABLE_BALANCE: "claimablebalance",
    LedgerEntryType.LIQUIDITY_POOL: "liquiditypool",
    LedgerEntryType.CONTRACT_DATA: "contractdata",
    LedgerEntryType.CONTRACT_CODE: "contractcode",
    LedgerEntryType.CONFIG_SETTING: "configsettings",
    LedgerEntryType.TTL: "ttl",
}

_ABSENT = object()


class LedgerTxnRoot(AbstractLedgerTxnParent):
    """SQL-backed root: entries live in per-type tables, commit writes
    them inside the caller's DB transaction (reference: LedgerTxnRoot +
    LedgerTxn*SQL.cpp).

    The entry cache holds DECODED LedgerEntry objects (or _ABSENT
    negatives) handed out as shared snapshots — the load path clones
    exactly once at the LedgerTxn that records the entry.

    What `prefetch` reads in bulk is held beside the cache
    (`_prefetched`: raw bytes, decoded on first access and then cached)
    until the next commit, and not in it: the cache is bounded and
    evicts at random, so once it is full it has room for no key set,
    and a close whose ledger outgrows it (a contract ledger's nonce
    and TTL keys are new every time) would pay a point SELECT for
    every key it had asked for ahead (reference analogue: the entry
    cache fed by prefetch, LedgerTxnRoot.h, which upstream empties at
    every commit).  `point_reads` counts the lookups that fell through
    both to a point SELECT (SQL only: one the bucket list answers is
    not counted)."""

    def __init__(self, db, header: Optional[LedgerHeader] = None,
                 cache_size: int = 4096):
        from ..util.cache import RandomEvictionCache
        self._db = db
        self._header = header or LedgerHeader()
        self._child = None
        self._cache: "RandomEvictionCache" = RandomEvictionCache(cache_size)
        # kb -> raw entry bytes, a decoded entry or _ABSENT: the answers
        # of `prefetch` since the last commit
        self._prefetched: Dict[bytes, object] = {}
        self.point_reads = 0
        self._bucket_list = None
        # state-archival lookup hook (protocol 23+): set by the
        # LedgerManager so RestoreFootprint can consult the hot archive
        # through its LedgerTxn chain (reference: the host's restore
        # path reading the hot archive bucket list)
        self.hot_archive = None
        self._contract_key_index: Optional[List[bytes]] = None
        # batch tuning (reference: PREFETCH_BATCH_SIZE,
        # MAX_BATCH_WRITE_COUNT/_BYTES) — set from config by Application
        self.prefetch_batch = 1000
        self.max_batch_write_count = 1024
        self.max_batch_write_bytes = 1024 * 1024
        # reference: BEST_OFFER_DEBUGGING_ENABLED
        self.best_offer_debugging = False

    def get_root(self) -> "LedgerTxnRoot":
        return self

    def contract_entry_keys(self):
        """Canonically ordered CONTRACT_DATA/CONTRACT_CODE key bytes
        (the eviction scan's walk order)."""
        out = []
        for table in ("contractdata", "contractcode"):
            out.extend(bytes(r[0]) for r in self._db.query_all(
                f"SELECT key FROM {table}"))
        return sorted(out)

    def contract_key_index(self) -> List[bytes]:
        """Sorted contract-key index: ONE full SELECT when first needed,
        then maintained by every commit_child — the bounded eviction
        scan never re-walks total contract state."""
        if self._contract_key_index is None:
            self._contract_key_index = list(self.contract_entry_keys())
        return self._contract_key_index

    def serve_from_bucket_list(self, bucket_list) -> None:
        """BucketListDB mode (reference: EXPERIMENTAL_BUCKETLIST_DB,
        bucket/readme.md:55-105): non-offer entry loads are answered by
        the bucket indexes (bloom-gated, newest level first) instead of
        SQL.  Offers stay in SQL — the order book needs its range
        queries, exactly as the reference keeps offers in the database
        under BucketListDB.  What a prefetch took from SQL before
        the switch is dropped: a miss there is no miss in the buckets."""
        self._bucket_list = bucket_list
        self._prefetched = {}

    # ------------------------------------------------------------- entries --
    @staticmethod
    def _table_for(kb: bytes) -> str:
        t = LedgerEntryType(struct.unpack(">i", kb[:4])[0])
        table = _TABLE_FOR_TYPE.get(t)
        releaseAssert(table is not None, f"no SQL table for {t!r}")
        return table

    def _lookup(self, kb: bytes) -> Optional[LedgerEntry]:
        hit = self._cache.maybe_get(kb)
        if hit is None:
            hit = self._prefetched.get(kb)
            if hit is None:
                return self._read_through(kb)
            if hit is not _ABSENT:
                if hit.__class__ is bytes:    # lazily decode prefetches
                    hit = self._prefetched[kb] = LedgerEntry.from_bytes(hit)
                self._cache.put(kb, hit)
        return None if hit is _ABSENT else hit

    def _read_through(self, kb: bytes) -> Optional[LedgerEntry]:
        """One key from the store, cached: what neither the cache nor
        a prefetch answered."""
        if self._bucket_list is not None \
                and not kb.startswith(_OFFER_KB_PREFIX):
            from ..xdr.ledger import BucketEntryType
            be = self._bucket_list.get_entry(LedgerKey.from_bytes(kb))
            if be is None or be.disc == BucketEntryType.DEADENTRY:
                self._cache.put(kb, _ABSENT)
                return None
            e = be.value
            self._cache.put(kb, e)
            return e
        self.point_reads += 1
        row = self._db.query_one(
            f"SELECT entry FROM {self._table_for(kb)} WHERE key=?", (kb,))
        if row:
            e = LedgerEntry.from_bytes(bytes(row[0]))
            self._cache.put(kb, e)
            return e
        self._cache.put(kb, _ABSENT)
        return None

    def prefetch(self, keys) -> int:
        """Batch-load entries ahead of their lookups: one SELECT ... IN
        (...) per table instead of a query per key (reference:
        LedgerTxnRoot prefetch + prefetchTxSourceIds,
        LedgerManagerImpl.cpp:805). The answers, misses too, stay in
        `_prefetched` until the next commit; one that is looked up
        moves into the cache decoded. A key set is as large as its tx
        set, so it cannot thrash the cache's hot entries out, and a
        full cache cannot turn it away. Returns the number of keys a
        lookup now answers without a read of its own."""
        cache = self._cache
        held = self._prefetched
        by_table: Dict[str, list] = {}
        n = 0
        for key in keys:
            kb = key.to_bytes() if hasattr(key, "to_bytes") else bytes(key)
            n += 1
            if kb in held:
                continue
            hit = cache.maybe_get(kb)
            if hit is not None:
                # held too: the lookups in between may evict it
                held[kb] = hit
                continue
            if self._bucket_list is not None \
                    and not kb.startswith(_OFFER_KB_PREFIX):
                # SQL is not authoritative for bucket-list-served keys
                # (entries may live only in buckets); holding an SQL
                # miss as _ABSENT here would shadow a live entry.
                e = self._read_through(kb)
                held[kb] = _ABSENT if e is None else e
                continue
            by_table.setdefault(self._table_for(kb), []).append(kb)
        # chunk to stay under sqlite's bound-parameter limit AND the
        # configured batch (reference: PREFETCH_BATCH_SIZE)
        step = min(500, max(1, self.prefetch_batch))
        for table, kbs in by_table.items():
            for i in range(0, len(kbs), step):
                chunk = kbs[i:i + step]
                marks = ",".join("?" * len(chunk))
                found = {bytes(row[0]): bytes(row[1])
                         for row in self._db.query_all(
                             f"SELECT key, entry FROM {table} "
                             f"WHERE key IN ({marks})", chunk)}
                for kb in chunk:
                    held[kb] = found.get(kb, _ABSENT)
        return n

    def get_header(self) -> LedgerHeader:
        return self._header

    def set_header(self, header: LedgerHeader) -> None:
        self._header = header.clone()

    def commit_child(self, delta, prev, header) -> None:
        # group per (table, kind) so sqlite sees executemany batches
        # instead of one statement per entry
        deletes: Dict[str, list] = {}
        upserts: Dict[str, list] = {}
        offer_rows: list = []
        cache_updates: list = []
        for kb, e in delta.items():
            table = self._table_for(kb)
            if e is None:
                deletes.setdefault(table, []).append((kb,))
                cache_updates.append((kb, _ABSENT))
                continue
            raw = e.to_bytes()
            if table == "offers":
                of: OfferEntry = e.data.value
                offer_rows.append(
                    (kb, raw, e.lastModifiedLedgerSeq,
                     of.sellerID.to_bytes(), of.offerID,
                     of.selling.to_bytes(), of.buying.to_bytes(),
                     of.price.n, of.price.d, of.price.n / of.price.d))
            else:
                upserts.setdefault(table, []).append(
                    (kb, raw, e.lastModifiedLedgerSeq))
            cache_updates.append((kb, e))
        def write_batches(rows, raw_at):
            # bound each executemany by count AND payload bytes
            # (reference: MAX_BATCH_WRITE_COUNT / MAX_BATCH_WRITE_BYTES,
            # the SQL batch upload bounds in BucketApplicator/SQL roots)
            batch, size = [], 0
            for r in rows:
                batch.append(r)
                if raw_at is not None:
                    size += len(r[raw_at])
                if len(batch) >= self.max_batch_write_count or \
                        size >= self.max_batch_write_bytes:
                    yield batch
                    batch, size = [], 0
            if batch:
                yield batch

        with self._db.transaction():
            for table, rows in deletes.items():
                for b in write_batches(rows, None):
                    self._db.executemany(
                        f"DELETE FROM {table} WHERE key=?", b)
            for table, rows in upserts.items():
                for b in write_batches(rows, 1):
                    self._db.executemany(
                        f"INSERT OR REPLACE INTO {table} "
                        "(key, entry, lastmodified) VALUES (?,?,?)", b)
            for b in write_batches(offer_rows, 1):
                self._db.executemany(
                    "INSERT OR REPLACE INTO offers (key, entry, "
                    "lastmodified, sellerid, offerid, sellingasset, "
                    "buyingasset, pricen, priced, price) "
                    "VALUES (?,?,?,?,?,?,?,?,?,?)", b)
        # cache reflects only durably committed state; committed objects
        # are adopted (the committing txn is closed, so they are frozen);
        # what was prefetched was read before this commit and goes
        self._prefetched = {}
        for kb, v in cache_updates:
            self._cache.put(kb, v)
        _index_apply_delta(self._contract_key_index, delta)
        if header is not None:
            self._header = header

    # ---------------------------------------------------------- order book --
    def best_offer(self, selling: Asset, buying: Asset,
                   exclude) -> Optional[Tuple[bytes, LedgerEntry]]:
        """Best offer via the indexed columns, skipping `exclude`d keys
        (those are overridden by open deltas).  Pages through candidates
        in (price, offerid) order exactly like the reference's
        loadBestOffers SQL (ledger/LedgerTxnOfferSQL.cpp:34-60)."""
        found = self._best_offer_sql(selling, buying, exclude)
        if self.best_offer_debugging:
            # reference: BEST_OFFER_DEBUGGING_ENABLED — cross-check the
            # indexed result against a full scan on every lookup
            check = self._best_offer_scan(selling, buying, exclude)
            from ..util.checks import releaseAssert
            releaseAssert(
                (found[0] if found else None) ==
                (check[0] if check else None),
                "best-offer debugging: indexed lookup disagrees with "
                "the full scan")
        return found

    def _best_offer_scan(self, selling, buying, exclude):
        best_kb, best = None, None
        for kb, e in self.iter_offers():
            if kb in exclude:
                continue
            of = e.data.value
            if of.selling != selling or of.buying != buying:
                continue
            if best is None or _offer_less(of, best.data.value):
                best_kb, best = kb, e
        return None if best_kb is None else (best_kb, best)

    def _best_offer_sql(self, selling: Asset, buying: Asset,
                        exclude) -> Optional[Tuple[bytes, LedgerEntry]]:
        sb = selling.to_bytes()
        bb = buying.to_bytes()
        offset = 0
        page = 8
        while True:
            rows = self._db.query_all(
                "SELECT key, entry FROM offers WHERE sellingasset=? AND "
                "buyingasset=? ORDER BY price, offerid LIMIT ? OFFSET ?",
                (sb, bb, page, offset))
            if not rows:
                return None
            for kb, raw in rows:
                kb = bytes(kb)
                if kb in exclude:
                    continue
                cached = self._cache.maybe_get(kb)
                if cached is not None and cached is not _ABSENT:
                    e = cached
                else:
                    e = LedgerEntry.from_bytes(bytes(raw))
                    self._cache.put(kb, e)
                # double rounding is monotone, so SQL order can only
                # COLLAPSE distinct rational prices onto one double —
                # resolve such ties with the exact comparator over every
                # row sharing the stored price (reference re-sorts each
                # loaded batch exactly, LedgerTxnRoot loadBestOffers)
                return self._exact_best_at_price(sb, bb, kb, e, exclude)
            offset += page
            page *= 2

    def _exact_best_at_price(self, sb, bb, kb, e, exclude):
        ties = self._db.query_all(
            "SELECT key, entry FROM offers WHERE sellingasset=? AND "
            "buyingasset=? AND price=(SELECT price FROM offers WHERE "
            "key=?) ORDER BY offerid", (sb, bb, kb))
        best_kb, best = kb, e
        for tkb, traw in ties:
            tkb = bytes(tkb)
            if tkb == kb or tkb in exclude:
                continue
            te = self._cache.maybe_get(tkb)
            if te is None or te is _ABSENT:
                te = LedgerEntry.from_bytes(bytes(traw))
                self._cache.put(tkb, te)
            if _offer_less(te.data.value, best.data.value):
                best_kb, best = tkb, te
        return best_kb, best

    def offers_by_account(self, account_id) -> Dict[bytes, LedgerEntry]:
        out = {}
        for kb, raw in self._db.query_all(
                "SELECT key, entry FROM offers WHERE sellerid=?",
                (account_id.to_bytes(),)):
            out[bytes(kb)] = LedgerEntry.from_bytes(bytes(raw))
        return out

    def iter_offers(self):
        for (kb, raw) in self._db.query_all("SELECT key, entry FROM offers"):
            yield bytes(kb), LedgerEntry.from_bytes(bytes(raw))

    def load_header_from_db(self) -> Optional[LedgerHeader]:
        row = self._db.query_one(
            "SELECT data FROM ledgerheaders ORDER BY ledgerseq DESC LIMIT 1")
        if not row:
            return None
        self._header = LedgerHeader.from_bytes(row[0])
        return self._header
