"""Protocol-next tree slice: the hot-archive bucket list.

Three guarantees:
  1. curr's wire language is untouched — pinned curr encodings stay
     byte-identical with next_types imported, and the curr namespace
     contains no hot-archive types;
  2. the next namespace's hashes differ and its new types round-trip;
  3. the bucket subsystem's core behaviors (sorted buckets, newest
     wins, spill cadence, deterministic hashes, HAS round-trip,
     assume-state reconstruction) hold under BOTH namespaces — the
     live list (curr) and the hot-archive list (next) run the same
     sweep.

Reference mechanism: src/protocol-curr and src/protocol-next built and
CI'd side by side (Makefile.am:46-51).
"""

import pytest

from stellar_core_tpu.bucket.bucket import Bucket, merge_buckets
from stellar_core_tpu.bucket.bucket_list import BucketList
from stellar_core_tpu.bucket.hot_archive import (HotArchiveBucket,
                                                 HotArchiveBucketList,
                                                 merge_hot_archive)
from stellar_core_tpu.history.archive import HistoryArchiveState
from stellar_core_tpu.xdr import next_types, schema
from stellar_core_tpu.xdr.ledger import BucketEntry, BucketEntryType
from stellar_core_tpu.xdr.ledger import BucketMetadata as CurrBucketMeta
from stellar_core_tpu.xdr.ledger_entries import (LedgerEntry, LedgerKey,
                                                 ledger_entry_key)
from stellar_core_tpu.xdr.next_types import (HotArchiveBucketEntry,
                                             HotArchiveBucketEntryType)

from stellar_core_tpu.tx.tx_utils import make_account_ledger_entry
from stellar_core_tpu.xdr.types import PublicKey


def _acct(i: int, balance: int = 1000) -> LedgerEntry:
    return make_account_ledger_entry(
        PublicKey.ed25519(bytes([i]) * 32), balance, seq_num=i)


def _key(i: int) -> LedgerKey:
    return ledger_entry_key(_acct(i))


# ------------------------------------------------------------- guarantee 1
def test_curr_wire_bytes_untouched():
    """A pinned curr-protocol encoding stays byte-identical with the
    next tree loaded, and curr knows nothing of hot-archive types."""
    curr = schema.curr_namespace()
    assert "HotArchiveBucketEntry" not in curr
    assert "HotArchiveBucketEntryType" not in curr
    # pinned: curr BucketEntry METAENTRY(protocol 20) wire bytes
    be = BucketEntry(BucketEntryType.METAENTRY,
                     CurrBucketMeta(ledgerVersion=20))
    assert be.to_bytes().hex() == (
        "ffffffff" + "00000014" + "00000000")
    # curr BucketMetadata has no bucketListType arm to encode
    assert "_BucketMetadataExt" not in curr or not hasattr(
        curr.get("_BucketMetadataExt", object), "HOT_ARCHIVE")


def test_next_namespace_extends_and_differs():
    ident = schema.identity()
    assert ident["curr"] != ident["next"]
    nxt = schema.next_namespace()
    assert nxt["HotArchiveBucketEntry"] is HotArchiveBucketEntry
    # next BucketMetadata can carry the list discriminator; curr can't
    meta = next_types.BucketMetadata(
        ledgerVersion=23,
        ext=next_types._BucketMetadataExt(
            1, next_types.BucketListType.HOT_ARCHIVE))
    raw = meta.to_bytes()
    assert next_types.BucketMetadata.from_bytes(raw) == meta
    with pytest.raises(Exception):
        CurrBucketMeta.from_bytes(raw)


# ------------------------------------------------------------- guarantee 2
def test_hot_archive_entry_roundtrips():
    T = HotArchiveBucketEntryType
    cases = [
        HotArchiveBucketEntry(T.HOT_ARCHIVE_ARCHIVED, _acct(1)),
        HotArchiveBucketEntry(T.HOT_ARCHIVE_LIVE, _key(2)),
        HotArchiveBucketEntry(T.HOT_ARCHIVE_DELETED, _key(3)),
        HotArchiveBucketEntry(
            T.HOT_ARCHIVE_METAENTRY,
            next_types.BucketMetadata(
                ledgerVersion=23,
                ext=next_types._BucketMetadataExt(
                    1, next_types.BucketListType.HOT_ARCHIVE))),
    ]
    for be in cases:
        assert HotArchiveBucketEntry.from_bytes(be.to_bytes()) == be


# --------------------------------------------- guarantee 3: both namespaces
def _curr_bucket_ops():
    """(make_bucket, merge, key_of, lookup_disc) for the live list."""
    def mk(ids, dead_ids=()):
        return Bucket.fresh(20, [], [_acct(i) for i in ids],
                            [_key(i) for i in dead_ids])

    def merge(a, b, bottom):
        return merge_buckets(a, b, keep_dead=not bottom, protocol=20)

    return mk, merge


def _next_bucket_ops():
    def mk(ids, dead_ids=()):
        entries = [HotArchiveBucketEntry(
            HotArchiveBucketEntryType.HOT_ARCHIVE_ARCHIVED, _acct(i))
            for i in ids]
        entries += [HotArchiveBucketEntry(
            HotArchiveBucketEntryType.HOT_ARCHIVE_LIVE, _key(i))
            for i in dead_ids]
        return HotArchiveBucket.from_entries(entries, 23)

    def merge(a, b, bottom):
        return merge_hot_archive(a, b, 23, bottom_level=bottom)

    return mk, merge


@pytest.mark.parametrize("namespace", ["curr", "next"])
def test_bucket_sweep_both_namespaces(namespace):
    """Sorted entries, newest wins, tombstone elision at the bottom —
    the same sweep over the curr live bucket and the next hot-archive
    bucket."""
    mk, merge = (_curr_bucket_ops() if namespace == "curr"
                 else _next_bucket_ops())
    old = mk([1, 2, 3])
    new = mk([2], dead_ids=[3])
    merged = merge(old, new, False)
    body = [e for e in merged.entries()
            if getattr(e.disc, "name", "") not in
            ("METAENTRY", "HOT_ARCHIVE_METAENTRY")]
    # sorted by key bytes
    from stellar_core_tpu.bucket.hot_archive import _entry_key_bytes
    if namespace == "next":
        keys = [_entry_key_bytes(e) for e in body]
    else:
        from stellar_core_tpu.bucket.bucket_index import entry_index_key
        keys = [entry_index_key(e) for e in body]
    assert keys == sorted(keys)
    # newest wins: key 3 carries the tombstone/restored marker
    discs = {k: e.disc.name for k, e in zip(keys, body)}
    assert len(body) == 3
    # bottom-level merge drops the tombstone kind
    bottom = merge(old, new, True)
    bot_names = {e.disc.name for e in bottom.entries()}
    assert "DEADENTRY" not in bot_names
    assert "HOT_ARCHIVE_LIVE" not in bot_names
    # hashes deterministic
    again = merge(old, new, False)
    assert again.hash == merged.hash


def test_hot_archive_list_lifecycle():
    """archive → restore → lookup across spills; hash determinism."""
    T = HotArchiveBucketEntryType
    hal = HotArchiveBucketList()
    for seq in range(1, 40):
        archived = [_acct(seq % 7 + 1, balance=seq)] if seq % 3 else []
        restored = [_key(seq % 5 + 1)] if seq % 11 == 0 else []
        hal.add_batch(seq, 23, archived, restored, [])
    # newest archived version of account 1 wins
    be = hal.get_entry(_key(1))
    assert be is not None
    if be.disc == T.HOT_ARCHIVE_ARCHIVED:
        assert be.value.data.value.balance >= 1
    # deterministic rebuild
    hal2 = HotArchiveBucketList()
    for seq in range(1, 40):
        archived = [_acct(seq % 7 + 1, balance=seq)] if seq % 3 else []
        restored = [_key(seq % 5 + 1)] if seq % 11 == 0 else []
        hal2.add_batch(seq, 23, archived, restored, [])
    assert hal.get_hash() == hal2.get_hash()
    # restored entries read as LIVE markers until merged to bottom
    hal.add_batch(40, 23, [], [_key(2)], [])
    assert hal.get_entry(_key(2)).disc == T.HOT_ARCHIVE_LIVE


def test_has_carries_hot_archive_and_curr_json_unchanged():
    """HAS: next-protocol manifests add hotArchiveBuckets; curr JSON is
    byte-identical to a HAS built without the field; assume-state
    reconstructs the list from the manifest (the catchup leg)."""
    bl = BucketList()
    bl.add_batch(1, 20, [], [_acct(1)], [])
    has_curr = HistoryArchiveState.from_bucket_list(1, bl, "test net")
    base_json = has_curr.to_json()
    assert "hotArchiveBuckets" not in base_json
    # round-trip preserves absence
    again = HistoryArchiveState.from_json(base_json)
    assert again.hot_archive_buckets is None
    assert again.to_json() == base_json

    hal = HotArchiveBucketList()
    for seq in range(1, 12):
        hal.add_batch(seq, 23, [_acct(seq % 4 + 1)], [], [])
    has_next = HistoryArchiveState.from_bucket_list(1, bl, "test net")
    has_next.hot_archive_buckets = hal.level_states()
    nxt_json = has_next.to_json()
    assert "hotArchiveBuckets" in nxt_json
    parsed = HistoryArchiveState.from_json(nxt_json)
    assert parsed.hot_archive_buckets == hal.level_states()
    # referenced hot buckets join the download set
    hot_hashes = {h for lvl in hal.level_states()
                  for h in (lvl["curr"], lvl["snap"])
                  if set(h) != {"0"}}
    assert hot_hashes <= set(parsed.bucket_hashes())

    # assume-state: reconstruct from the manifest + bucket store
    store = {}
    for lvl in hal.levels:
        for b in (lvl.curr, lvl.snap):
            if not b.is_empty():
                store[b.hash.hex()] = b.raw_bytes()
    rebuilt = HotArchiveBucketList.from_level_states(
        parsed.hot_archive_buckets, store.__getitem__)
    assert rebuilt.get_hash() == hal.get_hash()
