"""The debug-meta segment (`<bucket-dir>/meta-debug/`): the raw
`meta-debug-XXXXXXXX.xdr` is appended to by the close's tail and
compressed beside the closes into `.xdr.gz.tmp`; the checkpoint
ledger's tail only waits for the stream's end and its rename to
`.xdr.gz` (docs/CLOSE_PIPELINE.md, "The debug segment is compressed
beside the closes")."""

import errno
import gzip
import io
import os
import shutil
import subprocess
import threading
import time

import pytest

import test_ledger_close as lc
import test_standalone_app as m1  # noqa: F401  (env init)
from stellar_core_tpu.db.database import Database
from stellar_core_tpu.ledger import completion
from stellar_core_tpu.ledger import ledger_manager as lm_mod
from stellar_core_tpu.ledger.ledger_manager import LedgerManager
from stellar_core_tpu.main import Application
from stellar_core_tpu.main.command_line import main
from stellar_core_tpu.main.config import Config
from stellar_core_tpu.util.metrics import MetricsRegistry
from stellar_core_tpu.util.perf import ZoneRegistry
from stellar_core_tpu.util.timer import ClockMode, VirtualClock
from stellar_core_tpu.util.xdr_stream import read_record

SEG63 = "meta-debug-0000003f.xdr"
SEG127 = "meta-debug-0000007f.xdr"


def records_of(path) -> list:
    with open(path, "rb") as f:
        raw = f.read()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    out, f = [], io.BytesIO(raw)
    while True:
        rec = read_record(f)
        if rec is None:
            return out
        out.append(rec)


def counts(metrics) -> dict:
    return {name.rsplit("debugMeta.", 1)[1]: m["count"]
            for name, m in metrics.to_json().items()
            if name.startswith("ledger.debugMeta.")}


def capture_records(lm, monkeypatch) -> list:
    """Every (seq, record) `_write_debug_meta` is given, in order."""
    given = []
    write = lm._write_debug_meta

    def spy(record, seq):
        given.append((seq, record))
        write(record, seq)
    monkeypatch.setattr(lm, "_write_debug_meta", spy)
    return given


def closing_manager(tmp_path, defer: bool = True) -> LedgerManager:
    """A LedgerManager on a database that closes real (empty) ledgers
    with the debug segment on."""
    db = Database(":memory:")
    db.initialize()
    lm = lc.make_manager(db=db)
    lm._metrics = MetricsRegistry()
    lm.perf = ZoneRegistry()
    lm.defer_completion = defer
    lm.meta_debug_dir = str(tmp_path / "meta-debug")
    lm.meta_debug_ledgers = 512
    return lm


def close_to(lm, seq: int) -> None:
    while lm.get_last_closed_ledger_num() < seq:
        lc.close_with(lm, [])


def segment_manager(meta_dir, ledgers: int = 512) -> LedgerManager:
    """A LedgerManager that only ever writes the debug segment."""
    lm = LedgerManager(metrics=MetricsRegistry())
    lm.meta_debug_dir = str(meta_dir)
    lm.meta_debug_ledgers = ledgers
    return lm


def synthetic(seq: int) -> bytes:
    return (b"ledger %08d " % seq) * (1 + seq % 7) * 4


def node_conf(d, debug_ledgers: int = 512):
    os.makedirs(d, exist_ok=True)
    conf = d / "node.cfg"
    conf.write_text(
        f'DATABASE = "sqlite3://{d}/node.db"\n'
        f'BUCKET_DIR_PATH = "{d}/buckets"\n'
        'NETWORK_PASSPHRASE = "debug segment net"\n'
        'RUN_STANDALONE = true\nMANUAL_CLOSE = true\n'
        + (f'METADATA_DEBUG_LEDGERS = {debug_ledgers}\n'
           if debug_ledgers else ""))
    return conf


def start_app(conf, new_db: bool = True) -> Application:
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME),
                             Config.load(str(conf)), new_db=new_db)
    app.start()
    return app


# (a) one life, (i) with and without the deferred tail
@pytest.mark.parametrize("defer", [True, False],
                         ids=["deferred", "inline"])
def test_rotated_segment_holds_the_records_written(tmp_path, monkeypatch,
                                                   defer):
    lm = closing_manager(tmp_path, defer)
    given = capture_records(lm, monkeypatch)
    close_to(lm, 63)
    lm.join_completion()
    assert [seq for seq, _ in given] == list(range(2, 64))
    assert os.listdir(lm.meta_debug_dir) == [SEG63 + ".gz"]
    gz = os.path.join(lm.meta_debug_dir, SEG63 + ".gz")
    assert records_of(gz) == [rec for _, rec in given]
    raw_bytes = sum(4 + len(rec) for _, rec in given)
    assert counts(lm._metrics) == {
        "segment.streamed": 1, "bytes": raw_bytes}
    zones = lm.perf.report()
    assert zones["ledger.close.meta.compress"]["count"] == 1
    assert zones["ledger.debugMeta.compress"]["count"] == 62
    # the next segment is raw again while it is open
    close_to(lm, 64)
    lm.join_completion()
    lm._close_debug_meta()
    assert sorted(os.listdir(lm.meta_debug_dir)) == [
        SEG63 + ".gz", SEG127]
    assert records_of(os.path.join(lm.meta_debug_dir, SEG127)) == \
        [given[-1][1]]


# (i) the inline schedule leaves the same bytes on disk
def test_inline_tail_writes_the_same_files(tmp_path):
    files = []
    for defer in (True, False):
        lm = closing_manager(tmp_path / str(defer), defer)
        close_to(lm, 63)
        lm.join_completion()
        with open(os.path.join(lm.meta_debug_dir, SEG63 + ".gz"),
                  "rb") as f:
            files.append(f.read())
    assert files[0] == files[1]


# (b) a restart in the middle of the segment, with and without a torn
# tail record
@pytest.mark.parametrize("torn", [False, True], ids=["whole", "torn"])
def test_segment_is_caught_up_after_a_restart(tmp_path, torn):
    first = segment_manager(tmp_path)
    for seq in range(2, 31):
        first._write_debug_meta(synthetic(seq), seq)
    first._close_debug_meta()
    assert os.listdir(tmp_path) == [SEG63]
    if torn:
        with open(tmp_path / SEG63, "ab") as f:
            f.write(b"\x80\x00\x01\x00half a record")
    second = segment_manager(tmp_path)
    for seq in range(31, 64):
        second._write_debug_meta(synthetic(seq), seq)
    assert os.listdir(tmp_path) == [SEG63 + ".gz"]
    assert records_of(tmp_path / (SEG63 + ".gz")) == \
        [synthetic(seq) for seq in range(2, 64)]
    assert "segment.streamed" not in counts(first._metrics)
    got = counts(second._metrics)
    assert got.pop("bytes") == sum(
        4 + len(synthetic(seq)) for seq in range(2, 64))
    assert got == {"segment.caughtUp": 1}


# (c) the contract the readers' join and the benchmark's window rest on
def test_join_completion_returns_once_the_gz_is_on_disk(tmp_path,
                                                        monkeypatch):
    lm = closing_manager(tmp_path)
    close_to(lm, 62)
    lm.join_completion()
    finish = lm_mod._SegmentGzip._finish

    def slow_finish(self):
        time.sleep(0.3)
        finish(self)
    monkeypatch.setattr(lm_mod._SegmentGzip, "_finish", slow_finish)
    gz = os.path.join(lm.meta_debug_dir, SEG63 + ".gz")
    lc.close_with(lm, [])
    assert lm.get_last_closed_ledger_num() == 63
    assert not os.path.exists(gz)       # the close did not wait for it
    lm.join_completion()
    assert os.listdir(lm.meta_debug_dir) == [SEG63 + ".gz"]
    assert len(records_of(gz)) == 62


# (d) shutdown in the middle of a segment
def test_shutdown_mid_segment_keeps_the_raw_file(tmp_path, monkeypatch):
    monkeypatch.setattr(completion, "IDLE_EXIT_SECONDS", 0.05)
    before = set(threading.enumerate())
    app = start_app(node_conf(tmp_path / "node"))
    for _ in range(5):
        app.manual_close()          # LCL 6
    meta_dir = tmp_path / "node" / "buckets" / "meta-debug"
    lm = app.ledger_manager
    app.shutdown()
    assert lm._meta_debug_file is None and lm._meta_debug_gzip is None
    assert os.listdir(meta_dir) == [SEG63]
    assert len(records_of(meta_dir / SEG63)) == 5
    workers = [t for t in set(threading.enumerate()) - before
               if t.name == "meta-compress"]
    for t in workers:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in workers)


# (e) what a dead process left, and what `replay-debug-meta` reads
def test_stale_tmp_is_removed_and_never_replayed(tmp_path, capsys):
    conf1 = node_conf(tmp_path / "node1")
    app = start_app(conf1)
    while app.ledger_manager.get_last_closed_ledger_num() < 66:
        app.manual_close()
    final_hash = app.ledger_manager.get_last_closed_ledger_hash()
    app.shutdown()
    meta_dir = tmp_path / "node1" / "buckets" / "meta-debug"
    assert sorted(os.listdir(meta_dir)) == [SEG63 + ".gz", SEG127]
    # a process killed in the middle of segment 127 leaves its `.tmp`
    with open(meta_dir / (SEG127 + ".gz.tmp"), "wb") as f:
        f.write(b"\x1f\x8b\x08 half a gzip stream")

    conf2 = node_conf(tmp_path / "node2", debug_ledgers=0)
    start_app(conf2).shutdown()
    shutil.copytree(meta_dir, tmp_path / "node2" / "buckets" / "meta-debug")
    assert main(["--conf", str(conf2), "replay-debug-meta",
                 "--meta-dir", str(tmp_path / "node2" / "buckets")]) == 0
    captured = capsys.readouterr()
    assert "replayed 65 ledgers" in captured.out
    assert "truncated" not in captured.err
    app = start_app(conf2, new_db=False)
    assert app.ledger_manager.get_last_closed_ledger_num() == 66
    assert app.ledger_manager.get_last_closed_ledger_hash() == final_hash
    app.shutdown()

    # node 1 comes back: the first record it writes clears the `.tmp`
    app = start_app(conf1, new_db=False)
    app.manual_close()
    app.shutdown()
    assert sorted(os.listdir(meta_dir)) == [SEG63 + ".gz", SEG127]
    assert len(records_of(meta_dir / SEG127)) == 4


# (f) retention
@pytest.mark.parametrize("ledgers,keep", [(64, 1), (100, 2), (512, 8)])
def test_gc_keeps_the_segments_that_cover_the_setting(tmp_path, ledgers,
                                                      keep):
    lm = segment_manager(tmp_path, ledgers)
    for checkpoint in (63, 127, 191, 255):
        lm._write_debug_meta(synthetic(checkpoint - 1), checkpoint - 1)
        lm._write_debug_meta(synthetic(checkpoint), checkpoint)
    want = [f"meta-debug-{c:08x}.xdr.gz" for c in (63, 127, 191, 255)]
    assert sorted(os.listdir(tmp_path)) == want[-keep:]
    assert counts(lm._metrics)["segment.streamed"] == 4


# (g) the default: no debug meta, nothing started, nothing written
def test_no_debug_meta_starts_no_worker(tmp_path):
    before = set(threading.enumerate())
    app = start_app(node_conf(tmp_path / "node", debug_ledgers=0))
    for _ in range(3):
        app.manual_close()
    lm = app.ledger_manager
    assert lm.meta_debug_dir is None and lm._meta_debug_gzip is None
    started = {t.name for t in set(threading.enumerate()) - before}
    app.shutdown()
    assert "meta-compress" not in started
    assert not os.path.exists(tmp_path / "node" / "buckets" / "meta-debug")
    assert counts(app.metrics) == {}


# (h) a compress job that fails
def test_failed_compress_job_surfaces_and_keeps_the_raw_file(tmp_path,
                                                             monkeypatch):
    conf = node_conf(tmp_path / "node")
    meta_dir = tmp_path / "node" / "buckets" / "meta-debug"
    compress_to = lm_mod._SegmentGzip._compress_to

    class FullDisk:
        def write(self, data):
            raise OSError(errno.ENOSPC, "No space left on device")

        def close(self):
            pass

    def fails_at_ledger_10(self, size, seq):
        if seq == 10:
            self._dst.close()
            self._dst = FullDisk()
        compress_to(self, size, seq)
    monkeypatch.setattr(lm_mod._SegmentGzip, "_compress_to",
                        fails_at_ledger_10)
    app = start_app(conf)
    lm = app.ledger_manager
    while lm.get_last_closed_ledger_num() < 62:
        app.manual_close()      # nothing joins the compressor yet
    assert sorted(os.listdir(meta_dir)) == [SEG63]
    app.manual_close()          # 63 commits; its tail's rotation fails
    assert lm.get_last_closed_ledger_num() == 63
    with pytest.raises(RuntimeError) as failure:
        app.herder.join_completion()
    with pytest.raises(RuntimeError):
        app.manual_close()      # sticky: the next close halts at its barrier
    assert lm.get_last_closed_ledger_num() == 63
    cause = failure.value.__cause__
    while cause is not None and not isinstance(cause, OSError):
        cause = cause.__cause__
    assert cause is not None and cause.errno == errno.ENOSPC
    app.shutdown()
    assert sorted(os.listdir(meta_dir)) == [SEG63]
    assert len(records_of(meta_dir / SEG63)) == 62

    # the operator frees the disk and restarts: the next rotation is whole
    monkeypatch.setattr(lm_mod._SegmentGzip, "_compress_to", compress_to)
    app = start_app(conf, new_db=False)
    lm = app.ledger_manager
    assert lm.get_last_closed_ledger_num() == 63
    while lm.get_last_closed_ledger_num() < 127:
        app.manual_close()
    app.herder.join_completion()        # who lists the segments joins
    assert sorted(os.listdir(meta_dir)) == [SEG63, SEG127 + ".gz"]
    assert len(records_of(meta_dir / (SEG127 + ".gz"))) == 64
    assert counts(app.metrics)["segment.streamed"] == 1
    app.shutdown()


# (j) the file's format
def test_gz_is_a_gzip_file_of_deflate(tmp_path):
    lm = segment_manager(tmp_path)
    for seq in range(2, 64):
        lm._write_debug_meta(synthetic(seq), seq)
    gz = tmp_path / (SEG63 + ".gz")
    with open(gz, "rb") as f:
        head = f.read(4)
    # RFC 1952: magic 1f 8b, compression method 8 (deflate), no flags
    assert head == b"\x1f\x8b\x08\x00"
    want = b"".join(
        (len(synthetic(seq)) | 0x80000000).to_bytes(4, "big")
        + synthetic(seq) for seq in range(2, 64))
    assert gzip.decompress(gz.read_bytes()) == want
    program = shutil.which("gzip")
    if program is not None:
        assert subprocess.run([program, "-t", str(gz)],
                              timeout=60).returncode == 0
        out = subprocess.run([program, "-dc", str(gz)],
                             capture_output=True, timeout=60)
        assert out.returncode == 0 and out.stdout == want
