"""What the repo's records point at exists.

Two kinds of record are read by nothing on the CPU and break in
silence: the benchmark's per-layer readers, each of which looks a zone,
counter or span up by a string the program must print
(`benchmark/layer_metrics/*.py`; a renamed zone reads `null` on the
chip), and the documents, which cite files by path. Both are checked
here from the sources alone, with `ast`: nothing under `benchmark/` is
imported or edited, and no node runs.
"""

import ast
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "stellar_core_tpu")
READERS = os.path.join(ROOT, "benchmark", "layer_metrics")

# a zone, timer, histogram, meter or counter name: dotted, lower-case head
NAME = re.compile(r"^[a-z][A-Za-z0-9]*(\.[A-Za-z0-9_]+)+$")
# the lookups benchmark/harness/cell.py offers a reader
LOOKUPS = {"cell.zones.get", "cell.counters.get",
           "cell.spans.total", "cell.spans.named"}
# MetricsRegistry and ZoneRegistry methods whose first argument names
# what they open
METRIC_NEW = {"new_counter", "new_meter", "new_timer", "new_histogram"}
METRIC_PARTS = {"counter", "meter", "timer", "histogram"}
ZONE_OPEN = {"zone", "zone_into"}
ZONE_ADD_RECEIVERS = {"perf", "default_registry", "registry"}
# `ZoneRegistry.report()` lists a zone's on-CPU seconds under the zone's
# name with this suffix (util/perf.py ON_CPU): nothing opens that name
ON_CPU = ".onCpu"


def _python_files(top):
    for dirpath, dirnames, filenames in os.walk(top):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", "build")]
        for name in sorted(filenames):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def _parse(path):
    with open(path, encoding="utf-8") as fh:
        return ast.parse(fh.read(), path)


def _dotted(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    return ".".join([node.id] + parts[::-1])


def _string(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _opened_by_call(node):
    """The name a call opens in a registry of the program, or None."""
    if not isinstance(node.func, ast.Attribute) or not node.args:
        return None
    method = node.func.attr
    first = _string(node.args[0])
    if first is None:
        return None
    if method in METRIC_NEW or method in ZONE_OPEN:
        return first
    if method in METRIC_PARTS:
        parts = [_string(a) for a in node.args]
        return ".".join(parts) if all(parts) else None
    if method == "add":
        receiver = _dotted(node.func.value) or ""
        if receiver.split(".")[-1] in ZONE_ADD_RECEIVERS:
            return first
    return None


def _is_zone_call(node):
    return node.func.attr in ZONE_OPEN or node.func.attr == "add"


@pytest.fixture(scope="module")
def program_opens():
    """(every name `stellar_core_tpu/` opens as a zone, timer,
    histogram, meter or counter; those of them that are zones)."""
    names, zones = set(), set()
    for path in _python_files(PACKAGE):
        tree = _parse(path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                name = _opened_by_call(node)
                if name and NAME.match(name):
                    names.add(name)
                    if _is_zone_call(node):
                        zones.add(name)
                # util/perf.py `sched_lap(s0, metrics, on_cpu, run_delay)`
                # makes the two timers it is given the names of
                elif (_dotted(node.func) or "").split(".")[-1] \
                        == "sched_lap":
                    names.update(filter(None, map(_string, node.args[2:])))
            # util/jax_cache.py maps jax.monitoring events to zones
            elif isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id.endswith("_ZONES")
                    for t in node.targets) \
                    and isinstance(node.value, ast.Dict):
                found = set(filter(None, map(_string, node.value.values)))
                names |= found
                zones |= found
    return names, zones


@pytest.fixture(scope="module")
def program_names(program_opens):
    return program_opens[0]


@pytest.fixture(scope="module")
def bench_spans():
    """Every `bench.*` span the benchmark's drivers and harness add."""
    names = set()
    for sub in ("generators", "harness"):
        for path in _python_files(os.path.join(ROOT, "benchmark", sub)):
            for node in ast.walk(_parse(path)):
                if isinstance(node, ast.Call) and node.args \
                        and (_dotted(node.func) or "").endswith("spans.add"):
                    name = _string(node.args[0])
                    if name:
                        names.add(name)
    return names


def _class_members(path, cls_name):
    """Attributes `self.x = ...` of `__init__` and the methods and
    properties of one class."""
    members = set()
    for node in ast.walk(_parse(path)):
        if isinstance(node, ast.ClassDef) and node.name == cls_name:
            for item in ast.walk(node):
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    members.add(item.name)
                elif isinstance(item, ast.Attribute) \
                        and isinstance(item.ctx, ast.Store) \
                        and isinstance(item.value, ast.Name) \
                        and item.value.id == "self":
                    members.add(item.attr)
    return members


@pytest.fixture(scope="module")
def cell_members():
    return _class_members(
        os.path.join(ROOT, "benchmark", "harness", "cell.py"), "Cell") \
        | {"spec"}                      # set by harness/main.py run_cell


@pytest.fixture(scope="module")
def trace_members():
    return _class_members(
        os.path.join(ROOT, "benchmark", "harness", "trace.py"),
        "DeviceTrace")


def _reader_files():
    return sorted(f for f in os.listdir(READERS) if f.endswith(".py"))


@pytest.mark.parametrize("reader", _reader_files())
def test_layer_metric_reads_names_the_program_prints(
        reader, program_opens, bench_spans, cell_members, trace_members):
    program_names, program_zones = program_opens
    tree = _parse(os.path.join(READERS, reader))
    docstrings = {id(n.value) for n in ast.walk(tree)
                  if isinstance(n, ast.Expr) and _string(n.value) is not None}
    names, program = set(), None
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _dotted(node.func) in LOOKUPS:
            name = _string(node.args[0]) if node.args else None
            assert name is not None, (
                f"{reader}:{node.lineno}: {_dotted(node.func)} of something "
                "other than a string literal cannot be checked")
            names.add(name)
        elif isinstance(node, ast.Constant) and id(node) not in docstrings \
                and isinstance(node.value, str) and NAME.match(node.value):
            # a name looked up some other way: `report[z]`, an event's name
            names.add(node.value)
        elif isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "PROGRAM"
                for t in node.targets):
            program = _string(node.value)
    for name in sorted(names):
        if name.startswith("bench."):
            assert name in bench_spans, (
                f"{reader} reads span {name!r}; benchmark/generators and "
                f"benchmark/harness add {sorted(bench_spans)}")
        elif name.endswith(ON_CPU):
            # a derived name: tied to the zone the program opens under
            # the base name, which the reader takes the wall of
            base = name[:-len(ON_CPU)]
            assert base in program_zones, (
                f"{reader} reads {name!r}, and stellar_core_tpu/ opens "
                f"no zone {base!r} whose on-CPU seconds it could be")
            assert base in names, (
                f"{reader} reads {name!r} and not the zone {base!r}")
        else:
            assert name in program_names, (
                f"{reader} reads {name!r}, which stellar_core_tpu/ opens as "
                "no zone, timer, histogram, meter or counter")
    if program is not None:
        # trace.module_runs matches the XLA module `jit_<PROGRAM>`, which
        # JAX names after the function the verifier jits
        from stellar_core_tpu.ops import ed25519_kernel
        from stellar_core_tpu.ops.verifier import TpuBatchVerifier
        TpuBatchVerifier._ensure_shared_jits()
        jitted = TpuBatchVerifier._shared_jit_msg32
        assert jitted.__wrapped__ is ed25519_kernel.verify_kernel_msg32
        assert "jit_" + program == "jit_" + jitted.__name__ \
            == "jit_verify_kernel_msg32"
    # whatever else a reader takes from the cell or its device trace
    # exists there
    touched = 0
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name):
            if node.value.id == "cell":
                assert node.attr in cell_members, (reader, node.attr)
                touched += 1
            elif node.value.id == "trace":
                assert node.attr in trace_members, (reader, node.attr)
                touched += 1
    assert names or program or touched, f"{reader} reads nothing checkable"


# what PR 31 publishes about the moved completion barrier: no reader of
# the benchmark takes them, a traced line's zones and a node's `metrics`
# route do
@pytest.mark.parametrize("name", ["ledger.close.tail.hidden",
                                  "ledger.close.tail.waited",
                                  "database.tail.busy",
                                  "ledger.close.completeWait",
                                  "herder.joinCompletion"])
def test_barrier_counters_are_published_and_documented(name, program_names):
    assert name in program_names, (
        f"stellar_core_tpu/ opens no zone or counter {name!r}")
    for doc in ("docs/OBSERVABILITY.md", "docs/CLOSE_PIPELINE.md"):
        with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
            assert f"`{name}`" in fh.read(), f"{doc} does not name {name}"


# what ISSUE 32 publishes about signer resolution, chunks and their
# adoption: the readers `*.dense.py` take some (checked above, reader by
# reader); all of them are opened by the program and in the doc
@pytest.mark.parametrize("name", [
    "crypto.collectTuples", "crypto.collect.candidates",
    "crypto.collect.signatures", "crypto.verify.dispatch.chunks",
    "crypto.prevalidated.miss.pending", "crypto.prevalidated.miss.unknown",
    "catchup.batch.adoptLag"])
def test_dense_replay_names_are_published_and_documented(name,
                                                         program_names):
    assert name in program_names, (
        f"stellar_core_tpu/ opens no zone or counter {name!r}")
    with open(os.path.join(ROOT, "docs/OBSERVABILITY.md"),
              encoding="utf-8") as fh:
        assert f"`{name}`" in fh.read()


# what ISSUE 35 publishes about a catchup over more than one checkpoint:
# three are read by `*.range.py` readers of their own (checked above),
# the fourth decides `correct` in the range cell's driver
RANGE_NAMES = {
    "catchup.prefetch.ahead": "prefetch_ahead_ms.range.py",
    "catchup.batch.lead": "batch_lead_ms.range.py",
    "crypto.verify.dispatch.collectWait": "collect_wait_ms.range.py",
    "crypto.collect.carried": "../generators/range_replay.py",
}


@pytest.mark.parametrize("name", sorted(RANGE_NAMES))
def test_range_replay_names_are_published_documented_and_read(
        name, program_names):
    assert name in program_names, (
        f"stellar_core_tpu/ opens no zone, timer or counter {name!r}")
    for doc in ("docs/OBSERVABILITY.md", "PERF.md"):
        with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
            assert f"`{name}`" in fh.read(), f"{doc} does not name {name}"
    with open(os.path.join(READERS, RANGE_NAMES[name]),
              encoding="utf-8") as fh:
        assert f'"{name}"' in fh.read()


# what ISSUE 39 publishes about a received tx set: each is read by a
# `*.txset.py` reader (the three counters and the zone also decide
# `correct` in the cell's driver, generators/txset_follow.py)
TXSET_NAMES = {
    "herder.txset.validate": "txset_validate_ms.txset.py",
    "herder.txset.prevalidate.cached": "txset_cached_share.txset.py",
    "herder.txset.prevalidate.dispatched": "txset_cached_share.txset.py",
    "herder.txset.prevalidate.fallback": "txset_cached_share.txset.py",
    "herder.txset.receivedToValidated": "received_to_validated_ms.txset.py",
}


@pytest.mark.parametrize("name", sorted(TXSET_NAMES))
def test_txset_names_are_published_documented_and_read(name, program_names):
    assert name in program_names, (
        f"stellar_core_tpu/ opens no zone, timer or counter {name!r}")
    for doc in ("docs/OBSERVABILITY.md", "PERF.md"):
        with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
            assert f"`{name}`" in fh.read(), f"{doc} does not name {name}"
    with open(os.path.join(READERS, TXSET_NAMES[name]),
              encoding="utf-8") as fh:
        assert f'"{name}"' in fh.read()


# what ISSUE 41 publishes about Soroban authorization at apply: each is
# read by a `*.auth.py` reader of its own or decides `correct` in the
# cell's driver (generators/soroban_replay.py), and the two zones are
# reported by `add`, once a close
SOROBAN_NAMES = {
    "soroban.invoke": "soroban_invoke_us_per_tx.auth.py",
    "soroban.auth": "soroban_auth_us_per_tx.auth.py",
    "soroban.auth.entries.address": "soroban_auth_us_per_tx.auth.py",
    "soroban.auth.verify.prevalidated": "auth_prevalidated_share.auth.py",
    "soroban.auth.verify.fallback": "auth_prevalidated_share.auth.py",
    "crypto.collect.auth": "auth_tuple_share.auth.py",
    "soroban.auth.entries.source": None,
    "soroban.auth.failed": None,
}


@pytest.mark.parametrize("name", sorted(SOROBAN_NAMES))
def test_soroban_names_are_published_documented_and_read(name,
                                                         program_opens):
    names, zones = program_opens
    assert name in names, (
        f"stellar_core_tpu/ opens no zone or counter {name!r}")
    assert (name in zones) == (name in ("soroban.invoke", "soroban.auth"))
    for doc in ("docs/OBSERVABILITY.md", "PERF.md"):
        with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
            assert f"`{name}`" in fh.read(), f"{doc} does not name {name}"
    reader = SOROBAN_NAMES[name]
    if reader is not None:
        with open(os.path.join(READERS, reader), encoding="utf-8") as fh:
            assert f'"{name}"' in fh.read()


# what ISSUE 43 publishes about a contract ledger's reads: each is read
# by a `*.auth.py` reader of its own, over the `soroban.invoke` zone's
# count; neither is a zone
CLOSE_READ_NAMES = {
    "soroban.config.load": "soroban_config_loads_per_tx.auth.py",
    "ledger.root.point.sql": "root_point_reads_per_tx.auth.py",
}


@pytest.mark.parametrize("name", sorted(CLOSE_READ_NAMES))
def test_close_read_names_are_published_documented_and_read(name,
                                                            program_opens):
    names, zones = program_opens
    assert name in names, f"stellar_core_tpu/ opens no counter {name!r}"
    assert name not in zones
    for doc in ("docs/OBSERVABILITY.md", "docs/CLOSE_PIPELINE.md",
                "PERF.md"):
        with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
            assert f"`{name}`" in fh.read(), f"{doc} does not name {name}"
    tree = _parse(os.path.join(READERS, CLOSE_READ_NAMES[name]))
    looked_up = {_string(n) for n in ast.walk(tree)
                 if isinstance(n, ast.Constant) and _string(n)
                 and NAME.match(_string(n))}
    assert looked_up == {name, "soroban.invoke"}


def _borrowed_readers(suffix):
    """(reader file, the reader whose code makes its reading) of every
    `*<suffix>` reader that calls `cell.spec.layer_reader`."""
    for reader in _reader_files():
        if not reader.endswith(suffix):
            continue
        for node in ast.walk(_parse(os.path.join(READERS, reader))):
            if isinstance(node, ast.Call) \
                    and _dotted(node.func) == "cell.spec.layer_reader":
                yield reader, _string(node.args[0])


@pytest.mark.parametrize("suffix,count", [(".dense.py", 13),
                                          (".live.py", 3),
                                          (".range.py", 16)])
def test_readers_that_borrow_a_reading_name_a_reader_that_exists(suffix,
                                                                 count):
    """A `*.dense.py`, `*.live.py` or `*.range.py` reader that makes its
    reading with another reader's code names that reader's file."""
    borrowed = list(_borrowed_readers(suffix))
    for reader, lender in borrowed:
        assert os.path.exists(os.path.join(READERS, lender + ".py")), reader
    assert len(borrowed) == count


def test_txset_readers_that_borrow_a_reading_name_a_reader_that_exists():
    """As above for `*.txset.py`, with no count held: a later PR gives
    the cell a reader by adding a file."""
    borrowed = list(_borrowed_readers(".txset.py"))
    assert borrowed
    for reader, lender in borrowed:
        assert os.path.exists(os.path.join(READERS, lender + ".py")), reader
        assert not lender.endswith(".txset")


def test_auth_readers_that_borrow_a_reading_name_a_reader_that_exists():
    """As above for `*.auth.py`, with no count held."""
    borrowed = list(_borrowed_readers(".auth.py"))
    assert borrowed
    for reader, lender in borrowed:
        assert os.path.exists(os.path.join(READERS, lender + ".py")), reader
        assert not lender.endswith(".auth")


def test_flood_readers_that_borrow_a_reading_name_a_reader_that_exists():
    """As above for `*.flood.py`, with no count held."""
    borrowed = list(_borrowed_readers(".flood.py"))
    assert borrowed
    for reader, lender in borrowed:
        assert os.path.exists(os.path.join(READERS, lender + ".py")), reader
        assert not lender.endswith(".flood")


# what ISSUE 45 publishes about a flood burst and the shapes a node
# loads: each is read by a `*.flood.py` reader of its own or decides
# `correct` in the cell's driver (generators/txset_flood.py)
FLOOD_NAMES = {
    "herder.recvTransactions": "flood_admit_us_per_tx.flood.py",
    "herder.recvTransactions.verify": "flood_verify_wait_us_per_tx.flood.py",
    "herder.flood.received": "flood_verify_wait_us_per_tx.flood.py",
    "herder.flood.admitted": "flood_admit_us_per_tx.flood.py",
    "herder.flood.duplicate": None,
    "herder.flood.badSig": None,
    "herder.ledgerClosed": "queue_upkeep_ms.flood.py",
    "crypto.verify_service.flush.native":
        "verify_service_native_share.flood.py",
    "crypto.verify_service.occupancy": "flood_batch_occupancy.flood.py",
    "crypto.verify.shape.loaded": None,
    "crypto.verify.shape.missed": "shape_missed.flood.py",
    "ledger.root.point.sql": "root_point_reads_per_tx.flood.py",
}
FLOOD_ZONES = {"herder.recvTransactions", "herder.recvTransactions.verify",
               "herder.ledgerClosed"}


@pytest.mark.parametrize("name", sorted(FLOOD_NAMES))
def test_flood_names_are_published_documented_and_read(name, program_opens):
    names, zones = program_opens
    assert name in names, (
        f"stellar_core_tpu/ opens no zone or counter {name!r}")
    assert (name in zones) == (name in FLOOD_ZONES)
    for doc in ("docs/OBSERVABILITY.md", "PERF.md"):
        with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
            assert f"`{name}`" in fh.read(), f"{doc} does not name {name}"
    reader = FLOOD_NAMES[name]
    if reader is not None:
        with open(os.path.join(READERS, reader), encoding="utf-8") as fh:
            assert f'"{name}"' in fh.read()
    else:
        # no reader takes it: the cell's driver holds it in `correct`
        with open(os.path.join(ROOT, "benchmark", "generators",
                               "txset_flood.py"), encoding="utf-8") as fh:
            text = fh.read()
        assert f'"{name}"' in text or name.rsplit(".", 1)[0] + "." in text


def test_complete_wait_live_reads_the_barrier_zone_through_catchups_reader(
        program_names):
    """ISSUE 34's one new metric: `complete_wait_ms.live` is
    `complete_wait_ms.catchup`'s reading, and that reader looks up the
    zone the close opens round its join of the previous tail; the zone
    the readers' join opens is the one `herder_self_ms.live` subtracts."""
    assert dict(_borrowed_readers(".live.py"))[
        "complete_wait_ms.live.py"] == "complete_wait_ms.catchup"
    lender = _parse(os.path.join(READERS, "complete_wait_ms.catchup.py"))
    looked_up = {_string(n.args[0]) for n in ast.walk(lender)
                 if isinstance(n, ast.Call)
                 and _dotted(n.func) == "cell.zones.get"}
    assert looked_up == {"ledger.close.completeWait"}
    assert {"ledger.close.completeWait",
            "herder.joinCompletion"} <= program_names
    # opened by the readers' join, and by nothing on the close path
    herder = _parse(os.path.join(PACKAGE, "herder", "herder.py"))
    opened_in = {
        fn.name for fn in ast.walk(herder)
        if isinstance(fn, ast.FunctionDef)
        for n in ast.walk(fn)
        if isinstance(n, ast.Call)
        and _opened_by_call(n) == "herder.joinCompletion"}
    assert opened_in == {"join_completion"}
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        import json
        entry = [m for m in json.load(fh)["per_layer"]
                 if m["name"] == "complete_wait_ms.live"]
    assert entry == [{
        "name": "complete_wait_ms.live", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "ledger (close, apply)",
        "moves": "close_ms_p90",
        "workloads": ["standalone-pay1000.closed"]}]


# what ISSUE 37 adds: six readers of what a thread ran and what it
# stood still (the issue's two of `runtime.closing.runDelay` are not
# there: the chip's host keeps no schedstat, PERF.md §7). {reader: (what
# it looks up, the reader it borrows from, layer, the end-to-end metric
# it moves)}
# the dense and range cells are not listed: `benchmark/tests` pin the
# number of their metrics (PERF.md §7), and that directory is the
# benchmark's
REPLAY_CELLS = ["catchup-pay1000.replay"]
LIVE_CELLS = ["standalone-pay1000.closed"]
LEDGER, HERDER = "ledger (close, apply)", "herder (admission, tx queue)"
WAIT_READERS = {
    "apply_wait_us_per_tx.replay": (
        {"ledger.close.applyTx", "ledger.close.applyTx.onCpu"}, None,
        LEDGER, "catchup_ledgers_per_s"),
    "apply_wait_us_per_tx.live": (
        set(), "apply_wait_us_per_tx.replay", LEDGER, "close_ms_p90"),
    "admit_wait_us_per_tx.live": (
        {"herder.recvTransaction", "herder.recvTransaction.onCpu"}, None,
        HERDER, "applied_tx_per_s"),
    "tail_wait_us_per_tx.replay": (
        {"ledger.close.complete", "ledger.close.complete.onCpu"}, None,
        LEDGER, "catchup_ledgers_per_s"),
    "gc_us_per_tx.replay": (
        {"runtime.gc"}, None, LEDGER, "catchup_ledgers_per_s"),
    "gc_us_per_tx.live": (
        set(), "gc_us_per_tx.replay", LEDGER, "close_ms_p90"),
}


@pytest.mark.parametrize("metric", sorted(WAIT_READERS))
def test_wait_readers_look_up_what_the_program_reports(metric,
                                                       program_opens):
    """Each of ISSUE 37's readers looks up the names it is documented
    to, a derived on-CPU name only beside the zone it is derived from
    and only where the program opens that zone by `zone`, `zone_into`
    or `add` (a name `report()` derives is opened by nothing), and is
    entered in BENCHMARK.json for its cells."""
    import json
    names, zones = program_opens
    looked_up, lender, layer, moves = WAIT_READERS[metric]
    tree = _parse(os.path.join(READERS, metric + ".py"))
    found = {_string(n.args[0]) for n in ast.walk(tree)
             if isinstance(n, ast.Call) and _dotted(n.func) in LOOKUPS}
    assert found == looked_up
    for name in found:
        if name.endswith(ON_CPU):
            assert name not in names
            assert name[:-len(ON_CPU)] in zones & found
        else:
            assert name in names
    borrowed = dict(_borrowed_readers(metric.rsplit(".", 1)[1] + ".py"))
    assert borrowed.get(metric + ".py") == lender
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        entry = [m for m in json.load(fh)["per_layer"]
                 if m["name"] == metric]
    assert entry == [{
        "name": metric, "unit": "us", "better": "lower",
        "source": "program_span", "layer": layer, "moves": moves,
        "workloads": LIVE_CELLS if metric.endswith(".live")
        else REPLAY_CELLS}]


# every zone, timer, counter and instant ISSUE 37 adds is opened by the
# program and named in the operator's document and in PERF.md's table
@pytest.mark.parametrize("name", [
    "runtime.closing.onCpu", "runtime.closing.runDelay",
    "runtime.completion.onCpu", "runtime.completion.runDelay",
    "runtime.collect.onCpu", "runtime.collect.runDelay",
    "runtime.gc", "runtime.gc.gen2", "runtime.gc.collected",
    "runtime.stall"])
def test_runtime_names_are_published_and_documented(name, program_names):
    assert name in program_names, (
        f"stellar_core_tpu/ opens no zone, timer or counter {name!r}")
    for doc in ("docs/OBSERVABILITY.md", "PERF.md"):
        with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
            assert f"`{name}`" in fh.read(), f"{doc} does not name {name}"


# ------------------------------------------------------------ documents --

CITED = re.compile(r"`([^`\s]+)`")
PATH = re.compile(r"^[\w./-]+(\.(py|md|json|cfg)|/)$")
# where a document's short paths are rooted
BASES = ("", "stellar_core_tpu", "docs", "tests", "scripts", "benchmark")


def _documents():
    docs = sorted("docs/" + f for f in os.listdir(os.path.join(ROOT, "docs"))
                  if f.endswith(".md"))
    return ["README.md"] + docs


def _cited_paths(text):
    for token in CITED.findall(text):
        token = re.sub(r"(:\d+(-\d+)?(,\d+(-\d+)?)*)$", "", token)
        if PATH.match(token) and not token.startswith(("/", "~", "-")):
            yield token


@pytest.mark.parametrize("doc", _documents())
def test_document_cites_files_that_exist(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
        text = fh.read()
    missing = sorted({
        path for path in _cited_paths(text)
        if not any(os.path.exists(os.path.join(ROOT, base, path))
                   for base in BASES)})
    assert not missing, f"{doc} cites files that are not in the tree: " \
                        f"{missing}"
