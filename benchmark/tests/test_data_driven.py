"""A configuration, a traffic mix, a cell and a per-layer metric can
each be added as files and entries only: `rehearse.make_root` builds a
root from copies of everything that is there plus `data/added/`, and
refuses to replace a file. The two added cells are the CPU rehearsal of
both real cells at tiny size, traced; their result names the CPU and
carries no metric that comes from a device trace."""

import filecmp
import io
import json
import os

import pytest

from benchmark.harness.spec import Spec
from benchmark.tests import rehearse as R


def test_added_as_files_and_entries_only(tmp_path):
    root = R.make_root(str(tmp_path))
    # nothing that was there has changed
    for sub, _, files in os.walk(R.BENCH):
        if "__pycache__" in sub or os.sep + "tests" in sub:
            continue
        for f in files:
            src = os.path.join(sub, f)
            dst = os.path.join(root, "benchmark",
                               os.path.relpath(src, R.BENCH))
            assert filecmp.cmp(src, dst, shallow=False), src
    spec, real = Spec.load(root), Spec.load(R.ROOT)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        old = {e["name"]: e for e in real.doc[group]}
        new = {e["name"]: e for e in spec.doc[group]}
        assert set(old) <= set(new)
        for name, entry in old.items():
            kept = {k: v for k, v in new[name].items() if k != "workloads"}
            assert kept == {k: v for k, v in entry.items()
                            if k != "workloads"}
            assert set(entry.get("workloads", [])) <= \
                set(new[name].get("workloads", []))
    assert spec.config("tiny-catchup")["deployment"]["payment_ledgers"] == 5
    assert spec.traffic("tiny-closed")["generator"] == "closed_loop"
    assert spec.layer_reader("closes_counted") is not None


@pytest.mark.parametrize("workload", ["tiny-standalone.tiny-closed",
                                      "tiny-catchup.tiny-replay"])
def test_rehearsal_traced(tmp_path, workload):
    out = io.StringIO()
    rc = R.rehearse(["--workload", workload, "--seed", "4294967311",
                     "--seconds", "2", "--trace", "1"], str(tmp_path),
                    out=out)
    assert rc == 0
    doc = json.loads(out.getvalue().splitlines()[-1])
    assert doc["correct"] is True
    assert doc["device"]["platform"] == "cpu"
    assert "busy_s" not in doc["device"] and "breakdown" not in doc
    spec = Spec.load(str(tmp_path))
    from_device = {m["name"] for m in spec.doc["per_layer"]
                   if m["source"] == "device_trace"}
    assert doc["metrics"] and not from_device & set(doc["metrics"])
    if workload.startswith("tiny-standalone"):
        assert doc["metrics"]["closes_counted"]["value"] >= 1
        assert doc["metrics"]["device_sig_share.live"]["value"] == 0.0
    else:
        assert doc["metrics"]["device_sig_share.catchup"]["value"] > 100.0


def test_no_chip_no_result(tmp_path):
    """The real command path (look for a chip on) on the CPU: non-zero
    exit and not one line on stdout."""
    import time
    from benchmark.harness.main import main
    out = io.StringIO()
    rc = main(["--workload", "standalone-pay1000.closed", "--seed", "1",
               "--seconds", "1", "--trace", "0"], t0=time.perf_counter(),
              root=R.ROOT, out=out)
    assert rc != 0 and out.getvalue() == ""
