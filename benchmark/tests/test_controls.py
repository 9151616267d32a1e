"""With the timed path broken underneath, a run must come out not
correct — and sound runs must come out correct. Everything but the
harness's look for a chip is driven, at tiny size on the CPU."""

import io
import json

import pytest

from benchmark.tests.controls import CONTROLS
from benchmark.tests.rehearse import rehearse

STANDALONE = "tiny-standalone.tiny-closed"
CATCHUP = "tiny-catchup.tiny-replay"


def run(tmp_path, workload, control=None, trace=0):
    out = io.StringIO()
    rc = rehearse(["--workload", workload, "--seed", "2147483659",
                   "--seconds", "2", "--trace", str(trace)],
                  str(tmp_path), out=out,
                  driver_hook=CONTROLS[control] if control else None)
    assert rc == 0
    lines = out.getvalue().splitlines()
    return json.loads(lines[-1]), [ln for ln in lines if "FAILED" in ln]


@pytest.mark.parametrize("workload", [STANDALONE, CATCHUP])
def test_sound_run_is_correct(tmp_path, workload):
    doc, failed = run(tmp_path, workload)
    assert doc["correct"] is True and not failed
    assert doc["failed"] == 0 and doc["attempted"] > 0


@pytest.mark.parametrize("control,workload,failing", [
    ("catchup.accept_all", CATCHUP, "differ from the oracle"),
    ("catchup.wrong_on_some_lanes", CATCHUP, "differ from the oracle"),
    ("catchup.skips_the_device", CATCHUP, "did not dispatch to the device"),
    ("standalone.drop_acknowledged", STANDALONE, "acknowledged minus applied"),
    ("standalone.admission_accepts_everything", STANDALONE,
     "corrupted envelopes admitted"),
    ("standalone.device_accepts_everything", STANDALONE,
     "device verdicts that differ"),
    ("any.compiles_in_window", STANDALONE, "compiled inside the measured"),
])
def test_control_is_not_correct(tmp_path, control, workload, failing):
    doc, failed = run(tmp_path, workload, control)
    assert doc["correct"] is False
    assert any(failing in ln for ln in failed), failed
