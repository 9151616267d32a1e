"""The rules that let the node run on a chip machine, checked on the CPU:
where the compile cache goes, when a `tpu`-backend node refuses to
start, which native binaries may be opened, and what a process outside
the node can read about the device (`backendstatus`, `catchup`'s JSON
line)."""

import json
import os
import re

import pytest

import jax

from stellar_core_tpu.main import application
from stellar_core_tpu.main.application import device_backend_refusal
from stellar_core_tpu.native import loader
from stellar_core_tpu.util import jax_cache
from stellar_core_tpu.xdr import native_codec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ compile cache -----

def test_cache_dir_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache
    directory in code: JAX reads the variable itself."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "placed"))
    assert jax_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "placed").exists()


@pytest.mark.parametrize("cwd", ["a", "b/c"])
def test_cache_dir_is_one_fixed_path_from_any_cwd(monkeypatch, tmp_path,
                                                  cwd):
    (tmp_path / cwd).mkdir(parents=True)
    monkeypatch.chdir(tmp_path / cwd)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = jax_cache.cache_dir_for_backend()
    assert os.path.dirname(d) == os.path.join(REPO, ".jax_compile_cache")
    assert os.path.basename(d).startswith("cpu-")    # platform only


def test_no_cache_path_under_tests_and_one_place_sets_it():
    """`jax_compilation_cache_dir` is updated in util/jax_cache.py and
    nowhere else, and no path under tests/ serves as a cache."""
    offenders = []
    # the directories .gitignore names hold what building, testing and
    # builders' sessions leave behind (copies of the tree among them):
    # not the checkout's own files
    with open(os.path.join(REPO, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f
                   if line.strip().endswith("/")}
    assert {"_archive", "_proof", "_scratch", "chiprun_out"} <= ignored
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ignored]
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            if path in (os.path.abspath(__file__),
                        os.path.join(REPO, "stellar_core_tpu", "util",
                                     "jax_cache.py")):
                continue
            with open(path, errors="replace") as f:
                text = f.read()
            if '"jax_compilation_cache_dir"' in text or re.search(
                    r"""tests["',\s/]+\.jax_compile_cache""", text):
                offenders.append(os.path.relpath(path, REPO))
    assert offenders == []


# ------------------------------------------- no quiet CPU stand-in --------

@pytest.mark.parametrize("backend,platforms,refused", [
    ("cpu", None, True),        # JAX fell back to the CPU
    ("cpu", "", True),
    ("cpu", "tpu,cpu", True),   # asked for a TPU, got the CPU
    ("gpu", None, True),
    ("cpu", "cpu", False),      # the operator chose it, in JAX's terms
    ("tpu", None, False),
    ("tpu", "tpu", False),
    ("tpu", "cpu", False),
])
def test_device_backend_refusal(backend, platforms, refused):
    msg = device_backend_refusal(backend, platforms)
    assert (msg is not None) == refused
    if refused:
        assert repr(backend) in msg and "JAX_PLATFORMS=cpu" in msg


def test_tpu_backend_node_refuses_a_fallback_device(monkeypatch):
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock
    seen = []

    def refuse(default_backend, jax_platforms):
        seen.append((default_backend, jax_platforms))
        return "no TPU was found (test)"

    monkeypatch.setattr(application, "device_backend_refusal", refuse)
    cfg = get_test_config()
    cfg.SIGNATURE_VERIFY_BACKEND = "tpu"
    with pytest.raises(RuntimeError, match="no TPU was found"):
        Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    # decided from what JAX itself says: this suite chose the CPU
    assert seen == [("cpu", "cpu")]


# -------------------------------- natives built where they are run --------

def test_no_binary_is_committed():
    import subprocess
    if not os.path.isdir(os.path.join(REPO, ".git")):
        pytest.skip("not a git checkout")
    listed = subprocess.run(["git", "ls-files"], cwd=REPO, check=True,
                            capture_output=True, text=True).stdout.split()
    assert [f for f in listed if f.endswith((".so", ".o", ".a"))] == []
    assert "stellar_core_tpu/native/src/sha512_consts.h" not in listed


@pytest.mark.parametrize("what", ["machine", "sources", "flags"])
def test_built_name_carries_machine_sources_and_flags(monkeypatch, what):
    base = loader.built_path("lib", [b"src"], ["-O3"])
    if what == "machine":
        monkeypatch.setattr(loader, "machine_id", lambda: "another machine")
        other = loader.built_path("lib", [b"src"], ["-O3"])
    elif what == "sources":
        other = loader.built_path("lib", [b"src2"], ["-O3"])
    else:
        other = loader.built_path("lib", [b"src"], ["-O2"])
    assert other != base
    assert os.path.dirname(other) == loader._BUILD


def test_loader_never_opens_a_foreign_or_stale_binary(monkeypatch, tmp_path):
    """A build directory that holds binaries this machine did not
    build (a copied tree): the loader compiles its own and leaves the
    others unopened."""
    monkeypatch.setattr(loader, "_BUILD", str(tmp_path))
    here = loader.build()
    assert os.path.dirname(here) == str(tmp_path)
    with open(here, "rb") as f:
        assert f.read(4) == b"\x7fELF"
    assert os.path.exists(tmp_path / "sha512_consts.h")

    # the same tree on another machine: another name, and what the
    # first machine left behind is not what gets loaded
    with open(here, "wb") as f:
        f.write(b"built for a CPU this machine is not")
    monkeypatch.setattr(loader, "machine_id", lambda: "another machine")
    there = loader.build()
    assert there != here
    lib = loader.NativeLib(there)       # runs the SHA-512 self-test
    assert lib.sha512(b"abc")[:4].hex() == "ddaf35a1"

    # stale: a source change renames the file too
    monkeypatch.setattr(loader, "_gen_consts_header",
                        lambda real=loader._gen_consts_header:
                        real() + "// changed\n")
    assert loader.build() not in (here, there)


def test_xdr_codec_name_carries_the_machine(monkeypatch, tmp_path):
    monkeypatch.setattr(loader, "_BUILD", str(tmp_path))
    here = native_codec.build_ext()
    monkeypatch.setattr(loader, "machine_id", lambda: "another machine")
    there = native_codec.build_ext()
    assert there != here
    assert sorted(os.listdir(tmp_path)) == sorted(
        os.path.basename(p) for p in (here, there))


def test_failed_native_build_is_an_error(monkeypatch, tmp_path):
    monkeypatch.setattr(loader, "_BUILD", str(tmp_path))
    with pytest.raises(RuntimeError, match="native build failed"):
        loader.compile_shared(str(tmp_path / "x.so"),
                              ["g++", "-shared", "/nonexistent.cpp"])
    assert os.listdir(tmp_path) == []


# ----------------------------- what a process outside the node can read ---

def test_backendstatus_names_the_verifiers_own_devices():
    from stellar_core_tpu.ops.backend_supervisor import BackendSupervisor
    from stellar_core_tpu.ops.verifier import (ShardedBatchVerifier,
                                               TpuBatchVerifier)
    one = BackendSupervisor(TpuBatchVerifier()).status()["device"]
    assert one == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": 1}
    mesh = BackendSupervisor(
        ShardedBatchVerifier(devices=jax.devices()[:4])).status()["device"]
    assert mesh["count"] == 4 and mesh["platform"] == "cpu"


def test_catchup_prints_where_replay_ended(tmp_path, capsys):
    """`catchup` ends with one JSON line (state, LCL, its hash; on the
    tpu backend also `backendstatus` and what reached the device), so a
    caller that must stay off JAX can check a replay."""
    from test_history_catchup import make_publishing_app
    from stellar_core_tpu.main.command_line import main

    app_a, _, root = make_publishing_app(tmp_path, n_ledgers=66)
    try:
        want = bytes(app_a.database.query_one(
            "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq=63")[0])
        passphrase = app_a.config.NETWORK_PASSPHRASE
    finally:
        app_a.shutdown()
    conf = tmp_path / "b.cfg"
    conf.write_text(
        f'NETWORK_PASSPHRASE = "{passphrase}"\n'
        f'DATABASE = "sqlite3://{tmp_path}/b.db"\n'
        f'BUCKET_DIR_PATH = "{tmp_path}/b-buckets"\n'
        '[HISTORY.test]\n'
        f'get = "cp {root}/{{0}} {{1}}"\n')
    capsys.readouterr()
    assert main(["--conf", str(conf), "catchup", "current", "--new-db"]) == 0
    lines = capsys.readouterr().out.splitlines()
    doc = json.loads(next(ln for ln in lines if ln.startswith("{")))
    assert doc == {"state": "WORK_SUCCESS", "lcl": 63,
                   "lcl_hash": want.hex()}
    assert lines[-1] == "catchup WORK_SUCCESS, LCL 63"
