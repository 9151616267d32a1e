"""The verifier across devices: the sharded and hybrid meshes on the
virtual 8-device CPU mesh against the pure-Python oracle, and a node's
choice of verifier. Moved whole out of test_tpu_verifier.py (PR 40),
whose helpers it uses: a file is one work unit of the suite's workers,
and these tests' kernel shapes are their own.
"""

import pytest

from stellar_core_tpu.crypto import ed25519_ref as ref
from stellar_core_tpu.ops.verifier import (TpuBatchVerifier,
                                           ShardedBatchVerifier)

from test_tpu_verifier import _mk


def test_sharded_matches_single():
    sharded = ShardedBatchVerifier()
    assert sharded.ndev == 8, "conftest should expose 8 virtual devices"
    items = _mk(16, seed=10)
    p, s, m = items[5]
    items[5] = (p, s[:32] + bytes(32), m)
    got = sharded.verify_tuples(items)
    want = [ref.verify(p, s, m) for p, s, m in items]
    assert got == want


def test_hybrid_multihost_mesh_verifier():
    """2-D (dcn, ici) hybrid mesh — 2 virtual 'hosts' x 4 'chips' on the
    8-device CPU mesh (SURVEY.md §5.8 distributed-backend analogue):
    results identical to the single-device verifier."""
    import jax
    from stellar_core_tpu.ops.multihost import (HybridShardedVerifier,
                                                make_hybrid_mesh)
    devs = jax.devices()
    assert len(devs) >= 8, "conftest provides an 8-device CPU mesh"
    mesh = make_hybrid_mesh(devices=devs[:8], n_hosts=2)
    assert mesh.axis_names == ("dcn", "ici")
    assert mesh.devices.shape == (2, 4)
    v = HybridShardedVerifier(mesh=mesh)
    items = _mk(16, seed=13)
    # corrupt a couple
    items[2] = (items[2][0], items[2][1], b"other message")
    items[9] = (items[9][0], b"\x01" * 64, items[9][2])
    got = v.verify_tuples(items)
    want = [ref.verify(p, s, m) for p, s, m in items]
    assert got == want


def test_sharded_uneven_and_tiny_batches():
    """Batch sizes that don't divide the 8-device mesh pad through the
    bucketing path and still return exact per-signature results."""
    sharded = ShardedBatchVerifier()
    for n, seed in ((1, 20), (7, 21), (13, 22), (17, 23)):
        items = _mk(n, seed=seed)
        if n >= 3:
            p, s, m = items[2]
            items[2] = (p, s, m + b"!")      # corrupt one
        got = sharded.verify_tuples(items)
        want = [ref.verify(p, s, m) for p, s, m in items]
        assert got == want, n


def test_node_selects_sharded_verifier_and_validates_through_it():
    """A node booted with SIGNATURE_VERIFY_BACKEND=tpu on the 8-device
    mesh must auto-select the sharded verifier and route txset
    validation through it."""
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.simulation.drive import \
        validate_txset_through_batch_verifier
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    cfg = get_test_config()
    cfg.SIGNATURE_VERIFY_BACKEND = "tpu"
    app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
    app.start()
    try:
        bv = app.batch_verifier
        # PR 5: app.batch_verifier is the backend supervisor (circuit
        # breaker, docs/ROBUSTNESS.md) wrapping the selected verifier;
        # attribute access proxies through, so ndev still resolves
        assert hasattr(bv, "breaker_state")
        assert isinstance(bv._inner, ShardedBatchVerifier)
        assert bv.ndev == 8
        calls = validate_txset_through_batch_verifier(app)
        assert calls
    finally:
        app.shutdown()


def test_mesh_config_selection():
    """SIGNATURE_VERIFY_MESH picks the topology; invalid values reject."""
    from stellar_core_tpu.main import Application, get_test_config
    from stellar_core_tpu.ops.multihost import HybridShardedVerifier
    from stellar_core_tpu.util.timer import ClockMode, VirtualClock

    for mesh, expected in (("single", TpuBatchVerifier),
                           ("sharded", ShardedBatchVerifier),
                           ("hybrid", HybridShardedVerifier)):
        cfg = get_test_config()
        cfg.SIGNATURE_VERIFY_BACKEND = "tpu"
        cfg.SIGNATURE_VERIFY_MESH = mesh
        app = Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
        try:
            # the mesh-selected verifier sits behind the supervisor
            assert type(app.batch_verifier._inner) is expected, mesh
        finally:
            app.shutdown()

    cfg = get_test_config()
    cfg.SIGNATURE_VERIFY_BACKEND = "tpu"
    cfg.SIGNATURE_VERIFY_MESH = "bogus"
    with pytest.raises(ValueError):
        Application.create(VirtualClock(ClockMode.VIRTUAL_TIME), cfg)
