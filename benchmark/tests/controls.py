"""Ways to break the timed path underneath the harness, each standing
for what a later PR might be tempted to do. A `driver_hook` runs after
set-up and before the window; the benchmark's own command never passes
one. Used by test_controls.py at tiny size on the CPU and by
control.py at the cells' own size on the chip."""

from stellar_core_tpu.herder.tx_queue import AddResult


class _AcceptAll:
    """A verifier that checks nothing: every signature is 'valid'."""

    def __init__(self, inner):
        self._inner = inner
        self.batches = getattr(inner, "batches", [])

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def verify_tuples_async(self, items):
        self._inner.verify_tuples_async(items)()   # the device still runs
        rec = {"n": len(items), "results": [True] * len(items)}
        self.batches.append(rec)
        return lambda: [True] * len(items)

    def verify_tuples(self, items):
        return self.verify_tuples_async(items)()


class _WrongOnSomeLanes(_AcceptAll):
    """A degraded kernel: right on valid signatures, wrong on every
    invalid one whose public key's first byte is odd."""

    def verify_tuples_async(self, items):
        res = [bool(v) for v in self._inner.verify_tuples_async(items)()]
        out = [v or bool(p[0] & 1) for v, (p, _, _) in zip(res, items)]
        return lambda: out


class _SkipsTheDevice(_AcceptAll):
    """Right answers, but from the host: the supervisor never sees the
    batch, so no dispatch reaches the device."""

    def verify_tuples_async(self, items):
        from stellar_core_tpu.crypto.keys import verify_sig_uncached
        out = [verify_sig_uncached(p, s, m) for p, s, m in items]
        self.batches.append({"n": len(items), "results": out})
        return lambda: out


def _catchup(wrapper):
    def hook(driver):
        driver.wrap_verifier = wrapper
    return hook


def drop_acknowledged(driver) -> None:
    """standalone: the first transaction of every ledger is
    acknowledged PENDING and silently dropped."""
    herder = driver.app.herder
    real = herder.recv_transaction
    firsts = {id(ledger[0][0]) for ledger in driver.ledgers}

    def recv(frame, *a, **kw):
        if id(frame) in firsts:
            return AddResult.ADD_STATUS_PENDING
        return real(frame, *a, **kw)
    herder.recv_transaction = recv


def admission_accepts_everything(driver) -> None:
    """standalone: admission's per-signature verifier checks nothing."""
    driver.app.herder._verify = lambda pub, sig, msg: True


def device_accepts_everything(driver) -> None:
    """standalone: the node's device verifier checks nothing."""
    driver.app.batch_verifier = _AcceptAll(driver.app.batch_verifier)


def compiles_in_window(driver) -> None:
    """either cell: a program compiles before the window has closed."""
    import time
    real = driver.window

    def window(seconds):
        real(seconds)
        import jax
        import jax.numpy as jnp
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(3)).block_until_ready()
        driver.t_end = time.perf_counter()
    driver.window = window


CONTROLS = {
    # the control of each cell: the guarantee it breaks, see PERF.md
    "catchup.accept_all": _catchup(_AcceptAll),
    "catchup.wrong_on_some_lanes": _catchup(_WrongOnSomeLanes),
    "catchup.skips_the_device": _catchup(_SkipsTheDevice),
    "standalone.drop_acknowledged": drop_acknowledged,
    "standalone.admission_accepts_everything": admission_accepts_everything,
    "standalone.device_accepts_everything": device_accepts_everything,
    "any.compiles_in_window": compiles_in_window,
}
