"""A pass-through round the node's device verifier that keeps what went
in and what came out: the benchmark's own span at the boundary of the
verifier layer. It changes nothing: every call goes to the program's
supervised `batch_verifier` and every result comes back as it was."""

import time


class RecordingVerifier:
    def __init__(self, inner, spans):
        self._inner = inner
        self._spans = spans
        self.batches = []        # {"n", "results" (None until collected)}

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def verify_tuples_async(self, items):
        t0 = time.perf_counter()
        handle = self._inner.verify_tuples_async(items)
        t1 = time.perf_counter()
        rec = {"n": len(items), "results": None}
        self.batches.append(rec)
        self._spans.add("bench.verifier.dispatch", t0, t1, batch=len(items))

        def collect():
            res = handle()
            if rec["results"] is None:
                rec["results"] = [bool(v) for v in res]
                self._spans.add("bench.verifier.in_flight", t1,
                                time.perf_counter(), batch=len(items))
            return res
        return collect

    def verify_tuples(self, items):
        return self.verify_tuples_async(items)()
