"""Open loop: ragged single-cloud requests through ``PCNServer``.

Set-up builds the server (its buckets compile during construction),
draws the run's schedule (``gen.open_loop_schedule``: Poisson-like
arrivals at ``rate_hz`` and log-normal sizes, the same multiset for every
seed), makes every request's cloud and PRNG key from the seed, warms
every bucket's full and partial batches, then plays the open loop for
``warm_s`` seconds at the cell's rate on clouds of its own, so that the
window starts on a server in its steady state.

In the window the client submits each request at its due time, polling
the server meanwhile (which fires the batching timeouts), and notes when
it first sees each answer.  Latency is that moment minus the due time,
so a late submission counts against the server too; the client's own
lateness (submission minus due time) is kept as a diagnostic.  After the
last due time the client waits for the outstanding answers, up to
``drain_s`` past the window.

``serve_p95_ms``: 95th percentile of latency over every request due in
the window, a request never answered counting as infinite.
``served_clouds_per_s``: requests answered inside the window / the
window's seconds.  A sample of the answers, drawn from the seed and
holding the largest cloud, is compared with the reference.
"""
from __future__ import annotations

import time

import numpy as np

from bench import gen, program, weights
from bench import refcore as rc

POLL_S = 0.0005


class Driver:
    def __init__(self, cell: dict, seed: int, fam, seconds: float):
        self.cfg, self.tr = cell["config"], cell["traffic"]
        self.seed, self.fam, self.seconds = seed, fam, seconds

    def setup(self):
        from repro import engine, serve

        cfg, tr = self.cfg, self.tr
        self.weights = weights.make(cfg, gen.jax_key_words(self.seed, 1)[0])
        self.params = program.params(cfg, self.weights)
        eng = engine.PCNEngine(
            program.spec(cfg), mode=tr["engine"]["mode"],
            fc_backend=tr["engine"]["fc_backend"], isl_kw=program.isl_kw(cfg))
        buckets = serve.BucketSet.make(list(tr["buckets"]),
                                       batch=tr["bucket_batch"])
        self.server = serve.PCNServer(
            eng, self.params, buckets, timeout_s=tr["timeout_ms"] * 1e-3,
            fallback=tr["fallback"], max_in_flight=tr["max_in_flight"],
            seed=0)
        self.plan(tr["rate_hz"])
        # every bucket's full and partial batches
        warm = gen.make_clouds(gen.rng_for(self.seed, 4),
                               [b.n_points for b in buckets
                                for _ in range(b.batch + 1)])
        rids = [self.server.submit(c, key=np.zeros(2, np.uint32))
                for c in warm]
        self.server.drain()
        for r in rids:
            self.server.take(r)
        # the open loop at the cell's rate, on clouds of its own
        due, sizes = self._schedule(tr["rate_hz"], tr["warm_s"])
        clouds = gen.make_clouds(gen.rng_for(self.seed, 6), sizes)
        keys = gen.jax_key_words(self.seed, 6, n=len(sizes))
        self._play(due, clouds, keys, tr["warm_s"])

    def _schedule(self, rate_hz: float, seconds: float):
        tr = self.tr
        return gen.open_loop_schedule(
            self.seed, rate_hz=rate_hz, seconds=seconds,
            size_median=tr["size_median"], size_sigma=tr["size_sigma"],
            size_min=tr["size_min"], size_max=tr["size_max"])

    def plan(self, rate_hz: float):
        """The window's requests: due times, sizes, clouds and keys."""
        self.due, self.sizes = self._schedule(rate_hz, self.seconds)
        rng = gen.rng_for(self.seed, 2)
        self.clouds = gen.make_clouds(rng, self.sizes)
        self.keys = gen.jax_key_words(self.seed, 3, n=len(self.sizes))

    def window(self, seconds: float):
        (self.t0, self.latency_s, self.answered_in_window, self.late_s,
         self.logits, self.failed) = self._play(self.due, self.clouds,
                                                self.keys, seconds)

    def _play(self, due_s, clouds, keys, seconds: float):
        """Submit each request at its due time, then wait for the answers.
        -> (start, latencies, answered by ``seconds``, lateness, logits by
        request, requests failed or never answered)."""
        import jax
        from repro.serve.errors import RequestError
        srv = self.server
        n = len(due_s)
        seen = np.full(n, np.inf)
        late = np.zeros(n)
        logits: dict[int, np.ndarray] = {}
        pending: dict[int, int] = {}           # rid -> request index
        failed = set()

        def collect(now):
            for rid in [r for r in pending if srv.ready(r)]:
                i = pending.pop(rid)
                if srv.failed(rid):
                    failed.add(i)
                    try:
                        srv.take(rid)
                    except RequestError:       # popped; counted as missing
                        pass
                    continue
                seen[i] = now
                logits[i] = srv.take(rid)

        t0 = srv.clock()
        for i in range(n):
            due = t0 + due_s[i]
            while True:
                now = srv.clock()
                if now >= due:
                    break
                with jax.profiler.TraceAnnotation("bench.poll"):
                    srv.poll()
                    collect(srv.clock())
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    time.sleep(min(due - now, POLL_S))
            late[i] = srv.clock() - due
            with jax.profiler.TraceAnnotation("bench.submit"):
                rid = srv.submit(clouds[i], key=keys[i])
            pending[rid] = i
            collect(srv.clock())
        end = t0 + seconds
        give_up = end + self.tr["drain_s"]
        while pending and srv.clock() < give_up:
            with jax.profiler.TraceAnnotation("bench.poll"):
                srv.poll()
                collect(srv.clock())
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(POLL_S)
        return (t0, seen - (t0 + due_s), int((seen <= end).sum()), late,
                logits, failed | set(pending.values()))

    def release(self):
        srv = self.server
        srv.close()
        window = [r for r in srv.metrics.requests if r.t_arrival >= self.t0]
        self.queue_wait_ms = [1e3 * r.queue_wait_s for r in window]
        disp = [d for d in srv.metrics.dispatches if d.t_start >= self.t0]
        padded = sum(d.bucket[0] * d.bucket[1] for d in disp)
        valid = sum(d.valid_points for d in disp)
        self.padding_waste_pct = (100.0 * (1.0 - valid / padded)
                                  if padded else None)
        self.dispatches = len(disp)
        del self.server, self.params

    def _pick(self) -> list[int]:
        """A seeded sample of the requests, with the largest cloud."""
        n = len(self.sizes)
        rng = gen.rng_for(self.seed, 5)
        m = min(self.tr["check_requests"], n)
        pick = set(rng.choice(n, m - 1, replace=False).tolist())
        pick.add(int(np.argmax(self.sizes)))
        return sorted(pick)

    def check_inputs(self):
        """The clouds and keys whose answers are compared: the sample."""
        pick = self._pick()
        return [self.clouds[i] for i in pick], self.keys[pick]

    def check(self, ref):
        """The sample's answers against ``ref``, the reference readings of
        ``check_inputs`` (``refcore.rel_gap``).  -> (values, attempted, per-answer gaps)."""
        n = len(self.sizes)
        pick = self._pick()
        gaps = np.full(n, np.nan)
        for j, i in enumerate(pick):
            gaps[i] = (rc.rel_gap(np.asarray(self.logits[i]), ref[j])
                       if i in self.logits else np.inf)
        gaps[sorted(self.failed)] = np.inf
        sampled = gaps[pick]
        worst = float(sampled.max()) if not np.isnan(sampled).any() \
            else float("nan")
        return ({"logit_gap": worst,
                 "unanswered": float(len(self.failed))}, n, gaps)

    def diagnostics(self) -> dict:
        late = 1e3 * self.late_s
        return {"requests": len(self.sizes), "dispatches": self.dispatches,
                "client_late_ms": {"p50": float(np.median(late)),
                                   "p95": float(np.percentile(late, 95)),
                                   "max": float(late.max())}}

    def end_to_end(self) -> dict:
        return {"serve_p95_ms": 1e3 * gen.quantile(self.latency_s, 0.95),
                "served_clouds_per_s": self.answered_in_window / self.seconds}

    def layer_context(self, summary: dict) -> dict:
        return {"config": self.cfg, "traffic": self.tr, "trace": summary,
                "queue_wait_ms": self.queue_wait_ms,
                "padding_waste_pct": self.padding_waste_pct,
                "clouds": self.answered_in_window,
                "window_s": self.seconds}
