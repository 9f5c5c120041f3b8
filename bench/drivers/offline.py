"""Offline closed loop: full batches through ``PCNEngine.apply``.

Set-up draws a pool of ``pool_batches`` distinct batches of ``batch``
clouds of ``points`` points, each cloud with its own PRNG key, all from
the seed.  The window dispatches the pool's batches in turn, back to
back, with at most ``in_flight`` steps unfinished; when ``--seconds``
have passed it dispatches no more and awaits the last step.  The
window's length runs to that step's end.

``clouds_per_s`` = clouds whose logits were produced in the window /
the window's seconds.  Every step's logits are then compared with the
reference of its batch.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from bench import gen, program, weights
from bench import refcore as rc


class Driver:
    def __init__(self, cell: dict, seed: int, fam, seconds: float):
        self.cfg, self.tr = cell["config"], cell["traffic"]
        self.seed, self.fam = seed, fam

    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro import engine

        cfg, tr = self.cfg, self.tr
        b, p, n = tr["batch"], tr["pool_batches"], tr["points"]
        if n != cfg["points"]:
            raise ValueError(f"traffic points {n} != configuration's "
                             f"{cfg['points']}")
        self.weights = weights.make(cfg, gen.jax_key_words(self.seed, 1)[0])
        self.params = program.params(cfg, self.weights)
        self.engine = engine.PCNEngine(
            program.spec(cfg), mode=tr["engine"]["mode"],
            fc_backend=tr["engine"]["fc_backend"], isl_kw=program.isl_kw(cfg))
        rng = gen.rng_for(self.seed, 2)
        self.clouds = np.stack(gen.make_clouds(rng, [n] * (b * p)))
        self.keys = gen.jax_key_words(self.seed, 3, n=b * p)
        self.pool = [engine.Batch.make(
            jnp.asarray(self.clouds[i * b:(i + 1) * b]),
            key=jnp.asarray(self.keys[i * b:(i + 1) * b]))
            for i in range(p)]
        for batch in self.pool:            # compile, then run every batch
            jax.block_until_ready(self.engine.apply(self.params, batch))

    def window(self, seconds: float):
        import jax
        eng, params, pool = self.engine, self.params, self.pool
        depth = self.tr["in_flight"]
        outs, pending = [], collections.deque()
        t0 = time.perf_counter()
        step = 0
        while True:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = eng.apply(params, pool[step % len(pool)])
            outs.append(out)
            pending.append(out)
            step += 1
            if len(pending) >= depth:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(list(pending))
        self.window_s = time.perf_counter() - t0
        self.steps = step
        self.outs = outs

    def release(self):
        """Copy the logits to the host and drop the program's state."""
        self.logits = np.stack([np.asarray(o) for o in self.outs])
        del self.outs, self.pool, self.engine, self.params

    def check_inputs(self):
        """The clouds and keys whose answers are compared: the pool."""
        return list(self.clouds), self.keys

    def check(self, ref):
        """Every step's logits against ``ref``, the reference readings of
        ``check_inputs`` (``refcore.rel_gap``).  -> ({"logit_gap": worst relative gap},
        attempted, per-answer gaps)."""
        b, p = self.tr["batch"], len(self.clouds) // self.tr["batch"]
        ref = ref.reshape(p, b, *ref.shape[1:])
        got = self.logits                              # (steps, b, classes)
        gap = rc.rel_gap(got, ref[np.arange(self.steps) % p]).ravel()
        worst = float(gap.max()) if not np.isnan(gap).any() else float("nan")
        return {"logit_gap": worst}, int(gap.size), gap

    def diagnostics(self) -> dict:
        return {"steps": self.steps, "window_s": self.window_s}

    def end_to_end(self) -> dict:
        clouds = self.steps * self.tr["batch"]
        return {"clouds_per_s": clouds / self.window_s}

    def layer_context(self, summary: dict) -> dict:
        return {"config": self.cfg, "traffic": self.tr, "trace": summary,
                "clouds": self.steps * self.tr["batch"],
                "steps": self.steps, "window_s": self.window_s}
