"""What decides ``correct``: the control fails each cell's limit, and a
run whose timed path is broken underneath comes out not correct.

On the CPU at the rehearsal's tiny size (``run --rehearse``), which
skips the look for a chip and drives the rest of a run."""
import contextlib
import io
import json

import numpy as np
import pytest

from bench import families, gen, run, weights
from bench import refcore as rc

CELLS = ["pn2c-lpcn-b16", "pn2c-lpcn-serve", "pn2c-trad-b16"]


def tiny(name):
    cell = run.load_cell(name)
    run.shrink(cell)
    return cell


@pytest.mark.parametrize("name", CELLS)
def test_the_control_fails_the_limit(name):
    """The reference computed as three bf16 passes (``high``), put in the
    program's place, reads above the cell's logit_gap limit."""
    cell = tiny(name)
    cfg, mode = cell["config"], cell["traffic"]["engine"]["mode"]
    fam = families.of(cfg)
    w = weights.make(cfg, gen.jax_key_words(7, 1)[0])
    clouds = gen.make_clouds(gen.rng_for(7, 2), [cfg["points"]] * 4)
    keys = gen.jax_key_words(7, 3, n=4)
    ref = fam.reference(cfg, w, clouds, keys, mode)
    ctl = fam.reference(cfg, w, clouds, keys, mode, precision="high")
    gap = rc.rel_gap(ctl[:, 0], ref).max()
    assert gap > cell["limits"]["logit_gap"]["limit"]


def _run(name, seed=11):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run.main(["--workload", name, "--seed", str(seed), "--seconds", "1",
                  "--trace", "0", "--rehearse"])
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def cpu():
    import jax
    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal runs on the CPU")


def _alter_first_answer(out):
    """One logit of the batch's first answer moved by a tenth of the
    largest |logit|, where the answer is produced (on the device)."""
    return out.at[0, 0].add(0.1 * abs(out[0]).max())


def test_sound_rehearsal_is_correct(cpu):
    line = _run("pn2c-lpcn-b16")
    assert line["correct"] and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"


def test_altered_answer_in_a_batch_is_not_correct(cpu, monkeypatch):
    from repro import engine
    real = engine.PCNEngine.apply
    monkeypatch.setattr(engine.PCNEngine, "apply",
                        lambda self, p, b: _alter_first_answer(real(self, p,
                                                                    b)))
    line = _run("pn2c-lpcn-b16")
    assert not line["correct"] and line["failed"] > 0


def test_altered_answer_in_serving_is_not_correct(cpu, monkeypatch):
    from repro import engine
    real = engine.PCNEngine.bucket_callable

    def broken(self, params, batch_size, n_points):
        fn = real(self, params, batch_size, n_points)
        return lambda batch: _alter_first_answer(fn(batch))

    monkeypatch.setattr(engine.PCNEngine, "bucket_callable", broken)
    line = _run("pn2c-lpcn-serve")
    assert not line["correct"]


def test_unanswered_request_is_not_correct(cpu, monkeypatch):
    from bench.drivers import open_loop
    from repro import engine
    real = engine.PCNEngine.bucket_callable
    real_window = open_loop.Driver.window
    state = {"window": False, "poisoned": False}

    def window(self, seconds):
        state["window"] = True
        return real_window(self, seconds)

    def poisoned(self, params, batch_size, n_points):
        fn = real(self, params, batch_size, n_points)

        def call(batch):
            out = fn(batch)
            if state["window"] and not state["poisoned"]:
                state["poisoned"] = True     # the window's first batch
                out = out.at[0, 0].set(np.nan)
            return out
        return call

    monkeypatch.setattr(open_loop.Driver, "window", window)
    monkeypatch.setattr(engine.PCNEngine, "bucket_callable", poisoned)
    line = _run("pn2c-lpcn-serve")
    assert state["poisoned"] and not line["correct"]
    assert line["checks"]["unanswered"]["value"] > 0
