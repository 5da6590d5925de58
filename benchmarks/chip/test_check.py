"""The output check must fail a broken timed path.

Each test drives a whole run of a cell (``run.run``) at the test size of
the zamba2 layout on the CPU, skipping only the harness's look for a chip,
with one fault planted underneath in the program, and sees ``correct``
come out false; a sound run comes out true. The control (the float8
reference in the program's place) must read several times the program's
widest gap at this size too.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q benchmarks/chip
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

SEED = 2**33 + 17
SECONDS = 4.0


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout-shaped directory whose cells run the test-size model."""
    r = tmp_path_factory.mktemp("bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # the training path runs as a cell of its own here (its cell on the chip
    # is an open question: see PERF.md)
    spec["workloads"].append(dict(spec["workloads"][0],
                                  name="zamba2.chat_train",
                                  traffic="chat_train"))
    os.makedirs(r / "benchmarks" / "chip" / "traffic")
    os.makedirs(r / "benchmarks" / "chip" / "configs")
    for c in spec["configs"]:
        shutil.copy(os.path.join(HERE, "testdata", "zamba2-tiny.json"),
                    r / c["file"])
    for mix in ("chat", "chat_train"):
        shutil.copy(os.path.join(HERE, "testdata", mix + ".json"),
                    r / "benchmarks" / "chip" / "traffic" / (mix + ".json"))
    with open(r / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return str(r)


def _run(root, workload):
    import run
    return run.run(workload, SEED, SECONDS, False, root=root,
                   require_tpu=False)


def _failed(res):
    return [k for k, c in res["compared"].items() if not _ok(k, c)]


def _ok(k, c):
    import run
    return run._within(k, c)


def test_sound_runs_are_correct(root):
    for wl in ("zamba2.chat", "zamba2.chat_train"):
        res = _run(root, wl)
        assert res["correct"], res["compared"]


def test_state_left_unchanged(root, monkeypatch):
    """A decode step that returns the cache it was given."""
    from repro.models import decode as D
    real = D.decode_step

    def stale(params, cfg, cache, tokens, pos):
        logits, _ = real(params, cfg, cache, tokens, pos)
        return logits, cache
    monkeypatch.setattr(D, "decode_step", stale)
    res = _run(root, "zamba2.chat")
    assert not res["correct"] and "widest_logit_gap" in _failed(res)


def test_half_the_rows_left_out(root, monkeypatch):
    """A decode step that computes the first half of its rows and hands
    the second half the first half's logits."""
    from repro.models import decode as D
    real = D.decode_step

    def half(params, cfg, cache, tokens, pos):
        logits, new = real(params, cfg, cache, tokens, pos)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h]), new
    monkeypatch.setattr(D, "decode_step", half)
    res = _run(root, "zamba2.chat")
    assert not res["correct"] and "widest_logit_gap" in _failed(res)


def test_token_altered(root, monkeypatch):
    """Every served token after the first moved to its neighbour id."""
    from repro.serve import engine as E
    real = E.JaxModel.step

    def altered(self, state, rows):
        real(self, state, rows)
        for r in rows:
            if r is not None:
                r.tokens[-1] = (r.tokens[-1] + 1) % self.cfg.vocab
    monkeypatch.setattr(E.JaxModel, "step", altered)
    res = _run(root, "zamba2.chat")
    assert not res["correct"] and "widest_logit_gap" in _failed(res)


def test_training_state_unchanged(root, monkeypatch):
    """A training step that returns the weights it was given."""
    from repro.train import train_step as T
    real = T.make_train_step

    def make(cfg, opt_cfg, **kw):
        step = real(cfg, opt_cfg, **kw)

        def frozen(params, opt, batch):
            _, opt2, m = step(params, opt, batch)
            return params, opt2, m
        return frozen
    monkeypatch.setattr(T, "make_train_step", make)
    res = _run(root, "zamba2.chat_train")
    assert not res["correct"] and "train_update_norm_gap" in _failed(res)


def test_training_half_batch(root, monkeypatch):
    """A training step that drops half of its batch and takes the mean
    over the rest."""
    from repro.train import train_step as T
    real = T.make_train_step

    def make(cfg, opt_cfg, **kw):
        step = real(cfg, opt_cfg, **kw)

        def half(params, opt, batch):
            h = batch["tokens"].shape[0] // 2
            return step(params, opt, {k: v[:h] for k, v in batch.items()})
        return half
    monkeypatch.setattr(T, "make_train_step", make)
    res = _run(root, "zamba2.chat_train")
    assert not res["correct"]
    assert {"train_loss_gap", "train_grad_norm_gap"} & set(_failed(res))


def test_control_reads_far_above_the_program(root):
    """On the same sample the float8 control comes out not correct by the
    comparison ``run.py`` makes, and reads at least three times the
    program's widest gap."""
    import harness
    import run
    from control import verdict
    spec = run.load_spec(root)
    cfg = run.load_json(root, spec["configs"][0]["file"])
    with open(os.path.join(HERE, "testdata", "chat.json")) as f:
        mix = json.load(f)
    run.configure_jax(root)
    cell = harness.Cell(cfg, mix, SEED, SECONDS, False)
    cell.device_kind = "cpu"
    cell.build()
    cell.warm()
    cell.run_window()
    cell.free()
    prog = verdict(run.compare(cell, cfg, mix, SEED))
    ctrl = verdict(run.compare(cell, cfg, mix, SEED, control=True))
    assert prog["correct"] and not ctrl["correct"], (prog, ctrl)
    gap = "widest_logit_gap"
    assert ctrl["compared"][gap]["value"] >= \
        3 * prog["compared"][gap]["value"]
