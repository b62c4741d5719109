"""A run whose timed path is broken underneath comes out ``correct:
false``: the whole of a rehearsal run but for the subprocess, with the
family handed to the runner altered so that the PROGRAM computes
something else while the plain reference stays what it was."""
import dataclasses
import json

import pytest

from benchmarks import common, run


def _result(capsys, monkeypatch, cell, breaker):
    real = common.load_family

    def broken(name, kind):
        fam = real(name, kind)
        breaker(fam)
        return fam

    monkeypatch.setattr(common, "load_family", broken)
    assert run.main(["--rehearse", "--workload", cell, "--seed",
                     str(2**31 + 13), "--seconds", "2",
                     "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_served_model_that_computes_something_else(capsys, monkeypatch):
    """The engine serves a model whose norms divide by sqrt(var + 1)
    instead of sqrt(var + 1e-5); the reference keeps the file's eps.
    Every greedy token then follows other logits."""
    def breaker(fam):
        good_config, good_logits = fam.program_config, fam.reference_logits
        fam.program_config = lambda cfg: dataclasses.replace(
            good_config(cfg), norm_eps=1.0)
        fam.reference_logits = lambda rw, ids, pcfg: good_logits(
            rw, ids, dataclasses.replace(pcfg, norm_eps=1e-5))

    line = _result(capsys, monkeypatch, "toy-llama.chat-sat", breaker)
    assert line["correct"] is False and line["failed"] == 0


def test_a_train_step_that_learns_nothing(capsys, monkeypatch):
    """The step's loss reads right and its gradient is cut: the state
    comes back all but unchanged. The gradient norm is 0 against the
    reference's, and the loss does not fall."""
    def breaker(fam):
        import jax
        good = fam.loss_fn
        fam.loss_fn = lambda model: (
            lambda params, b: good(model)(
                jax.lax.stop_gradient(params), b))

    line = _result(capsys, monkeypatch, "toy-gpt2.train", breaker)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["toy-llama.chat-sat"])
def test_the_same_drive_unbroken_is_correct(capsys, monkeypatch, cell):
    line = _result(capsys, monkeypatch, cell, lambda fam: None)
    assert line["correct"] is True and line["failed"] == 0
