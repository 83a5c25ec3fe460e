"""A run with the timed path broken underneath must come out not
correct: the harness's run (set-up, window, output check) at a tiny size
on the CPU, past its look for a chip, with one fault planted in the
program for each fault the cell can have.  The limits are the cells' own
(``bench/limits``)."""
import numpy as np
import pytest

from bench.rehearse import tiny_cell
from bench.run import judge


def run_cell(name):
    cell, driver, limits = tiny_cell(name)
    out = driver.run(cell)
    nums, _ = driver.check(cell, out)
    return nums, limits


def test_sound_train_run_is_correct():
    nums, limits = run_cell("phi3_train_hybrid")
    assert set(nums) >= {"loss_gap.phase0", "grad_gap.phase0",
                         "loss_gap.phase1", "grad_gap.phase1"}, nums
    assert judge(nums, limits)[1], nums


def test_state_unchanged_is_caught(monkeypatch):
    from repro.kernels import dbl_merge

    def unchanged(p2, g2, **kw):
        if "master2" in kw:
            return p2, kw["master2"]
        return (p2, kw["vel2"]) if kw.get("vel2") is not None else p2
    monkeypatch.setattr(dbl_merge, "dbl_apply_flat2d", unchanged)
    nums, limits = run_cell("phi3_train_hybrid")
    for phase in (0, 1):
        assert nums[f"grad_gap.phase{phase}"] == pytest.approx(1.0)
    assert not judge(nums, limits)[1]


def test_half_batch_is_caught(monkeypatch):
    from repro import models
    loss_fn = models.loss_fn

    def half(params, cfg, batch, **kw):
        n = batch["tokens"].shape[0] // 2
        return loss_fn(params, cfg, {k: v[:n] for k, v in batch.items()},
                       **kw)
    monkeypatch.setattr(models, "loss_fn", half)
    nums, limits = run_cell("phi3_train_hybrid")
    assert not judge(nums, limits)[1], nums


def test_phase_boundary_that_drops_the_update_is_caught(monkeypatch):
    """The second phase starts from the first phase's input instead of
    its output: only the second phase's numbers can see it."""
    from repro.engine import TrainEngine
    run = TrainEngine.run
    start: dict = {}

    def dropping(self, phases, params, *a, **k):
        if "params" not in start:
            start["params"] = params
        else:
            params = start["params"]
        return run(self, phases, params, *a, **k)
    monkeypatch.setattr(TrainEngine, "run", dropping)
    nums, limits = run_cell("phi3_train_hybrid")
    assert not judge(nums, limits)[1], nums


@pytest.mark.parametrize("cell", ["phi3_serve_contig"])
def test_sound_serve_run_is_correct(cell):
    nums, limits = run_cell(cell)
    assert judge(nums, limits)[1], nums


@pytest.mark.parametrize("cell", ["phi3_serve_contig"])
def test_altered_token_is_caught(cell, monkeypatch):
    from repro.serve.engine import ServeEngine
    call = ServeEngine._call

    def altered(self, *a, **k):
        toks, logits = call(self, *a, **k)
        toks = (np.array(toks) + 1) % self.cfg.vocab_size
        return toks, logits
    monkeypatch.setattr(ServeEngine, "_call", altered)
    nums, limits = run_cell(cell)
    assert not judge(nums, limits)[1], nums
