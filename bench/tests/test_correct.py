"""``correct`` at a size a CPU holds: the harness's whole run (its look
for a chip skipped) comes out correct on the program as it is, and false
with the timed path broken underneath, once per fault the cells can
have; the control (the reference at float8 operands in the program's
place) fails the cell's limits too."""
import jax
import jax.numpy as jnp
import pytest

from bench import check
from bench.run import execute
from bench.tests import tiny

CELLS = list(tiny.CELLS)
SEED = 2 ** 33 + 17


def _run(name, seed=SEED, check_fn=None):
    w, cfg, tr = tiny.cell(name)
    out, lines = execute(w, cfg, tr, jax.devices(), seed=seed, seconds=1.5,
                         trace=False, check_fn=check_fn)
    return out, lines


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    out, lines = _run(name)
    assert out["correct"], "\n".join(lines)
    assert out["attempted"] > 0 and out["failed"] == 0


def _stale_price(monkeypatch):
    """The nearline update returns the price it was given."""
    import repro.serving.pipeline as pl

    monkeypatch.setattr(pl, "dual_descent",
                        lambda r, c, b, lam0, **k: (lam0, None))


def _half_window(monkeypatch):
    """Only the first half of the window is scored; the rest reuses it."""
    import repro.serving.pipeline as pl

    real = pl.reward_matrix_grouped

    def half(params, cfg, ctx, sh, plan):
        h = ctx.shape[0] // 2
        r = real(params, cfg, ctx[:h], sh, plan)
        return jnp.concatenate([r, r[:ctx.shape[0] - h]], axis=0)

    monkeypatch.setattr(pl, "reward_matrix_grouped", half)


def _altered_answer(monkeypatch):
    """Every request's clicks come back one too high."""
    import repro.serving.pipeline as pl

    real = pl._revenue_compact
    monkeypatch.setattr(pl, "_revenue_compact",
                        lambda *a, **k: real(*a, **k) + 1.0)


def _altered_decision(monkeypatch):
    """The reward model's chain axis comes back reversed."""
    import repro.serving.pipeline as pl

    real = pl.reward_matrix_grouped
    monkeypatch.setattr(pl, "reward_matrix_grouped",
                        lambda *a: real(*a)[:, ::-1])


@pytest.mark.parametrize("fault", [_stale_price, _half_window,
                                   _altered_answer, _altered_decision])
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_caught(monkeypatch, name, fault):
    fault(monkeypatch)
    out, lines = _run(name)
    assert not out["correct"], "\n".join(lines)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_limits(name):
    got = {}

    def both(run, seed):
        got["control"] = check.control(run, seed)
        return check.run(run, seed)

    _run(name, check_fn=both)
    ctl = dict(got["control"])
    faults = ctl.pop("faults")
    assert not check.verdict(ctl, check.limits(name)).correct, ctl
    # the planted update fault the price limit was set from reads over it
    assert faults["lam_err.decay_default"] > check.limits(name)["lam_err"]
