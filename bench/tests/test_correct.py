"""``correct`` at a size a CPU holds: the harness's whole run (its look
for a chip skipped) comes out correct on the program as it is, and false
with the timed path broken underneath, once per fault the cells can
have; the control (the reference at float8 operands in the program's
place) fails the cell's limits too."""
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest

from bench import build, check
from bench.run import execute
from bench.tests import tiny

CELLS = tiny.cells()
SEED = 2 ** 33 + 17


def _run(name, seed=SEED, check_fn=None, cell=None, where=None):
    w, cfg, tr = cell or tiny.cell(name)
    out, lines = execute(w, cfg, tr, jax.devices(), seed=seed, seconds=1.5,
                         trace=False, check_fn=check_fn, where=where)
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


# faults that every cell can have, and those of one request source's
# path (a new source brings its own in a test file of its own)
FAULTS = (_stale_price, _altered_decision)
SOURCE_FAULTS = {"replay": (_half_window, _altered_answer)}


def _fault_cases():
    for name in CELLS:
        source = build.load("traffic", build.workload(name)["traffic"])
        for fault in FAULTS + SOURCE_FAULTS.get(source["source"], ()):
            yield pytest.param(name, fault,
                               id=f"{name}-{fault.__name__}")


@pytest.mark.parametrize("name,fault", _fault_cases())
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


def test_a_cell_from_new_module_files_alone(tmp_path):
    """A cell whose request source and budget spec are modules that exist
    only under another directory - thin wrappers over ``replay`` and
    ``geotenants`` - is built, served and checked, and comes out
    correct, with no file of ``bench/`` added or changed."""
    (tmp_path / "sources").mkdir()
    (tmp_path / "specs").mkdir()
    (tmp_path / "sources" / "wrapped_replay.py").write_text(textwrap.dedent(
        """
        from bench.sources import replay

        cpu_cut = replay.cpu_cut


        class Source(replay.Source):
            pass
        """))
    (tmp_path / "specs" / "wrapped_geotenants.py").write_text(
        textwrap.dedent(
            """
            from bench.specs import geotenants

            cpu_cut = geotenants.cpu_cut


            class Spec(geotenants.Spec):
                pass


            class Reference(geotenants.Reference):
                pass
            """))
    w, cfg, tr = tiny.cell("geotenants-replay-sat")
    tr["source"] = "wrapped_replay"
    cfg["spec"]["kind"] = "wrapped_geotenants"
    out, lines = _run(w["name"], cell=(w, cfg, tr), where=str(tmp_path))
    assert out["correct"], "\n".join(lines)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not any(f.startswith("wrapped")
                   for _, _, files in os.walk(build.BENCH) for f in files)
