"""greenflow-check: every rule fires on a known-bad fixture, stays
quiet on its known-good twin, pragmas parse (and demand justification),
the jaxpr-audit gates catch deliberately broken toy jits, and the
self-run over src/ stays clean."""
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import lint_paths, lint_source
from repro.analysis.jaxpr_audit import audit_jitted

SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "src")


def run(code, path, rules=None):
    return lint_source(textwrap.dedent(code), path, rules=rules)


def codes(findings, *, suppressed=False):
    return [f.rule for f in findings if f.suppressed == suppressed]


# ---------------------------------------------------------------------------
# GF001 ordered collectives
# ---------------------------------------------------------------------------


def test_gf001_flags_raw_psum_in_serving():
    bad = """
    from jax import lax
    def stitch(x, ax):
        return lax.psum(x, ax)
    """
    assert "GF001" in codes(run(bad, "src/repro/serving/guard.py"))
    # same code outside the serving/distributed scope is fine
    assert codes(run(bad, "src/repro/training/trainer.py")) == []


def test_gf001_good_twin_ordered_psum():
    good = """
    from repro.distributed.sharding import ordered_psum
    def stitch(x, ax):
        return ordered_psum(x, ax)
    """
    assert codes(run(good, "src/repro/serving/guard.py")) == []


# ---------------------------------------------------------------------------
# GF002 hidden host syncs
# ---------------------------------------------------------------------------


def test_gf002_flags_item_and_device_get():
    bad = """
    import jax
    def drain(arr):
        total = arr.sum().item()
        host = jax.device_get(arr)
        return total, host
    """
    assert codes(run(bad, "src/repro/serving/stream.py")) \
        == ["GF002", "GF002"]


def test_gf002_flags_host_numpy_inside_traced_scope():
    bad = """
    import jax
    import numpy as np
    @jax.jit
    def fn(x):
        return np.asarray(x) + 1
    """
    assert "GF002" in codes(run(bad, "src/repro/serving/pipeline.py"))


def test_gf002_detects_the_builder_idiom():
    # fn is traced via `fn = shard_map(fn, ...)` + `jax.jit(fn)`, the
    # pipeline's _build_main_fn shape -- not via a decorator
    bad = """
    import jax
    import numpy as np
    from jax.experimental.shard_map import shard_map
    def build(mesh):
        def fn(x):
            return float(x[0]) * np.float32(2.0)
        fn = shard_map(fn, mesh=mesh, in_specs=None, out_specs=None)
        return jax.jit(fn)
    """
    got = codes(run(bad, "src/repro/serving/pipeline.py"))
    assert got.count("GF002") == 2  # float(traced) + np call


def test_gf002_good_twin_host_prep_and_static_casts():
    good = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    def prep(chunk):  # host-side window prep: numpy is fine here
        return np.asarray(chunk, np.float32)
    @jax.jit
    def fn(x):
        n = int(x.shape[0])  # static metadata never syncs
        return jnp.sum(x) / n
    """
    assert codes(run(good, "src/repro/serving/pipeline.py")) == []


# ---------------------------------------------------------------------------
# GF003 mean reassociation
# ---------------------------------------------------------------------------


def test_gf003_flags_mean_in_dual_arithmetic():
    bad = """
    import jax.numpy as jnp
    def step(lam, costs, used, budget, eta):
        norm = jnp.mean(costs) ** 2
        return jnp.maximum(lam + eta * (used - budget) / norm, 0.0)
    """
    assert "GF003" in codes(run(bad, "src/repro/core/primal_dual.py"))
    # reward-model losses may average freely
    assert codes(run(bad, "src/repro/core/reward_model.py")) == []


def test_gf003_good_twin_structured_divisor():
    good = """
    import jax.numpy as jnp
    def step(lam, costs, used, budget, eta, n):
        norm = jnp.sum(costs) ** 2 / (n * n)
        return jnp.maximum(lam + eta * (used - budget) / norm, 0.0)
    """
    assert codes(run(good, "src/repro/core/primal_dual.py")) == []


# ---------------------------------------------------------------------------
# GF004 jit hygiene
# ---------------------------------------------------------------------------


def test_gf004_flags_dead_static_argnames():
    bad = """
    import jax
    from functools import partial
    @partial(jax.jit, static_argnames=("max_iter",))
    def descend(lam, max_iters):
        return lam * max_iters
    """
    got = run(bad, "src/repro/core/anything.py")
    assert "GF004" in codes(got)
    assert "max_iter" in [f.message for f in got][0]


def test_gf004_static_argnames_call_form_and_good_twin():
    bad = """
    import jax
    def descend(lam, max_iters):
        return lam * max_iters
    fast = jax.jit(descend, static_argnames=("iters",))
    """
    assert "GF004" in codes(run(bad, "src/repro/core/anything.py"))
    good = bad.replace('"iters"', '"max_iters"')
    assert codes(run(good, "src/repro/core/anything.py")) == []


def test_gf004_kwargs_waives_static_argnames():
    good = """
    import jax
    from functools import partial
    @partial(jax.jit, static_argnames=("whatever",))
    def fn(x, **kw):
        return x
    """
    assert codes(run(good, "src/repro/core/anything.py")) == []


def test_gf004_flags_read_after_donation():
    bad = """
    import jax
    def run(f, lam, x):
        g = jax.jit(f, donate_argnums=(0,))
        out = g(lam, x)
        return out + lam  # lam's buffer is gone
    """
    got = run(bad, "src/repro/serving/anything.py")
    assert "GF004" in codes(got)


def test_gf004_good_twin_rebinding_clears_donation():
    good = """
    import jax
    def run(f, lam, x):
        g = jax.jit(f, donate_argnums=(0,))
        lam = g(lam, x)  # the dual-chain idiom: rebind the buffer
        return lam * 2
    """
    assert codes(run(good, "src/repro/serving/anything.py")) == []


# ---------------------------------------------------------------------------
# GF005 nondeterminism
# ---------------------------------------------------------------------------


def test_gf005_flags_wall_clock_and_global_rng():
    bad = """
    import random
    import time
    import numpy as np
    def make_window(t):
        start = time.time()
        noise = np.random.normal(size=8)
        pick = random.randint(0, 7)
        rng = np.random.default_rng()
        return start, noise, pick, rng
    """
    got = codes(run(bad, "src/repro/data/request_source.py"))
    assert got.count("GF005") == 4


def test_gf005_good_twin_seeded_and_injected():
    good = """
    import time
    import numpy as np
    def make_window(seed, t, clock=None):
        clock = clock or time.perf_counter  # reference, not a call
        rng = np.random.default_rng((seed, t))
        return clock, rng.normal(size=8)
    """
    assert codes(run(good, "src/repro/data/request_source.py")) == []


# ---------------------------------------------------------------------------
# GF006 signed zero
# ---------------------------------------------------------------------------


def test_gf006_flags_plus_zero():
    bad = """
    import jax.numpy as jnp
    def canon(x):
        return x + 0.0
    """
    assert "GF006" in codes(run(bad, "src/repro/cascade/engine.py"))


def test_gf006_good_twin_where():
    good = """
    import jax.numpy as jnp
    def canon(x):
        return jnp.where(x == 0.0, jnp.float32(0.0), x)
    """
    assert codes(run(good, "src/repro/cascade/engine.py")) == []


# ---------------------------------------------------------------------------
# Pragmas
# ---------------------------------------------------------------------------

_PSUM = """
from jax import lax
def stitch(x, ax):
    return lax.psum(x, ax)  # gf: allow[GF001] {why}
"""


def test_pragma_suppresses_with_justification():
    got = run(_PSUM.format(why="loopback test helper, order is fixed"),
              "src/repro/serving/guard.py")
    assert codes(got) == []  # nothing unsuppressed
    assert codes(got, suppressed=True) == ["GF001"]
    assert "loopback" in got[0].justification


def test_pragma_without_justification_is_a_finding():
    got = run(_PSUM.format(why=""), "src/repro/serving/guard.py")
    # the original finding survives AND the empty pragma is flagged
    assert sorted(codes(got)) == ["GF000", "GF001"]


def test_stale_pragma_is_a_finding():
    src = """
    def clean():  # gf: allow[GF001] nothing here actually trips it
        return 1
    """
    assert codes(run(src, "src/repro/serving/guard.py")) == ["GF000"]


def test_standalone_pragma_covers_next_code_line():
    src = """
    from jax import lax
    def stitch(x, ax):
        # gf: allow[GF001] reference reduction for the parity test
        return lax.psum(x, ax)
    """
    got = run(src, "src/repro/serving/guard.py")
    assert codes(got) == [] and codes(got, suppressed=True) == ["GF001"]


# ---------------------------------------------------------------------------
# jaxpr audit gates (toy jits, deliberately broken)
# ---------------------------------------------------------------------------


def test_audit_clean_toy_passes_and_sees_donation():
    fn = jax.jit(lambda x, y: x * 2.0 + y, donate_argnums=(0,))
    x = jnp.ones((8,), jnp.float32)
    res = audit_jitted(fn, (x, x), expect_donation=True)
    assert res.ok and res.donated, res.problems


def test_audit_catches_f64_upcast():
    with jax.enable_x64(True):
        fn = jax.jit(lambda x: x.astype(jnp.float64) * 2.0)
        res = audit_jitted(fn, (jnp.ones((4,), jnp.float32),))
    assert not res.ok
    assert any("f64" in p for p in res.problems)


def test_audit_catches_host_callback():
    def fn(x):
        return jax.pure_callback(
            lambda a: np.asarray(a) + 1,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
    res = audit_jitted(jax.jit(fn), (jnp.ones((4,), jnp.float32),))
    assert not res.ok
    assert any("callback" in p for p in res.problems)


def test_audit_catches_dropped_donation():
    # a scalar output cannot alias the donated (8,) input: jax warns
    # and the aliasing annotation vanishes -- both must be flagged
    fn = jax.jit(lambda x: jnp.sum(x), donate_argnums=(0,))
    res = audit_jitted(fn, (jnp.ones((8,), jnp.float32),),
                       expect_donation=True)
    assert not res.ok
    assert any("donat" in p for p in res.problems)
    assert not res.donated


def test_audit_bounds_the_transfer_set():
    fn = jax.jit(lambda *xs: sum(xs))
    args = tuple(jnp.ones((2,)) for _ in range(9))
    res = audit_jitted(fn, args, max_invars=8)
    assert not res.ok
    assert any("transfer" in p for p in res.problems)


# ---------------------------------------------------------------------------
# The real pipeline + the self-run regression
# ---------------------------------------------------------------------------


def test_audit_plain_pipeline_is_clean():
    from repro.analysis.jaxpr_audit import (audit_pipeline,
                                            build_audit_stack)
    pipe, window, extra = build_audit_stack("plain")
    results = audit_pipeline(pipe, window, extra, mode="plain")
    assert results and all(r.ok for r in results), \
        [(r.name, r.problems) for r in results]
    assert any(r.donated for r in results)  # the dual chain donates


@pytest.mark.slow
def test_audit_geotenants_pipeline_is_clean():
    from repro.analysis.jaxpr_audit import (audit_pipeline,
                                            build_audit_stack)
    pipe, window, extra = build_audit_stack("geotenants")
    results = audit_pipeline(pipe, window, extra, mode="geotenants")
    assert results and all(r.ok for r in results), \
        [(r.name, r.problems) for r in results]


def test_self_run_on_src_is_clean():
    findings = lint_paths([SRC_DIR])
    bad = [f.format() for f in findings if not f.suppressed]
    assert not bad, "\n".join(bad)
    # and every suppression carries a written justification
    assert all(f.justification for f in findings if f.suppressed)


def test_cli_exit_codes(tmp_path):
    from repro.analysis.__main__ import main
    p = tmp_path / "repro" / "serving" / "guard.py"
    p.parent.mkdir(parents=True)
    p.write_text("from jax import lax\n"
                 "def s(x, ax):\n"
                 "    return lax.psum(x, ax)\n")
    out = tmp_path / "report.json"
    assert main([str(p), "--format", "json", "--out", str(out)]) == 1
    import json
    doc = json.loads(out.read_text())
    assert doc["summary"]["by_rule"] == {"GF001": 1}
    p.write_text("def s(x):\n    return x\n")
    assert main([str(p)]) == 0
