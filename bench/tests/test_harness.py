"""What the harness promises whoever runs it."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import build
from bench.specs.geotenants import region_ci

ROOT = build.ROOT


def test_no_tpu_no_result():
    """Without a TPU the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "geotenants-replay-sat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_every_cell_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        assert os.path.exists(os.path.join(ROOT, configs[w["config"]]["file"]))
        traffic = build.load("traffic", w["traffic"])
        kind = build.load("configs", w["config"])["spec"]["kind"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "sources",
                                           f"{traffic['source']}.py"))
        assert os.path.isfile(os.path.join(ROOT, "bench", "specs",
                                           f"{kind}.py"))
        assert callable(build.module("sources", traffic["source"]).Source)
        assert callable(build.module("specs", kind).Spec)
        assert os.path.exists(os.path.join(ROOT, "bench", "limits",
                                           f"{w['name']}.json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))


def test_carbon_day_repeats_and_offsets_regions():
    spec = build.load("configs", "greenflow-geotenants")["spec"]
    ci = region_ci(spec)
    assert ci.shape == (spec["windows_per_day"], 2)
    assert abs(ci.mean() - spec["ci_mean"]) < 1e-9
    # region b peaks geo_offset_h hours after region a
    shift = int(spec["geo_offset_h"] * spec["windows_per_day"] / 24)
    assert np.allclose(np.roll(ci[:, 0], shift), ci[:, 1])


@pytest.mark.parametrize("kind,name", [("sources", "generated"),
                                       ("specs", "paper")])
def test_unknown_module_names_the_file_to_add(kind, name):
    with pytest.raises(SystemExit, match=f"add bench/{kind}/{name}.py"):
        build.module(kind, name)


@pytest.mark.parametrize("field", ["source", "spec"])
def test_a_cell_with_an_unknown_module_exits_nonzero(field):
    """Before the program builds anything, with a message that names the
    file to add; ``python bench/run.py`` then exits 1 with no result."""
    from bench.tests import tiny

    w, cfg, tr = tiny.cell(tiny.cells()[0])
    if field == "source":
        tr["source"], want = "generated", "bench/sources/generated.py"
    else:
        cfg["spec"]["kind"], want = "paper", "bench/specs/paper.py"
    with pytest.raises(SystemExit, match=want) as e:
        build.build(cfg, tr, seed=1, chips=1)
    assert e.value.code not in (0, None)
