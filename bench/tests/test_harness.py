"""What the harness promises whoever runs it."""
import json
import os
import subprocess
import sys

import numpy as np

from bench import build

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
        build.load("traffic", w["traffic"])
        assert os.path.exists(os.path.join(ROOT, "bench", "limits",
                                           f"{w['name']}.json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           f"{m['name']}.py"))


def test_carbon_day_repeats_and_offsets_regions():
    spec = build.load("configs", "greenflow-geotenants")["spec"]
    ci = build.region_ci(spec)
    assert ci.shape == (spec["windows_per_day"], 2)
    assert abs(ci.mean() - spec["ci_mean"]) < 1e-9
    # region b peaks geo_offset_h hours after region a
    shift = int(spec["geo_offset_h"] * spec["windows_per_day"] / 24)
    assert np.allclose(np.roll(ci[:, 0], shift), ci[:, 1])
