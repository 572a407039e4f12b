"""Environment provenance stamp for benchmark artifacts.

``env_info()`` answers "what machine, what stack, what commit produced
these numbers" - every ``BENCH_*.json`` written by ``benchmarks/run.py``
embeds it as an ``env`` block so the perf trajectory across PRs is
interpretable (a 2x "regression" that coincides with a jaxlib bump or a
2-core CI runner is not a regression).
"""
from __future__ import annotations

import os
import subprocess
from datetime import datetime, timezone


def _git_sha() -> str | None:
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=here,
            capture_output=True, text=True, timeout=5)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except Exception:
        return None


def env_info() -> dict:
    """Versions, backend, device kind, cores, git SHA, UTC timestamp."""
    info: dict = {
        "cpu_count": os.cpu_count(),
        "git_sha": _git_sha(),
        "timestamp_utc": datetime.now(timezone.utc).isoformat(
            timespec="seconds"),
    }
    # a backend that fails to come up raises here: a benchmark must not
    # go on to record numbers from some other device
    import jax
    import jaxlib
    info["jax"] = jax.__version__
    info["jaxlib"] = jaxlib.__version__
    info["backend"] = jax.default_backend()
    devs = jax.devices()
    info["device_kind"] = devs[0].device_kind
    info["n_devices"] = len(devs)
    return info
