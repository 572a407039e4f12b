"""A cell's configuration and traffic cut to a size a CPU test holds:
the same files with a small corpus, chain space and windows, then the
cut of the cell's own request source and budget spec modules."""
import json
import os

from bench import build


def config(name: str) -> dict:
    c = build.load("configs", name)
    c["world"].update(n_items=200, hist_len=12, n_users=1_000_000)
    c["chains"].update(n2=[40, 50, 60, 70, 80, 90, 100, 110],
                       n3=[10, 12, 14, 16, 18, 20, 22, 24], expose=5)
    for k in ("din", "dien"):
        c[k].update(item_vocab=200, embed_dim=8, seq_len=12,
                    attn_hidden=[16, 8], mlp_hidden=[32, 16])
    c["dssm"].update(item_vocab=200, embed_dim=8, hidden=[16, 8], d_out=8)
    c["ydnn"].update(item_vocab=200, embed_dim=8, hidden=[16, 8], d_out=8,
                     hist_len=12)
    return c


def traffic(name: str) -> dict:
    t = build.load("traffic", name)
    t.update(window=64)
    return t


def cells() -> list[str]:
    """The names of ``BENCHMARK.json``'s cells."""
    with open(os.path.join(build.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def cell(name: str) -> tuple[dict, dict, dict]:
    """(workload entry, tiny config, tiny traffic) of a cell."""
    w = build.workload(name)
    cfg, tr = config(w["config"]), traffic(w["traffic"])
    build.module("sources", tr["source"]).cpu_cut(cfg, tr)
    build.module("specs", cfg["spec"]["kind"]).cpu_cut(cfg)
    return dict(w, chips=1), cfg, tr
