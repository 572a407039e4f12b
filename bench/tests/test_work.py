"""bench/work.py's counts against the models' own FLOPs functions."""
import pytest

from bench import work
from bench.tests import tiny


@pytest.fixture(scope="module")
def cfg():
    return tiny.config("greenflow-geotenants")


def _model(cls, d):
    return cls(**{k: tuple(v) if isinstance(v, list) else v
                  for k, v in d.items()})


def test_din_item_is_the_models_own_count(cfg):
    from repro.models.recsys import din

    assert work.din_item(cfg["din"]) == din.flops_per_item(
        _model(din.DINConfig, cfg["din"]))


def test_dien_bills_the_interest_gru_once_per_user(cfg):
    from repro.models.recsys import dien

    # the model's count bills the per-user GRU to every item
    assert (work.dien_item(cfg["dien"]) + work.dien_user(cfg["dien"])
            == dien.flops_per_item(_model(dien.DIENConfig, cfg["dien"])))


@pytest.mark.parametrize("n_items", [1, 60, 200])
def test_ydnn_request_is_the_models_own_count(cfg, n_items):
    from repro.models.recsys import ydnn

    assert work.ydnn_request(cfg["ydnn"], n_items) == ydnn.flops_per_request(
        _model(ydnn.YDNNConfig, cfg["ydnn"]), n_items)


def test_dssm_request_is_tower_plus_one_dot_per_item(cfg):
    from repro.core.flops import dense_flops, mlp_flops

    c = cfg["dssm"]
    tower = mlp_flops([c["n_user_fields"] * c["embed_dim"], *c["hidden"],
                       c["d_out"]])
    assert work.dssm_request(c, 200) == tower + 200 * dense_flops(
        c["d_out"], 1, use_bias=False)


def test_chain_work_grows_with_the_chain(cfg):
    from bench.reference import chains

    ch = chains.chains(cfg)
    per_chain = work.chain_request(cfg)
    assert per_chain.shape == (ch.n,)
    # a chain that keeps more rank items needs more work, model for model
    for m in (0, 1):
        sel = (ch.model == m) & (ch.n2 == ch.n2.max())
        order = ch.n3[sel].argsort()
        assert (per_chain[sel][order][1:] > per_chain[sel][order][:-1]).all()


def test_replay_requests_need_the_reward_model_only(cfg):
    from types import SimpleNamespace

    from bench import build
    from bench.measure import Run
    from bench.sources import replay

    tr = tiny.traffic("replay-backlog-4096")
    replay.cpu_cut(cfg, tr)
    source = replay.Source(cfg, tr, build.chain_set(cfg), seed=3)
    run = Run(cell={}, cfg=cfg, traffic=tr,
              stack=SimpleNamespace(source=source), windows=[], t0=0.0,
              t_end=1.0, seconds=1.0, setup_s=0.0, chips=1, kind="")
    assert run.required_flops([]) == 0.0
    assert work.reward_request(cfg) > 0
    windows = [SimpleNamespace(n=4096), SimpleNamespace(n=64)]
    assert run.required_flops(windows) == 4160 * work.reward_request(cfg)
