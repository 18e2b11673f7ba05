"""Percentiles, TPOT, window accounting and the traffic generators."""

import json
from pathlib import Path

import pytest

import metrics
from traffic_kinds import open_poisson, topic_drain
from traffic_kinds.common import output_caps, prompt_lengths

DATA = Path(__file__).parent / "data"
REAL = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize(
    "values, p, want",
    [
        ([5, 1, 3, 2, 4], 0.5, 3),
        (list(range(1, 101)), 0.95, 95),
        (list(range(1, 101)), 0.50, 50),
        ([7], 0.95, 7),
        ([1, 2], 0.95, 2),
    ],
)
def test_percentile_is_nearest_rank(values, p, want):
    assert metrics.percentile(values, p) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        metrics.percentile([], 0.5)


def test_ttft_counts_from_due_and_tpot_is_the_per_request_mean():
    r = {"due": 10.0, "t_first": 10.25, "t_last": 11.25, "tokens": 41, "done": True}
    assert metrics.ttft_ms(r) == pytest.approx(250.0)
    assert metrics.tpot_ms(r) == pytest.approx(25.0)  # 1 s over 40 gaps
    assert metrics.tpot_ms({**r, "tokens": 1}) is None
    assert metrics.tpot_ms({**r, "done": False}) is None
    assert metrics.ttft_ms({"due": 1.0, "t_first": None}) is None


def test_tokens_count_only_inside_the_window():
    chunks = [(9.9, 5), (10.0, 1), (12.0, 2), (14.999, 4), (15.0, 8)]
    assert metrics.tokens_in_window(chunks, 10.0, 15.0) == 7
    out = metrics.end_to_end([], chunks, 10.0, 5.0)
    assert out == {"gen_tokens_per_s": pytest.approx(1.4)}


@pytest.mark.parametrize("path", sorted(REAL.glob("*.json")) + sorted((DATA / "traffic").glob("*.json")))
def test_traffic_same_seed_same_requests_other_seed_other_ids_only(path):
    params = json.loads(path.read_text())
    kind = {"open_poisson": open_poisson, "topic_drain": topic_drain}[params["kind"]]
    big = 2**31 + 11  # the driver's seeds pass 32 signed bits
    a = kind.schedule(params, big, 40.0, 32768)
    b = kind.schedule(params, big, 40.0, 32768)
    c = kind.schedule(params, 3, 40.0, 32768)
    assert a == b
    assert [r["prompt_ids"] for r in a] != [r["prompt_ids"] for r in c]
    # every seed offers the same sizes, caps and arrivals in the same order
    for key in (lambda r: len(r["prompt_ids"]), lambda r: r["cap"], lambda r: r["due_s"]):
        assert list(map(key, a)) == list(map(key, c))
    assert len({r["id"] for r in a}) == len(a)
    assert all(0 <= t < 40.0 for r in a for t in [r["due_s"]])
    assert all(0 <= i < 32767 for r in a for i in r["prompt_ids"])


def test_lengths_and_caps_follow_the_file():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32, "max": 1000}
    lengths = prompt_lengths(spec, 1000)
    assert lengths.min() >= 32 and lengths.max() <= 1000
    assert 240 <= sorted(lengths)[500] <= 272
    caps = output_caps({"32": 0.3, "96": 0.5, "256": 0.2}, 10)
    assert sorted(caps.tolist()) == [32] * 3 + [96] * 5 + [256] * 2


def test_a_split_metric_shares_its_quantity_file_and_a_tail_is_read_from_the_client():
    import run
    from readers import client

    assert run.metric_definition("decode_step_ms.chat") == run.metric_definition("decode_step_ms.drain")
    assert run.metric_definition("ttft_p95_ms")["reader"] == "client"
    with pytest.raises(FileNotFoundError):
        run.metric_definition("no_such_metric.chat")
    requests = [{"due": 0.0, "t_first": 0.001 * i} for i in range(1, 21)] + [{"due": 0.0, "t_first": None}]
    definition = {"quantity": "ttft_ms", "percentile": 0.95}
    assert client.read(definition, {"requests": requests}) == pytest.approx(19.0)
    assert client.read(definition, {"requests": []}) is None


def test_a_configuration_key_that_maps_onto_nothing_is_an_error():
    from modelcfg import model_config

    with pytest.raises(ValueError, match="num_hidden_layer"):
        model_config({"family": "mistral", "hidden_size": 64, "num_hidden_layer": 2}, "typo")
