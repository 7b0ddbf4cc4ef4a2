"""The traffic generator: seeded, on its grid, inside its clips."""
import os

import pytest

from bench.harness import spec

BENCH = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
CELLS = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]


def _mix(traffic, seed, vocab=1000):
    t = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic",
                                    traffic + ".json"))
    return t, spec.traffic_generator(t).make(t, vocab, seed)


@pytest.mark.parametrize("config,traffic", CELLS)
def test_same_seed_same_requests(config, traffic):
    _, a = _mix(traffic, 2 ** 31 + 3)
    _, b = _mix(traffic, 2 ** 31 + 3)
    for k in range(3):
        assert a.call(k) == b.call(k)


@pytest.mark.parametrize("config,traffic", CELLS)
def test_seeds_change_the_tokens_and_not_the_work(config, traffic):
    """Another seed gives other token ids and exactly the same lengths in
    the same order; later calls serve the same lengths in other orders."""
    _, a = _mix(traffic, 1)
    _, b = _mix(traffic, 2)
    pa, ma = a.call(0)
    pb, mb = b.call(0)
    assert pa != pb
    assert list(zip(map(len, pa), ma)) == list(zip(map(len, pb), mb))
    pc, mc = a.call(1)
    assert list(zip(map(len, pa), ma)) != list(zip(map(len, pc), mc))
    assert sorted(zip(map(len, pa), ma)) == sorted(zip(map(len, pc), mc))


@pytest.mark.parametrize("config,traffic", CELLS)
def test_lengths_on_grid_inside_clips_and_max_seq(config, traffic):
    t, mix = _mix(traffic, 9)
    cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                      config + ".json"))
    grid = t["prompt"]["grid"]
    prompts, max_new = mix.call(0)
    assert len(prompts) == t["batch"]
    for p, m in zip(prompts, max_new):
        assert len(p) % grid == 0
        assert t["prompt"]["min"] <= len(p) <= t["prompt"]["max"]
        assert t["output"]["min"] <= m <= t["output"]["max"]
        assert len(p) + m <= cfg["serve"]["max_seq"]
        assert min(p) >= 1 and max(p) < 1000


def test_lengths_follow_the_lognormal():
    """Stratified quantiles: the median request sits at the median."""
    t, mix = _mix("chat", 0)
    ps = sorted(p for p, _ in mix.shapes)
    os_ = sorted(o for _, o in mix.shapes)
    n = len(ps)
    assert ps[n // 2 - 1] <= t["prompt"]["median"] + 64 <= ps[n // 2] + 64
    assert abs(os_[n // 2] - t["output"]["median"]) <= 0.1 * t["output"][
        "median"]
