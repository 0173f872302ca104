"""The traffic generator: deterministic per seed, the same work for every
seed, and a warm-up trace that reaches every executable shape."""
import collections

import numpy as np
import pytest

from bench import spec, traffic

BIG_SEED = 2**31 + 977


MIXES = ["reason_long_kvhalf", "open_loop"]


def _mix(name):
    """A mix file, or the smoke cell's open-loop mix at full lengths."""
    if name == "open_loop":
        mix = spec.load_json(f"{spec.BENCH_DIR}/traffic/"
                             f"reason_long_kvhalf.json")
        mix["arrivals"] = {"process": "poisson", "rate_per_s": 0.6,
                           "lead_in_s": 20.0, "drain_cap_s": 120.0}
        mix["prompt_tokens"] = {"dist": "lognormal", "median": 512,
                                "sigma": 1.0, "min": 32, "max": 2048}
        mix["output_tokens"] = {"dist": "lognormal", "median": 128,
                                "sigma": 0.8, "min": 8, "max": 512}
        return mix
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/{name}.json")


@pytest.mark.parametrize("mix_name", MIXES)
def test_same_seed_same_requests(mix_name):
    mix = _mix(mix_name)
    a = traffic.generate(mix, BIG_SEED, 51, 151552)
    b = traffic.generate(mix, BIG_SEED, 51, 151552)
    assert [r.rid for r in a] == [r.rid for r in b]
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix_name", MIXES)
def test_seeds_share_the_work(mix_name):
    """Two seeds: the same multiset of prompt and output lengths (and of
    arrival gaps), in another order, with other token ids (the requests
    behind the steady-state set, block by block)."""
    mix = _mix(mix_name)
    live = mix["arrivals"].get("in_flight", 0)
    a = traffic.generate(mix, 11, 51, 151552)[live:]
    b = traffic.generate(mix, BIG_SEED, 51, 151552)[live:]
    n = min(len(a), len(b))
    n -= n % mix["block"]
    assert n > 0
    for field in (lambda r: r.prompt.shape[1], lambda r: r.max_new):
        assert (collections.Counter(field(r) for r in a[:n])
                == collections.Counter(field(r) for r in b[:n]))
    assert [r.max_new for r in a[:n]] != [r.max_new for r in b[:n]]
    assert not np.array_equal(a[0].prompt[:, :8], b[0].prompt[:, :8])


def test_lengths_follow_the_mix():
    mix = _mix("open_loop")
    reqs = traffic.generate(mix, 5, 51, 1000)
    p = np.array([r.prompt.shape[1] for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= 32 and p.max() <= 2048
    assert o.min() >= 8 and o.max() <= 512
    assert abs(np.median(p) - 512) < 80 and abs(np.median(o) - 128) < 20
    gaps = np.diff([r.arrival_s for r in reqs])
    rate = mix["arrivals"]["rate_per_s"]
    assert abs(1.0 / gaps.mean() - rate) < 0.15 * rate
    assert all(r.prompt.shape[1] + r.max_new <= mix["engine"]["max_len"]
               for r in reqs)


def test_backlog_arrives_at_once():
    mix = _mix("reason_long_kvhalf")
    reqs = traffic.generate(mix, 3, 51, 1000)
    live = mix["arrivals"]["in_flight"]
    assert len(reqs) == mix["arrivals"]["requests"]
    assert {r.arrival_s for r in reqs} == {0.0}
    assert all(1024 <= r.max_new <= 3584 for r in reqs[live:])
    assert all(r.prompt.shape[1] + r.max_new <= mix["engine"]["max_len"]
               for r in reqs)


def test_in_flight_requests_stand_for_the_steady_state():
    """The first ``in_flight`` requests are partway through their outputs:
    contexts spread from prompt to prompt + output, the outputs are
    length-biased, and every seed gets the same contexts and commitments."""
    mix = _mix("reason_long_kvhalf")
    live = mix["arrivals"]["in_flight"]
    assert traffic.in_flight_rids(mix) == [f"r{i:05d}" for i in range(live)]
    a = traffic.generate(mix, 3, 51, 1000)[:live]
    b = traffic.generate(mix, BIG_SEED, 51, 1000)[:live]
    ctx = sorted(r.prompt.shape[1] for r in a)
    assert sum(ctx) == sum(r.prompt.shape[1] for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    commit = [r.prompt.shape[1] + r.max_new for r in a]
    assert sum(commit) == sum(r.prompt.shape[1] + r.max_new for r in b)
    assert min(r.max_new for r in a) < 200 and max(ctx) > 3000
    # uniform progress over length-biased outputs: mean context about
    # prompt + E[O^2] / (2 E[O]) = ~340 + 1270
    assert 1400 < np.mean(ctx) < 1800
    # the uniform 1024..3584 output length-biased: mean E[O^2] / E[O]
    outs = traffic.length_biased(mix["output_tokens"], 4096)
    assert outs.mean() == pytest.approx(2541, rel=0.01)
    # the pool (7659 pages of 16 tokens at the cell's budget) admits all
    pages = sum(-(-(r.prompt.shape[1] + r.max_new) // 16) for r in a)
    assert pages <= 7659


def test_warmup_reaches_every_shape():
    engine = {"slots": 64, "max_prefill_tokens": 512}
    reqs = traffic.warmup_requests(engine, (1, 2, 4, 8), 8, 1000)
    assert reqs[0].prompt.shape[1] == 1023      # chunks 512, 256, ..., 1
    waves = collections.Counter(r.arrival_s for r in reqs[1:])
    sizes = collections.Counter(waves.values())
    assert sizes == {1: 4, 2: 4, 4: 4, 8: 4, 64: 4}
    needs = {r.max_new - 1 for r in reqs[1:]}
    assert needs == {1, 2, 4, 8}
