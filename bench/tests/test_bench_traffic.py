"""The traffic generator: one seed gives one schedule, another seed the
same sizes and gaps in another order, sessions grow by appending to
their last prompt, and a new mix is a new JSON file found by name."""
import collections
import json

import pytest

from bench.traffic import generator as g


def _draw(mix, seed, n=96):
    gen = g.Generator(mix, seed, 92544)
    return [gen.next() for _ in range(n)]


@pytest.mark.parametrize("name", ["batch", "prefix", "chat"])
def test_same_seed_same_traffic(name):
    mix = g.load_mix(name)
    a, b = _draw(mix, 2**31 + 17), _draw(mix, 2**31 + 17)
    assert [(r.prompt, r.max_new) for r in a] == \
        [(r.prompt, r.max_new) for r in b]
    if mix["loop"] == "open":
        assert g.arrival_times(mix, 5, 30.0) == g.arrival_times(mix, 5, 30.0)


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_other_seed_same_sizes_other_order(name):
    mix = g.load_mix(name)
    n = 2 * g.BLOCK
    a, b = _draw(mix, 1, n), _draw(mix, 2, n)
    sizes = lambda rs: collections.Counter(  # noqa: E731
        (len(r.prompt), r.max_new) for r in rs)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert collections.Counter(len(r.prompt) for r in a) == \
        collections.Counter(len(r.prompt) for r in b)
    assert collections.Counter(r.max_new for r in a) == \
        collections.Counter(r.max_new for r in b)
    assert a[0].prompt != b[0].prompt
    del sizes


def test_arrivals_same_gaps_other_order():
    mix = g.load_mix("prefix")
    per_block = g.BLOCK / mix["arrival"]["rate"]
    horizon = 3 * per_block + 1e-6
    t1, t2 = g.arrival_times(mix, 1, horizon), g.arrival_times(mix, 2, horizon)
    assert t1 != t2
    # every block of gaps lasts exactly BLOCK / rate: whole blocks hold
    # the same number of arrivals whatever the seed
    assert t1[g.BLOCK - 1] == pytest.approx(per_block)
    assert t2[2 * g.BLOCK - 1] == pytest.approx(2 * per_block)
    assert len(t1) == len(t2) == 3 * g.BLOCK


def test_sessions_extend_their_last_prompt():
    mix = g.load_mix("prefix")
    head = mix["system_prompt"]
    reqs = _draw(mix, 3, 200)
    cap = mix["sessions"]["max_prompt"]
    assert all(head < len(r.prompt) <= cap for r in reqs)
    system = reqs[0].prompt[:head]
    assert all(r.prompt[:head] == system for r in reqs)
    grown = _continued(reqs)
    # a share of the arrivals continues a session, less the few that
    # find none live at the start
    share = mix["sessions"]["share"]
    assert share * len(reqs) - g.BLOCK // 4 <= grown <= share * len(reqs)


def _continued(reqs):
    seen = [r.prompt for r in reqs]
    return sum(1 for i, p in enumerate(seen)
               if any(len(q) < len(p) and p[:len(q)] == q
                      for q in seen[:i]))


def test_every_seed_does_the_same_session_work():
    mix = g.load_mix("prefix")
    runs = [_draw(mix, seed, 8 * g.BLOCK) for seed in (1, 2, 2**31 + 5)]
    conts = [_continued(r) for r in runs]
    lens = [sum(len(x.prompt) for x in r) for r in runs]
    outs = [sum(x.max_new for x in r) for r in runs]
    assert max(conts) - min(conts) <= 1
    assert max(lens) / min(lens) < 1.01
    assert len(set(outs)) == 1
    assert runs[0][0].prompt != runs[1][0].prompt


def test_gamma_arrivals_are_burstier():
    mix = dict(g.load_mix("chat"))
    mix["arrival"] = {"kind": "gamma", "rate": 4.0, "cv": 3.0}
    t = g.arrival_times(mix, 1, 60.0)
    gaps = [b - a for a, b in zip(t, t[1:])]
    mean = sum(gaps) / len(gaps)
    var = sum((x - mean) ** 2 for x in gaps) / len(gaps)
    assert var ** 0.5 / mean > 1.5


def test_a_new_mix_is_a_new_file(tmp_path, monkeypatch):
    mix = {"loop": "open", "arrival": {"kind": "poisson", "rate": 2.0},
           "prompt": {"dist": "fixed", "value": 100},
           "output": {"dist": "uniform", "min": 8, "max": 8},
           "warm_seconds": 1}
    (tmp_path / "steady100.json").write_text(json.dumps(mix))
    monkeypatch.setattr(g, "HERE", tmp_path)
    got = g.load_mix("steady100")
    reqs = _draw(got, 9, 10)
    assert all(len(r.prompt) == 100 and r.max_new == 8 for r in reqs)
    with pytest.raises(FileNotFoundError):
        g.load_mix("no-such-mix")
