"""The sample the reference checks is a number of finished requests,
not a budget of tokens: the longest, then others drawn from the seed,
spread over the DP groups and their decode slots."""
from bench import correctness as K


def _done(n_dp=2, slots=32, per_slot=3):
    out = []
    for dp in range(n_dp):
        for s in range(slots):
            for k in range(per_slot):
                n = 64 + (dp * 7 + s * 13 + k * 29) % 449
                out.append(K.Served([2] * 10, [3] * n, dp, s))
    out.append(K.Served([2] * 10, [3] * 1024, 1, 5))      # the longest
    return out


def test_sample_holds_the_longest_and_spreads_over_groups_and_slots():
    done = _done()
    got = K.sample(done, 2**31 + 7, 16)
    assert len(got) == 16
    assert len(got[0].tokens) == 1024
    assert sorted(p.dp for p in got).count(0) == 8
    assert len({(p.dp, p.slot) for p in got}) == 16
    # hundreds of served tokens, not the one long request
    assert sum(len(p.tokens) for p in got) > 16 * 64


def test_sample_is_drawn_from_the_seed():
    done = _done()
    a, b = K.sample(done, 1, 16), K.sample(done, 1, 16)
    c = K.sample(done, 2, 16)
    key = lambda ps: [(p.dp, p.slot, len(p.tokens)) for p in ps]  # noqa
    assert key(a) == key(b) != key(c)


def test_sample_takes_all_where_fewer_finished():
    done = _done(n_dp=2, slots=2, per_slot=1)
    assert len(K.sample(done, 3, 16)) == len(done)
    assert K.sample([], 3, 16) == []
