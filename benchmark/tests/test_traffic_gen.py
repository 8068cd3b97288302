"""The one general generator: every seed gets the same shapes, order and
arrival times from the traffic file; the seed draws the token ids."""

import json
import os

import numpy as np
import pytest

from harness.traffic_gen import Load, arrival_offsets, request_shapes

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def traffic(**over):
    with open(os.path.join(HERE, "traffic", "serve-closed-chat.json")) as f:
        return dict(json.load(f), **over)


def test_shapes_stay_inside_the_files_limits():
    t = traffic()
    shapes = request_shapes(t)
    assert len(shapes) == t["n_shapes"]
    for p, o in shapes:
        assert t["prompt_len"]["lo"] <= p <= t["prompt_len"]["hi"]
        assert 2 <= o <= t["output_len"]["hi"] and p + o <= t["max_len"]


def test_closed_loop_gives_every_seed_the_same_shapes_in_the_same_order():
    t = traffic()
    a, b = Load(t, 50257, 1, 16), Load(t, 50257, 2 ** 31 + 5, 16)
    assert a.n_clients == 16
    sent_a = a.due(0.0, range(16)) + a.due(1.0, [3, 5])
    sent_b = b.due(0.0, range(16)) + b.due(1.0, [3, 5])
    assert [(c, len(p), n) for c, _, p, n in sent_a] == \
           [(c, len(p), n) for c, _, p, n in sent_b]
    assert any((pa != pb).any() for (_, _, pa, _), (_, _, pb, _)
               in zip(sent_a, sent_b)), "the seed draws the token ids"
    # the first fill's budgets are cut, so completions are spread
    full = dict(zip(range(18), [n for _, n in (a._shapes * 2)[:18]]))
    assert all(n <= full[i] for i, (_, _, _, n) in enumerate(sent_a))
    assert [n for _, _, _, n in sent_a[16:]] == [full[16], full[17]]
    assert a.due(2.0, []) == [] and a.next_due() is None


@pytest.mark.parametrize("arrivals,per_second", [
    ({"process": "poisson", "rate_rps": 20.0}, 20.0),
    ({"process": "bursty", "burst_size": 16, "burst_every_s": 2.0}, 8.0)])
def test_open_loop_arrivals_come_from_the_file_alone(arrivals, per_second):
    t = traffic(loop="open", arrivals=arrivals)
    a, b = Load(t, 50257, 1, 16), Load(t, 50257, 99, 16)
    a.start(100.0)
    b.start(100.0)
    got_a, got_b = a.due(200.0), b.due(200.0)
    assert [d for _, d, _, _ in got_a] == [d for _, d, _, _ in got_b]
    due = [d for _, d, _, _ in got_a]
    assert due == sorted(due) and 100.0 <= due[0] and due[-1] <= 200.0
    assert all(c is None for c, _, _, _ in got_a)
    assert len(due) == pytest.approx(per_second * 100, rel=0.15)
    assert a.next_due() > 200.0 and a.due(200.0) == []


def test_a_burst_arrives_together():
    gen = arrival_offsets({"process": "bursty", "burst_size": 4,
                           "burst_every_s": 2.0}, np.random.default_rng(0))
    t = [next(gen) for _ in range(8)]
    assert all(0.0 <= x <= 1.0 for x in t[:4])
    assert all(2.0 <= x <= 3.0 for x in t[4:])
