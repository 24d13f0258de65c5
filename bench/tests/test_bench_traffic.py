"""The traffic generator: deterministic in the seed, the stated rates, phases
and length distributions, and the same work for every seed."""
import math
from collections import Counter

import numpy as np
import pytest

from bench import traffic
from bench.tests.tiny import load

BIG = 2 ** 31 + 12345  # seeds wider than 32 signed bits


def mix(name):
    return load("traffic", name)


# one stream a camera: sixteen cameras, each scored by one variant
ONE_MODEL = {"frame_models": 1}


@pytest.mark.parametrize("name, over", [("camera-128", {}),
                                        ("camera-128", ONE_MODEL)],
                         ids=["committed", "one_model"])
def test_open_streams(name, over):
    mx = dict(mix(name), **over)
    a = traffic.open_schedule(mx, BIG, 10.0, 4, 1000)
    b = traffic.open_schedule(mx, BIG, 10.0, 4, 1000)
    c = traffic.open_schedule(mx, BIG + 1, 10.0, 4, 1000)
    assert [(x.due_s, x.variant) for x in a] == [(x.due_s, x.variant) for x in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    # every seed offers the same arrivals, and the same requests share a
    # variant; it names the variants and draws the tokens
    assert [x.due_s for x in a] == [x.due_s for x in c]
    assert [x.variant for x in a] != [x.variant for x in c]
    rename = {}
    for x, y in zip(a, c):
        assert rename.setdefault(x.variant, y.variant) == y.variant
    assert sorted(rename.values()) == list(range(4))
    assert not np.array_equal(a[0].tokens, c[0].tokens)
    period = mx["streams"] / mx["rate_per_s"]
    # each stream sends every `period` from a phase inside the first period,
    # so a window holds rate * seconds requests, give or take one a stream
    assert abs(len(a) - mx["rate_per_s"] * 10.0) <= mx["streams"]
    # and its variant stays: the first period holds each variant equally
    first = [x.variant for x in a if x.due_s < period]
    assert len(first) == mx["streams"]
    assert Counter(first) == {v: mx["streams"] // 4 for v in range(4)}
    dues = np.array([x.due_s for x in a])
    # the cameras' phases are spaced equally through the period
    cams = mx["streams"] // mx.get("frame_models", 1)
    assert np.allclose(np.diff(np.unique(dues)), period / cams)
    assert np.all(np.diff(dues) >= 0) and dues[0] >= 0 and dues[-1] < 10.0
    assert dues.min() < period
    assert all(x.tokens.shape == (mx["prompt_tokens"],) for x in a)
    assert all(0 <= x.tokens.min() and x.tokens.max() < 1000 for x in a[:50])


def test_cameras_send_one_frame_to_every_variant():
    """With ``frame_models`` equal to the variants, each camera's frame is
    one request to each variant at one due time, and the cameras' frames
    are spaced equally through the period."""
    mx = mix("camera-128")
    per = mx["frame_models"]
    items = traffic.open_schedule(mx, BIG, 10.0, per, 1000)
    frames = {}
    for x in items:
        frames.setdefault(x.due_s, []).append(x.variant)
    assert all(sorted(v) == list(range(per)) for v in frames.values())
    times = np.array(sorted(frames))
    spacing = per / mx["rate_per_s"]
    assert np.allclose(np.diff(times), spacing)
    assert np.isclose(times[0], spacing / 2)
    assert len(items) == per * len(times)
    assert abs(len(items) - mx["rate_per_s"] * 10.0) <= per


def test_sample_takes_each_variant():
    rng = np.random.default_rng(3)
    by_var = {v: list(range(v * 10, v * 10 + 10)) for v in range(4)}
    pick = traffic.sample_indices(rng, by_var, 2)
    assert len(pick) == 8
    assert Counter(i // 10 for i in pick) == {0: 2, 1: 2, 2: 2, 3: 2}
    again = traffic.sample_indices(np.random.default_rng(3), by_var, 2)
    assert pick == again


def test_score_sample_lies_in_the_first_half():
    from bench.drivers import open_streams

    mx = mix("camera-128")
    items = traffic.open_schedule(mx, BIG, 10.0, 4, 1000)
    sample, single_from = open_streams.sample(mx, BIG, items, 10.0)
    assert len(sample) == 4 * mx["sample_per_variant"]
    assert Counter(items[i].variant for i in sample) == {
        v: mx["sample_per_variant"] for v in range(4)}
    assert all(items[i].due_s < 5.0 for i in sample)
    assert 0 <= single_from < sum(1 for it in items if it.due_s < 5.0)
    assert (sample, single_from) == open_streams.sample(mx, BIG, items, 10.0)


def test_chat_lengths_are_log_uniform_and_the_same_for_every_seed():
    """The lengths are log-normal about the mix's means, cut at its limits
    (the name is older than the distribution), and every seed asks for the
    same ones; the clients are dealt to the variants by popularity."""
    mx = mix("chat-16")
    a = traffic.ChatClients(mx, BIG, 4, 1000)
    b = traffic.ChatClients(mx, BIG + 1, 4, 1000)
    ks = range(200)
    sizes_a = [a.sizes(c, k) for c in range(a.clients) for k in ks]
    prompts = np.array([p for p, _ in sizes_a])
    outputs = np.array([o for _, o in sizes_a])
    for x, key in ((prompts, "prompt_tokens"), (outputs, "output_tokens")):
        d = mx[key]
        assert x.min() >= d["min"] and x.max() <= d["max"]
        # the median of a log-normal of mean m and log-spread s is
        # m exp(-s^2 / 2); the share cut at the upper limit is its tail
        median = d["mean"] * np.exp(-d["sigma"] ** 2 / 2)
        assert abs(np.median(x) / median - 1) < 0.1, (key, np.median(x))
        z = (np.log(d["max"] + 0.5) - np.log(median)) / d["sigma"]
        tail = 0.5 * math.erfc(z / math.sqrt(2))
        assert abs(np.mean(x == d["max"]) - tail) < 0.02, key
    # the seed deals the lists to other clients and draws other tokens, but
    # asks for the same lengths
    sizes_b = [b.sizes(c, k) for c in range(b.clients) for k in ks]
    assert sorted(sizes_a) == sorted(sizes_b) and sizes_a != sizes_b
    assert sorted(Counter(a.variant.tolist()).values()) == [2, 2, 4, 8]
    fav_a, fav_b = (Counter(t.variant.tolist()).most_common(1)[0][0]
                    for t in (a, b))
    assert fav_a != fav_b  # the seed picks the favourite
    r1, r2 = a.request(3, 5), traffic.ChatClients(mx, BIG, 4, 1000).request(3, 5)
    assert np.array_equal(r1.prompt, r2.prompt) and r1.max_new == r2.max_new
    assert (len(r1.prompt), r1.max_new) == a.sizes(3, 5)
    assert not np.array_equal(r1.prompt, b.request(3, 5).prompt)


def test_chat_sample_is_of_later_requests_early_in_the_window():
    """The schedule follows the lengths, and the sample takes, per variant,
    requests live in the window's first steps that are not their client's
    first, the longest among them included; the same for the same seed."""
    from bench.drivers import closed_chat

    mx = mix("chat-16")
    chats = traffic.ChatClients(mx, BIG, 4, 1000)
    plan = closed_chat.schedule(chats, 1000)
    for c in range(chats.clients):
        mine = [(k, t, n) for cc, k, t, n in plan if cc == c]
        assert [k for k, _, _ in mine] == list(range(len(mine)))
        assert mine[0][1] == 0 and mine[-1][1] < 1000 <= mine[-1][1] + mine[-1][2]
        for (k, t, n), (_, t2, _) in zip(mine, mine[1:]):
            p, o = chats.sizes(c, k)
            assert n == p + o - 1 and t2 == t + n
    got = closed_chat.sample(mx, BIG, chats)
    assert got == closed_chat.sample(mx, BIG, chats)
    lo, hi = mx["warm_steps"], mx["warm_steps"] + mx["sample_steps"]
    start = {(c, k): (t, n) for c, k, t, n in plan}
    assert all(k > 0 and start[c, k][0] < hi and sum(start[c, k]) > lo
               for c, k in got)
    by_var = Counter(int(chats.variant[c]) for c, _ in got)
    assert by_var == {v: mx["sample_per_variant"] for v in range(4)}
    for v in range(4):
        cands = [(c, k) for (c, k), (t, n) in start.items()
                 if chats.variant[c] == v and k > 0 and t < hi and t + n > lo]
        longest = max(chats.sizes(*ck)[1] for ck in cands)
        assert longest in {chats.sizes(*ck)[1] for ck in got if ck in cands}


def test_closed_loop_resubmits_on_retirement():
    """Each client sends its requests in order, the next as soon as the
    last retires and never before; none after the loop closes."""
    from types import SimpleNamespace

    from bench.drivers import closed_chat

    mx = dict(mix("chat-16"), clients_per_variant=[2, 2])
    chats = traffic.ChatClients(mx, BIG, 2, 1000)
    sent = []
    dec = SimpleNamespace(stats={"bank_dispatches": 0}, slots={}, completions=[],
                          submit=lambda r: sent.append(r.meta))
    loop = closed_chat.Loop(dec, chats, ("m0", "m1"), set())
    for c in range(4):
        loop.submit(c)
    assert sent == [(0, 0), (1, 0), (2, 0), (3, 0)]

    def retire(*keys):
        for key in keys:
            p, o = chats.sizes(*key)
            dec.completions.append(SimpleNamespace(
                request=SimpleNamespace(meta=key, prompt=[0] * p), tokens=[0] * o))
        loop.on_step(dec, 0)

    retire((2, 0))
    retire()
    retire((0, 0), (2, 1))
    assert sent[4:] == [(2, 1), (0, 1), (2, 2)]
    loop.open = False
    loop.paused = 1e4  # a stop of the loop is left out of its times
    retire((1, 0))
    assert len(sent) == 7 and set(loop.done) == {(2, 0), (0, 0), (2, 1), (1, 0)}
    assert loop.first[1, 0] < loop.first[2, 0] - 1e3
    # each step's rows: the live slots (none here) and the retired ones
    assert [len(lengths) for lengths, _, _ in loop.steps] == [1, 0, 2, 1]
