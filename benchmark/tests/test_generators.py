"""Each generator's traffic is a pure function of its seed, and the seed
orders the work without changing its amount."""


import numpy as np
import pytest

from benchmark import manifest as manifest_mod


def _mod(kind):
    return manifest_mod.Manifest().module("generators", kind)


def _traffic(name):
    return manifest_mod.Manifest().traffic(name)


def test_train_batches_are_a_function_of_the_seed():
    gen = _mod("train_steps")._batches
    a, b = gen(7, 256, 2, 3, 16), gen(7, 256, 2, 3, 16)
    c = gen(2 ** 31 + 9, 256, 2, 3, 16)           # a large seed, as the driver's
    first = next(a)
    assert first.shape == (2, 3, 16) and first.dtype == np.int32
    assert (first == next(b)).all() and (next(a) == next(b)).all()
    assert not (first == next(c)).all()
    assert first.min() >= 0 and first.max() < 256


@pytest.mark.parametrize("ahead", [0, 1, 2])
def test_every_block_sent_is_timed_and_a_late_host_idles_no_device(ahead):
    """A device that takes 2 s a block and starts a block when it is sent or
    when the one before ends, whichever is later; a host that is 0.5 s late
    after every wait.  Every block sent is waited for, in order, and counted;
    at most ``ahead`` + 1 are in flight; with a block ahead the lateness
    costs the device nothing, with none it is in every block."""
    now, free_at, ends, waited, most = [0.0], [0.0], [], [], [0]

    def send_block():
        free_at[0] = max(free_at[0], now[0]) + 2.0
        ends.append(free_at[0])
        most[0] = max(most[0], len(ends) - len(waited))
        return len(ends) - 1

    def wait(i):
        waited.append(i)
        now[0] = max(now[0], ends[i]) + 0.5          # the host comes back late

    times, done = _mod("train_steps").timed_blocks(
        send_block, wait, 0.0, 20.0, ahead, clock=lambda: now[0])
    assert done == waited == list(range(len(ends)))
    assert most[0] == ahead + 1
    assert sum(times) == pytest.approx(now[0])
    last = len(times) - ahead        # the completion at which sending stopped
    assert sum(times[:last]) >= 20.0 > sum(times[:last - 1])
    per_block = (ends[-1] - ends[0]) / (len(ends) - 1)
    assert per_block == pytest.approx(2.0 if ahead else 2.5)


def test_serving_requests_are_a_function_of_the_seed():
    make = _mod("serve_requests").make_requests
    for name in ("chat-steady", "doc-prefill"):
        tr = _traffic(name)
        a = make(tr, 5_000_000_011, 40.0, 32000)
        assert a == make(tr, 5_000_000_011, 40.0, 32000)
        b = make(tr, 12, 40.0, 32000)
        assert a != b
        # the seed gives the token ids; arrivals and sizes are the traffic
        # file's (schedule_seed), the same for every seed
        for key in (lambda r: r["due"], lambda r: len(r["prompt"]),
                    lambda r: r["want"]):
            assert list(map(key, a)) == list(map(key, b))
        assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
        other = make(dict(tr, schedule_seed=1), 12, 40.0, 32000)
        assert sorted(len(r["prompt"]) for r in other) == \
            sorted(len(r["prompt"]) for r in b)
        assert [len(r["prompt"]) for r in other] != \
            [len(r["prompt"]) for r in b]
        assert [r["due"] for r in a] == sorted(r["due"] for r in a)
        assert all(0 <= t < 32000 for r in a[:5] for t in r["prompt"])


def test_open_loop_schedule_fills_preroll_and_window():
    make = _mod("serve_requests").make_requests
    tr = _traffic("chat-steady")
    arr = tr["arrivals"]
    reqs = make(tr, 3, 40.0, 32000)
    pre = [r for r in reqs if r["due"] < 0]
    win = [r for r in reqs if r["due"] >= 0]
    assert len(pre) == round(arr["rate_per_s"] * arr["preroll_s"])
    assert len(win) == round(arr["rate_per_s"] * 40.0)
    assert min(r["due"] for r in reqs) >= -arr["preroll_s"]
    assert max(r["due"] for r in reqs) < 40.0
    p = tr["prompt_tokens"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in reqs)
    # the window's own multiset holds the whole distribution
    med = sorted(len(r["prompt"]) for r in win)[len(win) // 2]
    assert 0.85 * p["median"] < med < 1.15 * p["median"]


def test_backlog_is_all_due_at_the_start_and_balanced():
    make = _mod("serve_requests").make_requests
    tr = _traffic("doc-prefill")
    for schedule in (0, 1, 2):
        reqs = make(dict(tr, schedule_seed=schedule), 5, 40.0, 32000)
        assert len(reqs) == tr["arrivals"]["count"]
        assert all(r["due"] == 0.0 for r in reqs)
        lens = [len(r["prompt"]) for r in reqs]
        whole = sum(lens) / len(lens)
        # any prefix the window drains holds the same mix of sizes
        for n in (48, 96, 128, 144):
            assert abs(sum(lens[:n]) / n - whole) < 0.02 * whole
        # one output token each: the one the last prefill chunk samples
        assert {r["want"] for r in reqs} == {1}
        # the queue outlasts the longest window at twice today's rate
        assert sum(lens) > 2 * 8000 * 51


def test_the_chat_trace_is_the_traffic_files_for_every_seed():
    """Arrivals and sizes of ``chat-steady`` are one fixed trace: the
    multisets the traffic file states, in one order, whatever ``--seed``."""
    make = _mod("serve_requests").make_requests
    tr = _traffic("chat-steady")
    runs = [make(tr, seed, 51.0, 32000) for seed in (0, 11, 2 ** 31 + 11)]
    shape = [[(r["due"], len(r["prompt"]), r["want"]) for r in reqs]
             for reqs in runs]
    assert shape[0] == shape[1] == shape[2]
    win = [r for r in runs[0] if r["due"] >= 0]
    assert len(win) == round(tr["arrivals"]["rate_per_s"] * 51.0)
    o = tr["output_tokens"]
    outs = sorted(r["want"] for r in win)
    assert o["min"] <= outs[0] and outs[-1] <= o["max"]
    assert 0.85 * o["median"] < outs[len(outs) // 2] < 1.15 * o["median"]
    gaps = [b["due"] - a["due"] for a, b in zip(win, win[1:])]
    assert sum(gaps) / len(gaps) == pytest.approx(
        1.0 / tr["arrivals"]["rate_per_s"], rel=0.05)


def test_an_unknown_arrival_process_is_refused():
    make = _mod("serve_requests").make_requests
    tr = _traffic("chat-steady")
    bad = dict(tr, arrivals=dict(tr["arrivals"], process="poisson"))
    with pytest.raises(ValueError):
        make(bad, 1, 40.0, 32000)


def test_warm_prompts_cover_every_window_bucket():
    mod = _mod("serve_requests")
    picked = mod.warm_prompt_lengths(32, 3584, 512, 16, 256)
    assert len(picked) <= 6
    want = {2, 4, 8, 16, 32, 64, 128, 256}
    got = set()
    for n in picked:
        for start in range(0, n, 512):
            got.add(mod.window_bucket(min(start + 512, n), 16, 256))
    assert got == want
    assert [mod.window_bucket(t, 16, 256) for t in (1, 16, 17, 512, 513, 9000)] \
        == [1, 1, 2, 32, 64, 256]
