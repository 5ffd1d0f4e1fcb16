"""The traffic generator: seeded, prefix-stable, within its bounds."""
import tiny  # noqa: F401  (puts bench/ and src/ on the path)

from harness import traffic

CHAT = dict(tiny.CHAT, rate_rps=2.0)


def test_same_seed_same_schedule_and_prompts():
    a, b = traffic.schedule(CHAT, 30.0), traffic.schedule(CHAT, 30.0)
    assert a == b and len(a) > 20
    assert traffic.prompt_tokens(a, 512, 2**33 + 1) == \
        traffic.prompt_tokens(b, 512, 2**33 + 1)


def test_other_seed_other_prompts_same_sizes():
    a = traffic.schedule(CHAT, 30.0)
    p1 = traffic.prompt_tokens(a, 512, 1)
    p2 = traffic.prompt_tokens(a, 512, 2)
    assert p1 != p2
    assert [len(p) for p in p1] == [len(p) for p in p2] == \
        [x.prompt_len for x in a]


def test_other_schedule_seed_other_schedule():
    a = traffic.schedule(CHAT, 30.0)
    b = traffic.schedule(dict(CHAT, schedule_seed=99), 30.0)
    assert [(x.due_s, x.prompt_len) for x in a] != \
        [(x.due_s, x.prompt_len) for x in b]


def test_longer_window_extends_the_same_sequence():
    short, long = traffic.schedule(CHAT, 10.0), traffic.schedule(CHAT, 30.0)
    assert long[:len(short)] == short and len(long) > len(short)


def test_due_order_and_bounds():
    a = traffic.schedule(CHAT, 60.0)
    assert [x.req_id for x in a] == list(range(len(a)))
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))
    assert all(8 <= x.prompt_len <= 64 and 2 <= x.output_len <= 12
               for x in a)
    # gamma arrivals at 2 req/s: the offered count is near rate * seconds
    assert 60 <= len(a) <= 200


def test_backlog_all_due_at_start():
    a = traffic.schedule(tiny.BACKLOG, 5.0)
    assert len(a) == tiny.BACKLOG["n_requests"]
    assert all(x.due_s == 0.0 for x in a)
    assert all(4 <= x.output_len <= 10 for x in a)


def test_max_tokens_covers_the_longest_request():
    assert traffic.max_tokens(CHAT) == 64 + 12


def test_preroll_arrivals_come_before_the_window():
    mix = dict(CHAT, preroll_s=5.0)
    a = traffic.schedule(mix, 10.0)
    assert a[0].due_s >= -5.0 and a[0].due_s < 0.0
    assert a[-1].due_s < 10.0
    pre = [x for x in a if x.due_s < 0]
    assert 3 <= len(pre) <= 30
