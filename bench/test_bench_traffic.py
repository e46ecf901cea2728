"""The traffic generators: seeded, in range, sessions in order."""

import numpy as np
import pytest

from harness import traffic

CHAT = {
    "generator": "poisson", "rate_per_s": 2.0, "block": 16,
    "prompt_tokens": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                      "min": 64, "max": 1024},
    "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                      "min": 32, "max": 512},
}
SESSIONS = {
    "generator": "sessions", "session_rate_per_s": 1.0, "block": 16,
    "system_prompts": 4, "system_tokens": 64,
    "turns": {"min": 2, "max": 5},
    "user_tokens": {"dist": "lognormal", "median": 16, "sigma": 0.7,
                    "min": 4, "max": 40},
    "answer_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                      "min": 4, "max": 12},
    "think_s": {"dist": "uniform", "min": 0.5, "max": 2.0},
}
BIG_SEED = 2**40 + 12345


def schedule(spec, seed, n, stream=0, gen_out=None):
    gen = traffic.make(spec, vocab=1000, seed=seed, stream=stream)
    if gen_out is not None:
        gen_out.append(gen)
    return [gen.pop() for _ in range(n)]


@pytest.mark.parametrize("seed", [0, BIG_SEED])
def test_same_seed_same_schedule(seed):
    a, b = schedule(CHAT, seed, 40), schedule(CHAT, seed, 40)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = schedule(CHAT, seed + 1, 40)
    assert [r.due for r in a] != [r.due for r in c]


def test_streams_differ():
    a, b = schedule(CHAT, 7, 16, 0), schedule(CHAT, 7, 16, 1)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_lengths_in_range_and_every_seed_offers_the_same_work():
    works = []
    for seed in (1, 2, BIG_SEED):
        gens = []
        reqs = schedule(CHAT, seed, 32, gen_out=gens)   # two whole blocks
        n = [r.prompt.size for r in reqs]
        m = [r.max_new for r in reqs]
        assert min(n) >= 64 and max(n) <= 1024
        assert min(m) >= 32 and max(m) <= 512
        assert all(r.prompt.max() < 1000 for r in reqs)
        dues = [r.due for r in reqs]
        assert dues == sorted(dues)
        works.append((sorted(n), sorted(m), round(gens[0].next_due(), 9)))
    assert works[0] == works[1] == works[2]
    # a whole block spans block / rate seconds exactly, on every seed,
    # so a window of that length holds one block's requests
    for seed in (3, BIG_SEED):
        gen = traffic.make(CHAT, vocab=1000, seed=seed, stream=0)
        dues = [gen.pop().due for _ in range(32)]
        assert dues[16] == 16 / 2.0 and dues[0] == 0.0
        assert sum(d < 8.0 for d in dues) == 16


def test_sessions_turns_in_order_and_carry_history():
    gen = traffic.make(SESSIONS, vocab=1000, seed=BIG_SEED, stream=0)
    last_prompt = {}
    t = 0.0
    for _ in range(60):
        req = gen.pop()
        sid, turn = req.key
        assert req.due >= t - 1e-9 or turn > 0
        assert 4 <= req.max_new <= 12
        if turn == 0:
            assert any(np.array_equal(req.prompt[:64], s)
                       for s in gen.system_tokens)
        else:
            prev, answer = last_prompt[sid]
            assert turn == prev[0] + 1
            n = prev[1].size
            assert np.array_equal(req.prompt[:n], prev[1])
            assert np.array_equal(req.prompt[n:n + answer.size], answer)
            assert 4 <= req.prompt.size - n - answer.size <= 40
        answer = np.arange(req.max_new, dtype=np.int32) + 900
        last_prompt[sid] = ((turn, req.prompt), answer)
        gen.finished(req.key, answer, req.due + 0.1)
        t = req.due
    assert any(turn > 0 for (turn, _), _ in last_prompt.values())


def test_sessions_same_seed_same_schedule():
    def run(seed):
        gen = traffic.make(SESSIONS, vocab=1000, seed=seed, stream=0)
        out = []
        for _ in range(30):
            req = gen.pop()
            out.append((req.key, req.due, req.prompt.tobytes()))
            gen.finished(req.key, np.ones(req.max_new, np.int32),
                         req.due + 1.0)
        return out

    assert run(5) == run(5)
    assert run(5) != run(6)


def sessions_plan(spec, seed, n=40, answer_delay=lambda key: 1.0):
    """(key, due, prompt size, answer length) of the first ``n`` turns,
    and their prompts, each answer back ``answer_delay(key)`` after its
    turn was due."""
    gen = traffic.make(spec, vocab=1000, seed=seed, stream=0)
    plan, prompts = [], []
    for _ in range(n):
        req = gen.pop()
        plan.append((req.key, round(req.due, 9), req.prompt.size,
                     req.max_new))
        prompts.append(req.prompt)
        gen.finished(req.key, np.ones(req.max_new, np.int32),
                     req.due + answer_delay(req.key))
    return plan, prompts


def test_sessions_fixed_schedule_same_turns_on_every_seed():
    fixed = {**SESSIONS, "fixed_schedule": True}
    a, ids_a = sessions_plan(fixed, 5)
    b, ids_b = sessions_plan(fixed, BIG_SEED)
    assert a == b
    assert not all(np.array_equal(x, y) for x, y in zip(ids_a, ids_b))
    # without it the seed orders the sessions
    assert sessions_plan(SESSIONS, 5)[0] != sessions_plan(SESSIONS, BIG_SEED)[0]


def test_sessions_plan_does_not_hang_on_the_order_answers_return():
    """A session's turns are drawn when it opens: answers that come back
    in another order move the later turns' times, not their sizes."""
    fixed = {**SESSIONS, "fixed_schedule": True}
    slow_odd = lambda key: 3.0 if key[0] % 2 else 0.2   # noqa: E731
    a, ids_a = sessions_plan(fixed, 9)
    b, ids_b = sessions_plan(fixed, 9, answer_delay=slow_odd)
    sizes = lambda plan: sorted((k, n, m) for k, _, n, m in plan)  # noqa: E731
    common = {k for k, *_ in a} & {k for k, *_ in b}
    assert len(common) > 20
    assert ([x for x in sizes(a) if x[0] in common]
            == [x for x in sizes(b) if x[0] in common])
    by_key = lambda plan, ids: {p[0]: i for p, i in zip(plan, ids)}  # noqa: E731
    pa, pb = by_key(a, ids_a), by_key(b, ids_b)
    assert all(np.array_equal(pa[k], pb[k]) for k in common)


def test_max_len_bounds_every_request():
    assert traffic.max_len(CHAT) == 1536
    assert traffic.max_len(SESSIONS) == 64 + 5 * (40 + 12)
    gen = traffic.make(SESSIONS, vocab=1000, seed=0, stream=0)
    for _ in range(80):
        req = gen.pop()
        assert req.prompt.size + req.max_new <= traffic.max_len(SESSIONS)
        gen.finished(req.key, np.ones(req.max_new, np.int32), req.due)
