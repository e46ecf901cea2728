"""Seeded open-loop traffic, read from one data file per mix.

A mix file (``bench/traffic/<name>.json``) names a generator and its
parameters. Two generators cover every mix so far:

* ``poisson``: independent requests, each with its own prompt and
  answer length, arriving at ``rate_per_s``. A block of ``block``
  arrivals spans exactly ``block / rate_per_s`` seconds, so a window of
  that length (or a whole multiple) holds the same requests on every
  seed.
* ``sessions``: multi-turn chats. Sessions arrive at
  ``session_rate_per_s``; each opens with one of a few shared system
  prompts, and its next turn is sent ``think_s`` after the previous
  answer reached the client, carrying the whole history as its prompt.

Steadiness: every seed gets the same work in another order. Lengths,
gaps and think times are drawn in blocks of ``block`` items from fixed
quantiles of their distributions, and the seed only permutes each
block (and picks token ids). So any window that spans whole blocks
offers the same tokens on every seed. A window that cuts sessions
short holds tokens that depend on the order too (a five-turn session
that opens early against one that opens late), so a sessions mix with
``"fixed_schedule": true`` takes every permutation from a stream that
all seeds share: each seed then sends the same turns at the same times,
and picks only their token ids.
"""

from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Any

import numpy as np

SEED_MASK = 2**63 - 1
SHARED = SEED_MASK + 1      # the seed of a fixed schedule: no --seed's


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent stream ``stream`` of ``seed`` (any non-negative int)."""
    return np.random.default_rng([int(seed) & SEED_MASK, int(stream)])


def plan_rng(spec: dict, rng: np.random.Generator,
             stream: int) -> np.random.Generator:
    """Where the mix's permutations come from: the seed's own ``rng``,
    or with ``fixed_schedule`` one stream that every seed shares."""
    return (np.random.default_rng([SHARED, int(stream)])
            if spec.get("fixed_schedule") else rng)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a length or time distribution: the
    midpoints of ``n`` equal-probability strata, clipped to
    ``[min, max]``. ``lognormal`` takes ``median`` and ``sigma``;
    ``uniform`` spans ``[min, max]``; ``exponential`` takes ``mean``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        from statistics import NormalDist

        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "exponential":
        x = -dist["mean"] * np.log1p(-u)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    return np.clip(x, lo, hi)


def _int_quantiles(dist: dict, n: int) -> np.ndarray:
    return np.rint(quantiles(dist, n)).astype(np.int64)


@dataclasses.dataclass
class Request:
    """One request as the client sends it. ``due`` is seconds from the
    start of the stream; ``key`` names it for the generator's callbacks."""

    key: Any
    due: float
    prompt: np.ndarray
    max_new: int


class _Blocks:
    """Endless per-block permutations of fixed stratified draws."""

    def __init__(self, values: np.ndarray, rng: np.random.Generator):
        self.values, self.rng, self.buf = values, rng, []

    def take(self):
        if not self.buf:
            self.buf = list(self.rng.permutation(self.values))
        return self.buf.pop()


class Poisson:
    """Independent requests at a fixed mean rate; unshared prompts."""

    def __init__(self, spec: dict, *, vocab: int, seed: int, stream: int,
                 rate: float | None = None):
        self.spec, self.vocab = spec, vocab
        self.rng = rng_for(seed, stream)
        n = int(spec.get("block", 64))
        rate = float(rate or spec["rate_per_s"])
        gap = {"dist": "exponential", "mean": 1.0 / rate}
        # a block of arrivals spans exactly block / rate seconds, and the
        # next block starts there: a window of whole blocks holds the
        # same requests on every seed
        self.span = n / rate
        gaps = quantiles(gap, n)
        self.gaps = _Blocks(gaps * (self.span / gaps.sum()), self.rng)
        self.block = n
        pairs = np.stack([_int_quantiles(spec["prompt_tokens"], n),
                          self.rng_fixed_perm(
                              _int_quantiles(spec["output_tokens"], n))], 1)
        self.sizes = _Blocks(pairs, self.rng)
        self.t = 0.0
        self.n = 0

    @staticmethod
    def rng_fixed_perm(x: np.ndarray) -> np.ndarray:
        """Pair prompt and answer strata the same way on every seed."""
        return np.random.default_rng(0).permutation(x)

    def next_due(self) -> float:
        return self.t

    def pop(self) -> Request:
        n, m = (int(v) for v in self.sizes.take())
        prompt = self.rng.integers(0, self.vocab, n, dtype=np.int32)
        req = Request(key=self.n, due=self.t, prompt=prompt, max_new=m)
        self.n += 1
        self.t += float(self.gaps.take())
        if self.n % self.block == 0:
            self.t = self.n // self.block * self.span
        return req

    def finished(self, key, tokens: np.ndarray, t: float) -> None:
        return None

    @staticmethod
    def max_len(spec: dict) -> int:
        return int(spec["prompt_tokens"]["max"] + spec["output_tokens"]["max"])

    @staticmethod
    def max_answer(spec: dict) -> int:
        return int(spec["output_tokens"]["max"])


class Sessions:
    """Multi-turn chats behind a few shared system prompts."""

    def __init__(self, spec: dict, *, vocab: int, seed: int, stream: int,
                 rate: float | None = None):
        self.spec, self.vocab = spec, vocab
        self.rng = rng_for(seed, stream)
        n = int(spec.get("block", 64))
        rate = float(rate or spec["session_rate_per_s"])
        gap = {"dist": "exponential", "mean": 1.0 / rate}
        # as in ``Poisson``: a block of session arrivals spans exactly
        # block / rate seconds
        self.span, self.block = n / rate, n
        plan = plan_rng(spec, self.rng, stream)
        gaps = quantiles(gap, n)
        self.gaps = _Blocks(gaps * (self.span / gaps.sum()), plan)
        lo, hi = spec["turns"]["min"], spec["turns"]["max"]
        self.turns = _Blocks(np.resize(np.arange(lo, hi + 1), n), plan)
        self.users = _Blocks(_int_quantiles(spec["user_tokens"], n), plan)
        self.answers = _Blocks(_int_quantiles(spec["answer_tokens"], n),
                               plan)
        self.thinks = _Blocks(quantiles(spec["think_s"], n), plan)
        self.systems = _Blocks(np.resize(np.arange(spec["system_prompts"]),
                                         n), plan)
        # the shared system prompts: the same ids on every seed, so the
        # prefix cache sees the same sharing whatever the seed
        sys_rng = np.random.default_rng(1)
        self.system_tokens = [
            sys_rng.integers(0, vocab, spec["system_tokens"], dtype=np.int32)
            for _ in range(spec["system_prompts"])]
        self.t_next_session = 0.0
        self.n_sessions = 0
        self.ready: list[tuple[float, int, Request]] = []   # heap by due
        self.sessions: dict[int, dict] = {}
        self._seq = 0

    def _new_session(self) -> None:
        """Open the next session with its whole plan: every turn's user
        tokens, answer length and the pause before it, drawn now, so
        that the plan does not hang on the order in which answers come
        back."""
        sid = self.n_sessions
        self.n_sessions += 1
        n = int(self.turns.take())
        s = {"turns": n, "turn": 0,
             "history": self.system_tokens[int(self.systems.take())].copy(),
             "users": [self.rng.integers(0, self.vocab, int(self.users.take()),
                                         dtype=np.int32) for _ in range(n)],
             "answers": [int(self.answers.take()) for _ in range(n)],
             "thinks": [float(self.thinks.take()) for _ in range(n - 1)]}
        self.sessions[sid] = s
        self._push(self._turn(sid, self.t_next_session))
        self.t_next_session += float(self.gaps.take())
        if self.n_sessions % self.block == 0:
            self.t_next_session = self.n_sessions // self.block * self.span

    def _turn(self, sid: int, due: float) -> Request:
        s = self.sessions[sid]
        s["history"] = np.concatenate([s["history"], s["users"][s["turn"]]])
        return Request(key=(sid, s["turn"]), due=due,
                       prompt=s["history"].copy(),
                       max_new=s["answers"][s["turn"]])

    def _push(self, req: Request) -> None:
        heapq.heappush(self.ready, (req.due, self._seq, req))
        self._seq += 1

    def next_due(self) -> float:
        while not self.ready or self.t_next_session <= self.ready[0][0]:
            self._new_session()
        return self.ready[0][0]

    def pop(self) -> Request:
        self.next_due()
        return heapq.heappop(self.ready)[2]

    def finished(self, key, tokens: np.ndarray, t: float) -> None:
        """The answer to turn ``key`` reached the client at ``t``: its
        tokens join the history, and the next turn is due after a pause."""
        sid, turn = key
        s = self.sessions[sid]
        s["history"] = np.concatenate([s["history"],
                                       np.asarray(tokens, np.int32)])
        s["turn"] = turn + 1
        if s["turn"] >= s["turns"]:
            del self.sessions[sid]
            return
        self._push(self._turn(sid, t + s["thinks"][turn]))

    @staticmethod
    def max_len(spec: dict) -> int:
        turns = spec["turns"]["max"]
        return int(spec["system_tokens"] + turns * (
            spec["user_tokens"]["max"] + spec["answer_tokens"]["max"]))

    @staticmethod
    def max_answer(spec: dict) -> int:
        return int(spec["answer_tokens"]["max"])


GENERATORS = {"poisson": Poisson, "sessions": Sessions}


def make(spec: dict, **kw):
    """The generator a mix file names, on stream ``kw['stream']`` of the
    seed."""
    try:
        cls = GENERATORS[spec["generator"]]
    except KeyError:
        raise ValueError(f"unknown generator {spec.get('generator')!r}; "
                         f"known: {sorted(GENERATORS)}") from None
    return cls(spec, **kw)


def max_len(spec: dict) -> int:
    """Longest prompt plus answer the mix can send."""
    return GENERATORS[spec["generator"]].max_len(spec)


def max_answer(spec: dict) -> int:
    """Longest answer the mix asks for."""
    return GENERATORS[spec["generator"]].max_answer(spec)
