"""One general request generator, driven by a mix file.

A mix (`traffic/<mix>.json`) of a serving runner gives:

    loop           "closed" (each of `clients` sends its next request
                   when the last one answered) or "open" (arrivals on a
                   schedule, whether or not earlier ones finished)
    clients        closed loop: how many callers
    rate_rps       open loop: mean arrivals a second
    arrival        open loop: "poisson", or "burst" (groups of `burst_n`
                   back to back at the same mean rate)
    users, zipf_s  open loop: size of the user population and its skew;
                   0 users = no request shares anything
    prefix_tokens  tokens of a user's own prefix (system prompt and
                   history) in front of every prompt of theirs
    prompt_tokens  [lo, hi] fresh tokens of a prompt
    answer_tokens  [lo, hi] tokens asked for
    shape_seed     seeds every LENGTH, ARRIVAL TIME and user RANK

Two seeds do different things.  `shape_seed`, from the mix, fixes the
sizes and the arrival times, so every run of a cell offers the same
work at the same moments.  `--seed` draws every token, and decides
which user holds which prefix and which closed-loop client gets which
sequence of sizes: the same work in another order.  So runs with
different seeds differ by the system's noise, not by the luck of the
draw, and a tail over some tens of requests still repeats.
"""

from __future__ import annotations

import math

import numpy as np

_M31 = 2 ** 31 - 1


class Item:
    """One request: `due_s` seconds after the window opens (open loop;
    None in a closed loop), the prompt, and the answer length asked."""

    __slots__ = ("due_s", "client", "prompt", "max_new")

    def __init__(self, due_s, client, prompt, max_new):
        self.due_s = due_s
        self.client = client
        self.prompt = prompt
        self.max_new = max_new


def rng(*parts):
    """A RandomState from any whole numbers (a `--seed` may be over
    2**31)."""
    acc = 0
    for p in parts:
        acc = (acc * 1000003 + int(p)) % _M31
    return np.random.RandomState(acc)


def tokens(state, n, vocab):
    return state.randint(1, vocab, (n,)).astype(np.int32)


def _length(state, lo_hi):
    lo, hi = lo_hi
    return int(state.randint(lo, hi + 1))


def arrival_times(mix, seconds):
    """Open loop: floor(rate x seconds) arrival times in [0, seconds).
    The gaps are the evenly spaced quantiles of the exponential law,
    shuffled by `shape_seed`: a Poisson-like stream whose count and
    mean rate are exact.  "burst" sends groups of `burst_n` back to
    back with such gaps between groups."""
    rate = float(mix["rate_rps"])
    n = int(math.floor(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s offers nothing in {seconds} s")
    group = int(mix.get("burst_n", 1)) if mix.get("arrival") == "burst" \
        else 1
    n_gaps = -(-n // group)
    u = (np.arange(n_gaps) + 0.5) / n_gaps
    gaps = -np.log1p(-u)
    rng(mix["shape_seed"], 1).shuffle(gaps)
    starts = np.cumsum(gaps)
    starts *= seconds / (starts[-1] + gaps.mean())
    intra = 1.0 / (50.0 * rate)
    times = [s + k * intra for s in starts for k in range(group)][:n]
    return [float(t) for t in times]


def open_loop(mix, seconds, seed, vocab):
    """The whole schedule of an open-loop window, in order of time."""
    shape = rng(mix["shape_seed"], 2)
    users = int(mix.get("users", 0))
    prefix_n = int(mix.get("prefix_tokens", 0)) if users else 0
    if users:
        p = np.arange(1, users + 1, dtype=np.float64) \
            ** -float(mix["zipf_s"])
        p /= p.sum()
        who = rng(seed, 3).permutation(users)     # rank -> user id
    tail_rng = rng(seed, 4)
    prefixes, items = {}, []
    for t in arrival_times(mix, seconds):
        tail_n = _length(shape, mix["prompt_tokens"])
        max_new = _length(shape, mix["answer_tokens"])
        parts = []
        user = -1
        if users:
            user = int(who[int(shape.choice(users, p=p))])
            if prefix_n:
                if user not in prefixes:
                    prefixes[user] = tokens(rng(seed, 5, user),
                                            prefix_n, vocab)
                parts.append(prefixes[user])
        parts.append(tokens(tail_rng, tail_n, vocab))
        items.append(Item(t, user, np.concatenate(parts), max_new))
    return items


class ClosedLoop:
    """`next(client)` is that caller's next request.  Sequence k of
    sizes comes from `shape_seed` alone; `--seed` deals the sequences
    to the clients and draws the tokens."""

    def __init__(self, mix, seed, vocab):
        self.mix = mix
        self.vocab = vocab
        n = int(mix["clients"])
        self.clients = n
        deal = rng(seed, 6).permutation(n)
        self._shape = [rng(mix["shape_seed"], 7, deal[c])
                       for c in range(n)]
        self._tok = [rng(seed, 8, c) for c in range(n)]

    def next(self, client):
        shape, tok = self._shape[client], self._tok[client]
        prompt_n = _length(shape, self.mix["prompt_tokens"])
        max_new = _length(shape, self.mix["answer_tokens"])
        return Item(None, client, tokens(tok, prompt_n, self.vocab),
                    max_new)
