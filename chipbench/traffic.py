"""The one traffic generator: closed-loop agents over documents.

A mix (``chipbench/traffic/<name>.json``) is parameters only:

- ``groups`` workflows, each with a document of ``doc_tokens`` tokens;
  with ``doc_shared`` the document is prefilled once as an
  ``AgentSession`` of the sessions' adapter, whose base KV every agent of
  the group inherits; every agent forks from a session of its own on its
  document (a private prompt, when not shared);
- ``agents_per_group`` agents per workflow, each with its own adapter;
- a turn appends ``instruction_tokens`` + ``observation_tokens`` (a mock
  tool observation) to the agent's branch and asks for a lognormal number
  of output tokens (``output_tokens``: median, sigma, min, max; sigma 0
  asks for the median every turn);
- after ``turns_per_fork`` turns the agent forks again from its document,
  so the contexts in flight stay in one range for the whole run;
- with ``stagger_first_turn``, agent ``i`` of ``n`` asks in its first turn
  for ``(i + 1) / n`` of the median length, for every seed, so that from
  the start one agent's turn ends every ``1 / n`` of a turn, as in a loop
  that has run a while, and not all at once.

Every agent waits for its reply before it takes its next turn (a closed
loop, tool latency 0).  The agents' ``k``-th turns (round ``k``) ask for
one fixed set of output lengths, the lognormal's quantiles at evenly
spaced levels, the same for every seed; the seed only deals them out to
the agents, and draws every token.  So two seeds ask for the same work in
another order.  After ``rounds`` rounds an agent starts the rounds again.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


GOLDEN = 0.6180339887498949


def round_lengths(spec: Dict, n: int, k: int) -> List[int]:
    """The ``n`` output lengths of round ``k`` (every agent's ``k``-th
    turn): the lognormal's quantiles at the levels (j + f_k) / n, rounded
    and clipped to [min, max], with the offsets f_k spread over [0, 1) so
    that successive rounds reach different parts of the distribution."""
    nd = NormalDist()
    f = (0.5 + k * GOLDEN) % 1.0
    out = []
    for j in range(n):
        z = nd.inv_cdf(min(max((j + f) / n, 1e-6), 1 - 1e-6))
        v = round(spec["median"] * math.exp(spec["sigma"] * z))
        out.append(int(min(spec["max"], max(spec["min"], v))))
    return out


def longest_context(mix: Dict) -> int:
    """Tokens of the longest request the mix can send (prompt plus its
    output budget): a branch at its last turn before the next fork."""
    turn = mix["instruction_tokens"] + mix["observation_tokens"]
    return (mix["doc_tokens"] +
            mix["turns_per_fork"] * (turn + mix["output_tokens"]["max"]))


def working_set_pages(mix: Dict, page: int) -> int:
    """Pages of KV the mix holds at most at once: each document once, and
    each agent's branch beyond its document at its longest (one page more
    for a page the branch shares with the document), plus the server's
    scratch page."""
    n = mix["groups"] * mix["agents_per_group"]
    docs = mix["groups"] if mix["doc_shared"] else n
    branch = pages_for(longest_context(mix) - mix["doc_tokens"], page) + 1
    return docs * pages_for(mix["doc_tokens"], page) + n * branch + 1


def shortest_context(mix: Dict) -> int:
    """Tokens of the shortest request: a fork's first turn."""
    return (mix["doc_tokens"] + mix["instruction_tokens"] +
            mix["observation_tokens"])


@dataclasses.dataclass
class Turn:
    agent: int
    adapter: int
    prompt: List[int]            # the whole prompt sent
    new_tokens: List[int]        # what this turn adds to the branch
    first_of_fork: bool          # a fork from the document (turn 0)
    max_new: int


class Agent:
    """One agent: an adapter, a document and its branch of turns."""

    def __init__(self, index: int, group: int, adapter: int,
                 doc: List[int], lengths: List[int], mix: Dict,
                 rng: np.random.Generator, vocab: int):
        """``lengths``: the output length of each round's turn."""
        self.index, self.group, self.adapter = index, group, adapter
        self.doc = doc
        self.lengths = lengths
        self.first_len = 0           # the first turn's, where staggered
        self.mix = mix
        self.rng = rng
        self.vocab = vocab
        self.turns = 0
        self.branch: List[int] = list(doc)

    def next_turn(self) -> Turn:
        m = self.mix
        first = self.turns % m["turns_per_fork"] == 0
        if first:
            self.branch = list(self.doc)
        new = self.rng.integers(
            0, self.vocab,
            m["instruction_tokens"] + m["observation_tokens"]).tolist()
        max_new = self.lengths[self.turns % len(self.lengths)]
        if self.turns == 0 and self.first_len:
            max_new = self.first_len
        self.turns += 1
        return Turn(self.index, self.adapter, self.branch + new, new, first,
                    max_new)

    def finish_turn(self, turn: Turn, output: List[int]) -> None:
        """The reply extends the branch the next turn forks from."""
        self.branch = turn.prompt + list(output)


class Traffic:
    """Every agent of a mix, made from ``seed``.  Adapter 0 is the
    sessions' own; agent ``i`` serves with adapter ``i + 1``."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        n_groups, per = mix["groups"], mix["agents_per_group"]
        n = n_groups * per
        rounds = []
        for k in range(mix["rounds"]):
            lengths = round_lengths(mix["output_tokens"], n, k)
            order = np.random.default_rng([seed, 4, k]).permutation(n)
            rounds.append([lengths[j] for j in order])
        self.docs: List[List[int]] = []
        self.agents: List[Agent] = []
        for g in range(n_groups):
            rng = np.random.default_rng([seed, 0, g])
            self.docs.append(rng.integers(0, vocab,
                                          mix["doc_tokens"]).tolist())
        for i in range(n):
            rng = np.random.default_rng([seed, 1, i])
            self.agents.append(Agent(i, i // per, i + 1, self.docs[i // per],
                                     [r[i] for r in rounds], mix, rng,
                                     vocab))
            if mix.get("stagger_first_turn"):
                med = mix["output_tokens"]["median"]
                self.agents[i].first_len = max(1, round(med * (i + 1) / n))

    @property
    def n_adapters(self) -> int:
        return len(self.agents) + 1

    @property
    def sessions(self) -> bool:
        return bool(self.mix["doc_shared"])

    def sample(self, finished: List, seed: int, min_tokens: int,
               max_requests: int) -> List:
        """A sample of finished turns for the reference, drawn from
        ``seed``: the one with the most served tokens, then others in a
        seeded order until ``min_tokens`` served tokens or
        ``max_requests`` turns."""
        if not finished:
            return []
        order = sorted(range(len(finished)),
                       key=lambda i: (-len(finished[i].tokens), i))
        picked = [order[0]]
        rest = order[1:]
        rng = np.random.default_rng([seed, 2])
        rest = [rest[j] for j in rng.permutation(len(rest))]
        total = len(finished[picked[0]].tokens)
        for i in rest:
            if total >= min_tokens or len(picked) >= max_requests:
                break
            picked.append(i)
            total += len(finished[i].tokens)
        return [finished[i] for i in picked]


def pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def pages_for(tokens: int, page: int) -> int:
    return -(-tokens // page)
