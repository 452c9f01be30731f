"""The one general traffic generator: a traffic file's parameters and a seed
in, requests out. JAX-free; runs in the load-generating parent.

A traffic file (`benchmarks/traffic/<name>.json`) names its `generator`:

- `open_loop`: independent students. Exponential gaps at the cell's fixed
  `rate_per_s`; every request has a due time and is timed from it.
- `closed_loop`: `students` callers who each wait for their answer and then
  ask again at once (no think time), their first questions spread over
  `start_spread_s`.

Both draw from the same courses: each course has one assignment context of
a stated length in tokens, and a request is its course's context followed
by a question whose length is log-normal within stated ends. Every seed
gets the SAME multiset of gaps (or starts), courses and question lengths
(the quantile midpoints of each distribution: a stratified sample, so that
no seed is busier or burstier in total than another) and the seed alone
decides the order, by a shuffle of each, and the words. A prompt longer
than the configuration's `max_prompt_tokens` is sent as it is: the served
engine cuts it, and `describe` counts how many it will cut.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import os
import random
import statistics
from typing import Dict, List, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float      # seconds after the window opens (open loop)
    course: int
    query: str
    query_tokens: int


class Words:
    """Seeded text of a stated length in tokens of the serving vocabulary
    (`benchmarks/vocab`, loaded through the program's own tokenizer class,
    as the server loads it)."""

    def __init__(self):
        from distributed_lms_raft_llm_tpu.utils.tokenizer import BPETokenizer

        self.tok = BPETokenizer.from_files(
            os.path.join(HERE, "vocab", "vocab.json"),
            os.path.join(HERE, "vocab", "merges.txt"))
        with open(os.path.join(HERE, "text", "words.txt")) as fh:
            self.words = fh.read().split()
        # GPT-2's pre-tokeniser splits at the space before a word, so a
        # text of space-led words has the sum of the words' token counts.
        self.cost = [len(self.tok.encode(" " + w)) for w in self.words]
        self.ones = [w for w, c in zip(self.words, self.cost) if c == 1]
        if not self.ones:
            raise ValueError("the word list has no one-token word")

    def count(self, text: str) -> int:
        return len(self.tok.encode(text))

    def text(self, rng: random.Random, tokens: int) -> str:
        """Space-led words that encode to exactly `tokens` tokens."""
        out, left = [], tokens
        while left > 0:
            i = rng.randrange(len(self.words))
            if self.cost[i] <= left:
                out.append(self.words[i])
                left -= self.cost[i]
            elif left <= 2:
                out.extend(rng.choice(self.ones) for _ in range(left))
                left = 0
        return "".join(" " + w for w in out)


def midpoints(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def lognormal_lengths(n: int, median: float, sigma: float, lo: int,
                      hi: int) -> List[int]:
    """The n quantile midpoints of a log-normal, rounded and clipped."""
    inv = statistics.NormalDist().inv_cdf
    return [int(min(hi, max(lo, round(median * math.exp(sigma * inv(u))))))
            for u in midpoints(n)]


def exponential_gaps(n: int, rate: float) -> List[float]:
    return [-math.log(1.0 - u) / rate for u in midpoints(n)]


def shares(n: int, weights: Sequence[float]) -> List[int]:
    """n items over the weights, largest remainder: item i's class."""
    total = float(sum(weights))
    exact = [n * w / total for w in weights]
    counts = [int(x) for x in exact]
    for i in sorted(range(len(weights)), key=lambda i: exact[i] - counts[i],
                    reverse=True)[: n - sum(counts)]:
        counts[i] += 1
    return [c for c, k in enumerate(counts) for _ in range(k)]


class Traffic:
    """What one run sends: built from a traffic file, the cell's own
    parameters, the seed and the window length."""

    def __init__(self, spec: dict, cell: dict, seed: int, seconds: float,
                 max_prompt_tokens: int, words: Words = None):
        self.generator = spec["generator"]
        if self.generator not in ("open_loop", "closed_loop"):
            raise ValueError(f"unknown generator {self.generator!r}")
        seed = int(seed)
        rng = random.Random(f"traffic/{seed}")
        words = words or Words()
        # What the server puts around a query, so that `describe` can say
        # how many prompts the engine will cut to `max_prompt_tokens`.
        self._room = max_prompt_tokens - int(spec["template_tokens"])
        self.courses = spec["courses"]
        self.contexts = [
            f"Course {i + 1} assignment:"
            + words.text(rng, int(c["context_tokens"]))
            for i, c in enumerate(self.courses)
        ]
        q = spec["question_tokens"]
        weights = [c["share"] for c in self.courses]

        def build(course: int, q_tokens: int, due: float) -> Request:
            query = (self.contexts[course] + "\nQuestion from a student:"
                     + words.text(rng, q_tokens) + "?")
            return Request(due, course, query, words.count(query))

        # Asked before the window opens, one per course: the class's
        # contexts are in the prefix cache, as they are for a course that
        # has been asking all week.
        self.warmup = [build(c, int(q["lo"]), 0.0)
                       for c in range(len(self.courses))]

        def shuffled(values: list, tag: str) -> list:
            """`values` in the order this seed gives them under `tag`."""
            values = list(values)
            random.Random(f"order/{seed}/{tag}").shuffle(values)
            return values

        if self.generator == "open_loop":
            rate = float(cell["rate_per_s"])
            n = int(rate * seconds)
            gaps = shuffled(exponential_gaps(n, rate), "gaps")
            lengths = shuffled(lognormal_lengths(
                n, q["median"], q["sigma"], q["lo"], q["hi"]), "lengths")
            course = shuffled(shares(n, weights), "courses")
            due, self.requests = 0.0, []
            for g, ln, c in zip(gaps, lengths, course):
                due += g
                self.requests.append(build(c, ln, due))
            self.students = 0
        else:
            self.students = int(cell["students"])
            self.starts = shuffled([u * float(spec["start_spread_s"])
                                    for u in midpoints(self.students)],
                                   "starts")
            self.course_of = shuffled(shares(self.students, weights),
                                      "courses")
            # Every round of questions is the same multiset of lengths, one
            # per student, in an order of the round's own.
            self._lengths = lognormal_lengths(
                self.students, q["median"], q["sigma"], q["lo"], q["hi"])
            self._shuffled, self._rounds = shuffled, {}
            self._build, self._made = build, {}

    def next_request(self, student: int, k: int, due_s: float) -> Request:
        """A closed-loop student's k-th question."""
        key = (student, k)
        if key not in self._made:
            if k not in self._rounds:
                self._rounds[k] = self._shuffled(self._lengths, f"round/{k}")
            self._made[key] = self._build(self.course_of[student],
                                          self._rounds[k][student], 0.0)
        return dataclasses.replace(self._made[key], due_s=due_s)

    def digest(self) -> str:
        """What the run will send, as one hash: the schedule and prompts of
        an open loop, the starts and first four rounds of a closed one."""
        if self.generator == "open_loop":
            doc = [[r.due_s, r.course, r.query] for r in self.requests]
        else:
            doc = [self.starts, [
                [self.next_request(s, k, 0.0).query for k in range(4)]
                for s in range(self.students)]]
        return hashlib.sha256(json.dumps(doc).encode()).hexdigest()

    def describe(self) -> Dict[str, object]:
        """The drawn distribution, for the line a run prints before its
        result."""
        if self.generator == "open_loop":
            reqs = self.requests
        else:
            reqs = [self.next_request(s, k, 0.0)
                    for s in range(self.students) for k in range(4)]
        toks = sorted(r.query_tokens for r in reqs)
        per_course = [sum(1 for r in reqs if r.course == c)
                      for c in range(len(self.courses))]
        doc = {
            "generator": self.generator,
            "requests_described": len(reqs),
            "query_tokens_min_median_max": [
                toks[0], toks[len(toks) // 2], toks[-1]],
            "query_tokens_sum": sum(toks),
            "requests_per_course": per_course,
            "context_tokens": [c["context_tokens"] for c in self.courses],
            "queries_the_engine_will_cut": sum(
                1 for r in reqs if r.query_tokens > self._room),
            "digest": self.digest(),
        }
        if self.generator == "open_loop":
            doc["last_due_s"] = reqs[-1].due_s if reqs else None
        else:
            doc["students"] = self.students
        return doc
