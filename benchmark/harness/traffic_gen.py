"""One general generator for every traffic file of kind ``serve``.

A traffic file fixes a multiset of request shapes (prompt length, output
budget) through its own ``shape_seed``, and how they arrive (``loop``):

- ``"closed"``: as many callers as decode slots, each sending its next
  request when its last one finished;
- ``"open"``: arrivals on a clock of their own, whatever the server does,
  ``"arrivals": {"process": "poisson", "rate_rps": r}`` or
  ``{"process": "bursty", "burst_size": n, "burst_every_s": s}``.

Every ``--seed`` gets the same shapes in the same order and the same arrival
times, all drawn from the file's ``shape_seed``; the seed draws the prompts'
token ids (and, in the driver, the weights). A window holds fewer requests
than would cover the multiset, so an order or a schedule drawn from the seed
decides which requests fall into the window: runs with different seeds then
differ far more than two runs of one seed (PR 26, on the chip: 1.2 % against
0.01 % for ``serve_tok_s``).

Length distributions: ``{"dist": "lognormal", "median": m, "sigma": s,
"lo": a, "hi": b}`` (clipped) or ``{"dist": "fixed", "value": v}``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

Sent = Tuple[Optional[int], float, np.ndarray, int]


def draw_lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def request_shapes(traffic: Dict[str, Any]) -> List[Tuple[int, int]]:
    """The traffic's fixed multiset of (prompt_len, output_budget)."""
    rng = np.random.default_rng(int(traffic["shape_seed"]))
    n = int(traffic["n_shapes"])
    prompts = draw_lengths(traffic["prompt_len"], n, rng)
    outs = draw_lengths(traffic["output_len"], n, rng)
    outs = np.minimum(outs, int(traffic["max_len"]) - prompts)
    return [(int(p), int(max(o, 2))) for p, o in zip(prompts, outs)]


def arrival_offsets(arrivals: Dict[str, Any], rng: np.random.Generator
                    ) -> Iterator[float]:
    """Seconds from the clock's start, ascending, without end. Copied in
    substance from serving/traffic.py::TrafficGenerator (poisson: i.i.d.
    exponential gaps; bursty: ``burst_size`` arrivals every
    ``burst_every_s``, each a small exponential after its burst's start)."""
    process = arrivals["process"]
    if process == "poisson":
        t = 0.0
        while True:
            t += float(rng.exponential(1.0 / float(arrivals["rate_rps"])))
            yield t
    elif process == "bursty":
        every, size = float(arrivals["burst_every_s"]), int(
            arrivals["burst_size"])
        k = 0
        while True:
            jitter = np.sort(rng.exponential(every * 0.01, size))
            for j in jitter:
                yield k * every + float(min(j, every * 0.5))
            k += 1
    else:
        raise ValueError(f"unknown arrival process {process!r}")


class Load:
    """The requests a serve cell offers. ``due(now, finished)`` gives those
    to send now as (client or None, the moment the request was due, prompt
    ids, output budget); ``now`` is seconds on the caller's clock, started
    with ``start(now)``; ``finished`` lists the clients whose last request
    has finished (closed loop). The k-th request of the run, whoever sends
    it, takes the k-th shape of the file's order, cycling."""

    def __init__(self, traffic: Dict[str, Any], vocab_size: int, seed: int,
                 slots: int) -> None:
        shapes = request_shapes(traffic)
        frng = np.random.default_rng([int(traffic["shape_seed"]), 0x04DE4])
        self._shapes = [shapes[i] for i in frng.permutation(len(shapes))]
        self._rng = np.random.default_rng([int(seed), 0x5EED])
        self._vocab = vocab_size
        self._sent = 0
        self.longest_prompt = max(p for p, _ in shapes)
        self.loop = traffic["loop"]
        if self.loop == "closed":
            self.n_clients = slots
            # first fill: budgets cut to evenly spread shares, so that
            # completions are spread from the start as in a steady state
            self._first_cut = list(frng.permutation(
                (np.arange(self.n_clients) + 0.5) / self.n_clients))
            self._first = [True] * self.n_clients
        elif self.loop == "open":
            self.n_clients = 0
            self._arrivals = arrival_offsets(traffic["arrivals"], frng)
            self._next = next(self._arrivals)
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        self._t0 = 0.0

    def start(self, now: float) -> None:
        self._t0 = now

    def _request(self, cut: float = 1.0) -> Tuple[np.ndarray, int]:
        plen, out = self._shapes[self._sent % len(self._shapes)]
        self._sent += 1
        if cut < 1.0:
            out = max(2, int(np.ceil(out * cut)))
        prompt = self._rng.integers(4, self._vocab, (plen,)).astype(np.int32)
        return prompt, out

    def due(self, now: float, finished: Sequence[int] = ()) -> List[Sent]:
        out: List[Sent] = []
        if self.loop == "closed":
            for c in finished:
                cut = 1.0
                if self._first[c]:
                    self._first[c], cut = False, self._first_cut[c]
                out.append((c, now) + self._request(cut))
            return out
        while self._t0 + self._next <= now:
            out.append((None, self._t0 + self._next) + self._request())
            self._next = next(self._arrivals)
        return out

    def next_due(self) -> Optional[float]:
        """When the next open-loop arrival is due (None in a closed loop)."""
        return None if self.loop == "closed" else self._t0 + self._next
