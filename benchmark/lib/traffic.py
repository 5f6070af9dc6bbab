"""The one general traffic generator: everything a run sends is drawn here
from ``--seed`` and the parameters of a ``benchmark/traffic/<mix>.json``.

A mix is data only. Its sections, all optional except ``vocab_words``:

- ``corpus``   documents indexed during set-up: ``docs``, ``words``;
- ``backlog``  documents staged as files and released at once (a closed
  loop by nature: the connector takes them as fast as the engine lets it):
  ``docs``, ``words``, ``files_per_dir``;
- ``queries``  open-loop queries: ``arrivals``, ``words``, ``k``;
- ``documents`` open-loop live documents: ``arrivals``, ``words``, and
  ``read_your_write`` (each write is followed, as much later as the
  configuration promises it visible, by a query for its own text; those
  queries count against ``queries``' rate).

``words`` is a length distribution: ``{"dist": "geometric", "mean", "shift",
"min", "max"}`` (clip(geometric(1/mean) + shift, min, max)) or ``{"dist":
"uniform", "min", "max"}``. ``arrivals`` is ``{"process": "poisson" |
"even", "rate_per_s"}``. Texts are over the ``word{i}`` vocabulary of
``vocab_words`` words, which the synthetic WordPiece vocab holds whole.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def draw_lengths(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "geometric":
        raw = rng.geometric(1.0 / spec["mean"], size=n) + spec.get("shift", 0)
        return np.clip(raw, spec["min"], spec["max"]).astype(np.int64)
    if dist == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, size=n)
    raise ValueError(f"unknown length distribution {dist!r}")


def make_texts(rng: np.random.Generator, lengths: np.ndarray,
               vocab_words: int) -> list[str]:
    """One text of ``lengths[i]`` words per entry, words uniform over the
    vocabulary."""
    words = [f"word{i}" for i in range(vocab_words)]
    ids = rng.integers(0, vocab_words, size=int(lengths.sum())).tolist()
    out, pos = [], 0
    for n in lengths.tolist():
        out.append(" ".join([words[j] for j in ids[pos:pos + n]]))
        pos += n
    return out


def arrival_times(rng: np.random.Generator, spec: dict, t0: float,
                  t1: float) -> np.ndarray:
    """Due times in [t0, t1) of an arrival process (seconds)."""
    process, rate = spec["process"], spec["rate_per_s"]
    if rate <= 0 or t1 <= t0:
        return np.zeros(0)
    if process == "even":
        return np.arange(t0, t1, 1.0 / rate)
    if process == "poisson":
        # enough exponential gaps to pass t1 with overwhelming probability
        n = int((t1 - t0) * rate * 1.5 + 32)
        times = t0 + np.cumsum(rng.exponential(1.0 / rate, size=n))
        while times[-1] < t1:
            more = times[-1] + np.cumsum(rng.exponential(1.0 / rate, size=n))
            times = np.concatenate([times, more])
        return times[times < t1]
    raise ValueError(f"unknown arrival process {process!r}")


def cut_span(rng: np.random.Generator, text: str, n_words: int) -> str:
    """A contiguous span of ``n_words`` words of ``text`` (all of it where
    it is shorter)."""
    words = text.split()
    n = min(int(n_words), len(words))
    start = int(rng.integers(0, len(words) - n + 1))
    return " ".join(words[start:start + n])


@dataclasses.dataclass(frozen=True)
class Event:
    """One thing the load generator does at ``due`` seconds after the
    schedule's origin. ``kind`` is "query" or "write". A query carries its
    ``text``, its ``k`` and, for a read-your-write query, the ``doc`` (file
    name) that must come back first. A write carries the ``doc`` it makes
    and its ``text``."""

    due: float
    kind: str
    text: str
    k: int = 0
    doc: str | None = None


def open_loop_schedule(mix: dict, seed: int, horizon_s: float,
                       corpus: list[str], visible_within_s: float,
                       prefix: str = "live") -> list[Event]:
    """The events of ``horizon_s`` seconds of a mix's open-loop traffic,
    sorted by due time. ``corpus`` holds the indexed documents that corpus
    queries cut their spans from; ``visible_within_s`` is the
    configuration's guarantee, at whose edge a mix with ``read_your_write``
    reads each write back; ``prefix`` starts the names of the documents
    written."""
    rng = np.random.default_rng([seed, 0x51ED])
    events: list[Event] = []
    q, d = mix.get("queries"), mix.get("documents")
    ryw_rate = 0.0
    if d is not None:
        due = arrival_times(rng, d["arrivals"], 0.0, horizon_s)
        texts = make_texts(rng, draw_lengths(rng, d["words"], len(due)),
                           mix["vocab_words"])
        read_back = bool(d.get("read_your_write")) and q is not None
        for i, (t, text) in enumerate(zip(due.tolist(), texts)):
            name = f"{prefix}{i:07d}.txt"
            events.append(Event(t, "write", text, doc=name))
            if read_back and t + visible_within_s < horizon_s:
                events.append(Event(t + visible_within_s, "query", text,
                                    k=q["k"], doc=name))
        if read_back:
            ryw_rate = d["arrivals"]["rate_per_s"]
    if q is not None:
        arrivals = dict(q["arrivals"])
        # read-your-write queries are among the stated rate, not on top
        arrivals["rate_per_s"] = max(0.0, arrivals["rate_per_s"] - ryw_rate)
        due = arrival_times(rng, arrivals, 0.0, horizon_s)
        lengths = draw_lengths(rng, q["words"], len(due))
        texts = [cut_span(rng, corpus[int(rng.integers(0, len(corpus)))], n)
                 for n in lengths]
        events.extend(Event(t, "query", text, k=q["k"])
                      for t, text in zip(due.tolist(), texts))
    events.sort(key=lambda e: e.due)
    return events


def corpus_texts(mix: dict, section: str, seed: int) -> list[str]:
    """The documents of ``mix[section]`` (``corpus`` or ``backlog``)."""
    part = mix[section]
    rng = np.random.default_rng([seed, 0xC0 if section == "corpus" else 0xB1])
    return make_texts(rng, draw_lengths(rng, part["words"], part["docs"]),
                      mix["vocab_words"])
