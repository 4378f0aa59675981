"""The one traffic generator: queries and their send times from a mix
file (``bench/traffic/<mix>.json``) and a seed.

Mix keys:

- ``app``: the application the queries go to (``advanced_rag``);
- ``source``: where the sizes below come from;
- ``docs_per_query``: the documents each query brings;
- ``question_words``, ``doc_words``: each ``{"mean": m, "sigma": s}``,
  a log-normal length in words (one word is one token to the system's
  tokenizer) with mean ``m`` and log-space deviation ``s``;
- ``loop``: ``open`` (arrivals on a schedule, whatever the system does)
  or ``closed`` (``clients`` callers, each sending its next query when
  its last one is answered);
- open loop: ``rate_qps``, ``ramp_s`` (arrivals before the window
  opens, so it opens on a steady queue) and ``tail_s`` (arrivals go on
  after the close while the window's queries finish);
- closed loop: ``clients``.

Every seed gets the same set of sizes and the same set of inter-arrival
gaps, each taken at evenly spaced quantiles of its distribution, in an
order drawn from the seed: the seed changes what is asked and when, not
how much. Sizes are drawn per group of queries: each segment of an open
loop (ramp, window, tail) is one group, and a closed loop's queries
form groups of ``CLOSED_GROUP`` consecutive indices.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import List, Tuple

import numpy as np

TOPICS = ["optics", "finance", "biology", "chess", "espresso", "sailing",
          "volcanoes", "glaciers", "typography", "astronomy", "beekeeping",
          "carpentry", "cartography", "ceramics", "cycling", "falconry",
          "forestry", "geology", "harbors", "irrigation", "jazz",
          "lighthouses", "metallurgy", "mycology", "navigation", "opera",
          "origami", "orchards", "pottery", "railways", "robotics",
          "seismology", "tapestry", "telescopes", "textiles", "tides",
          "vineyards", "weaving", "windmills", "zoology"]
CLOSED_GROUP = 64

Sizes = Tuple[int, Tuple[int, ...]]       # question words, document words


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one stream of one seed (seeds may exceed 32 bits)."""
    seed = int(seed)
    return np.random.default_rng([seed % 2**32, seed // 2**32, *stream])


def lognormal_quantiles(dist: dict, n: int) -> np.ndarray:
    """Lengths in words at the ``n`` evenly spaced quantiles of a
    log-normal with mean ``dist["mean"]``, at least one word each."""
    s = float(dist["sigma"])
    mu = np.log(float(dist["mean"])) - s * s / 2
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.maximum(1, np.rint(np.exp(mu + s * z))).astype(int)


def group_sizes(mix: dict, n: int, rng: np.random.Generator) -> List[Sizes]:
    """Sizes of ``n`` queries: the quantiles of each length, in an order
    from ``rng``."""
    k = int(mix["docs_per_query"])
    q = lognormal_quantiles(mix["question_words"], n)
    d = lognormal_quantiles(mix["doc_words"], n * k)
    rng.shuffle(q)
    rng.shuffle(d)
    return [(int(q[i]), tuple(int(x) for x in d[i * k:(i + 1) * k]))
            for i in range(n)]


def make_query(seed: int, idx: int, sizes: Sizes) -> dict:
    """Query ``idx`` of seed ``seed``: a question and its documents of
    the given lengths, their words drawn from the seed."""
    rng = rng_for(seed, 1, idx)
    q_words, doc_words = sizes
    topics = [TOPICS[int(i)] for i in rng.integers(0, len(TOPICS),
                                                   len(doc_words))]
    docs = []
    for i, (t, n) in enumerate(zip(topics, doc_words)):
        vals = rng.integers(0, 1000, n // 6 + 1)
        words = " ".join(f"Fact {j} about {t}: value {int(v)}." for j, v in
                         enumerate(vals)).split()[:n]
        docs.append({"id": f"d{idx}.{i}", "topic": t,
                     "text": " ".join(words)})
    pool = " ".join(d["text"] for d in docs).split()
    picks = rng.integers(0, len(pool), max(0, q_words - 2))
    question = " ".join(["what", "is"] + [pool[int(i)] for i in picks])
    return {"question": " ".join(question.split()[:q_words]), "docs": docs}


def spaced(n: int, total: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` send offsets in ``[0, total)``: gaps at the n quantiles of an
    exponential, scaled to sum to ``total``, in an order from ``rng``;
    the first send is at 0."""
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= total / gaps.sum()
    rng.shuffle(gaps)
    return np.cumsum(gaps) - gaps


def open_schedule(mix: dict, seed: int, seconds: float
                  ) -> Tuple[List[float], List[Sizes], Tuple[float, float]]:
    """Send times (seconds from the first send) and sizes of an open-loop
    run's queries, and the window ``(open, close)`` on that clock.
    Exactly ``round(rate * seconds)`` queries are due in the window."""
    rate = float(mix["rate_qps"])
    times: List[float] = []
    sizes: List[Sizes] = []
    start = 0.0
    window = None
    for i, length in enumerate((float(mix["ramp_s"]), float(seconds),
                                float(mix["tail_s"]))):
        n = int(round(rate * length))
        times.extend(start + spaced(n, length, rng_for(seed, 2, i)))
        sizes.extend(group_sizes(mix, n, rng_for(seed, 3, i)))
        if i == 1:
            window = (start, start + length)
        start += length
    return times, sizes, window


def closed_queries(mix: dict, client: int, k: int) -> int:
    """Index of client ``client``'s ``k``-th query."""
    return client + int(mix["clients"]) * k


def closed_sizes(mix: dict, seed: int, idx: int) -> Sizes:
    """Sizes of closed-loop query ``idx``."""
    g, i = divmod(idx, CLOSED_GROUP)
    return group_sizes(mix, CLOSED_GROUP, rng_for(seed, 4, g))[i]
