"""Traffic: a mix is a data file of parameters; this is the one generator.

Copied from the program's ``tfservingcache_tpu/lab/workload.py``
(``WorkloadSpec`` / ``compile_schedule``: tenants x zipf x arrival process x
prompt-length mix x turns, seeded) so that a later PR may change the program
and not the yardstick, and extended with lognormal lengths, a shared system
prompt and a time horizon instead of a request count. The same
``(mix, seed)`` always compiles to the same schedule.

Every draw is STRATIFIED: a fixed amount of work drawn from the seed. The
exponential gaps, the lengths and the tenants are the distributions' own
quantiles (the same multiset for every seed) in an order the seed draws, so
two seeds offer the same tokens and requests and differ in who meets whom.
Plain seeded draws offered 34.8k-41.7k prompt tokens from seed to seed at 41
requests a window, more than any bound could absorb (PERF.md, PR 22).

Who meets whom is work too where a step's time follows the lanes in flight
(an expert model's step reads the experts its live lanes hit: 5.7 ms at one
lane, 9.0 at five): with the order drawn whole from the seed, the same
multiset read ``tpot_p50_ms`` 9.0-9.9 from seed to seed in
``olmoe-chat-steady`` where one seed repeats to 0.7 % (PERF.md, PR 28). A mix
may therefore fix its load profile with ``order``: the order of gaps, lengths
and tenants is then drawn once from ``order.base_seed``, the same for every
seed, and the run's seed permutes values only among ``order.swap_ranks``
neighbouring quantiles (a few percent apart), so every seed offers the same
load second by second and differs in which of near-equal requests stands
where, in every token id and in the weights.

A mix (the ``traffic`` object of ``benchmark/workloads/<cell>.json``):

    verb            "generate" | "predict"
    tenants         how many tenants the requests are spread over
    zipf_s          popularity skew over tenants by rank (0 = uniform)
    arrival         "poisson" | "burst"
    rate_rps        mean arrivals a second over the horizon (of conversations'
                    first turns)
    burst_size, burst_gap_s           for "burst"
    prompt          {"lognormal": {"median", "sigma", "min", "max"}}
                    | {"choice": {"lens": [...], "weights": [...]}}
    output          same shapes as ``prompt`` (generate only)
    turns, turn_gap_s, turn_suffix  multi-turn sessions: each further turn's
                    prompt is the previous prompt + a fresh suffix
    shared_prefix_tokens   a system prompt, drawn once per tenant, that
                    starts every prompt of that tenant (0 = none)
    prompt_per_tenant      true = one prompt per tenant, drawn once (every
                    answer of a tenant can then be compared byte for byte)
    order           absent = the seed draws the whole order | {"base_seed",
                    "swap_ranks"}: one order for every seed, the seed swaps
                    only values within ``swap_ranks`` neighbouring quantiles

``burst``, ``turns`` and ``shared_prefix_tokens`` are used by no cell yet:
they are what the cells PERF.md keeps for later (`mistral7b-chat-bursty`,
`mistral7b-sessions-shared`) need, so that those can arrive as data files.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Any

import numpy as np

ARRIVALS = ("poisson", "burst")


@dataclasses.dataclass(frozen=True)
class Request:
    """One scheduled request: due ``at_s`` seconds after the replay starts."""

    index: int
    at_s: float
    tenant: int
    prompt: tuple[int, ...]
    max_new: int
    conv: int
    turn: int


def tenant_weights(n: int, zipf_s: float) -> np.ndarray:
    """Rank-ordered popularity: weight of tenant i is 1/(i+1)^s."""
    if zipf_s <= 0.0 or n == 1:
        w = np.ones(n)
    else:
        w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), zipf_s)
    return w / w.sum()


def midpoints(n: int) -> np.ndarray:
    """The ``n`` probabilities (i + 1/2) / n: a distribution's own sample."""
    return (np.arange(n) + 0.5) / max(n, 1)


class Shuffler:
    """Puts a distribution's quantiles (given in rank order) into arrival
    order. Without ``order`` the seed's ``rng`` draws the whole permutation.
    With ``order`` (see the module docstring) a generator made from
    ``base_seed`` alone draws it, and ``rng`` only permutes the ranks inside
    each run of ``swap_ranks`` neighbours."""

    def __init__(self, rng: np.random.Generator, order: dict | None,
                 n_tenants: int) -> None:
        self.rng = rng
        self.base = self.swap = None
        if order is not None:
            self.base = np.random.default_rng(
                [int(order["base_seed"]), n_tenants, 0x7E])
            self.swap = max(1, int(order.get("swap_ranks", 1)))

    def __call__(self, values: np.ndarray) -> np.ndarray:
        if self.base is None:
            return self.rng.permutation(values)
        ranks = np.arange(len(values))
        for lo in range(0, len(values), self.swap):
            ranks[lo:lo + self.swap] = self.rng.permutation(
                ranks[lo:lo + self.swap])
        return np.asarray(values)[ranks[self.base.permutation(len(values))]]


def spread_choice(weights: np.ndarray, n: int, shuffle) -> np.ndarray:
    """``n`` indices whose counts are ``weights * n`` to the nearest whole
    request, in an order ``shuffle`` draws (a ``Shuffler``, or a
    generator's ``permutation``)."""
    edges = np.cumsum(weights / weights.sum())
    return shuffle(np.searchsorted(edges, midpoints(n), side="right")
                   .clip(0, len(weights) - 1))


def draw_lengths(spec: dict[str, Any], n: int, shuffle) -> np.ndarray:
    """``n`` lengths from a length spec (see the module docstring): the
    distribution's own quantiles, in an order ``shuffle`` draws."""
    if "lognormal" in spec:
        p = spec["lognormal"]
        mu, sigma = np.log(float(p["median"])), float(p["sigma"])
        z = np.asarray([NormalDist().inv_cdf(u) for u in midpoints(n)])
        raw = shuffle(np.exp(mu + sigma * z))
        return np.clip(np.rint(raw), int(p["min"]), int(p["max"])).astype(int)
    if "choice" in spec:
        p = spec["choice"]
        lens = np.asarray(p["lens"], int)
        w = np.asarray(p.get("weights") or np.ones(len(lens)), np.float64)
        return lens[spread_choice(w, n, shuffle)]
    raise ValueError(f"length spec needs 'lognormal' or 'choice': {spec}")


def _conv_starts(mix: dict[str, Any], horizon_s: float,
                 shuffle) -> np.ndarray:
    """Arrival offsets of the conversations' first turns, up to the horizon."""
    arrival = mix.get("arrival", "poisson")
    rate = float(mix["rate_rps"])
    if arrival not in ARRIVALS:
        raise ValueError(f"unknown arrival {arrival!r}; one of {ARRIVALS}")
    n = max(1, int(np.ceil(rate * horizon_s)))
    if arrival == "burst":
        size = max(1, int(mix.get("burst_size", 8)))
        gap = float(mix.get("burst_gap_s", size / rate))
        return (np.arange(n) // size) * gap
    # the exponential distribution's own n gaps, shuffled, and stretched so
    # that the last request is due just inside the horizon
    gaps = shuffle(-np.log1p(-midpoints(n)) * horizon_s / n)
    starts = np.cumsum(gaps) - gaps[:1] / 2
    return starts * min(1.0, horizon_s * (n - 0.5) / n / max(starts[-1], 1e-9))


def compile_schedule(mix: dict[str, Any], seed: int, vocab: int,
                     horizon_s: float) -> list[Request]:
    """The mix's requests due in ``[0, horizon_s)``, sorted by due time.
    Token ids are drawn from [1, vocab): 0 is the program's pad."""
    n_tenants = int(mix.get("tenants", 1))
    rng = np.random.default_rng([int(seed), n_tenants, 0x7C])
    vocab = max(2, int(vocab))
    shuffle = Shuffler(rng, mix.get("order"), n_tenants)
    starts = _conv_starts(mix, horizon_s, shuffle)
    n_conv = len(starts)
    weights = tenant_weights(n_tenants, float(mix.get("zipf_s", 0.0)))
    tenants = spread_choice(weights, n_conv, shuffle)
    turns = max(1, int(mix.get("turns", 1)))
    plens = draw_lengths(mix["prompt"], n_conv, shuffle)
    verb = mix.get("verb", "generate")
    olens = (draw_lengths(mix["output"], n_conv * turns, shuffle)
             if verb == "generate" else np.zeros(n_conv * turns, int))
    shared = int(mix.get("shared_prefix_tokens", 0))
    per_tenant = bool(mix.get("prompt_per_tenant", False))
    # drawn from streams of their own so that adding a tenant or a system
    # prompt does not reshuffle the arrivals
    trng = np.random.default_rng([int(seed), n_tenants, 0x7D])
    system = [tuple(int(t) for t in trng.integers(1, vocab, shared))
              for _ in range(n_tenants)]
    fixed = [tuple(int(t) for t in trng.integers(
        1, vocab, int(draw_lengths(mix["prompt"], 1, trng.permutation)[0])))
        for _ in range(n_tenants)] if per_tenant else None

    out: list[Request] = []
    for conv in range(n_conv):
        tenant = int(tenants[conv])
        if fixed is not None:
            prompt = fixed[tenant]
        else:
            body = max(1, int(plens[conv]) - shared)
            prompt = system[tenant] + tuple(
                int(t) for t in rng.integers(1, vocab, body))
        for turn in range(turns):
            at = float(starts[conv] + turn * float(mix.get("turn_gap_s", 1.0)))
            if turn > 0:
                prompt = prompt + tuple(int(t) for t in rng.integers(
                    1, vocab, int(mix.get("turn_suffix", 32))))
            if at < horizon_s:
                out.append(Request(0, at, tenant, prompt,
                                   int(olens[conv * turns + turn]), conv, turn))
    out.sort(key=lambda r: (r.at_s, r.conv, r.turn))
    return [dataclasses.replace(r, index=i) for i, r in enumerate(out)]


def describe(schedule: list[Request]) -> dict[str, Any]:
    """The counts and lengths a run prints about its schedule."""
    if not schedule:
        return {"requests": 0}
    p = np.asarray([len(r.prompt) for r in schedule])
    o = np.asarray([r.max_new for r in schedule])
    q = lambda a: [int(np.percentile(a, x)) for x in (0, 50, 90, 100)]  # noqa: E731
    return {"requests": len(schedule), "tenants": len({r.tenant for r in schedule}),
            "prompt_min_p50_p90_max": q(p), "output_min_p50_p90_max": q(o),
            "longest_request": int((p + o).max()),
            "span_s": round(schedule[-1].at_s, 3)}
