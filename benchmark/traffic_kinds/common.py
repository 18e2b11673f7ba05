"""What the traffic kinds share: sizes drawn so that every seed offers the
same work, and the client side of one streamed answer. No jax."""

from __future__ import annotations

import json
import math
from statistics import NormalDist

import numpy as np


def stratified(n: int, quantile) -> np.ndarray:
    """n values at the mid-points of n equal-probability strata: the same
    multiset whatever the seed."""
    return np.array([quantile((i + 0.5) / n) for i in range(n)])


def prompt_lengths(spec: dict, n: int) -> np.ndarray:
    """`{"dist": "lognormal", "median", "sigma", "min", "max"}` or
    `{"dist": "uniform", "min", "max"}` → n whole lengths, unordered."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        values = stratified(n, lambda u: lo + u * (hi - lo))
    elif spec["dist"] == "lognormal":
        mu, sigma, normal = math.log(spec["median"]), float(spec["sigma"]), NormalDist()
        values = stratified(n, lambda u: math.exp(mu + sigma * normal.inv_cdf(u)))
    else:
        raise ValueError(f"unknown prompt length distribution {spec['dist']!r}")
    return np.clip(np.rint(values), lo, hi).astype(int)


def output_caps(shares: dict, n: int) -> np.ndarray:
    """`{"32": 0.3, "96": 0.5, ...}` → n caps in those shares (largest
    remainder), unordered."""
    caps = sorted(shares, key=int)
    exact = [shares[c] * n / sum(shares.values()) for c in caps]
    counts = [int(x) for x in exact]
    for i in sorted(range(len(caps)), key=lambda i: exact[i] - counts[i], reverse=True):
        if sum(counts) == n:
            break
        counts[i] += 1
    return np.repeat([int(c) for c in caps], counts)


def order_rng(params: dict) -> np.random.Generator:
    """The order of sizes and arrivals is the traffic file's (`order_seed`),
    not `--seed`'s: on the chip two runs of one seed agreed to a fraction of a
    percent while two seeds that only reordered the same requests differed by
    10 to 40% in the tails (PERF.md, PR 23), so the order was changing the
    work. `--seed` draws the token ids."""
    return np.random.default_rng(int(params.get("order_seed", 0)))


def requests_for(params: dict, seed: int, n: int, vocab_size: int, order) -> list[dict]:
    """n requests: the fixed multisets of lengths and caps in the file's
    order, with prompt ids drawn from `seed` over the whole vocabulary (the
    last id, the unknown-word entry, left out)."""
    rng = np.random.default_rng(seed)
    lengths = order.permutation(prompt_lengths(params["prompt_tokens"], n))
    caps = order.permutation(output_caps(params["output_caps"], n))
    return [
        {
            "id": f"r{seed & 0xFFFFFFFF:08x}{i:06d}",
            "prompt_ids": rng.integers(0, vocab_size - 1, int(length)).tolist(),
            "cap": int(cap),
        }
        for i, (length, cap) in enumerate(zip(lengths, caps))
    ]


def parse_push(data: str) -> tuple[dict, str]:
    record = json.loads(data)["record"]
    value = record.get("value")
    return record.get("headers") or {}, value if isinstance(value, str) else ""


def new_record(request: dict, due: float) -> dict:
    """What the client keeps of one request."""
    return {
        "id": request["id"], "cap": request["cap"], "due": due,
        "prompt_tokens": request["prompt_tokens"], "sent": None, "t_first": None,
        "t_last": None, "tokens": 0, "chunks": [], "done": False, "error": None,
    }


def note_chunk(record: dict, now: float, headers: dict, text: str) -> bool:
    """Count one streamed chunk: a generated token is one whitespace-separated
    word. Returns whether it was the stream's last."""
    words = len(text.split())
    if record["t_first"] is None:
        record["t_first"] = now
        record["first_chunk_tokens"] = words
    record["t_last"] = now
    record["tokens"] += words
    record["chunks"].append((now, words))
    record["done"] = headers.get("stream-last-message") == "true"
    return record["done"]
