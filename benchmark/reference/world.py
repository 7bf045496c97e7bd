"""The simulated world, in plain numpy: the seed's draws, where the nodes
stand, which are down, and how long a message takes.

Written from the semantics the configurations state, not from the
program's code:

* Draws are counter based: a draw is a pure function of (seed, purpose,
  index), the murmur3 32-bit finalizer over a golden-ratio combination
  of two words.  A purpose is a 32-bit tag (four ASCII letters).
* Nodes stand in cities, chosen by population weight (the reference's
  NodeBuilder with city positions), on the 2000 x 1112 world map whose
  distances wrap around in both directions (Node.java).
* `nodes_down` distinct nodes are down: the ones with the smallest draws.
* NetworkLatencyByDistanceWJitter (NetworkLatency.java): one-way latency
  is half of (0.022 ms per mile + 4.862 ms + a generalized-Pareto jitter
  of shape 1.4, location -0.3, scale 0.35), computed in float32 and cut
  to whole ms; a node's latency to itself is 1 ms, and any other is at
  least 1 ms.  The jitter's quantile is a per-message draw in [0, 100).
  The float32 part is two small tables (see `latency_tables`).
"""

from __future__ import annotations

import json
from functools import lru_cache
from pathlib import Path

import numpy as np

M32 = 0xFFFFFFFF

TAG_BUILDER = 0x4E4F4445   # "NODE": node placement
TAG_LATENCY = 0x4C415443   # "LATC": per-message latency quantiles
TAG_BAD = 0x48424144       # "HBAD": which nodes are down
TAG_RANK = 0x48524E4B      # "HRNK": reception ranks
TAG_LEVEL = 0x484C564C     # "HLVL": the level a node verifies next
TAG_EMIT = 0x48454D49      # "HEMI": emission orders

MAP_W, MAP_H = 2000, 1112


def u32(x) -> np.ndarray:
    """The 32 bits of an integer (a seed may be negative as int32)."""
    return np.asarray(x, np.int64).astype(np.uint64) & np.uint64(M32)


def fmix(x) -> np.ndarray:
    """murmur3's fmix32, on uint64 arrays that hold 32-bit values."""
    x = u32(x) if np.asarray(x).dtype != np.uint64 else x
    m = np.uint64(M32)
    x = x ^ (x >> np.uint64(16))
    x = (x * np.uint64(0x85EBCA6B)) & m
    x = x ^ (x >> np.uint64(13))
    x = (x * np.uint64(0xC2B2AE35)) & m
    return x ^ (x >> np.uint64(16))


def mix(a, b) -> np.ndarray:
    """One draw from two words: fmix(fmix(a) xor b * golden ratio)."""
    b = (u32(b) * np.uint64(0x9E3779B9)) & np.uint64(M32)
    return fmix(fmix(u32(a)) ^ b)


def mix3(a, b, c) -> np.ndarray:
    return mix(mix(a, b), c)


def draw_below(key, idx, n) -> np.ndarray:
    """Uniform whole numbers in [0, n) for each index under `key`."""
    n = np.maximum(np.asarray(n, np.int64), 1).astype(np.uint64)
    return (mix(idx, key) % n).astype(np.int64)


def draw_unit(key, idx) -> np.ndarray:
    """Uniform float32 in [0, 1): the top 24 bits of the draw."""
    top = (mix(idx, key) >> np.uint64(8)).astype(np.float32)
    return top * np.float32(1.0 / (1 << 24))


def permute(key, x, bits) -> np.ndarray:
    """A keyed bijection of [0, 2**bits), applied to x: three rounds of
    (xor a key word, multiply by an odd key word, xor-shift right, multiply
    by an odd constant, xor-shift right), every step invertible modulo
    2**bits.  `bits` may vary per element; bits 0 maps to 0."""
    bits = np.asarray(bits, np.int64)
    mask = ((np.uint64(1) << np.clip(bits, 0, 31).astype(np.uint64))
            - np.uint64(1))
    s1 = np.maximum(1, (bits + 1) // 2).astype(np.uint64)
    s2 = np.maximum(1, (2 * bits) // 3).astype(np.uint64)
    x = u32(x) & mask
    key = u32(key)
    for c in (0x9E3779B9, 0x85EBCA6B, 0xC2B2AE35):
        k = fmix(key ^ np.uint64(c))
        x = (x ^ (k & mask)) & mask
        x = (x * (k | np.uint64(1))) & mask
        x = x ^ (x >> s1)
        x = (x * np.uint64(0x6A09E667)) & mask
        x = x ^ (x >> s2)
    return (x & mask).astype(np.int64)


@lru_cache(maxsize=1)
def cities():
    doc = json.loads((Path(__file__).parent / "cities.json").read_text())
    rows = doc["rows"]
    return (np.array([r[1] for r in rows], np.int64),
            np.array([r[2] for r in rows], np.int64),
            np.array([r[3] for r in rows], np.float64))


def place_in_cities(seed: int, n: int) -> np.ndarray:
    """Each node's city, drawn by population weight."""
    xs, ys, pop = cities()
    key = mix(seed, TAG_BUILDER)
    u = draw_unit(mix(key, 3), np.arange(n))
    cum = np.cumsum(pop / pop.sum()).astype(np.float32)
    return np.minimum(np.searchsorted(cum, u, side="left"), len(xs) - 1)


def down_nodes(seed: int, n: int, k: int) -> np.ndarray:
    """The `k` nodes that are down: the smallest draws, ties by id."""
    down = np.zeros(n, bool)
    if k:
        pri = mix(np.arange(n), mix(seed, TAG_BAD))
        down[np.argsort(pri, kind="stable")[:k]] = True
    return down


#: float32 map units -> miles: half the earth's perimeter over the map's
#: largest torus distance (a whole number of units)
MILES_PER_UNIT = (24860.0 / 2.0) / int(((MAP_W / 2) ** 2 +
                                        (MAP_H / 2) ** 2) ** 0.5)
MAX_DIST = int(((MAP_W / 2) ** 2 + (MAP_H / 2) ** 2) ** 0.5) + 1


def torus_dist(xp, dx, dy):
    """Whole map units between points dx, dy apart (already folded onto
    the torus): the float32 root of the integer square, cut."""
    return xp.sqrt((dx * dx + dy * dy).astype(xp.float32)).astype(xp.int32)


def one_way_ms(xp, dist, q):
    """NetworkLatencyByDistanceWJitter's one-way latency in float32, cut
    to whole ms, at distance `dist` and jitter quantile q/100."""
    f32 = xp.float32
    y = xp.clip(q.astype(f32) / f32(100), f32(0), f32(0.999999))
    pareto = f32(-0.3) + f32(0.35 / 1.4) * (
        xp.power(f32(1) - y, f32(-1.4)) - f32(1))
    jitter = xp.where(y < f32(1e-6), f32(-0.3), pareto)
    miles = dist.astype(f32) * f32(MILES_PER_UNIT)
    return ((miles * f32(0.022) + f32(4.862) + jitter) * f32(0.5)) \
        .astype(xp.int32)


def latency_tables(evaluate):
    """(city x city distance, distance x quantile latency) as integer
    tables.  The two float32 formulas above are cut to whole numbers, so
    the last bit of a root or a power can move a latency by a whole ms:
    `evaluate(fn, *arrays)` runs `fn(xp, *arrays)` where the cell runs
    (the benchmark passes XLA on the cell's device, whose float32 sqrt
    and pow are not numpy's), and everything else here is integer."""
    xs, ys, _ = cities()
    dx = np.abs(xs[:, None] - xs[None, :])
    dy = np.abs(ys[:, None] - ys[None, :])
    dx = np.minimum(dx, MAP_W - dx).astype(np.int32)
    dy = np.minimum(dy, MAP_H - dy).astype(np.int32)
    dist = np.asarray(evaluate(torus_dist, dx, dy), np.int64)
    lat = np.asarray(evaluate(one_way_ms,
                              np.arange(MAX_DIST, dtype=np.int32)[:, None],
                              np.arange(100, dtype=np.int32)[None, :]),
                     np.int64)
    return dist, lat


def on_host(fn, *arrays):
    """`latency_tables`' evaluator for numpy (the CPU tests)."""
    return fn(np, *arrays)


def latency_ms(tables, city, src, dst, q) -> np.ndarray:
    """Latency of messages src -> dst with jitter quantiles q: at least
    1 ms, and 1 ms from a node to itself."""
    dist, lat = tables
    ms = np.maximum(1, lat[dist[city[src], city[dst]], q])
    return np.where(src == dst, 1, ms)
