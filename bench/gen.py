"""The benchmark's own generators: graphs, search keys and traffic schedules.

Everything here is made from the run's ``--seed`` and the parameters in a
configuration or traffic file. None of it imports the program, so a change
to the program's generators cannot move the yardstick.

Graphs are host arrays ``(n, src, dst)``: int64 edge lists, undirected by
edge doubling, self loops removed.

* ``rmat``: Graph500 Kronecker/RMAT (Graph500 specification, "Graph
  Generation"): A/B/C/D quadrant probabilities, ``edge_factor * 2**scale``
  directed edges, vertex ids randomised by a permutation.
* ``urand``: GAP "urand" (Beamer, Asanovic, Patterson, arXiv:1508.03619):
  ``edge_factor * 2**scale`` edges with uniform endpoints.

The seed draws the vertex labels; the edge multiset comes from the
configuration's fixed ``base_seed`` (see :func:`make_graph`).

Search keys follow the Graph500 rule: drawn uniformly, without
replacement, from the vertices of degree > 0.

Traffic schedules keep the work the same for every seed: the request
descriptors, their inter-arrival gaps and the base vertex each popularity
rank names are drawn from the traffic file's fixed ``base_seed``;
``--seed`` draws the labels those vertices go by. An open loop's tail
depends on the order of its requests as much as on their multiset, so the
order is fixed too.
"""
from __future__ import annotations

import functools

import numpy as np

SEED_MOD = 1 << 64

# stream tags: each use of the seed draws from its own independent stream
# (a block or set index adds multiples of 16)
_KEYS, _RANKS, _SAMPLE, _ARRIVALS = 2, 3, 5, 6


def rng(seed: int, tag: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed,
    negative or wider than 32 bits, is taken modulo 2**64."""
    return np.random.default_rng([int(seed) % SEED_MOD, tag])


# -- graphs -----------------------------------------------------------------
def device_key(seed: int):
    """A JAX key from any whole seed (its 64 low bits, folded in 32 at a
    time)."""
    import jax

    s = int(seed) % SEED_MOD
    key = jax.random.key(0)
    return jax.random.fold_in(jax.random.fold_in(key, s & 0xFFFFFFFF),
                              s >> 32)


@functools.cache
def _edges_jit():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(0, 1, 2))
    def edges(gen: str, scale: int, m: int, abcd, base, label):
        """Directed edges of the base graph (from ``base``) with vertex
        labels permuted by ``label``: int32 ``(src, dst)``, on device."""
        n = 1 << scale
        if gen == "rmat":
            a, b, c, d = abcd[0], abcd[1], abcd[2], abcd[3]
            p_src1 = c + d                  # P(src bit = 1)
            p_dst1 = (b / (a + b), d / (c + d))  # P(dst bit 1 | src bit)
            src = jnp.zeros(m, jnp.int32)
            dst = jnp.zeros(m, jnp.int32)
            for level, k in enumerate(jax.random.split(base, scale)):
                k1, k2 = jax.random.split(k)
                sbit = jax.random.uniform(k1, (m,)) < p_src1
                dbit = jax.random.uniform(k2, (m,)) < jnp.where(
                    sbit, p_dst1[1], p_dst1[0])
                src = src | (sbit.astype(jnp.int32) << level)
                dst = dst | (dbit.astype(jnp.int32) << level)
        else:
            k1, k2 = jax.random.split(base)
            src = jax.random.randint(k1, (m,), 0, n, jnp.int32)
            dst = jax.random.randint(k2, (m,), 0, n, jnp.int32)
        perm = jax.random.permutation(label, n).astype(jnp.int32)
        return perm[src], perm[dst]

    return edges


@functools.cache
def _labels_jit():
    import jax

    return jax.jit(jax.random.permutation, static_argnums=1)


def labels(n: int, seed: int) -> np.ndarray:
    """The seed's vertex labels: :func:`make_graph` names base vertex ``b``
    ``labels(n, seed)[b]``."""
    return np.asarray(_labels_jit()(device_key(seed), n), np.int64)


def make_graph(cfg: dict, seed: int):
    """The configuration's graph for ``seed``: ``(n, src, dst)``, int64.

    The edge multiset is drawn once from the configuration's ``base_seed``
    (RMAT or uniform endpoints, one jitted call on the default device);
    ``seed`` draws the vertex labels, a permutation of all ``2**scale``
    ids, as Graph500 asks. Every seed thus gets the same degree sequence
    and subgraph sizes under another labelling, and so the same compiled
    shapes."""
    if cfg["generator"] not in ("rmat", "urand"):
        raise ValueError(f"unknown generator {cfg['generator']!r}")
    n = 1 << cfg["scale"]
    m = n * cfg["edge_factor"]
    abcd = tuple(cfg.get("rmat_abcd", (0.25, 0.25, 0.25, 0.25)))
    src, dst = _edges_jit()(cfg["generator"], cfg["scale"], m, abcd,
                      device_key(cfg["base_seed"]), device_key(seed))
    return undirected(n, np.asarray(src, np.int64), np.asarray(dst, np.int64))


def undirected(n: int, src: np.ndarray, dst: np.ndarray):
    """Drop self loops, then double every edge (both directions stored)."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    return n, np.concatenate([src, dst]), np.concatenate([dst, src])


def non_isolated(n: int, src: np.ndarray) -> np.ndarray:
    """Sorted ids of the vertices with at least one edge."""
    return np.nonzero(np.bincount(src, minlength=n))[0]


def search_keys(n: int, src: np.ndarray, count: int, seed: int) -> np.ndarray:
    """Graph500 search keys: ``count`` distinct non-isolated vertices."""
    cand = non_isolated(n, src)
    return rng(seed, _KEYS).choice(cand, size=min(count, cand.size),
                                   replace=False)


# -- traffic ----------------------------------------------------------------
def exp_gaps(count: int, total: float) -> np.ndarray:
    """``count`` exponential inter-arrival gaps (the quantiles at
    ``(i + 0.5) / count``), scaled to sum to ``total`` seconds: a Poisson
    process's gaps with the sampling noise of their count taken out."""
    u = (np.arange(count) + 0.5) / count
    g = -np.log1p(-u)
    return g * (total / g.sum())


def exact_mix(count: int, shares: list, r: np.random.Generator) -> np.ndarray:
    """Indices into ``shares`` with each share's count exact (largest
    remainder), in an order drawn from ``r``."""
    shares = np.asarray(shares, dtype=np.float64)
    want = shares / shares.sum() * count
    base = np.floor(want).astype(np.int64)
    short = count - int(base.sum())
    base[np.argsort(-(want - base), kind="stable")[:short]] += 1
    return r.permutation(np.repeat(np.arange(len(shares)), base))


def zipf_ranks(count: int, support: int, exponent: float,
               r: np.random.Generator) -> np.ndarray:
    """``count`` 0-based ranks with P(rank k) proportional to
    ``(k + 1) ** -exponent`` over ``support`` ranks."""
    w = np.arange(1, support + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(w)
    return np.minimum(np.searchsorted(cdf, r.random(count) * cdf[-1],
                                      side="right"), support - 1)


def point_requests(traffic: dict, count: int, support: int, block: int):
    """One block of point requests: ``(kind_idx, src_rank, tgt_rank)``,
    drawn from the traffic file's ``base_seed`` and ``block`` (the warm-up
    prefix and the window are separate blocks), the same for every run.
    Kinds hold their shares exactly."""
    base = np.random.default_rng([int(traffic["base_seed"]), block])
    kinds = exact_mix(count, [k["share"] for k in traffic["kinds"]], base)
    src = zipf_ranks(count, support, traffic["zipf_exponent"], base)
    tgt = zipf_ranks(count, support, traffic["zipf_exponent"], base)
    return kinds, src, tgt


def popularity_order(n: int, src: np.ndarray, seed: int,
                     base_seed: int) -> np.ndarray:
    """Vertex named by each popularity rank. The ranking is drawn once,
    from the traffic file's ``base_seed``, over the non-isolated vertices of
    the configuration's base graph; ``seed`` only names them by its labels.
    So every seed sends its requests to the same base vertices, and the
    requests do the same work."""
    perm = labels(n, seed)
    base = np.nonzero(np.bincount(src, minlength=n)[perm] > 0)[0]
    order = np.random.default_rng([int(base_seed), _RANKS])
    return perm[base[order.permutation(base.size)]]


def arrival_times(count: int, seconds: float, seed: int) -> np.ndarray:
    """Scheduled send times in ``[0, seconds)``: the fixed gap multiset of
    :func:`exp_gaps` in an order drawn from ``seed``, first send at 0."""
    gaps = rng(seed, _ARRIVALS).permutation(exp_gaps(count, seconds))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def sample(count: int, k: int, seed: int, tag: int = 0) -> np.ndarray:
    """Sorted sample of ``min(k, count)`` distinct positions in
    ``range(count)``, drawn from ``seed``."""
    r = rng(seed, _SAMPLE + 16 * tag)
    return np.sort(r.choice(count, size=min(k, count), replace=False))


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length
    (Vitter's algorithm R), its draws made from ``seed``: it holds at most
    ``k`` items however long the stream runs."""

    def __init__(self, k: int, seed: int, tag: int = 0):
        self.k, self.seen, self.items = k, 0, []
        self._r = rng(seed, _SAMPLE + 16 * tag)

    def offer(self, item) -> None:
        if self.seen < self.k:
            self.items.append(item)
        else:
            j = int(self._r.integers(self.seen + 1))
            if j < self.k:
                self.items[j] = item
        self.seen += 1
