"""The yardstick's own arithmetic and generators, on the CPU at tiny sizes.

    python -m pytest bench/tests
"""
import json
import os

import numpy as np
import pytest

import control
import gen
import reference
import run

ROOT = run.ROOT
SEED = 2**31 + 977          # wider than 32 signed bits, as run seeds may be


def test_generators_are_deterministic_per_seed():
    cfgs = [run.load_json("bench/configs/graph500.json"),
            run.load_json("bench/configs/gap-urand.json")]
    for cfg in cfgs:
        cfg = dict(cfg, scale=8)
        a, b, c = (gen.make_graph(cfg, s) for s in (SEED, SEED, SEED + 1))
        assert a[0] == 256
        assert np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
        assert not np.array_equal(a[1], c[1])
        n, src, dst = a
        assert src.size == dst.size and not (src == dst).any()
        # edge-doubled: the multiset of (u, v) equals that of (v, u)
        fwd = np.sort(src * n + dst)
        assert np.array_equal(fwd, np.sort(dst * n + src))
        assert src.size <= 2 * cfg["edge_factor"] * n
        # another seed relabels the same edge multiset: the degree sequence,
        # and so every compiled shape, stays
        assert np.array_equal(np.sort(np.bincount(src, minlength=n)),
                              np.sort(np.bincount(c[1], minlength=n)))


def test_labels_are_the_relabelling_of_the_base_graph():
    cfg = dict(run.load_json("bench/configs/graph500.json"), scale=8)
    (n, a, _), (_, b, _) = (gen.make_graph(cfg, s) for s in (SEED, 7))
    base = [np.argsort(gen.labels(n, s))[e] for s, e in ((SEED, a), (7, b))]
    assert np.array_equal(base[0], base[1])


def test_popular_vertices_are_the_same_base_vertices_every_seed():
    cfg = dict(run.load_json("bench/configs/graph500.json"), scale=10)
    traffic = run.load_json("bench/traffic/point-zipf.json")
    named = []
    for s in (SEED, 7):
        n, src, _ = gen.make_graph(cfg, s)
        names = gen.popularity_order(n, src, s, traffic["base_seed"])
        assert np.array_equal(np.sort(names), gen.non_isolated(n, src))
        named.append(np.argsort(gen.labels(n, s))[names])
    assert np.array_equal(named[0], named[1])


@pytest.mark.parametrize("config", ["graph500", "graph500-s20"])
def test_fixed_keys_are_the_same_base_vertices_every_seed(config):
    cfg = dict(run.load_json(f"bench/configs/{config}.json"), scale=10)
    traffic = run.load_json("bench/traffic/bfs-fixed.json")
    based, lanes = [], []
    for s in (SEED, 7):
        n, src, _ = gen.make_graph(cfg, s)
        sets = run.key_sets(cfg, traffic, n, src, s)
        keys = np.concatenate(sets)
        assert all(k.size == traffic["keys_per_set"] for k in sets)
        # a Kronecker graph this small has fewer non-isolated vertices
        # than max_sets whole sets ask for: as many whole sets as there are
        assert len(sets) == gen.non_isolated(n, src).size // sets[0].size
        assert np.unique(keys).size == keys.size
        assert np.isin(keys, gen.non_isolated(n, src)).all()
        base = np.argsort(gen.labels(n, gen.label_seed(cfg, s)))[keys]
        lanes.append(base)
        based.append([np.sort(b) for b in np.split(base, len(sets))])
    # each set holds the same base vertices for every seed, on other lanes
    assert all(np.array_equal(a, b) for a, b in zip(*based))
    assert not np.array_equal(lanes[0], lanes[1])


def test_a_label_seed_gives_every_seed_the_same_graph():
    cfg = dict(run.load_json("bench/configs/graph500-s20.json"), scale=8)
    assert gen.label_seed(cfg, SEED) == cfg["label_seed"]
    (n, a, c), (_, b, d) = (gen.make_graph(cfg, s) for s in (SEED, 7))
    assert np.array_equal(a, b) and np.array_equal(c, d)
    # the same graph as the seed-labelled configuration's at that seed
    free = {k: v for k, v in cfg.items() if k != "label_seed"}
    assert np.array_equal(gen.make_graph(free, cfg["label_seed"])[1], a)


def test_seed_drawn_keys_are_unchanged_without_a_base_seed():
    cfg = dict(run.load_json("bench/configs/gap-urand.json"), scale=10)
    traffic = run.load_json("bench/traffic/bfs.json")
    assert "base_seed" not in traffic
    size = traffic["keys_per_set"]
    for s in (SEED, 7):
        n, src, _ = gen.make_graph(cfg, s)
        cand = gen.non_isolated(n, src)
        count = size * traffic["max_sets"]
        want = gen.rng(s, 2).choice(cand, size=min(count, cand.size),
                                    replace=False)
        assert np.array_equal(want, gen.search_keys(n, src, count, s))
        sets = run.key_sets(cfg, traffic, n, src, s)
        whole = want.size // size * size
        assert np.array_equal(np.concatenate(sets), want[:whole])


def test_rmat_skew_and_urand_uniformity():
    n, src, _ = gen.make_graph(
        dict(run.load_json("bench/configs/graph500.json"), scale=12), 3)
    deg = np.bincount(src, minlength=n)
    assert deg.max() > 20 * deg.mean()          # Kronecker hubs
    n, src, _ = gen.make_graph(
        dict(run.load_json("bench/configs/gap-urand.json"), scale=12), 3)
    deg = np.bincount(src, minlength=n)
    assert deg.max() < 3 * deg.mean()           # Poisson(32)
    assert abs(deg.mean() - 32) < 0.5


def test_search_keys_are_distinct_non_isolated_and_seeded():
    n, src = 64, np.array([0, 1, 1, 5, 9, 9, 9, 40], dtype=np.int64)
    keys = gen.search_keys(n, src, 4, SEED)
    assert len(set(keys.tolist())) == 4
    assert set(keys.tolist()) <= {0, 1, 5, 9, 40}
    assert np.array_equal(keys, gen.search_keys(n, src, 4, SEED))
    assert sorted(gen.search_keys(n, src, 99, SEED).tolist()) == [0, 1, 5, 9,
                                                                  40]


def test_arrivals_keep_rate_and_exponential_shape():
    count, seconds = 2000, 50.0
    t = gen.arrival_times(count, seconds, SEED)
    assert t[0] == 0.0 and np.all(np.diff(t) > 0) and t[-1] < seconds
    gaps = np.diff(np.append(t, seconds))
    assert gaps.sum() == pytest.approx(seconds)
    # exponential: the median gap is ln 2 of the mean, the spread equals it
    assert np.median(gaps) / gaps.mean() == pytest.approx(np.log(2), rel=0.02)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.05)
    # every seed offers the same gaps, in another order
    other = np.diff(np.append(gen.arrival_times(count, seconds, 5), seconds))
    assert np.allclose(np.sort(gaps), np.sort(other))
    assert not np.allclose(gaps, other)


def test_point_mix_holds_its_shares_and_zipf_shape():
    traffic = run.load_json("bench/traffic/point-zipf.json")
    kinds, src, tgt = gen.point_requests(traffic, 1000, 1 << 20, 1)
    shares = [k["share"] for k in traffic["kinds"]]
    assert np.bincount(kinds, minlength=4).tolist() == [
        round(1000 * s) for s in shares]
    # Zipf(1): rank 0 is drawn about 1 / H(2**20) of the time, rank 9 a
    # tenth as often
    big = gen.zipf_ranks(200000, 1 << 20, 1.0, np.random.default_rng(0))
    top = np.mean(big == 0)
    assert top == pytest.approx(1 / np.sum(1 / np.arange(1, 2**20 + 1)),
                                rel=0.05)
    assert np.mean(big == 9) == pytest.approx(top / 10, rel=0.2)
    # every run sends the same requests; another block other ones
    k2, s2, _ = gen.point_requests(traffic, 1000, 1 << 20, 1)
    assert np.array_equal(kinds, k2) and np.array_equal(src, s2)
    assert not np.array_equal(src, gen.point_requests(traffic, 1000,
                                                      1 << 20, 0)[1])


def test_teps_counts_half_the_stored_edges_of_the_component():
    # two components: a triangle (3 undirected edges) and a path (2)
    und = [(0, 1), (1, 2), (2, 0), (3, 4), (4, 5)]
    src = np.array([u for u, v in und] + [v for u, v in und])
    dst = np.array([v for u, v in und] + [u for u, v in und])
    g = reference.Graph(7, src, dst)
    assert g.teps_edges([0, 2, 3, 5]).tolist() == [3, 3, 2, 2]
    assert g.teps_edges([6]).tolist() == [0]


def test_reference_bfs_and_answers():
    und = [(0, 1), (1, 2), (2, 3), (0, 4), (5, 6)]
    src = np.array([u for u, v in und] + [v for u, v in und])
    dst = np.array([v for u, v in und] + [u for u, v in und])
    g = reference.Graph(7, src, dst)
    U = reference.UNREACHED
    lev = g.levels([0])[0]
    assert lev.tolist() == [0, 1, 2, 3, 1, U, U]
    assert reference.answer("distance_limited", lev, 2).tolist() == [
        0, 1, 2, U, 1, U, U]
    assert reference.answer("multi_target", lev, None, (3, 6)) == {3: 3,
                                                                     6: U}
    assert reference.answer("reachability", lev).tolist() == [
        True] * 5 + [False] * 2
    assert reference.same({3: 3, 6: U}, {3: 3, 6: U})
    assert not reference.same(lev.astype(bool), lev)
    assert control.truncated(lev).tolist() == [0, 1, 2, U, 1, U, U]


def test_reference_levels_match_a_queue_bfs_for_many_sources():
    from collections import deque

    n, src, dst = gen.make_graph(
        dict(run.load_json("bench/configs/graph500.json"), scale=9), 5)
    g = reference.Graph(n, src, dst)
    adj = [[] for _ in range(n)]
    for u, v in zip(src.tolist(), dst.tolist()):
        adj[u].append(v)
    sources = list(range(0, n, 5))          # more than one 64-bit word
    got = g.levels(sources)
    for s in sources:
        want = [reference.UNREACHED] * n
        want[s] = 0
        q = deque([s])
        while q:
            u = q.popleft()
            for v in adj[u]:
                if want[v] == reference.UNREACHED:
                    want[v] = want[u] + 1
                    q.append(v)
        assert got[s].tolist() == want


def test_percentile_is_nearest_rank():
    v = list(range(1, 11))
    assert run.percentile(v, 50) == 5 and run.percentile(v, 90) == 9
    assert run.percentile(v, 100) == 10 and run.percentile([7.0], 90) == 7.0


def test_open_loop_metrics_cover_every_request_and_the_whole_window():
    due = np.array([0.0, 1.0, 2.0, 3.0, 9.0])
    done = np.array([0.5, 4.0, np.nan, 12.0, 9.1])
    m = run.open_loop_metrics(due, done, seconds=10.0, end=20.0)
    # answered inside the window: three of five, over all 10 s
    assert m["served_qps"] == pytest.approx(0.3)
    # latencies 0.5, 3.0, 18.0 (never answered: up to the drain's end),
    # 9.0, 0.1 -> median 3.0, nearest-rank p90 18.0
    assert m["latency_p50_s"] == pytest.approx(3.0)
    assert m["latency_p90_s"] == pytest.approx(18.0)


def specs():
    """``BENCHMARK.json`` and the cells that wait to join it."""
    out = []
    for rel in run.SPECS:
        with open(os.path.join(ROOT, rel)) as f:
            out.append(json.load(f))
    return out


def test_every_cell_resolves_to_its_files_by_name():
    bench, pending = specs()
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
    for spec in (bench, pending):
        for w in spec["workloads"]:
            cell = run.load_cell(w["name"])
            assert cell["cell"] == w
            assert cell["traffic"]["driver"] in run.DRIVERS
            assert cell["config"]["generator"] in ("rmat", "urand")
            assert cell["end_to_end"] and cell["per_layer"]
            for m in cell["per_layer"]:
                assert callable(run.reader(m["name"]))
                assert m["moves"] in {e["name"] for e in cell["end_to_end"]}
        assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    names = {w["name"] for w in bench["workloads"]}
    assert not names & {w["name"] for w in pending["workloads"]}


def test_metric_readers_read_nothing_from_an_empty_window():
    ctx = {"sent": 0, "no_lane": 0, "stats": {}, "spans": [],
           "reduced": None}
    for spec in specs():
        for m in spec["per_layer"]:
            assert run.reader(m["name"])(ctx) is None, m["name"]


def test_reservoir_is_bounded_uniform_and_seeded():
    def draw(seed, n=200, k=8):
        r = gen.Reservoir(k, seed)
        for i in range(n):
            r.offer(i)
        return sorted(r.items)

    assert draw(SEED) == draw(SEED) and draw(SEED) != draw(SEED + 1)
    assert len(draw(SEED)) == 8 and len(set(draw(SEED))) == 8
    assert draw(SEED, n=5) == [0, 1, 2, 3, 4]
    # every position is about equally likely: k / n each
    hits = np.bincount(np.concatenate([draw(s) for s in range(2000)]),
                       minlength=200)
    assert hits.mean() == pytest.approx(2000 * 8 / 200)
    assert hits.min() > 40 and hits.max() < 120


def test_key_set_checks_hold_the_first_set_whole_and_a_bounded_sample():
    traffic = run.load_json("bench/traffic/bfs.json")
    size = traffic["keys_per_set"]
    c = run.KeySetChecks(traffic, SEED)
    for i in range(20):
        keys = np.arange(i * size, (i + 1) * size)
        c.add(i, keys, [f"answer {k}" for k in keys])
    got = c.all()
    first = [d["source"] for d in got[:size]]
    assert first == list(range(size))
    later = [d["source"] for d in got[size:]]
    assert len(later) == traffic["check_sample"] and min(later) >= size
    assert len(set(later)) == len(later)
    assert all(d["answer"] == f"answer {d['source']}" for d in got)


def test_host_ms_per_boundary_takes_the_union_of_nested_spans():
    spans = [("serve.boundary", 0.0, 0.010), ("serve.gather", 0.001, 0.004),
             ("serve.reseed", 0.006, 0.002), ("serve.boundary", 1.0, 0.004),
             ("serve.gather.deferred", 1.010, 0.006), ("serve.poll", 0, 5)]
    v = run.reader("host_ms_per_boundary.point")({"spans": spans})
    assert v == pytest.approx((10 + 4 + 6) / 2)


def test_control_fails_the_comparison_on_three_seeds():
    for name in ("graph500.point-zipf", "gap-urand.bfs",
                 "graph500-s20.bfs-fixed"):
        c = run.load_cell(name)
        c["config"]["scale"] = 10
        for seed in (SEED, 11, 12):
            res = control.control(c, seed, seconds=51.0, sets=3)
            assert res["compared"] > 0
            assert res["wrong_answers"] > 0, (name, seed, res)
