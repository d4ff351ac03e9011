"""A whole run of each cell on the CPU at a tiny size, without the chip
check: a sound program comes out correct, and each fault planted in the
timed path underneath makes ``correct`` come out false.

    python -m pytest bench/tests
"""
import numpy as np
import pytest

import run

SEED = 2**31 + 4242


def tiny(name: str, seconds: float = 3.0) -> dict:
    r = run.load_cell(name)
    r["config"]["scale"] = 9
    r["config"]["engine"]["edge_chunk"] = 2048
    r.update(seed=SEED, seconds=seconds, trace=False, rate=None)
    if r["traffic"]["driver"] == "open_loop":
        r["traffic"].update(rate_qps=12.0, drain_s=60.0)
    else:
        r["traffic"].update(max_sets=4)
    return r


CELLS = ["graph500.point-zipf", "gap-urand.bfs"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run.run_cell(tiny(name), require_tpu=False)
    assert res["correct"] and res["failed"] == 0
    assert res["checks"]["wrong_answers"]["value"] == 0
    assert set(res["metrics"]) >= {"setup_s"}


def altered(orig):
    """An answer altered where it is produced: one vertex's level moved."""
    def unpack(q, row, **kw):
        res = orig(q, row, **kw)
        if isinstance(res, dict):
            t = next(iter(res))
            return {**res, t: res[t] + 1}
        res = np.array(res)
        res[np.argmax(res != res[0]) if res.dtype != bool else 0] ^= 1
        return res
    return unpack


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(name, monkeypatch):
    from repro.serve import engine

    monkeypatch.setattr(engine, "unpack_result", altered(engine.unpack_result))
    res = run.run_cell(tiny(name), require_tpu=False)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_one_lane_altered_in_every_set_is_not_correct(monkeypatch):
    from repro.serve import BFSServeEngine

    orig = BFSServeEngine.submit_many

    def submit_many(self, queries):
        res = orig(self, queries)
        last = np.array(res[-1])
        last[np.argmax(last != last[0])] += 1
        return res[:-1] + [last]

    monkeypatch.setattr(BFSServeEngine, "submit_many", submit_many)
    res = run.run_cell(tiny("gap-urand.bfs"), require_tpu=False)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_half_the_requests_left_out_is_not_correct(monkeypatch):
    from repro.serve import ServeFrontend

    orig = ServeFrontend.submit
    count = [0]

    def submit(self, sess, queries):
        count[0] += 1
        if count[0] > 1 and count[0] % 2:   # after the warm-up prefix call
            return 0
        return orig(self, sess, queries)

    monkeypatch.setattr(ServeFrontend, "submit", submit)
    r = tiny("graph500.point-zipf")
    r["traffic"]["drain_s"] = 2.0
    res = run.run_cell(r, require_tpu=False)
    assert not res["correct"] and res["failed"] > 0


def test_half_the_key_set_left_out_is_not_correct(monkeypatch):
    from repro.serve import BFSServeEngine

    orig = BFSServeEngine.submit_many

    def submit_many(self, queries):
        half = len(queries) // 2
        return orig(self, queries[:half]) + [None] * (len(queries) - half)

    monkeypatch.setattr(BFSServeEngine, "submit_many", submit_many)
    res = run.run_cell(tiny("gap-urand.bfs"), require_tpu=False)
    assert not res["correct"]
    assert res["checks"]["unanswered"]["value"] > 0
