"""Recipes, loops and entries found by name from files of their own
(``bench/lookup.py``), held to what they drew before they were files:
the constants below were taken from the commit in which ``bench/fields.py``
and ``bench/load.py`` still held them as tables. And the ``steps`` loop:
a closed loop over whole steps."""
import concurrent.futures as cf
import contextlib
import hashlib
import json
import threading
import time
import types

import numpy as np
import pytest

from bench import fields, harness, lookup
from bench.tests.test_bench_cells import SEED

CONTENT_SEED = 3000000014

#: sha256 of the float32 bytes of each pool member in turn
POOLS = {
    ("nyx_like", (12, 12, 12), 2, None):
        "ef912acde30d9b3b4b0930a9750c5bc88babebe70ca3beb9bb7ed767a499a1b8",
    ("nyx_like", (12, 12, 12), 2, CONTENT_SEED):
        "e7af1ee8fac47019787ce5fb3fbdcf74a2198d69b656ca0f5529afa41fe7226e",
    ("climate_like", (16, 32), 3, None):
        "24f520865de258ff62c827992704a0226bf389eae5e5a04408cadd6423ced34f",
    ("climate_like", (16, 32), 3, CONTENT_SEED):
        "d9049bee6f377781507eced74347428d70353f539be8956fcf761ce846d7658b",
}

#: (pool item of each request, requests whose answers were kept) over a
#: pool of 5, sample 3: closed for 23 ticks of one tick a call; open at
#: 4 req/s for 5 s
LOOPS = {
    "closed": ([0, 4, 1, 2, 3, 0, 4, 1, 2, 3, 0, 4, 1, 2, 3, 0, 4, 1, 2, 3,
                0, 4, 1], [4, 19, 20]),
    "open": ([3, 1, 3, 2, 4, 2, 4, 2, 1, 3, 4, 4, 0, 0, 2, 3, 0, 1, 1, 0],
             [5, 9, 11]),
}


def _sha(pool):
    h = hashlib.sha256()
    for f in pool:
        h.update(np.ascontiguousarray(f, np.float32).tobytes())
    return h.hexdigest()


def _no_span(_name):
    return contextlib.nullcontext()


@pytest.mark.parametrize("key", sorted(POOLS, key=str),
                         ids=lambda k: f"{k[0]}-{k[3]}")
def test_a_pool_is_what_it_was_before_recipes_were_files(key):
    recipe, shape, n, content_seed = key
    pool = fields.field_pool(recipe, shape, n, SEED, content_seed)
    assert _sha(pool) == POOLS[key]


def test_a_list_of_recipes_makes_member_i_by_recipe_i_mod_len():
    shape, n = (8, 10, 12), 3
    mixed = fields.field_pool(["nyx_like", "climate_like"], shape, n, SEED)
    nyx = fields.field_pool("nyx_like", shape, n, SEED)
    climate = fields.field_pool("climate_like", shape, n, SEED)
    for i, want in enumerate([nyx[0], climate[1], nyx[2]]):
        np.testing.assert_allclose(mixed[i], want, rtol=1e-5, atol=1e-5)
    assert mixed[0].min() > 0 and climate[1].min() < 0


@pytest.mark.parametrize("kind,known", [
    ("recipes", "nyx_like"), ("loops", "steps"), ("entries", "service"),
    ("metrics", "setup_s")])
def test_an_unknown_name_fails_with_the_known_ones(kind, known):
    with pytest.raises(LookupError) as err:
        lookup.find(kind, "no_such_name")
    assert known in str(err.value) and "no_such_name" in str(err.value)
    assert known in lookup.known(kind)


def test_a_pool_of_an_unknown_recipe_fails_with_the_known_ones():
    with pytest.raises(LookupError, match="climate_like"):
        fields.field_pool("no_such_recipe", (4, 4), 1, SEED)


class _Clock:
    """A clock that only calls and sleeps move: one tick a call."""

    def __init__(self):
        self.t = 0.0

    def perf_counter(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 0.0)


class _Echo:
    """An entry that answers with a copy of the item, at once; a call
    takes one tick of ``clock``, a submit ``submit_ticks``."""

    def __init__(self, clock, submit_ticks=0.0):
        self.clock = clock
        self.submit_ticks = submit_ticks

    def call(self, op, item):
        self.clock.t += 1.0
        return item.copy()

    def submit(self, op, item):
        self.clock.t += self.submit_ticks
        fut = cf.Future()
        fut.set_result(item.copy())
        return fut


@pytest.mark.parametrize("name", sorted(LOOPS))
def test_a_loop_draws_what_it_drew_before_loops_were_files(name,
                                                           monkeypatch):
    loop = lookup.find("loops", name)
    clock = _Clock()
    monkeypatch.setattr(loop, "time", clock)
    items = [np.full(k + 1, k, np.uint8) for k in range(5)]
    rng = np.random.default_rng(np.random.SeedSequence([SEED, 2]))
    traffic = {"rate_per_s": 4.0, "schedule_seed": 1, "drain_s": 5}
    seconds = 23.0 if name == "closed" else 5.0
    reqs = loop.run(_Echo(clock), "decompress", items, seconds, rng,
                    traffic, 3, _no_span)
    assert all(r.ok for r in reqs)
    assert ([r.item for r in reqs],
            [r.i for r in reqs if r.answer is not None]) == LOOPS[name]


class _Slow:
    """An entry whose answer to item ``k`` arrives ``delay * (k + 1)``
    seconds after its submit, from a thread of its own, as a service's
    do."""

    def __init__(self, delay):
        self.delay = delay
        self.pool = cf.ThreadPoolExecutor(4)

    def submit(self, op, item):
        def answer():
            time.sleep(self.delay * (int(item[0]) + 1))
            return item.copy()
        return self.pool.submit(answer)

    def close(self):
        self.pool.shutdown(wait=True)


def _steps(entry, seconds, step=3, pool=4, sample=2):
    items = [np.full(k + 1, k, np.uint8) for k in range(pool)]
    rng = np.random.default_rng(SEED)
    return lookup.find("loops", "steps").run(
        entry, "decompress", items, seconds, rng, {"step": step}, sample,
        _no_span)


def test_a_step_waits_for_all_of_its_members():
    entry = _Slow(0.02)
    try:
        reqs = _steps(entry, 0.5)
    finally:
        entry.close()
    assert len(reqs) % 3 == 0 and len(reqs) >= 6
    steps = [reqs[s:s + 3] for s in range(0, len(reqs), 3)]
    for step in steps:
        assert sorted(r.item for r in step) == [0, 1, 2]
        assert all(r.ok and r.done >= r.sent for r in step)
    for this, after in zip(steps, steps[1:]):
        assert min(r.sent for r in after) >= max(r.done for r in this)
    # each step draws its own order
    assert len({tuple(r.item for r in s) for s in steps}) > 1
    assert sum(r.answer is not None for r in reqs) == 2


def test_the_step_in_flight_at_the_close_counts(monkeypatch):
    # a submit takes a quarter tick: the second step starts at 0.75,
    # before the close at 1, and ends at 1.5, after it
    clock = _Clock()
    monkeypatch.setattr(lookup.find("loops", "steps"), "time", clock)
    reqs = _steps(_Echo(clock, submit_ticks=0.25), 1.0)
    assert len(reqs) == 6 and all(r.ok for r in reqs)
    assert min(r.sent for r in reqs[3:]) < 1.0 < max(r.done for r in reqs)


def test_a_one_shot_entry_is_refused():
    entry = lookup.find("entries", "one_shot").start(
        types.SimpleNamespace())
    with pytest.raises(TypeError, match="no submit"):
        _steps(entry, 0.1)


def test_a_step_larger_than_the_pool_is_refused():
    with pytest.raises(ValueError, match="pool holds 2"):
        _steps(_Echo(_Clock()), 0.1, step=3, pool=2)


def test_a_warmed_steps_run_compiles_nothing_in_its_window(tmp_path):
    """After the service's warm-up, the window of a ``steps`` rehearsal
    lowers only the Lorenzo transform, ``jit(wrapped)``, which the
    program lowers again on every call (a program cost the benchmark
    records, not one of the harness)."""
    root = tmp_path
    (root / "bench").symlink_to(harness.BENCH)
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "cesm-atm.steps", "config": "cesm-atm",
                               "traffic": "service", "chips": 1, "why": "t"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    lines = []
    lock = threading.Lock()

    def log(line):
        with lock:
            lines.append(line)

    out = harness.run_cell(
        "cesm-atm.steps", SEED, 1.0, False, require_tpu=False, root=root,
        overrides={"config": {"shape": [16, 32]},
                   "traffic": {"loop": "steps", "step": 3, "pool": 3,
                               "check_sample": 3}},
        log=log)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] % 3 == 0 and out["failed"] == 0
    window = [json.loads(ln.split(" ", 2)[2]) for ln in lines
              if ln.startswith("# compiles_in_window ")]
    assert len(window) == 1
    assert set(window[0]["lowered_by_name"]) <= {"jit(wrapped)"}, window
