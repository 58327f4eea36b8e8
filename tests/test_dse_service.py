"""DSE-as-a-service: bit-parity vs direct campaigns under concurrency,
cache-served repeat queries, retry on poisoned devices, admission control,
and the ResultCache store itself."""
import threading

import pytest

from repro.core.engine import row_cache_key
from repro.core.mapper import GAConfig, search_campaign
from repro.core.result_cache import ResultCache
from repro.core.spec import make_variant
from repro.core.workloads import conv, dwconv
from repro.runtime.ft import FaultInjector
from repro.serve import DSEService

CFG = GAConfig(population=8, generations=3, seed=0)
SPEC = make_variant("1111")


def _model_a():
    # a1 == a2 by shape -> dedups within the request
    return [conv("a1", 16, 8, 14, 14, 3, 3),
            conv("a2", 16, 8, 14, 14, 3, 3),
            conv("a3", 32, 16, 7, 7, 1, 1)]


def _model_b():
    # b1 shares a1's shape AND first-occurrence seed -> dedups ACROSS requests
    return [conv("b1", 16, 8, 14, 14, 3, 3),
            dwconv("b2", 16, 14, 14, 3, 3)]


def _assert_same(got, want):
    """Bit-identical ModelResults (floats compared with ==, not allclose)."""
    assert got.runtime == want.runtime
    assert got.energy == want.energy
    assert got.edp == want.edp
    assert len(got.per_layer) == len(want.per_layer)
    for g, w in zip(got.per_layer, want.per_layer):
        assert g.runtime == w.runtime and g.energy == w.energy
        assert g.feasible == w.feasible
        assert g.history == w.history


# -- service ---------------------------------------------------------------


def test_concurrent_clients_bit_identical_to_solo_campaign():
    """N client threads, overlapping models, distinct GA seeds: every answer
    must equal a direct search_campaign for that request alone — the packing
    of rows from different clients into shared waves must never leak."""
    requests = [(_model_a(), SPEC, CFG),
                (_model_b(), SPEC, CFG),
                (_model_a(), SPEC, GAConfig(population=8, generations=3,
                                            seed=11)),
                (_model_b(), SPEC, GAConfig(population=8, generations=3,
                                            seed=11, objective="energy"))]
    want = [search_campaign([(layers, spec)], cfg)[0]
            for layers, spec, cfg in requests]

    with DSEService() as svc:
        got = [None] * len(requests)
        errs = []

        def client(i):
            layers, spec, cfg = requests[i]
            try:
                got[i] = svc.query(layers, spec, cfg, timeout=300)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(requests))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        for g, w in zip(got, want):
            _assert_same(g, w)
        stats = svc.stats()
    assert stats["queries"] == len(requests)
    # within- and cross-request dedup: fewer rows dispatched than planned
    assert stats["rows_dispatched"] < stats["rows_planned"]


def test_repeat_query_served_from_cache_without_dispatch():
    with DSEService() as svc:
        first = svc.query(_model_a(), SPEC, CFG, timeout=300)
        dispatched = svc.stats()["rows_dispatched"]
        misses = svc.cache.stats()["misses"]
        again = svc.query(_model_a(), SPEC, CFG, timeout=300)
        _assert_same(again, first)
        assert svc.stats()["rows_dispatched"] == dispatched
        assert svc.cache.stats()["misses"] == misses
        assert svc.cache.stats()["hits"] > 0


def test_cache_persists_across_service_restarts(tmp_path):
    path = str(tmp_path / "rows.pkl")
    with DSEService() as svc:
        want = svc.query(_model_a(), SPEC, CFG, timeout=300)
        svc.cache.save(path)
    cache = ResultCache()
    cache.load(path)
    with DSEService(cache=cache) as svc2:
        got = svc2.query(_model_a(), SPEC, CFG, timeout=300)
        _assert_same(got, want)
        assert svc2.stats()["rows_dispatched"] == 0


def test_poisoned_device_mid_campaign_retries():
    """First engine dispatch raises (the shape a lost device takes after
    run_batched_ga drains its in-flight queue); the service must retry per
    the runtime.ft restart discipline and still answer bit-identically."""
    want = search_campaign([(_model_b(), SPEC)], CFG)[0]
    with DSEService(fault_injector=FaultInjector((0,))) as svc:
        got = svc.query(_model_b(), SPEC, CFG, timeout=300)
        _assert_same(got, want)
        assert svc.stats()["retries"] == 1
    # nothing is cached from a failed dispatch: the retry started clean
    # (rows_dispatched counts unique fresh keys once)


def test_retries_exhausted_rejects_clients_not_service():
    with DSEService(fault_injector=FaultInjector((0, 1)),
                    max_retries=1) as svc:
        with pytest.raises(RuntimeError, match="after 2 attempts"):
            svc.query(_model_b(), SPEC, CFG, timeout=300)
        # the dispatcher survives a failed wave: next query still runs
        want = search_campaign([(_model_a(), SPEC)], CFG)[0]
        _assert_same(svc.query(_model_a(), SPEC, CFG, timeout=300), want)


def test_oversized_query_rejected_with_progress():
    with DSEService(max_wave_rows=1) as svc:
        with pytest.raises(ValueError, match="max_wave_rows"):
            svc.query(_model_a(), SPEC, CFG, timeout=60)
        small = [conv("s", 8, 8, 7, 7, 3, 3)]
        want = search_campaign([(small, SPEC)], CFG)[0]
        _assert_same(svc.query(small, SPEC, CFG, timeout=300), want)
        assert svc.stats()["rejected"] == 1


def test_submit_after_close_raises():
    svc = DSEService()
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(_model_a(), SPEC, CFG)


def test_cache_stats_reports_all_stores():
    with DSEService() as svc:
        svc.query(_model_a(), SPEC, CFG, timeout=300)
        stats = svc.cache_stats()
    assert set(stats) >= {"mapper_rows", "reference", "order", "pair",
                          "shape", "repr"}
    assert stats["mapper_rows"]["misses"] > 0


# -- ResultCache store -----------------------------------------------------


def test_result_cache_lru_bound_and_counters():
    c = ResultCache(maxsize=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1          # touch: a becomes most-recent
    c.put("c", 3)                   # evicts b
    assert c.get("b") is None
    assert c.get("a") == 1 and c.get("c") == 3
    s = c.stats()
    assert s["evictions"] == 1 and s["misses"] == 1 and s["hits"] == 3
    assert len(c) == 2


def test_result_cache_merge_first_writer_wins():
    c = ResultCache()
    assert c.merge("k", 1) == 1
    assert c.merge("k", 2) == 1     # setdefault semantics
    assert c.get("k") == 1


def test_result_cache_pair_ops_atomic():
    c = ResultCache(maxsize=64)
    assert c.get_pair("s", "h") is None
    a, b = c.merge_pair("s", 10, "h", 20)
    assert (a, b) == (10, 20)
    assert c.get_pair("s", "h") == (10, 20)
    # a half-present pair reads as a miss, and merge replaces BOTH halves
    # (the surviving half is stale once its partner was evicted)
    c2 = ResultCache(maxsize=64)
    c2.put("s", 10)
    assert c2.get_pair("s", "h") is None
    assert c2.merge_pair("s", 99, "h", 20) == (99, 20)
    assert c2.get_pair("s", "h") == (99, 20)


def test_result_cache_thread_safety_under_contention():
    c = ResultCache(maxsize=128)

    def worker(seed):
        for i in range(200):
            k = (seed * 7 + i) % 64
            got = c.merge(k, k * 2)
            assert got == k * 2     # value is a pure function of the key
            c.get(k)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = c.stats()
    assert s["size"] <= 128
    assert s["hits"] + s["misses"] == 8 * 200


def test_row_cache_key_excludes_names_and_placement():
    cfg1 = GAConfig(population=8, generations=3, pipeline=False)
    cfg2 = GAConfig(population=8, generations=3, pipeline=True, devices=2)
    rows1 = _rows(_model_a(), cfg1)
    rows2 = _rows([conv("other-name", 16, 8, 14, 14, 3, 3),
                   conv("x", 16, 8, 14, 14, 3, 3),
                   conv("y", 32, 16, 7, 7, 1, 1)], cfg2)
    assert [row_cache_key(r, cfg1) for r in rows1] == \
           [row_cache_key(r, cfg2) for r in rows2]


def _rows(layers, cfg):
    from repro.core.mapper import plan_model_rows, request_rows
    row_index, _ = plan_model_rows(layers)
    return request_rows(layers, SPEC, cfg, row_index)


def test_interrupted_save_leaves_previous_snapshot_intact(tmp_path,
                                                          monkeypatch):
    """A crash mid-save (killed service, full disk) must not clobber the
    previous complete snapshot with a truncated pickle — save writes a
    temp file and os.replace()s it into place only on success."""
    import pickle as _pickle

    from repro.core import result_cache as rc_mod

    path = str(tmp_path / "rows.pkl")
    cache = ResultCache()
    cache.put("k", 1)
    assert cache.save(path) == 1

    cache.put("k2", 2)

    def _dump_partial_then_die(items, f):
        f.write(b"\x80\x04corrupt")          # truncated-pickle prefix
        raise OSError("disk full mid-save")

    monkeypatch.setattr(rc_mod.pickle, "dump", _dump_partial_then_die)
    with pytest.raises(OSError):
        cache.save(path)
    monkeypatch.setattr(rc_mod.pickle, "dump", _pickle.dump)

    # no temp droppings, and the previous snapshot still loads whole
    assert sorted(p.name for p in tmp_path.iterdir()) == ["rows.pkl"]
    fresh = ResultCache()
    assert fresh.load(path) == 1
    assert fresh.get("k") == 1


# -- runtime lock-order cross-check (the dynamic half of REP007) ------------


def test_runtime_lock_orders_subset_of_static_lock_graph(monkeypatch):
    """Wrap the four real locks in recording proxies, drive the service
    (concurrent clients + cache_stats' flexion-table pass), and assert the
    acquisition orders threads ACTUALLY took are a subset of the statically
    derived REP007 lock graph.  If call-graph resolution ever misses an
    acquisition path, the runtime edges drift outside the static set and
    this fails — the static analysis can't silently under-approximate."""
    import types
    from pathlib import Path

    from _lockorder import (DSE_SERVICE_LOCK_ID, JAX_EVAL_LOCK_ID,
                            RESULT_CACHE_LOCK_ID, TABLE_LOCK_ID,
                            LockOrderRecorder)
    from repro.analysis.walker import Project
    from repro.analysis.locksets import lock_order_edges
    from repro.core import flexion_batched as fb
    from repro.serve import dse_service

    repo = Path(__file__).resolve().parents[1]
    static = lock_order_edges(Project.load(repo))

    rec = LockOrderRecorder()
    # module-global flexion locks: the _locked_memo wrapper and
    # flexion_cache_stats resolve them by name at call time
    monkeypatch.setattr(fb, "_TABLE_LOCK",
                        rec.wrap(TABLE_LOCK_ID, threading.Lock()))
    monkeypatch.setattr(fb, "_JAX_EVAL_LOCK",
                        rec.wrap(JAX_EVAL_LOCK_ID, threading.Lock()))
    # DSEService._lock: substitute dse_service's threading module with a
    # shim whose Lock() returns a recording proxy (Condition wraps it via
    # the standard acquire/release/_release_save protocol)
    shim = types.SimpleNamespace(
        Lock=rec.lock_factory(DSE_SERVICE_LOCK_ID),
        RLock=threading.RLock, Condition=threading.Condition,
        Thread=threading.Thread, Event=threading.Event)
    monkeypatch.setattr(dse_service, "threading", shim)

    cache = ResultCache()
    rec.wrap_instance_lock(cache, RESULT_CACHE_LOCK_ID)

    with DSEService(cache=cache) as svc:
        got, errs = [None, None], []

        def client(i, layers):
            try:
                got[i] = svc.query(layers, SPEC, CFG, timeout=300)
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=client, args=(0, _model_a())),
                   threading.Thread(target=client, args=(1, _model_b()))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        svc.cache_stats()               # holds _TABLE_LOCK over the memos

    named = {TABLE_LOCK_ID, JAX_EVAL_LOCK_ID,
             RESULT_CACHE_LOCK_ID, DSE_SERVICE_LOCK_ID}
    observed = {(a, b) for a, b in rec.edges
                if a in named and b in named}
    # every runtime order must be statically predicted (today both sides
    # are empty: the tree holds no lock while taking another — an edge
    # appearing on either side alone is the regression this test pins)
    assert observed <= static, (
        f"runtime lock orders {sorted(observed - static)} not in the "
        f"static REP007 graph {sorted(static)}")
    # the recorder really saw the named locks work (guards against a
    # wrapper that silently records nothing)
    assert {DSE_SERVICE_LOCK_ID, RESULT_CACHE_LOCK_ID,
            TABLE_LOCK_ID} <= rec.acquired
    for g in got:
        assert g is not None
