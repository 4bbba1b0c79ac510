"""The multi-process worker runtime (core/workers): real spawned
worker processes behind campaign dispatch. Record parity with the
single-node in-process engine (homogeneous, pooled + prefetched +
disk-cached + adaptive — the ISSUE-5 acceptance combination),
worker-crash recovery via heartbeat liveness with pool-aware re-issue,
the first-completion-wins dedup gate (a re-issued straggler's late
results never duplicate an emitted record), cross-process warm replay
through the shared multi-process-safe DiskResultStore, and the
config validation that keeps simulation-only knobs out of the real
runtime."""
import numpy as np
import pytest

from repro.core.backends import DiskResultStore, ResultCache
from repro.core.campaign import (CampaignController, CampaignExecutor,
                                 ControllerConfig, ExecutorConfig,
                                 FaultInjection)
from repro.core.engine import AdaParseEngine, EngineConfig
from repro.core.workers import (LocalWorkerPool, ProcessWorkerPool,
                                WorkerPool)


def _assert_same_records(a: dict, b: dict):
    assert set(a) == set(b)
    for i in a:
        assert a[i].parser == b[i].parser
        assert a[i].cost_s == b[i].cost_s
        assert len(a[i].pages) == len(b[i].pages)
        for pa, pb in zip(a[i].pages, b[i].pages):
            np.testing.assert_array_equal(pa, pb)


@pytest.fixture(scope="module")
def single_run(corpus, ft_router):
    """The reference record set every process campaign must reproduce
    byte-for-byte."""
    ccfg, docs = corpus
    test = docs[75:]
    ecfg = EngineConfig(alpha=0.1, batch_size=16)
    return test, ecfg, AdaParseEngine(ecfg, ft_router, ccfg).run(test)


def test_process_pool_matches_single_node(corpus, ft_router, single_run):
    """2 real worker processes produce the byte-identical record set of
    the single-node in-process run, and both workers did real work."""
    ccfg, _ = corpus
    test, ecfg, single = single_run
    xcfg = ExecutorConfig(n_nodes=2, runtime="process")
    res = CampaignExecutor(ecfg, xcfg, ft_router, ccfg).run(test)
    _assert_same_records(single, res.records)
    assert res.wall_s > 0 and res.docs_per_s > 0
    assert all(s.n_docs > 0 for s in res.node_stats)
    assert sum(s.n_docs for s in res.node_stats) == len(test)


def test_process_pool_pools_prefetch_disk_adaptive_parity(
        corpus, ft_router, single_run, tmp_path):
    """The ISSUE-5 acceptance bar: a 4-worker fleet with heterogeneous
    pools + prefetch windows + a shared on-disk result store + adaptive
    rounds reproduces the single-node record set byte-for-byte; a
    subsequent single-process warm run over the same store dir replays
    everything the worker processes wrote (multi-process-safe WAL)."""
    ccfg, _ = corpus
    test, ecfg, single = single_run
    store = DiskResultStore(tmp_path / "cache")
    xcfg = ExecutorConfig(n_nodes=4,
                          node_pools=["cpu", "cpu", "cpu", "gpu"],
                          prefetch_depth=2, runtime="process")
    res = CampaignController(ecfg, xcfg, ControllerConfig(rounds=2),
                             ft_router, ccfg).run(test, cache=store)
    _assert_same_records(single, res.records)
    assert res.rounds == 2
    assert res.cache_hits == 0 and res.cache_misses > 0
    # GPU-pool worker completed re-parses but ingested nothing
    assert res.node_stats[3].n_docs == 0
    assert res.node_stats[3].n_expensive > 0
    assert sum(s.n_docs for s in res.node_stats[:3]) == len(test)

    # cross-process warm replay: a fresh store over the same dir sees
    # every batch the four workers stored
    store2 = DiskResultStore(tmp_path / "cache")
    warm = CampaignExecutor(
        ecfg, ExecutorConfig(n_nodes=2, straggler_rate=0.0),
        ft_router, ccfg).run(test, cache=store2)
    _assert_same_records(single, warm.records)
    assert warm.cache_misses == 0
    assert warm.cache_hits == res.cache_misses


def test_process_pool_survives_worker_crash(corpus, ft_router,
                                            single_run):
    """Kill a worker mid-campaign (hard os._exit with a batch in
    flight): liveness detection re-issues its work to the surviving
    peer and the record set still matches the single-node run."""
    ccfg, _ = corpus
    test, ecfg, single = single_run
    xcfg = ExecutorConfig(
        n_nodes=2, runtime="process", heartbeat_timeout_s=5.0,
        heartbeat_interval_s=0.1,
        fault_injection=FaultInjection(crash_after=((1, 1),)))
    res = CampaignExecutor(ecfg, xcfg, ft_router, ccfg).run(test)
    assert res.reissued >= 1
    _assert_same_records(single, res.records)
    assert sum(s.n_docs for s in res.node_stats) == len(test)


@pytest.mark.parametrize("slowdown", [0.9, 1.4])
def test_heartbeat_reissue_never_duplicates_records(corpus, ft_router,
                                                    single_run, slowdown):
    """Property (ISSUE-5): heartbeat-deadline re-issue never duplicates
    an emitted record, whatever the straggler timing. Worker 1 stops
    heartbeating but keeps working (slowed) — its batches re-issue to
    the peer, both attempts eventually produce results, and exactly one
    emission per batch survives: per-doc records match the single-node
    run and the per-node doc counts sum to the corpus exactly."""
    ccfg, _ = corpus
    test, ecfg, single = single_run
    # the deadline stretches by 0.3 s per task the last beat reported
    # queued, so a muted worker's backlog (slowdown s per task) always
    # outlasts it and a re-issue fires
    xcfg = ExecutorConfig(
        n_nodes=2, runtime="process", heartbeat_timeout_s=0.3,
        heartbeat_interval_s=0.1, straggler_grace_s=2.5,
        fault_injection=FaultInjection(mute_after=((1, 0),),
                                       mute_slowdown_s=slowdown))
    res = CampaignExecutor(ecfg, xcfg, ft_router, ccfg).run(test)
    _assert_same_records(single, res.records)
    assert res.reissued >= 1
    # no double emission: every doc counted exactly once across nodes
    assert sum(s.n_docs for s in res.node_stats) == len(test)
    # the straggler's late result for a re-issued batch was dropped,
    # not emitted (observable once its sleep ends within the grace)
    assert res.duplicates_dropped >= 1


class _FakeQ:
    """Capture-only stand-in for a worker task queue."""

    def __init__(self):
        self.sent = []

    def put(self, msg):
        self.sent.append(msg)


def _bare_pool(n_nodes=2, window=3):
    """A ProcessWorkerPool with coordinator state only — no processes,
    no queues — for unit-testing the dispatch/liveness bookkeeping."""
    import numpy as np

    from repro.core.workers import ProcessWorkerPool as P
    pool = P.__new__(P)
    pool.n_nodes = n_nodes
    pool.pools = None
    pool.cheap_dev = pool.exp_dev = "cpu"
    pool.reparse_nodes = list(range(n_nodes))
    pool.alpha = 0.1
    pool._alpha_of = {}
    pool._window = window
    pool.clocks = np.zeros(n_nodes)
    pool._tasks = {}
    pool._open = set()
    pool._late = set()
    pool._load = [0] * n_nodes
    pool._dead = set()
    pool._quiet = set()
    pool._stalled = set()
    pool._next_task_id = 0
    pool._reissued_tasks = [0] * n_nodes
    pool.reissued = 0
    pool.reissued_reparse = 0
    pool._shm = None                     # inline payloads
    pool.task_qs = [_FakeQ() for _ in range(n_nodes)]
    return pool


def test_recovered_straggler_window_counts_owed_late_results():
    """Regression (recovery window overcommit): a quieted worker's
    re-issued batches are still executing on it when it heartbeats
    back; the refill must count those owed late results against the
    ``1 + prefetch_depth`` window instead of refilling it in full —
    pre-fix the recovered straggler held window + owed batches."""
    from collections import deque

    pool = _bare_pool(n_nodes=2, window=3)
    pending = {1: deque({"batch_key": k, "docs": ()} for k in range(8))}
    pool._top_up(pending)
    assert pool._load[1] == 3            # window full, 5 batches queued

    # worker 1 misses its heartbeat deadline: quiet + re-issue
    pool._quiet.add(1)
    pool._reissue_from(1)
    assert pool._load[1] == 0 and pool._owed(1) == 3
    assert pool._load[0] == 3            # peers took the batches over
    assert pool.reissued == 3

    # worker 1 heartbeats back while its 3 batches still execute
    pool._quiet.discard(1)
    sent_before = len(pool.task_qs[1].sent)
    pool._top_up(pending)
    assert len(pool.task_qs[1].sent) == sent_before
    assert pool._load[1] + pool._owed(1) <= pool._window

    # one late result lands -> exactly one slot frees
    pool._late.discard(next(iter(pool._late)))
    pool._top_up(pending)
    assert len(pool.task_qs[1].sent) == sent_before + 1
    assert pool._load[1] + pool._owed(1) <= pool._window


class _FakeProc:
    """Always-alive stand-in for a spawned worker process."""

    def is_alive(self):
        return True


def test_backlogged_but_alive_worker_not_reissued_early():
    """Regression (backlog vs wedge): a worker whose last heartbeat
    reported a deep task queue is digesting a backlog — its beacons can
    sit behind bulky results in the shared queue well past the base
    deadline. The coordinator must extend that worker's effective
    deadline (one extra base timeout per reported queued task, bounded)
    instead of re-issuing its in-flight work; a worker that reported an
    *empty* queue and then went silent keeps the base deadline and is
    policed as wedged."""
    import time
    from collections import deque

    pool = _bare_pool(n_nodes=2, window=3)
    pool.xcfg = ExecutorConfig(n_nodes=2, runtime="process",
                               heartbeat_timeout_s=1.0)
    pool.procs = [_FakeProc(), _FakeProc()]
    pool._hb_task = [None, None]
    pool._hb_delay = [0.0, 0.0]
    pending = {0: deque([{"batch_key": 0, "docs": ()}]),
               1: deque([{"batch_key": 1, "docs": ()}])}
    pool._top_up(pending)
    assert pool._load == [1, 1]

    # both workers silent for 2x the base deadline; only worker 0's
    # last beacon reported queued work
    pool._beat = [time.time() - 2.0, time.time() - 2.0]
    pool._hb_depth = [3, 0]
    assert pool._deadline_for(0) == pytest.approx(4.0)   # 1 + min(3,4)
    assert pool._deadline_for(1) == pytest.approx(1.0)
    pool._police()
    assert 0 not in pool._quiet          # backlogged but alive: spared
    assert 1 in pool._quiet              # silent with an empty queue
    assert pool.reissued == 1
    assert pool._load[0] == 2            # worker 1's task moved over

    # the depth grant is bounded: a huge reported backlog cannot defer
    # policing forever
    pool._hb_depth[0] = 500
    assert pool._deadline_for(0) == pytest.approx(5.0)
    pool._beat[0] = time.time() - 6.0
    pool._police()
    assert 0 in pool._quiet              # past even the extended bound


def test_heartbeat_send_stamp_is_same_host_only():
    """Regression (cross-machine clock skew): ``Heartbeat.sent_mono``
    is a CLOCK_MONOTONIC stamp whose epoch is per-machine — boot time —
    so differencing it against the coordinator's clock is meaningless
    off-host. Liveness deadlines always run on coordinator *receive*
    time (``_beat``); the send stamp only feeds the same-host
    queue-delay diagnostic, and a pool whose workers may live on other
    machines (``_mono_comparable = False``, the fabric contract) must
    leave that diagnostic untouched however skewed the stamp."""
    import time

    from repro.core.workers import Heartbeat

    pool = _bare_pool(n_nodes=1, window=1)
    pool.procs = [_FakeProc()]
    pool._beat = [0.0]
    pool._hb_depth = [-1]
    pool._hb_task = [None]
    pool._hb_delay = [0.0]
    pool.obs_spans = []
    pool._obs_snaps = {}

    # a worker on a machine booted much later: its monotonic clock is
    # thousands of seconds behind/ahead of the coordinator's
    pool._mono_comparable = False
    for skew in (9999.0, -9999.0):
        pool._handle(Heartbeat(0, time.time(), None,
                               sent_mono=time.monotonic() + skew,
                               queue_depth=2))
        assert pool._hb_delay[0] == 0.0  # diagnostic never computed
        # liveness state still updates from coordinator receive time
        assert pool._beat[0] == pytest.approx(time.time(), abs=2.0)
        assert pool._hb_depth[0] == 2

    # the same-host spawn runtime keeps the diagnostic: a stamp from
    # the shared clock yields the real (non-negative) queue delay
    pool._mono_comparable = True
    pool._handle(Heartbeat(0, time.time(), None,
                           sent_mono=time.monotonic() - 0.5,
                           queue_depth=0))
    assert 0.4 < pool._hb_delay[0] < 5.0
    # ...and even on one host, a stamp from the future (clock step
    # between reads) clamps at zero rather than going negative
    pool._handle(Heartbeat(0, time.time(), None,
                           sent_mono=time.monotonic() + 50.0,
                           queue_depth=0))
    assert pool._hb_delay[0] == 0.0


def test_straggler_flap_recovers_without_overcommit(corpus, ft_router,
                                                    single_run):
    """End-to-end flap (mute → re-issue → heartbeats resume): the
    recovered worker is re-admitted at reduced window while it still
    owes late results, and the record set matches the single-node run
    with every doc counted exactly once."""
    ccfg, _ = corpus
    test, ecfg, single = single_run
    xcfg = ExecutorConfig(
        n_nodes=2, runtime="process", prefetch_depth=2,
        heartbeat_timeout_s=0.5, heartbeat_interval_s=0.1,
        straggler_grace_s=2.5,
        # the one-task mute window (3 s) outlasts the longest stretched
        # heartbeat deadline, 0.5 s x (1 + 4 queued), so it must re-issue
        fault_injection=FaultInjection(mute_after=((1, 0),),
                                       unmute_after=((1, 2),),
                                       mute_slowdown_s=3.0))
    res = CampaignExecutor(ecfg, xcfg, ft_router, ccfg).run(test)
    _assert_same_records(single, res.records)
    assert res.reissued >= 1
    assert sum(s.n_docs for s in res.node_stats) == len(test)


def test_process_runtime_rejects_simulation_only_config(corpus,
                                                        ft_router):
    """Actionable errors before any process spawns: simulated speed
    factors and in-memory result stores are local-runtime concepts."""
    ccfg, docs = corpus
    ecfg = EngineConfig(alpha=0.1, batch_size=16)
    with pytest.raises(ValueError, match="simulation-only"):
        CampaignExecutor(
            ecfg, ExecutorConfig(n_nodes=2, runtime="process",
                                 node_speed_factors=[1.0, 4.0]),
            ft_router, ccfg).run(docs[75:])
    with pytest.raises(ValueError, match="cannot be shared across"):
        CampaignExecutor(
            ecfg, ExecutorConfig(n_nodes=2, runtime="process"),
            ft_router, ccfg).run(docs[75:], cache=ResultCache())
    with pytest.raises(ValueError, match="heartbeat_timeout_s"):
        CampaignExecutor(
            ecfg, ExecutorConfig(n_nodes=2, runtime="process",
                                 heartbeat_timeout_s=0.0),
            ft_router, ccfg).run(docs[75:])
    with pytest.raises(ValueError, match="unknown worker runtime"):
        CampaignExecutor(
            ecfg, ExecutorConfig(n_nodes=2, runtime="threads"),
            ft_router, ccfg).run(docs[75:])


def test_reissue_candidates_exclude_precedes_pool_short_circuit():
    """Dead workers are removed from the fleet *before* the same-pool
    short-circuit: with every same-pool peer dead, CPU work still
    falls through to cross-pool nodes, while GPU work (which cannot
    cross) correctly finds no peer."""
    from repro.core import scheduler

    pools = ["cpu", "cpu", "gpu"]
    # both CPU workers dead: cpu work may run on the GPU node's host
    assert scheduler.reissue_candidates(0, pools, "cpu", 3,
                                        exclude={1}) == [2]
    # without exclusion the dead same-pool peer masks the fallback
    assert scheduler.reissue_candidates(0, pools, "cpu", 3) == [1]
    # gpu work never leaves its pool, dead peers or not
    assert scheduler.reissue_candidates(2, ["cpu", "gpu", "gpu"],
                                        "gpu", 3, exclude={1}) == []
    assert scheduler.reissue_candidates(0, None, "cpu", 3,
                                        exclude={2}) == [1]


def test_local_pool_satisfies_worker_pool_protocol(corpus, ft_router):
    """Both runtimes sit behind one structural protocol; the executor
    and controller never branch on the concrete pool type."""
    ccfg, docs = corpus
    ecfg = EngineConfig(alpha=0.1, batch_size=16)
    ex = CampaignExecutor(ecfg, ExecutorConfig(n_nodes=2), ft_router,
                          ccfg)
    pool = ex._make_pool(2, [0, 1], [0, 1], None, {}, None)
    assert isinstance(pool, LocalWorkerPool)
    assert isinstance(pool, WorkerPool)
    for method in ("drain", "node_telemetry", "set_alpha", "node_stats",
                   "snapshot_cache", "finalize", "close"):
        assert callable(getattr(ProcessWorkerPool, method))
