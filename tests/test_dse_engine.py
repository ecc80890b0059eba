"""DSEEngine tests: parallel determinism, memo-cache correctness, the
cross-process shared memo store, Pareto extraction, and the
infeasible-point skip contract.

These tests intentionally avoid hypothesis so they run on a bare
install — the seeded random checks below mirror the property tests in
test_solver.py for the vectorized minmax ``extra`` path.

The CI matrix re-runs this file with ``DFMODEL_TEST_MP_CONTEXT``
(fork | spawn | forkserver), ``DFMODEL_TEST_SHARED_CACHE`` (1 | 0),
``DFMODEL_TEST_PRUNE`` (1 | 0) and ``DFMODEL_TEST_RANK`` (1 | 0):
engines built through :func:`_engine` pick those up, so every pool
transport is exercised with the shared store, the candidate-pruning
stage and the learned rank stage both on and off.
"""
from __future__ import annotations

import dataclasses
import os
import warnings

import numpy as np
import pytest

from repro.core import (DSEEngine, SweepSpec, cache_stats, caching_disabled,
                        clear_caches, pareto_frontier, stop_after_feasible,
                        sweep)
from repro.core.dse import design_grid
from repro.core.memo import GLOBAL_CACHE
from repro.core.solver import minmax_partition, minmax_partition_scalar
from repro.workloads.llm import LLAMA_68M, gpt_workload
from repro.workloads.scenarios import get_scenario, scenario_names

# module-level so the workload builder is picklable under spawn semantics
def _tiny_work(system):
    return gpt_workload(LLAMA_68M, global_batch=64, microbatch=1)


def _engine(**kwargs) -> DSEEngine:
    """DSEEngine honoring the CI-matrix env knobs (explicit kwargs win)."""
    env_ctx = os.environ.get("DFMODEL_TEST_MP_CONTEXT")
    if env_ctx:
        kwargs.setdefault("mp_context", env_ctx)
    env_shared = os.environ.get("DFMODEL_TEST_SHARED_CACHE")
    if env_shared is not None:
        kwargs.setdefault("shared_cache",
                          env_shared not in ("0", "", "off"))
    env_prune = os.environ.get("DFMODEL_TEST_PRUNE")
    if env_prune is not None:
        kwargs.setdefault("prune",
                          "off" if env_prune in ("0", "", "off") else "on")
    env_rank = os.environ.get("DFMODEL_TEST_RANK")
    if env_rank is not None:
        kwargs.setdefault("rank",
                          "off" if env_rank in ("0", "", "off") else "on")
    return DSEEngine(**kwargs)


SMOKE_SPEC = SweepSpec(n_chips=16,
                       chips=("H100", "SN30"),
                       topologies=("torus2d", "dgx2"),
                       mem_net=(("DDR", "PCIe"), ("HBM", "NVLink")),
                       max_tp=16)


# --------------------- vectorized minmax (seeded fallback) --------------------
def test_minmax_extra_vectorized_matches_scalar_seeded():
    rng = np.random.default_rng(0)
    for _ in range(60):
        n = int(rng.integers(2, 10))
        costs = rng.uniform(0.1, 100.0, size=n).tolist()
        p = int(rng.integers(1, 5))
        pen = float(rng.uniform(0.0, 50.0))

        def extra(i, j, pen=pen):
            return pen + 0.25 * (j - i)

        vb, vo = minmax_partition(costs, p, extra=extra)
        sb, so = minmax_partition_scalar(costs, p, extra=extra)
        assert vb == sb
        assert vo == so  # bit-identical
        vb0, vo0 = minmax_partition(costs, p)
        sb0, so0 = minmax_partition_scalar(costs, p)
        assert (vb0, vo0) == (sb0, so0)


def test_minmax_extra_agrees_with_bnb_on_seeded_dags():
    """Seeded mirror of the hypothesis property in test_solver.py: on
    chain-connected random DAGs the extra-path DP matches the exact B&B
    certifier restricted to the same group count."""
    from conftest import random_dag
    from repro.core.solver import branch_and_bound

    rng = np.random.default_rng(7)
    for _ in range(15):
        g = random_dag(rng, max_kernels=6)
        p_eff = min(int(rng.integers(1, 4)), g.n)
        order = g.topo_order
        costs = [g.kernels[i].flops for i in order]
        w_topo = np.array([g.kernels[i].weight_bytes for i in order])

        def extra(i, j, w_topo=w_topo):
            return float(w_topo[i:j].sum()) * 1e-6

        def objective(assign, costs=costs, extra=extra):
            worst = 0.0
            for part in sorted(set(int(a) for a in assign)):
                members = [i for i in range(len(costs)) if assign[i] == part]
                lo, hi = min(members), max(members) + 1
                assert members == list(range(lo, hi))  # chain ⇒ contiguous
                worst = max(worst, float(sum(costs[lo:hi])) + extra(lo, hi))
            return worst

        _, bc = branch_and_bound(
            g, p_eff, objective,
            feasible=lambda a, p=p_eff: len(set(a.tolist())) == p)
        bounds, dp_obj = minmax_partition(costs, p_eff, extra=extra)
        assert len(bounds) == p_eff
        assert dp_obj == pytest.approx(bc, rel=1e-9)


def test_minmax_extra_objective_matches_returned_split():
    costs = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0]

    def extra(i, j):
        return 0.5 * (j - i)

    bounds, obj = minmax_partition(costs, 3, extra=extra)
    assert bounds[0] == 0 and len(bounds) == 3
    ends = bounds[1:] + [len(costs)]
    groups = [sum(costs[i:j]) + extra(i, j) for i, j in zip(bounds, ends)]
    assert obj == pytest.approx(max(groups), rel=1e-12)


# ------------------------------ determinism ----------------------------------
def _scalar_reference(spec: SweepSpec):
    """The serial scalar path (plan+price per point, no batching)."""
    return sweep(_tiny_work, n_chips=spec.n_chips, chips=spec.chips,
                 topologies=spec.topologies, mem_net=spec.mem_net,
                 max_tp=spec.max_tp, phased=False)


def test_parallel_engine_matches_serial_sweep_exactly():
    """Parallel phased sweep must reproduce the scalar row list
    bit-for-bit — same order, same floats — on a 2-chip × 2-topology
    smoke grid."""
    clear_caches()
    with caching_disabled():
        serial = _scalar_reference(SMOKE_SPEC)
    clear_caches()
    engine = _engine(parallel=True, max_workers=2)
    par = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert len(par) == len(serial) > 0
    assert [p.row() for p in par] == [p.row() for p in serial]


def test_serial_engine_matches_sweep_exactly():
    clear_caches()
    engine = DSEEngine(parallel=False)
    pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]


def test_perpoint_engine_matches_phased_engine():
    """The retained PR 1 per-point path and the phased path are the same
    sweep, bit for bit."""
    clear_caches()
    perpoint = _engine(parallel=True, max_workers=2, phased=False)
    a = perpoint.sweep(_tiny_work, SMOKE_SPEC)
    clear_caches()
    phased = _engine(parallel=True, max_workers=2, phased=True)
    b = phased.sweep(_tiny_work, SMOKE_SPEC)
    assert [p.row() for p in a] == [p.row() for p in b]


@pytest.mark.parametrize("method", ["spawn", "forkserver"])
def test_engine_explicit_mp_context_matches_serial(method):
    """Spawn-context plumbing: an explicit non-fork start method ships
    picklable tasks and still reproduces the scalar reference exactly."""
    import multiprocessing

    if method not in multiprocessing.get_all_start_methods():
        pytest.skip(f"{method} not available on this platform")
    clear_caches()
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    clear_caches()
    engine = DSEEngine(parallel=True, max_workers=2, mp_context=method)
    assert engine._start_method() == method
    pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]


def test_engine_rejects_unknown_mp_context():
    with pytest.raises(ValueError):
        DSEEngine(mp_context="teleport")


_WORKER_PROBE = '''
import os
import sys

BORN = os.environ.get("JAX_PLATFORMS")  # as each process imported __main__
import jax  # imported first, as a training or serving process has


def probe(_):
    from jax._src import xla_bridge

    pinned = jax.config.jax_platforms
    if pinned != "cpu":  # never reach for an accelerator unpinned
        return BORN, os.environ.get("JAX_PLATFORMS"), pinned, None
    jax.devices()  # what a careless worker would do
    return (BORN, os.environ.get("JAX_PLATFORMS"), pinned,
            tuple(sorted(xla_bridge._backends)))


if __name__ == "__main__":
    from repro.core import DSEEngine

    engine = DSEEngine(parallel=True, max_workers=2)
    assert engine._start_method() == "forkserver", engine._start_method()
    with engine:
        seen = set(engine._session_pool.map(probe, range(8), chunksize=1))
    print(sorted(seen, key=repr))
    assert seen == {("cpu", "cpu", "cpu", ("cpu",))}, seen
    assert "JAX_PLATFORMS" not in os.environ  # the parent's is untouched
'''


def test_pool_workers_are_pinned_to_the_cpu(tmp_path):
    """A pool worker of an engine whose process imported jax (forkserver)
    is born pinned to the CPU platform: its environment names the CPU
    before it imports ``__main__`` and jax, and it initializes no JAX
    backend but the CPU even where its parent's environment names none. A
    parent that holds the chip relies on it."""
    import multiprocessing
    import subprocess
    import sys

    if "forkserver" not in multiprocessing.get_all_start_methods():
        pytest.skip("forkserver not available on this platform")
    script = tmp_path / "probe_workers.py"
    script.write_text(_WORKER_PROBE)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"OUT:\n{proc.stdout}\nERR:\n{proc.stderr}"


def test_candidate_matrix_shipping_spawn_exactly_once():
    """Spawn workers ship one PlannedGroup (candidate matrix + winners)
    per (chip, net, topology) system group; the parent's batched
    re-pricing must account for every grid cell exactly once and at least
    one candidate per group — and still reproduce the scalar reference."""
    import multiprocessing

    if "spawn" not in multiprocessing.get_all_start_methods():
        pytest.skip("spawn not available on this platform")
    clear_caches()
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    clear_caches()
    engine = _engine(parallel=True, max_workers=2, mp_context="spawn")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a serial fallback would hide bugs
        pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]
    stats = engine.last_plan_stats
    assert stats is not None, "parallel phased path did not run"
    grid = SMOKE_SPEC.grid()
    system_groups = {(c, n, t) for c, _m, n, t in grid}
    assert stats["cells"] == len(grid)          # every cell exactly once
    assert stats["groups"] == len(system_groups)  # one matrix per system
    assert stats["candidates"] >= stats["groups"]
    # a second sweep resets the accounting rather than accumulating
    engine.sweep(_tiny_work, SMOKE_SPEC)
    assert engine.last_plan_stats["cells"] == len(grid)


def test_backend_divergence_is_detected_not_silently_accepted():
    """If the parent's batched selection (on a non-numpy backend) ever
    disagreed with the worker's shipped winners, the sweep must fail
    loudly (RuntimeError), because a silent disagreement would mean a
    non-certified backend."""
    pytest.importorskip("jax")
    from repro.core.dse import plan_design_groups

    clear_caches()
    grid = SMOKE_SPEC.grid()
    engine = DSEEngine(parallel=False, pricing_backend="jax")
    groups = plan_design_groups(_tiny_work, grid, SMOKE_SPEC.n_chips,
                                max_tp=SMOKE_SPEC.max_tp)
    tampered = [dataclasses.replace(
        g, winner_rows=tuple(r + 1 if r >= 0 else r
                             for r in g.winner_rows))
        for g in groups if len(g.matrix)]
    with pytest.raises(RuntimeError, match="not bit-identical"):
        engine._finish_plan_groups(tampered, len(grid))
    # the numpy-reference parent skips the tautological re-pricing pass
    clear_caches()
    ref_engine = DSEEngine(parallel=False)
    ref_engine._finish_plan_groups(groups, len(grid))
    assert ref_engine.last_plan_stats["verified"] is False


# ------------------------------ streaming ------------------------------------
def test_sweep_iter_delivers_every_index_exactly_once():
    clear_caches()
    engine = _engine(parallel=True, max_workers=2)
    items = list(engine.sweep_iter(_tiny_work, SMOKE_SPEC))
    grid = SMOKE_SPEC.grid()
    assert sorted(it.index for it in items) == list(range(len(grid)))
    assert all(it.cell == grid[it.index] for it in items)
    # re-ordered by grid index, the streamed points equal the batch sweep
    ordered = [it.point for it in sorted(items, key=lambda it: it.index)
               if it.point is not None]
    ref = _scalar_reference(SMOKE_SPEC)
    assert [p.row() for p in ordered] == [p.row() for p in ref]


def test_sweep_iter_early_exit_stops_submission():
    """With a serial engine the grid is planned lazily: stopping after the
    first item must leave the rest of the grid untouched."""
    calls = []

    def counting_work(system):
        calls.append(system.name)
        return _tiny_work(system)

    clear_caches()
    engine = DSEEngine(parallel=False)
    items = list(engine.sweep_iter(counting_work, SMOKE_SPEC,
                                   stop=lambda item: True))
    assert len(items) == 1
    assert len(calls) == 1 < len(SMOKE_SPEC.grid())


def test_sweep_iter_midstream_pool_failure_keeps_exactly_once():
    """If the pool dies after streaming some items, the serial fallback
    must deliver only the remaining indices — never duplicates."""
    clear_caches()
    engine = _engine(parallel=True, max_workers=2)
    grid = SMOKE_SPEC.grid()

    def flaky_parallel_iter(work_fn, spec, g, stop):
        for item in engine._serial_iter(work_fn, spec,
                                        [(0, g[0]), (3, g[3])], stop):
            yield item
        raise OSError("worker died")

    engine._parallel_iter = flaky_parallel_iter
    with pytest.warns(RuntimeWarning, match="streaming serially"):
        items = list(engine.sweep_iter(_tiny_work, SMOKE_SPEC))
    assert sorted(it.index for it in items) == list(range(len(grid)))


def test_sweep_iter_stop_after_feasible():
    clear_caches()
    engine = DSEEngine(parallel=False)
    items = list(engine.sweep_iter(_tiny_work, SMOKE_SPEC,
                                   stop=stop_after_feasible(2)))
    feas = [it for it in items
            if it.point is not None and it.point.plan.feasible]
    assert len(feas) == 2
    assert len(items) < len(SMOKE_SPEC.grid())


# ------------------------------ memo cache -----------------------------------
def test_cache_hits_on_default_style_grid_and_values_identical():
    """The default grid shares inner solves across points: the cache must
    actually hit, and cached results must equal cold solves exactly."""
    clear_caches()
    with caching_disabled():
        cold = sweep(_tiny_work, n_chips=SMOKE_SPEC.n_chips,
                     chips=SMOKE_SPEC.chips,
                     topologies=SMOKE_SPEC.topologies,
                     mem_net=SMOKE_SPEC.mem_net, max_tp=SMOKE_SPEC.max_tp)
    clear_caches()
    warm = sweep(_tiny_work, n_chips=SMOKE_SPEC.n_chips,
                 chips=SMOKE_SPEC.chips,
                 topologies=SMOKE_SPEC.topologies,
                 mem_net=SMOKE_SPEC.mem_net, max_tp=SMOKE_SPEC.max_tp)
    stats = cache_stats()
    assert stats.hits > 0
    assert stats.by_space["sharding"][0] > 0
    assert stats.by_space["minmax"][0] > 0
    assert [p.row() for p in warm] == [p.row() for p in cold]


def test_cache_second_run_is_pure_hit_and_identical():
    clear_caches()
    first = sweep(_tiny_work, n_chips=SMOKE_SPEC.n_chips,
                  chips=SMOKE_SPEC.chips, topologies=SMOKE_SPEC.topologies,
                  mem_net=SMOKE_SPEC.mem_net, max_tp=SMOKE_SPEC.max_tp)
    before = cache_stats()
    second = sweep(_tiny_work, n_chips=SMOKE_SPEC.n_chips,
                   chips=SMOKE_SPEC.chips, topologies=SMOKE_SPEC.topologies,
                   mem_net=SMOKE_SPEC.mem_net, max_tp=SMOKE_SPEC.max_tp)
    after = cache_stats()
    assert after.hits > before.hits
    assert after.misses == before.misses  # second run never solves cold
    assert [p.row() for p in second] == [p.row() for p in first]


# --------------------------- shared memo store -------------------------------
@pytest.mark.parametrize("method", ["fork", "spawn", "forkserver"])
def test_shared_cache_sweep_matches_serial(method):
    """Every pool transport, with the cross-process store attached, must
    reproduce the scalar reference bit-for-bit, populate the store, and
    detach + tear it down before the sweep returns."""
    import multiprocessing

    if method not in multiprocessing.get_all_start_methods():
        pytest.skip(f"{method} not available on this platform")
    clear_caches()
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    clear_caches()
    engine = DSEEngine(parallel=True, max_workers=2, mp_context=method,
                       shared_cache=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a serial fallback would hide bugs
        # this test forks on purpose (the explicit transport matrix);
        # jax's at-fork advisory is expected here — the engine's AUTO
        # pick avoiding fork once jax is loaded is covered in
        # tests/test_search.py
        warnings.filterwarnings("ignore", message=r"os\.fork\(\)",
                                category=RuntimeWarning)
        pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]
    stats = engine.last_shared_stats
    assert stats is not None, "shared store did not run"
    assert stats["backend"] == ("server" if method == "spawn" else "mmap")
    assert stats["inserts"] > 0 and stats["entries"] > 0
    assert stats["misses"] > 0
    assert GLOBAL_CACHE.shared is None  # torn down, not leaked


def test_shared_cache_perpoint_path_matches_serial():
    clear_caches()
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    clear_caches()
    engine = _engine(parallel=True, max_workers=2, phased=False,
                     shared_cache=True)
    pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]
    assert engine.last_shared_stats is not None
    assert engine.last_shared_stats["entries"] > 0


def test_shared_cache_sweep_iter_exactly_once_and_torn_down():
    clear_caches()
    engine = _engine(parallel=True, max_workers=2, shared_cache=True)
    items = list(engine.sweep_iter(_tiny_work, SMOKE_SPEC))
    grid = SMOKE_SPEC.grid()
    assert sorted(it.index for it in items) == list(range(len(grid)))
    assert GLOBAL_CACHE.shared is None
    assert engine.last_shared_stats is not None
    ordered = [it.point for it in sorted(items, key=lambda it: it.index)
               if it.point is not None]
    clear_caches()
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    assert [p.row() for p in ordered] == [p.row() for p in ref]


def test_shared_cache_serial_engine_runs_without_store():
    clear_caches()
    engine = DSEEngine(parallel=False, shared_cache=True)
    pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert engine.last_shared_stats is None  # no pool → no store
    assert GLOBAL_CACHE.shared is None
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]


def test_shared_cache_uncached_engine_stays_cold():
    clear_caches()
    engine = DSEEngine(parallel=True, max_workers=2, use_cache=False,
                       shared_cache=True)
    pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert engine.last_shared_stats is None  # use_cache=False wins
    assert pts


def test_shared_cache_torn_down_on_pool_failure():
    """An unpicklable work_fn under spawn kills the pool before it runs;
    the sweep must fall back serially AND tear the store down."""
    import multiprocessing

    if "spawn" not in multiprocessing.get_all_start_methods():
        pytest.skip("spawn not available on this platform")
    clear_caches()
    unpicklable = lambda system: _tiny_work(system)  # noqa: E731
    engine = DSEEngine(parallel=True, max_workers=2, mp_context="spawn",
                       shared_cache=True)
    with pytest.warns(RuntimeWarning, match="falling back to serial"):
        pts = engine.sweep(unpicklable, SMOKE_SPEC)
    assert GLOBAL_CACHE.shared is None  # torn down despite the failure
    assert engine.last_shared_stats is not None  # stats captured first
    clear_caches()
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]


def test_engine_rejects_unknown_shared_cache():
    with pytest.raises(ValueError):
        DSEEngine(shared_cache="carrier-pigeon")


# --------------------------- infeasible points -------------------------------
def _undecomposable_work(system):
    # global_batch == 1 forces DP == 1; with max_tp == 1 and n_layers == 1
    # (pp ≤ 3) no (tp, pp, dp) decomposition of 16 chips exists.
    from repro.workloads.hpl import hpl_workload

    return hpl_workload()


def test_sweep_skips_undecomposable_points_without_crashing():
    spec = SweepSpec(n_chips=16, chips=("H100",), topologies=("torus2d",),
                     mem_net=(("HBM", "NVLink"),), max_tp=1)
    clear_caches()
    serial = sweep(_undecomposable_work, n_chips=spec.n_chips,
                   chips=spec.chips, topologies=spec.topologies,
                   mem_net=spec.mem_net, max_tp=spec.max_tp)
    assert serial == []  # skipped, not raised
    engine = DSEEngine()
    assert engine.sweep(_undecomposable_work, spec) == []


def test_sweep_returns_point_per_cell_when_all_decompose():
    # same workload, but with TP unbounded every cell decomposes (tp=16):
    # nothing may be dropped and order must follow the grid.
    spec = SweepSpec(n_chips=16, chips=("H100", "SN30"),
                     topologies=("torus2d",),
                     mem_net=(("HBM", "NVLink"),), max_tp=None)
    engine = DSEEngine()
    pts = engine.sweep(_undecomposable_work, spec)
    assert len(pts) == len(design_grid(spec.chips, spec.mem_net,
                                       spec.topologies))


# ------------------------------- Pareto --------------------------------------
class _FakePlan:
    def __init__(self, feasible):
        self.feasible = feasible


class _FakePoint:
    def __init__(self, u, c, p, feasible=True):
        self.utilization, self.cost_eff, self.power_eff = u, c, p
        self.plan = _FakePlan(feasible)


def test_pareto_frontier_drops_dominated_points():
    a = _FakePoint(0.9, 10.0, 5.0)
    b = _FakePoint(0.8, 20.0, 4.0)
    dominated = _FakePoint(0.7, 9.0, 3.0)   # worse than a everywhere
    front = pareto_frontier([a, b, dominated])
    assert a in front and b in front and dominated not in front


def test_pareto_frontier_feasible_auto_fallback():
    bad = _FakePoint(0.5, 5.0, 5.0, feasible=False)
    good = _FakePoint(0.4, 4.0, 4.0, feasible=True)
    # feasible point exists → frontier restricted to it even if dominated
    assert pareto_frontier([bad, good]) == [good]
    # no feasible points → fall back to all, frontier non-empty
    assert pareto_frontier([bad]) == [bad]
    assert pareto_frontier([]) == []


def test_pareto_points_mutually_nondominated():
    rng = np.random.default_rng(1)
    pts = [_FakePoint(*rng.uniform(0.1, 1.0, size=3)) for _ in range(40)]
    front = pareto_frontier(pts)
    assert front
    for x in front:
        for y in front:
            if x is y:
                continue
            assert not (y.utilization >= x.utilization
                        and y.cost_eff >= x.cost_eff
                        and y.power_eff >= x.power_eff)


# --------------------------- scenario registry -------------------------------
def test_scenario_registry_lists_all_families():
    assert set(scenario_names()) == {"llm", "dlrm", "hpl", "fft",
                                     "moe", "mamba2", "serving"}
    with pytest.raises(KeyError):
        get_scenario("nope")


def test_serving_scenario_is_inference_only():
    sc = get_scenario("serving", smoke=True)
    work = sc.work_fn(None)
    assert work.bwd_flop_mult == 0.0
    assert work.optimizer_bytes_per_param_byte == 0.0
    assert work.dp_allreduce is False


@pytest.mark.parametrize("name", ["llm", "dlrm", "hpl", "fft",
                                  "moe", "mamba2", "serving"])
def test_smoke_scenarios_sweep_and_have_nonempty_frontier(name):
    engine = _engine()
    res = engine.sweep_scenario(name, smoke=True)
    assert res.points, f"{name} smoke sweep returned no design points"
    assert res.frontier, f"{name} smoke sweep has an empty Pareto frontier"
    assert all(any(f is p for p in res.points) for f in res.frontier)
    # frontier rows carry the workload tag for the bench tables
    assert res.rows()[0]["workload"] == name


# --------------------------- candidate pruning -------------------------------
def test_prune_on_off_engines_identical_across_all_scenarios():
    """The pruning acceptance property at engine level: for EVERY
    scenario family, a prune-on sweep returns DesignPoint rows identical
    to a prune-off sweep, while pricing strictly fewer candidate rows in
    aggregate (last_plan_stats accounting)."""
    enumerated = survived = 0
    for name in scenario_names():
        clear_caches()
        on = DSEEngine(parallel=False, prune="on")
        res_on = on.sweep_scenario(name, smoke=True)
        stats = on.last_plan_stats
        assert stats is not None and stats["prune"] is True
        assert stats["priced"] == stats["survived"] <= stats["enumerated"]
        enumerated += stats["enumerated"]
        survived += stats["survived"]
        clear_caches()
        off = DSEEngine(parallel=False, prune="off")
        res_off = off.sweep_scenario(name, smoke=True)
        assert off.last_plan_stats["prune"] is False
        assert ([p.row() for p in res_on.points]
                == [p.row() for p in res_off.points]), name
    assert survived < enumerated, "pruning never dropped a row anywhere"


def test_survivor_index_map_shipping_spawn_exactly_once():
    """Spawn workers with a non-numpy parent ship PRUNED matrices plus
    survivor index maps, exactly one group per system; the parent's
    batched re-pricing covers only surviving rows, every shipped winner
    is a survivor, and the CERTIFY_EVERY-sampled groups additionally
    carry the unpruned matrix for the parent's scalar-scan check."""
    import multiprocessing

    pytest.importorskip("jax")
    if "spawn" not in multiprocessing.get_all_start_methods():
        pytest.skip("spawn not available on this platform")
    from repro.core.dse import CERTIFY_EVERY

    clear_caches()
    with caching_disabled():
        ref = _scalar_reference(SMOKE_SPEC)
    clear_caches()
    engine = DSEEngine(parallel=True, max_workers=2, mp_context="spawn",
                       pricing_backend="jax", prune="on")
    captured: dict = {}
    orig = engine._finish_plan_groups

    def spy(groups, n_cells):
        captured["groups"] = groups
        return orig(groups, n_cells)

    engine._finish_plan_groups = spy
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a serial fallback would hide bugs
        pts = engine.sweep(_tiny_work, SMOKE_SPEC)
    assert [p.row() for p in pts] == [p.row() for p in ref]
    groups = captured["groups"]
    grid = SMOKE_SPEC.grid()
    assert sorted(i for g in groups for i in g.indices) == \
        list(range(len(grid)))                      # every cell exactly once
    full_shipped = 0
    for g in groups:
        assert g.survivors is not None, "pruned group shipped no index map"
        assert len(g.survivors) == len(g.matrix) == g.prune_stats["survived"]
        assert list(g.survivors) == sorted(set(g.survivors))  # unique, sorted
        assert all(0 <= s < g.n_candidates for s in g.survivors)
        assert all(r in g.survivors for r in g.winner_rows if r >= 0)
        if g.full_matrix is not None:
            full_shipped += 1
            assert len(g.full_matrix) == g.n_candidates
    n_tasks = len({(c, n, t) for c, _m, n, t in grid})
    want_sampled = len([i for i in range(n_tasks) if i % CERTIFY_EVERY == 0])
    assert full_shipped == want_sampled
    stats = engine.last_plan_stats
    assert stats["survived"] < stats["enumerated"]
    assert stats["priced"] == stats["survived"]
    assert stats["scalar_certified_groups"] == want_sampled
    assert stats["parent_certified_groups"] == want_sampled
    assert stats["verified"] is True and stats["prune"] is True


def test_parent_scalar_certification_detects_dropped_winner():
    """If pruning (or IPC) ever mangled a shipped winner, the parent's
    sampled full-matrix re-pricing must fail loudly."""
    from repro.core.dse import plan_design_groups

    clear_caches()
    grid = SMOKE_SPEC.grid()
    groups = plan_design_groups(_tiny_work, grid, SMOKE_SPEC.n_chips,
                                max_tp=SMOKE_SPEC.max_tp, prune="on",
                                certify=True)
    assert any(g.full_matrix is not None for g in groups)
    tampered = [dataclasses.replace(
        g, winner_rows=tuple(r + 1 if r >= 0 else r for r in g.winner_rows))
        if g.full_matrix is not None else g for g in groups]
    engine = DSEEngine(parallel=False, prune="on")
    with pytest.raises(RuntimeError, match="not winner-preserving"):
        engine._finish_plan_groups(tampered, len(grid))
    # untampered groups certify clean
    engine._finish_plan_groups(groups, len(grid))
    assert engine.last_plan_stats["scalar_certified_groups"] > 0


def test_prune_off_engine_ships_full_matrices():
    """prune='off' keeps the PR 3 contract: full matrices, no survivor
    maps, no sampled certification shipping."""
    from repro.core.dse import plan_design_groups

    clear_caches()
    grid = SMOKE_SPEC.grid()
    groups = plan_design_groups(_tiny_work, grid, SMOKE_SPEC.n_chips,
                                max_tp=SMOKE_SPEC.max_tp, prune="off")
    for g in groups:
        assert g.survivors is None
        assert g.full_matrix is None
        assert len(g.matrix) == g.n_candidates
        assert g.prune_stats["survived"] == g.prune_stats["enumerated"]
