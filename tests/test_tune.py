"""Autotuner tests (DESIGN.md §11, core/tune.py).

The contract under test, per the tuner's design:

* ``REPRO_TUNE=off`` (the CI default) — every plan from all four engines
  is the heuristic one: identical objects to the untuned lru path, and
  the tuner's selection machinery is never consulted.
* tuning on — tuned and untuned plans may differ in tiles / grid order /
  engine choice, but executing them is bit-identical for fp32 and bf16,
  including ragged and zero-size shapes.
* the persistent cache survives hostile conditions: corrupt, stale, and
  other-version files are ignored and rebuilt, concurrent writers cannot
  tear the file, a recorded winner short-circuits re-timing.
* tuned plans get the same lru identity guarantees as untuned plans.
"""

from __future__ import annotations

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dist_plan, index_plan, plan, stencil, tune
from repro.kernels import ops


def _clear_tuned_caches():
    plan._plan_tuned_cached.cache_clear()
    index_plan._plan_tuned_cached.cache_clear()
    stencil._plan_tuned_cached.cache_clear()
    dist_plan._plan_rearrange_tuned.cache_clear()
    dist_plan._plan_stencil_tuned.cache_clear()


@pytest.fixture
def tune_cache(tmp_path, monkeypatch):
    """Point the persistent cache at a throwaway path and clear the tuned
    lru caches (they may hold plans tuned against another cache file)."""
    path = tmp_path / "tune.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    _clear_tuned_caches()
    yield path
    _clear_tuned_caches()


JACOBI = stencil.Stencil(((1, 0), (-1, 0), (0, 1), (0, -1)), (0.25,) * 4)


# ---------------------------------------------------------------------------
# REPRO_TUNE=off: bit-identical heuristic plans, tuner never consulted
# ---------------------------------------------------------------------------


class TestOffBitIdentity:
    SHAPES = [
        ((8, 64, 4, 16), (0, 2, 1, 3)),   # split-heads (vec transpose)
        ((16, 8, 32), (2, 1, 0)),          # generic reorder
        ((5, 7, 3), (1, 0, 2)),            # ragged
        ((0, 4, 8), (2, 0, 1)),            # zero-size
    ]

    def test_rearrange_off_is_untuned_object(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "off")
        for shape, perm in self.SHAPES:
            for dt in (jnp.float32, jnp.bfloat16):
                p_env = plan.plan_rearrange(shape, dt, perm)
                p_explicit = plan.plan_rearrange(shape, dt, perm, tuned=False)
                p_unset = plan._plan_cached(
                    shape, jnp.dtype(dt).name, perm, "out"
                )
                assert p_env is p_explicit is p_unset

    def test_off_never_consults_selection(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "off")

        def boom(*a, **k):  # pragma: no cover - would fail the test
            raise AssertionError("tuner consulted under REPRO_TUNE=off")

        monkeypatch.setattr(tune, "select", boom)
        plan._plan_cached.cache_clear()
        plan.plan_rearrange((4, 8, 16), jnp.float32, (1, 0, 2))
        index_plan.plan_index_op((32, 16), jnp.float32, 16, "gather")
        stencil.plan_stencil((32, 64), jnp.float32, JACOBI.repeat(2).stages)
        dist_plan.plan_dist_rearrange(
            (("x", 4),), ("x", None), (None, "x"), (8, 16), jnp.float32, (1, 0)
        )

    def test_index_off_is_untuned_object(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "off")
        for args in [
            ((64, 32), 48, "gather", False, 1),
            ((64, 32), 48, "gather", True, 1),
            ((16, 8), 32, "scatter", True, 1),
            ((32, 16), 24, "gather_combine", True, 2),
            ((0, 16), 8, "gather", True, 1),
            ((16, 16), 0, "gather", False, 1),
        ]:
            src, n_out, sem, masked, k = args
            a = index_plan.plan_index_op(
                src, jnp.bfloat16, n_out, sem, masked=masked, top_k=k
            )
            b = index_plan.plan_index_op(
                src, jnp.bfloat16, n_out, sem, masked=masked, top_k=k, tuned=False
            )
            assert a is b

    def test_stencil_off_is_untuned_object(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "off")
        prog = JACOBI.repeat(3)
        a = prog.compile((64, 96), jnp.float32)
        b = prog.compile((64, 96), jnp.float32, tuned=False)
        assert a is b

    def test_dist_off_is_untuned_object(self, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "off")
        mk = (("x", 8),)
        a = dist_plan.plan_dist_rearrange(
            mk, ("x", None, None), (None, None, "x"), (64, 128, 256),
            jnp.float32, (1, 0, 2),
        )
        b = dist_plan.plan_dist_rearrange(
            mk, ("x", None, None), (None, None, "x"), (64, 128, 256),
            jnp.float32, (1, 0, 2), tuned=False,
        )
        assert a is b


# ---------------------------------------------------------------------------
# tuned == untuned results, bit-identical (fp32 + bf16, ragged, zero-size)
# ---------------------------------------------------------------------------


def _sample(shape, dt, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dt)


class TestTunedEquivalence:
    @pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "shape,perm",
        [
            ((4, 64, 4, 16), (0, 2, 1, 3)),  # vec-transpose route
            ((16, 8, 32), (2, 1, 0)),        # reorder route
            ((8, 32, 16), (0, 2, 1)),        # scalar transpose route
            ((5, 7, 3), (1, 0, 2)),          # ragged
            ((0, 4, 8), (2, 0, 1)),          # zero-size
        ],
    )
    def test_rearrange(self, pallas_interpret, tune_cache, monkeypatch, shape, perm, dt):
        monkeypatch.setenv("REPRO_TUNE", "cost")
        x = _sample(shape, dt)
        p0 = plan.plan_rearrange(shape, dt, perm, tuned=False)
        p1 = plan.plan_rearrange(shape, dt, perm, tuned=True)
        y0 = ops.apply_plan(x, p0)
        y1 = ops.apply_plan(x, p1)
        assert y0.dtype == y1.dtype and y0.shape == y1.shape
        assert bool(jnp.all(y0 == y1))

    @pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "n_src,cols,n_out,sem,masked,k",
        [
            (64, 32, 48, "gather", False, 1),   # rowwise is a candidate here
            (64, 32, 48, "gather", True, 1),
            (24, 16, 40, "scatter", True, 1),   # capacity scatter
            (40, 16, 24, "gather_combine", True, 2),
            (7, 5, 11, "gather", True, 1),      # ragged
            (16, 16, 0, "gather", False, 1),    # zero-size
        ],
    )
    def test_index(self, pallas_interpret, tune_cache, monkeypatch,
                   n_src, cols, n_out, sem, masked, k, dt):
        monkeypatch.setenv("REPRO_TUNE", "cost")
        x = _sample((n_src, cols), dt)
        rng = np.random.default_rng(1)
        p0 = index_plan.plan_index_op(
            (n_src, cols), dt, n_out, sem, masked=masked, top_k=k, tuned=False
        )
        p1 = index_plan.plan_index_op(
            (n_src, cols), dt, n_out, sem, masked=masked, top_k=k, tuned=True
        )
        if sem == "gather_combine":
            idx = jnp.asarray(
                rng.integers(-1 if masked else 0, n_src, (n_out, k)), jnp.int32
            )
            gates = jnp.asarray(rng.random((n_out, k)), jnp.float32)
            y0 = ops.apply_index_plan(x, idx, p0, gates=gates)
            y1 = ops.apply_index_plan(x, idx, p1, gates=gates)
        elif sem == "scatter":
            idx = jnp.asarray(
                rng.permutation(n_out)[:n_src], jnp.int32
            )
            y0 = ops.apply_index_plan(x, idx, p0)
            y1 = ops.apply_index_plan(x, idx, p1)
        else:
            lo = -2 if masked else 0
            idx = jnp.asarray(
                rng.integers(lo, max(n_src, 1), (n_out,)), jnp.int32
            )
            y0 = ops.apply_index_plan(x, idx, p0)
            y1 = ops.apply_index_plan(x, idx, p1)
        assert bool(jnp.all(y0 == y1))

    @pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(64, 96), (50, 40)])
    @pytest.mark.parametrize("boundary", ["zero", "reflect"])
    def test_stencil(self, pallas_interpret, tune_cache, monkeypatch,
                     shape, boundary, dt):
        monkeypatch.setenv("REPRO_TUNE", "cost")
        prog = JACOBI.repeat(3)
        x = _sample(shape, dt)
        p0 = prog.compile(shape, dt, boundary=boundary, tuned=False)
        p1 = prog.compile(shape, dt, boundary=boundary, tuned=True)
        y0 = ops.stencil_program(
            x, p0.stages_exec, boundary=boundary,
            block_rows=p0.block_rows or None, fused=p0.mode == "fused",
        )
        y1 = ops.stencil_program(
            x, p1.stages_exec, boundary=boundary,
            block_rows=p1.block_rows or None, fused=p1.mode == "fused",
        )
        assert bool(jnp.all(y0 == y1))

    def test_tuned_plan_still_one_pallas_call(self, pallas_interpret,
                                              tune_cache, monkeypatch):
        """Tuning changes which plan is cached, never the lowering shape:
        a tuned rearrangement still executes as exactly ONE pallas_call
        (the §3 contract), and a tuned stencil program stays one fused
        kernel."""
        monkeypatch.setenv("REPRO_TUNE", "cost")
        shape, perm = (4, 64, 4, 16), (0, 2, 1, 3)
        x = _sample(shape, jnp.float32)
        p1 = plan.plan_rearrange(shape, jnp.float32, perm, tuned=True)
        jaxpr = str(jax.make_jaxpr(lambda a: ops.apply_plan(a, p1))(x))
        assert jaxpr.count("pallas_call[") == 1
        g = _sample((64, 96), jnp.float32)
        sp = JACOBI.repeat(3).compile((64, 96), jnp.float32, tuned=True)
        assert sp.mode == "fused"
        jaxpr = str(jax.make_jaxpr(
            lambda a: ops.stencil_program(
                a, sp.stages_exec, boundary="zero",
                block_rows=sp.block_rows or None, fused=True,
            )
        )(g))
        assert jaxpr.count("pallas_call[") == 1

    def test_rowwise_engine_candidate_bit_identical(self, pallas_interpret):
        """The engine-choice candidate (seed rowwise kernel vs blocked
        kernel) is exact — the precondition for the tuner offering it."""
        x = _sample((32, 16), jnp.float32)
        idx = jnp.asarray(
            np.random.default_rng(2).integers(0, 32, (20,)), jnp.int32
        )
        p_row = index_plan._build_plan(
            32, 16, "float32", 20, "gather", False, 1, engine="rowwise"
        )
        p_blk = index_plan.plan_index_op((32, 16), jnp.float32, 20, "gather")
        assert p_row.mode == "rowwise" and p_row.kernel == "gather_rows"
        assert bool(jnp.all(
            ops.apply_index_plan(x, idx, p_row)
            == ops.apply_index_plan(x, idx, p_blk)
        ))

    def test_dist_tuned_strategy_stays_feasible(self, tune_cache, monkeypatch):
        """Dist tuning only moves between strategies the executors run and
        the §10 suite proves bit-identical (exec-level identity is covered
        on the 8-device mesh in test_dist_plan.py)."""
        monkeypatch.setenv("REPRO_TUNE", "cost")
        mk = (("x", 8),)
        p0 = dist_plan.plan_dist_rearrange(
            mk, ("x", None, None), (None, None, "x"), (64, 128, 256),
            jnp.float32, (1, 0, 2), tuned=False,
        )
        p1 = dist_plan.plan_dist_rearrange(
            mk, ("x", None, None), (None, None, "x"), (64, 128, 256),
            jnp.float32, (1, 0, 2), tuned=True,
        )
        assert p1.strategy in ("all_to_all", "replicate")
        assert (p1.in_spec, p1.out_spec) == (p0.in_spec, p0.out_spec)
        s0 = dist_plan.plan_dist_stencil(
            mk, "x", (64, 128), jnp.float32, JACOBI.repeat(4).stages, "zero",
            tuned=False,
        )
        s1 = dist_plan.plan_dist_stencil(
            mk, "x", (64, 128), jnp.float32, JACOBI.repeat(4).stages, "zero",
            tuned=True,
        )
        assert s0.strategy == "halo"
        assert s1.strategy in ("halo", "replicate")

    def test_measured_mode_equivalence(self, pallas_interpret, tune_cache,
                                       monkeypatch):
        """REPRO_TUNE=measure actually times candidates (tiny shapes) and
        the measured winner still computes identical bytes."""
        monkeypatch.setenv("REPRO_TUNE", "measure")
        shape, perm = (2, 16, 4, 8), (0, 2, 1, 3)
        x = _sample(shape, jnp.float32)
        p0 = plan.plan_rearrange(shape, jnp.float32, perm, tuned=False)
        p1 = plan.plan_rearrange(shape, jnp.float32, perm, tuned=True)
        assert bool(jnp.all(ops.apply_plan(x, p0) == ops.apply_plan(x, p1)))
        assert tune_cache.exists()  # the winner was persisted


# ---------------------------------------------------------------------------
# selection machinery
# ---------------------------------------------------------------------------


def _cands(costs):
    return [
        tune.Candidate(label=f"c{i}", params=(("i", i),), cost_s=c)
        for i, c in enumerate(costs)
    ]


class TestSelect:
    def test_cost_mode_picks_min_first_wins_ties(self):
        cands = _cands([2.0, 1.0, 1.0])
        got = tune.select("t", "k", cands, None, mode="cost", persist=False)
        assert got.label == "c1"
        cands = _cands([1.0, 1.0, 2.0])
        got = tune.select("t", "k", cands, None, mode="cost", persist=False)
        assert got.label == "c0"  # heuristic wins the tie

    def test_no_runner_falls_back_to_cost_in_measure_mode(self):
        cands = _cands([3.0, 1.0])
        got = tune.select("t", "k", cands, None, mode="measure", persist=False)
        assert got.label == "c1"

    def test_single_candidate_short_circuits(self):
        cands = _cands([1.0])
        assert tune.select("t", "k", cands, None, mode="measure") is cands[0]

    def test_measure_skips_raising_candidates(self, tune_cache):
        cands = _cands([1.0, 2.0, 3.0])

        def factory(c):
            if c.label != "c2":
                raise ValueError("illegal candidate")
            return lambda: 0

        got = tune.select("t", "k1", cands, factory, mode="measure")
        assert got.label == "c2"

    def test_measure_all_fail_keeps_heuristic_without_persisting(self, tune_cache):
        cands = _cands([1.0, 2.0])

        def factory(c):
            def run():
                raise ValueError("boom")
            return run

        got = tune.select("t", "k2", cands, factory, mode="measure")
        assert got.label == "c0"
        # a transient all-fail must NOT record a winner (it would
        # short-circuit re-tuning forever, and inf is not strict JSON)
        assert tune.lookup("t|k2") is None


# ---------------------------------------------------------------------------
# the persistent cache: hostile files, atomicity, short-circuit
# ---------------------------------------------------------------------------


class TestCacheRobustness:
    def test_missing_file_is_empty(self, tune_cache):
        assert tune.load_cache()["entries"] == {}

    def test_corrupt_file_ignored_and_rebuilt(self, tune_cache):
        tune_cache.write_text("{not json!!")
        assert tune.load_cache()["entries"] == {}
        tune.store_entry("k", {"label": "x"})
        doc = json.loads(tune_cache.read_text())  # valid again
        assert doc["entries"]["k"]["label"] == "x"

    def test_other_version_and_backend_ignored(self, tune_cache):
        good = tune.load_cache()
        for field, bad in (("schema", 999), ("jax", "0.0.1"), ("backend", "tpu9")):
            doc = {**good, field: bad, "entries": {"k": {"label": "stale"}}}
            tune_cache.write_text(json.dumps(doc))
            assert tune.load_cache()["entries"] == {}, field

    def test_lookup_roundtrip(self, tune_cache):
        tune.store_entry("a|b", {"label": "c1", "us": 1.0})
        assert tune.lookup("a|b")["label"] == "c1"
        assert tune.lookup("missing") is None

    def test_recorded_winner_short_circuits_timing(self, tune_cache, monkeypatch):
        cands = _cands([1.0, 2.0])
        tune.store_entry("t|k", {"label": "c1"})

        def boom(*a, **k):  # pragma: no cover - would fail the test
            raise AssertionError("re-timed despite a recorded winner")

        monkeypatch.setattr(tune, "time_candidates", boom)
        got = tune.select("t", "k", cands, lambda c: (lambda: 0), mode="measure")
        assert got.label == "c1"

    def test_unknown_recorded_winner_retunes(self, tune_cache):
        cands = _cands([1.0, 2.0])
        tune.store_entry("t|k", {"label": "gone-since-refactor"})
        got = tune.select("t", "k", cands, lambda c: (lambda: 0), mode="measure")
        assert got.label in ("c0", "c1")
        assert tune.lookup("t|k")["label"] == got.label  # rewritten

    def test_concurrent_writers_never_tear(self, tune_cache):
        def writer(i):
            for j in range(10):
                tune.store_entry(f"k{i}-{j}", {"label": f"w{i}", "us": j})

        threads = [threading.Thread(target=writer, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        doc = json.loads(tune_cache.read_text())  # parseable => not torn
        assert doc["schema"] == tune.SCHEMA_VERSION
        assert doc["entries"]  # last writer's merge survived intact
        for rec in doc["entries"].values():
            assert "label" in rec

    def test_unwritable_cache_is_ignored(self, monkeypatch):
        monkeypatch.setenv(
            "REPRO_TUNE_CACHE", "/proc/definitely/not/writable/tune.json"
        )
        tune.store_entry("k", {"label": "x"})  # must not raise


# ---------------------------------------------------------------------------
# lru identity for tuned plans
# ---------------------------------------------------------------------------


class TestTunedIdentity:
    def test_rearrange_tuned_identity(self, tune_cache, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "cost")
        a = plan.plan_rearrange((8, 64, 4, 16), jnp.float32, (0, 2, 1, 3), tuned=True)
        b = plan.plan_rearrange((8, 64, 4, 16), jnp.float32, (0, 2, 1, 3), tuned=True)
        assert a is b

    def test_index_tuned_identity(self, tune_cache, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "cost")
        a = index_plan.plan_index_op((64, 32), jnp.float32, 48, "gather", tuned=True)
        b = index_plan.plan_index_op((64, 32), jnp.float32, 48, "gather", tuned=True)
        assert a is b

    def test_stencil_tuned_identity(self, tune_cache, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "cost")
        prog = JACOBI.repeat(2)
        assert prog.compile((64, 96), jnp.float32, tuned=True) is prog.compile(
            (64, 96), jnp.float32, tuned=True
        )

    def test_env_on_routes_default_calls_through_tuner(self, tune_cache, monkeypatch):
        monkeypatch.setenv("REPRO_TUNE", "cost")
        before = plan.tuned_plan_cache_info().misses
        p = plan.plan_rearrange((4, 32, 2, 8), jnp.float32, (0, 2, 1, 3))
        after = plan.tuned_plan_cache_info().misses
        assert after == before + 1
        # and the tuned default call caches to the same object
        assert plan.plan_rearrange((4, 32, 2, 8), jnp.float32, (0, 2, 1, 3)) is p


# ---------------------------------------------------------------------------
# the pre-warm CLI
# ---------------------------------------------------------------------------


class TestTuneCLI:
    def test_warm_and_list(self, tune_cache, monkeypatch, capsys):
        from repro import tune as tune_cli

        monkeypatch.setenv("REPRO_TUNE", "off")  # main() overwrites; restore after
        rc = tune_cli.main([
            "--arch", "qwen2-7b", "--batch", "2", "--seq", "32",
            "--grid", "64", "--mode", "cost",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "split_heads" in out and "stencil: jacobi" in out
        assert tune_cli.main(["--list"]) == 0
