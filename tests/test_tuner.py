"""repro.tuner: plan keying, cache-hit/no-rejit, persistence, correctness.

Acceptance (ISSUE 1): repeated tuned_apply on the same (spec, shape,
dtype) must hit the plan cache with zero re-trace/re-jit; persistence
must round-trip through the JSON file; and every tuned plan must stay
numerically equal to the `direct` backend oracle across paper_suite().
"""
import json
import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import BACKENDS, apply_stencil
from repro.core.stencil import make_stencil, paper_suite
from repro.kernels.dispatch import applicable_backends
from repro.tuner import (Plan, PlanCache, autotune, candidate_plans, plan_for,
                         plan_key, shape_bucket, spec_fingerprint, static_cost,
                         tuned_apply, tuned_apply_batched)
from repro.tuner.plan import PLAN_SCHEMA, PlanKey, mesh_desc


def _x(spec, dims, rng, dtype=jnp.float32):
    shape = tuple(s + 2 * spec.radius for s in dims)
    return jnp.asarray(rng.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# plans and keys
# ---------------------------------------------------------------------------

def test_plan_dict_roundtrip():
    p = Plan(backend="sptc", L=8, fuse_rows=True, temporal_steps=2)
    assert Plan.from_dict(p.to_dict()) == p


def test_plan_key_encode_decode_roundtrip():
    key = PlanKey(spec_fp="abc123", bucket=(64, 128), dtype="float32",
                  device="cpu")
    assert PlanKey.decode(key.encode()) == key


def test_spec_fingerprint_is_content_hash():
    a = make_stencil("box", 2, 2, seed=1)
    b = make_stencil("box", 2, 2, seed=1)     # same content, new object
    c = make_stencil("box", 2, 2, seed=2)
    assert spec_fingerprint(a) == spec_fingerprint(b)
    assert spec_fingerprint(a) != spec_fingerprint(c)


def test_shape_bucket_rounds_up_to_pow2():
    assert shape_bucket((37, 41)) == (64, 64)
    assert shape_bucket((64,)) == (64,)
    assert shape_bucket((65, 1)) == (128, 1)
    # nearby sizes share a plan; the key still splits on dtype and device
    spec = make_stencil("star", 2, 1, seed=0)
    assert plan_key(spec, (60, 60), jnp.float32) == \
        plan_key(spec, (64, 33), jnp.float32)
    assert plan_key(spec, (60, 60), jnp.float32) != \
        plan_key(spec, (60, 60), jnp.bfloat16)


# ---------------------------------------------------------------------------
# candidate enumeration + cost model
# ---------------------------------------------------------------------------

def test_candidates_are_applicable_and_valid():
    for spec in paper_suite():
        plans = candidate_plans(spec)
        assert plans
        ok = applicable_backends(spec)
        for p in plans:
            assert p.backend in ok and p.backend in BACKENDS
            assert p.L % 2 == 0 and p.L >= 2 * spec.radius + 2
            assert static_cost(spec, p) > 0


def test_tpu_candidates_hold_pallas_only_beyond_1d(monkeypatch):
    """On a TPU every 2-D spec is offered all three Pallas backends; a 1-D
    spec none (its (N, 1) column would pad 128x on the lane axis)."""
    from repro.kernels.dispatch import PALLAS_BACKENDS
    monkeypatch.delenv("REPRO_TUNER_INCLUDE_PALLAS", raising=False)
    for spec in paper_suite():
        got = applicable_backends(spec, "tpu")
        pallas = [b for b in got if b in PALLAS_BACKENDS]
        assert pallas == ([] if spec.ndim == 1 else list(PALLAS_BACKENDS))
        assert set(got) - set(pallas) == {"direct", "gemm", "sptc"}


def test_cost_mode_autotune_builds_nothing():
    spec = make_stencil("box", 2, 3, seed=0)
    calls = []
    res = autotune(spec, (70, 70), mode="cost",
                   engine_factory=lambda *a: calls.append(a))
    assert res.mode == "cost" and not calls
    assert res.plan in candidate_plans(spec)
    # the model prefers the SpTC path (K/2 MACs on the matrix unit) for a
    # large box stencil — the paper's headline claim
    assert res.plan.backend == "sptc"


# ---------------------------------------------------------------------------
# cache behavior: plan hits, zero re-jit
# ---------------------------------------------------------------------------

def test_repeat_apply_hits_cache_no_rejit(rng):
    spec = make_stencil("box", 2, 2, seed=3)
    x = _x(spec, (30, 34), rng)
    cache = PlanCache()
    y1 = tuned_apply(spec, x, cache=cache, mode="cost")
    assert cache.stats.plan_misses == 1 and cache.stats.tunes == 1
    builds = cache.stats.engine_builds
    assert builds == 1
    y2 = tuned_apply(spec, x, cache=cache, mode="cost")
    assert cache.stats.engine_builds == builds      # no new engine
    assert cache.stats.plan_hits >= 1 and cache.stats.tunes == 1
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    # the jitted executable was not re-traced either
    plan = plan_for(spec, x.shape, x.dtype, cache=cache, mode="cost")
    eng = cache.engine(spec, plan)
    assert eng._fn._cache_size() == 1


def test_apply_stencil_reuses_engine_across_calls(rng):
    """The seed's dead `_cached_engine` replacement: the functional entry
    point must not build a fresh engine per call."""
    from repro.tuner.cache import default_cache
    spec = make_stencil("star", 2, 2, seed=8)
    x = _x(spec, (26, 28), rng)
    apply_stencil(spec, x, backend="gemm")
    builds = default_cache().stats.engine_builds
    apply_stencil(spec, x, backend="gemm")
    assert default_cache().stats.engine_builds == builds


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

def test_plan_persistence_roundtrip(tmp_path, rng):
    path = tmp_path / "plans.json"
    spec = make_stencil("box", 2, 1, seed=5)
    x = _x(spec, (22, 26), rng)

    cache_a = PlanCache(path=path)
    plan = plan_for(spec, x.shape, x.dtype, cache=cache_a, mode="cost")
    assert path.exists() and cache_a.stats.saves >= 1

    cache_b = PlanCache(path=path)                 # fresh process, warm file
    assert cache_b.stats.loads == 1 and len(cache_b) == len(cache_a)
    assert plan_for(spec, x.shape, x.dtype, cache=cache_b) == plan
    assert cache_b.stats.tunes == 0                # no retune after reload


def test_persistence_ignores_corrupt_file(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text("{not json")
    with pytest.warns(RuntimeWarning, match="unreadable"):
        cache = PlanCache(path=path)
    assert len(cache) == 0 and cache.stats.loads == 0


# ---------------------------------------------------------------------------
# schema forward/backward compatibility (PR-8 satellite)
# ---------------------------------------------------------------------------

def test_plan_from_dict_tolerates_unknown_and_missing_fields():
    d = Plan(backend="gemm", L=4).to_dict()
    d["novel_future_knob"] = 123                 # unknown: ignored
    assert Plan.from_dict(d) == Plan(backend="gemm", L=4)
    legacy = {"backend": "sptc", "L": 8}         # schema-1: fields default
    p = Plan.from_dict(legacy)
    assert p == Plan(backend="sptc", L=8, fuse_rows=False, temporal_steps=1)
    with pytest.raises(ValueError, match="schema"):
        Plan.from_dict({"schema": PLAN_SCHEMA + 1, "backend": "gemm", "L": 4})


def test_plan_key_decodes_v1_and_tolerates_unknown_fields():
    key = PlanKey(spec_fp="abc", bucket=(64, 32), dtype="float32",
                  device="cpu")
    legacy = "spec=abc;shape=64x32;dtype=float32;dev=cpu"
    assert PlanKey.decode(legacy) == key         # v1: coeff/steps default
    assert PlanKey.decode(key.encode() + ";future=knob") == key
    with pytest.raises(ValueError, match="newer"):
        PlanKey.decode(f"v{PLAN_SCHEMA + 1};" + legacy)
    with pytest.raises(ValueError, match="prefix"):
        PlanKey.decode("garbage")


def test_plan_key_univ_roundtrip_and_v2_back_compat():
    key = PlanKey(spec_fp="abc", bucket=(64, 32), dtype="float32",
                  device="cpu", univ="jnp+pallas")
    assert PlanKey.decode(key.encode()) == key
    # a pre-v3 key carries no universe field: decodes as plain-jnp tuning
    v2 = "v2;spec=abc;shape=64x32;dtype=float32;dev=cpu;coeff=const;steps=1"
    assert PlanKey.decode(v2).univ == "jnp"


def test_pallas_universe_plans_cannot_poison_jnp_cache(tmp_path, monkeypatch):
    """A plan tuned with the Pallas backends forced in (interpret-mode
    correctness sweep) must never be served to a plain-CPU process."""
    spec = make_stencil("box", 2, 1, seed=6)
    monkeypatch.delenv("REPRO_TUNER_INCLUDE_PALLAS", raising=False)
    plain = plan_key(spec, (20, 20), jnp.float32)
    monkeypatch.setenv("REPRO_TUNER_INCLUDE_PALLAS", "1")
    forced = plan_key(spec, (20, 20), jnp.float32)
    assert plain.univ == "jnp" and forced.univ == "jnp+pallas"
    assert plain.encode() != forced.encode()
    cache = PlanCache(path=tmp_path / "plans.json")
    cache.store(forced, Plan(backend="pallas_sptc", L=4))
    monkeypatch.delenv("REPRO_TUNER_INCLUDE_PALLAS")
    assert cache.lookup(plan_key(spec, (20, 20), jnp.float32)) is None
    assert cache.lookup(forced) == Plan(backend="pallas_sptc", L=4)


def test_plan_key_mesh_roundtrip_and_v3_back_compat():
    key = PlanKey(spec_fp="abc", bucket=(64, 32), dtype="float32",
                  device="cpu", mesh="4x2")
    assert PlanKey.decode(key.encode()) == key
    # a pre-v4 key carries no mesh field: decodes as single-device tuning
    v3 = ("v3;spec=abc;shape=64x32;dtype=float32;dev=cpu;coeff=const;"
          "steps=1;univ=jnp")
    assert PlanKey.decode(v3).mesh == "1"
    assert PLAN_SCHEMA == 4 and key.encode().startswith("v4;")


def test_mesh_desc_canonicalization():
    # everything single-device-shaped collapses to the SAME key as None
    for trivial in (None, 1, (1,), (1, 1), "1", "1x1"):
        assert mesh_desc(trivial) == "1", trivial
    assert mesh_desc(8) == "8"
    assert mesh_desc((4, 2)) == "4x2"
    assert mesh_desc("4x2") == "4x2"
    assert mesh_desc((4, 1)) == "4"              # extent-1 axes dropped

    class FakeMesh:                              # jax.sharding.Mesh shape
        axis_names = ("sp0", "sp1")
        shape = {"sp0": 4, "sp1": 2}
    assert mesh_desc(FakeMesh()) == "4x2"
    with pytest.raises(ValueError, match=">= 1"):
        mesh_desc((4, 0))
    with pytest.raises(ValueError, match="unparseable"):
        mesh_desc("4xpotato")
    with pytest.raises(TypeError, match="mesh must be"):
        mesh_desc(3.5)


def test_sharded_plans_cannot_poison_single_device_cache(tmp_path):
    """Mirror of the universe-poisoning fence: a plan tuned for a 4x2
    block partition must never be served to a single-device lookup, and
    vice versa — the geometries want different backends/tile sizes."""
    spec = make_stencil("box", 2, 1, seed=6)
    plain = plan_key(spec, (20, 20), jnp.float32)
    sharded = plan_key(spec, (20, 20), jnp.float32, mesh=(4, 2))
    assert plain.mesh == "1" and sharded.mesh == "4x2"
    assert plain.encode() != sharded.encode()
    cache = PlanCache(path=tmp_path / "plans.json")
    cache.store(sharded, Plan(backend="sptc", L=8))
    assert cache.lookup(plain) is None
    assert cache.lookup(sharded) == Plan(backend="sptc", L=8)
    # and the sharded entry round-trips through the JSON file
    reloaded = PlanCache(path=tmp_path / "plans.json")
    assert reloaded.lookup(sharded) == Plan(backend="sptc", L=8)
    # a degenerate all-1 mesh IS single-device: shares the plain entry
    assert plan_key(spec, (20, 20), jnp.float32, mesh=(1, 1)) == plain


def test_batched_accepts_generators_and_rejects_junk(rng):
    """_validate_batch used to iterate generators lazily and fail deep in
    jnp.stack with an opaque error; now it materializes them loudly."""
    spec = make_stencil("star", 2, 1, seed=2)
    xs = [_x(spec, (18, 18), rng) for _ in range(3)]
    stacked = tuned_apply_batched(spec, jnp.stack(xs), mode="cost")
    via_gen = tuned_apply_batched(spec, (x for x in xs), mode="cost")
    np.testing.assert_allclose(np.asarray(via_gen), np.asarray(stacked),
                               rtol=1e-6, atol=1e-6)
    with pytest.raises(TypeError, match="iterable of per-job arrays"):
        tuned_apply_batched(spec, object(), mode="cost")
    with pytest.raises(ValueError, match="empty"):
        tuned_apply_batched(spec, iter([]), mode="cost")


def test_plan_key_splits_on_coeff_and_steps():
    spec = make_stencil("box", 2, 1, seed=1)
    base = plan_key(spec, (20, 20), jnp.float32)
    assert base.coeff == "const" and base.steps == 1
    k2 = plan_key(spec, (20, 20), jnp.float32, temporal_steps=2)
    c = np.ones((18, 18, 3, 3))
    var = plan_key(spec, (20, 20), jnp.float32, coefficients=c)
    assert len({base.encode(), k2.encode(), var.encode()}) == 3
    assert var.coeff.startswith("var-")


def test_pre_pr8_cache_file_round_trips(tmp_path, rng):
    """A v1 cache file (unversioned keys, schema-1 plans) still hits —
    ``tuned_apply`` must not retune against a pre-PR-8 persisted cache."""
    spec = make_stencil("box", 2, 1, seed=5)
    x = _x(spec, (22, 26), rng)
    key = plan_key(spec, x.shape, x.dtype)
    legacy_key = (f"spec={key.spec_fp};"
                  f"shape={'x'.join(str(s) for s in key.bucket)};"
                  f"dtype={key.dtype};dev={key.device}")
    legacy_plan = {"backend": "gemm", "L": 4, "fuse_rows": False,
                   "star_fast_path": True}
    path = tmp_path / "plans.json"
    path.write_text(json.dumps({"version": 1,
                                "plans": {legacy_key: legacy_plan}}))
    cache = PlanCache(path=path)
    assert len(cache) == 1 and cache.stats.loads == 1
    got = tuned_apply(spec, x, cache=cache, mode="cost")
    assert cache.stats.tunes == 0                # the legacy entry hit
    want = apply_stencil(spec, x, backend="direct")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_cache_skips_corrupt_and_future_entries_with_warning(tmp_path):
    spec = make_stencil("box", 1, 1, seed=2)
    good_key = plan_key(spec, (40,), jnp.float32).encode()
    payload = {"version": 2, "plans": {
        good_key: Plan(backend="gemm", L=4).to_dict(),
        "garbage-key": Plan(backend="gemm", L=4).to_dict(),
        f"v{PLAN_SCHEMA + 1};{good_key}": Plan(backend="gemm", L=4).to_dict(),
        good_key.replace("steps=1", "steps=2"):
            {"schema": PLAN_SCHEMA + 1, "backend": "gemm", "L": 4},
    }}
    path = tmp_path / "plans.json"
    path.write_text(json.dumps(payload))
    with pytest.warns(RuntimeWarning, match="skipping entry"):
        cache = PlanCache(path=path)
    assert len(cache) == 1 and cache.stats.skipped_entries == 3
    assert cache.lookup(plan_key(spec, (40,), jnp.float32)) is not None


def test_future_versioned_file_is_ignored_whole(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text(json.dumps({"version": 99, "plans": {}}))
    with pytest.warns(RuntimeWarning, match="version"):
        cache = PlanCache(path=path)
    assert len(cache) == 0 and cache.stats.loads == 0


def test_save_merges_concurrent_writers(tmp_path):
    """Two caches sharing one file converge on the union of their plans."""
    path = tmp_path / "plans.json"
    spec_a = make_stencil("box", 1, 1, seed=3)
    spec_b = make_stencil("box", 1, 2, seed=4)
    key_a = plan_key(spec_a, (40,), jnp.float32)
    key_b = plan_key(spec_b, (40,), jnp.float32)
    cache_a = PlanCache(path=path)
    cache_b = PlanCache(path=path)
    cache_a.store(key_a, Plan(backend="gemm", L=4))      # writes the file
    cache_b.store(key_b, Plan(backend="sptc", L=6))      # merges, then writes
    assert len(cache_b) == 2 and cache_b.stats.merges == 1
    fresh = PlanCache(path=path)
    assert len(fresh) == 2
    assert fresh.lookup(key_a) == Plan(backend="gemm", L=4)
    assert fresh.lookup(key_b) == Plan(backend="sptc", L=6)


def test_save_conflicts_prefer_memory(tmp_path):
    path = tmp_path / "plans.json"
    spec = make_stencil("box", 1, 1, seed=3)
    key = plan_key(spec, (40,), jnp.float32)
    cache_a = PlanCache(path=path)
    cache_b = PlanCache(path=path)
    cache_a.store(key, Plan(backend="gemm", L=4))
    cache_b.store(key, Plan(backend="sptc", L=6))        # same key: b wins b's
    assert PlanCache(path=path).lookup(key) == Plan(backend="sptc", L=6)


# ---------------------------------------------------------------------------
# correctness: tuned plans == direct oracle across the paper suite
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["cost"])
def test_tuned_matches_direct_over_paper_suite(mode, rng):
    cache = PlanCache()
    for spec in paper_suite():
        dims = {1: (131,), 2: (24, 27), 3: (9, 10, 11)}[spec.ndim]
        x = _x(spec, dims, rng)
        got = tuned_apply(spec, x, cache=cache, mode=mode)
        want = apply_stencil(spec, x, backend="direct")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_every_candidate_plan_matches_direct(rng):
    """Stronger than the tuned pick: ALL candidates are valid executions."""
    spec = make_stencil("box", 2, 2, seed=6)
    x = _x(spec, (21, 23), rng)
    cache = PlanCache()
    want = np.asarray(apply_stencil(spec, x, backend="direct"))
    for plan in candidate_plans(spec):
        got = np.asarray(cache.engine(spec, plan)(x))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=str(plan))


# ---------------------------------------------------------------------------
# batched execution
# ---------------------------------------------------------------------------

def test_tuned_apply_temporal_matches_repeated_direct(rng):
    spec = make_stencil("star", 2, 1, seed=12)
    x = _x(spec, (20, 22), rng)                  # dims + 2r; k=2 needs 2·(2r)
    x = jnp.asarray(np.pad(np.asarray(x), spec.radius))
    cache = PlanCache()
    got = tuned_apply(spec, x, cache=cache, mode="cost", temporal_steps=2)
    want = apply_stencil(spec, apply_stencil(spec, x, backend="direct"),
                         backend="direct")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the k=2 plan keys separately from the single-step plan
    assert cache.stats.tunes == 1
    tuned_apply(spec, x, cache=cache, mode="cost")
    assert cache.stats.tunes == 2


def test_tuned_apply_variable_coefficients(rng):
    from repro.core.engine import StencilEngine
    spec = make_stencil("box", 2, 1, seed=13)
    dims = (10, 12)
    c = rng.normal(size=dims + (3, 3))
    x = jnp.asarray(rng.normal(size=(12, 14)), jnp.float32)
    cache = PlanCache()
    got = tuned_apply(spec, x, cache=cache, mode="cost", coefficients=c)
    want = StencilEngine(spec, backend="direct", coefficients=c)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # var plans tune per content fingerprint, apart from the const plan
    assert cache.stats.tunes == 1
    tuned_apply(spec, x, cache=cache, mode="cost", coefficients=c)
    assert cache.stats.tunes == 1                # same field: cache hit
    tuned_apply(spec, x, cache=cache, mode="cost")
    assert cache.stats.tunes == 2                # const plan is separate


def test_batched_matches_per_instance(rng):
    spec = make_stencil("star", 2, 1, seed=7)
    xs = jnp.asarray(rng.normal(size=(5, 40, 44)), jnp.float32)
    cache = PlanCache()
    got = tuned_apply_batched(spec, xs, cache=cache, mode="cost")
    assert got.shape == (5, 38, 42)
    for i in range(xs.shape[0]):
        want = apply_stencil(spec, xs[i], backend="direct")
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_batched_accepts_sequence_of_same_shape_jobs(rng):
    spec = make_stencil("box", 1, 1, seed=9)
    xs = [jnp.asarray(rng.normal(size=(50,)), jnp.float32) for _ in range(3)]
    cache = PlanCache()
    got = tuned_apply_batched(spec, xs, cache=cache, mode="cost")
    want = tuned_apply_batched(spec, jnp.stack(xs), cache=cache, mode="cost")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batched_rejects_mismatched_shapes(rng):
    """The old behavior silently assumed one shape; now the error names
    the offending jobs and their shapes."""
    spec = make_stencil("star", 2, 1, seed=7)
    xs = [jnp.zeros((34, 34)), jnp.zeros((34, 34)), jnp.zeros((36, 34))]
    with pytest.raises(ValueError) as ei:
        tuned_apply_batched(spec, xs, cache=PlanCache(), mode="cost")
    msg = str(ei.value)
    assert "(34, 34)" in msg and "(36, 34)" in msg and "job 2" in msg


def test_batched_rejects_mismatched_dtypes_and_bad_rank(rng):
    spec = make_stencil("star", 2, 1, seed=7)
    cache = PlanCache()
    xs = [jnp.zeros((34, 34), jnp.float32), jnp.zeros((34, 34), jnp.bfloat16)]
    with pytest.raises(ValueError, match="dtype"):
        tuned_apply_batched(spec, xs, cache=cache, mode="cost")
    with pytest.raises(ValueError, match="empty"):
        tuned_apply_batched(spec, [], cache=cache, mode="cost")
    with pytest.raises(ValueError, match="B, \\*spatial"):
        tuned_apply_batched(spec, jnp.zeros((34, 34)), cache=cache,
                            mode="cost")
    with pytest.raises(ValueError, match="halo"):
        tuned_apply_batched(spec, jnp.zeros((4, 2, 34)), cache=cache,
                            mode="cost")


def test_batched_reuses_compiled_program(rng):
    spec = make_stencil("box", 1, 1, seed=9)
    xs = jnp.asarray(rng.normal(size=(4, 66)), jnp.float32)
    cache = PlanCache()
    tuned_apply_batched(spec, xs, cache=cache, mode="cost")
    builds = cache.stats.engine_builds
    tuned_apply_batched(spec, xs, cache=cache, mode="cost")
    assert cache.stats.engine_builds == builds


# ---------------------------------------------------------------------------
# timing mode (small, smoke-level — CI stays fast)
# ---------------------------------------------------------------------------

def test_timing_mode_smoke(rng):
    spec = make_stencil("box", 1, 1, seed=10)
    x = _x(spec, (96,), rng)
    res = autotune(spec, x.shape, x.dtype, mode="time", warmup=1, iters=2)
    assert res.mode == "time"
    assert res.plan in candidate_plans(spec)
    assert all(c.score > 0 for c in res.candidates)


def test_timing_mode_raises_naming_the_failing_plan(rng):
    """A candidate that fails to build/compile/run is a fault on this
    device: autotune raises and names the plan, never skips it."""
    from repro.tuner.search import _default_engine_factory
    spec = make_stencil("box", 1, 1, seed=12)
    x = _x(spec, (64,), rng)
    bad = next(p for p in candidate_plans(spec) if p.backend == "gemm")

    def factory(s, p, coefficients=None):
        if p == bad:
            raise ValueError("kernel refused")
        return _default_engine_factory(s, p, coefficients=coefficients)

    with pytest.raises(RuntimeError, match=re.escape(str(bad))) as ei:
        autotune(spec, x.shape, x.dtype, mode="time", iters=1,
                 engine_factory=factory)
    assert isinstance(ei.value.__cause__, ValueError)


def test_time_mode_prunes_losing_candidate_engines(rng):
    """A timed tune must not leave every losing candidate's jitted engine
    resident — only the winner (and pre-existing engines) survive."""
    spec = make_stencil("box", 1, 1, seed=11)
    x = _x(spec, (80,), rng)
    cache = PlanCache()
    plan = plan_for(spec, x.shape, x.dtype, cache=cache, mode="time", iters=2)
    assert cache.engine_plans(spec) == frozenset({plan})


def test_autotune_rejects_bad_mode():
    with pytest.raises(ValueError):
        autotune(make_stencil("box", 1, 1), (32,), mode="fastest")
