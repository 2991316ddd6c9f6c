"""Plan + compiled-engine cache with JSON persistence.

Three maps, three lifetimes:

  plans     PlanKey -> Plan.  Cheap, serializable — persisted to a JSON
            file so tuning survives process restarts (set the path, or
            the ``REPRO_TUNER_CACHE`` env var for the default cache).
  engines   (spec fingerprint, Plan, coeff fingerprint) -> StencilEngine.
            Holds the jitted executable; this is what kills the
            re-jit-per-call pattern the dead ``_cached_engine`` was meant
            to prevent.
  batched   same key -> jit(vmap(engine)).  The many-user entry: one
            compiled program for a whole batch.

Persistence format (version 2; version-1 files still load)::

    {"version": 2, "plans": {"v2;spec=...;shape=...;dtype=...;dev=...;
                             coeff=const;steps=1":
                             {"schema": 2, "backend": "sptc", "L": 8, ...}}}

Forward compatibility: a future-versioned file, or any individual entry
whose key/plan fails to decode, is skipped with a warning — never fatal
(a fleet mixing code revisions must not poison each other's caches).
Keys are re-canonicalized on load, so version-1 entries keep hitting.

Writes are atomic (tmp file + rename) and *merging*: if the file changed
on disk since this process last read it (another server tuned
concurrently), the on-disk entries are merged in first — in-memory plans
win conflicts — so a fleet converges on the union of its tuned plans.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import warnings
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.core.engine import StencilEngine
from repro.core.stencil import StencilSpec
from repro.tuner.plan import (Plan, PlanKey, coefficients_fingerprint,
                              mesh_desc, spec_fingerprint)

CACHE_ENV_VAR = "REPRO_TUNER_CACHE"
_FORMAT_VERSION = 2
_READABLE_VERSIONS = (1, 2)

#: engine-map key: (spec fingerprint, plan, coefficient fingerprint)
EngineKey = Tuple[str, Plan, str]

#: sharded-engine key: (spec fingerprint, plan, mesh geometry, grid axes)
ShardedKey = Tuple[str, Plan, str, Tuple[int, ...]]


@dataclasses.dataclass
class CacheStats:
    plan_hits: int = 0
    plan_misses: int = 0
    tunes: int = 0
    engine_builds: int = 0
    engine_hits: int = 0
    loads: int = 0
    saves: int = 0
    merges: int = 0
    skipped_entries: int = 0

    @property
    def plan_hit_rate(self) -> float:
        total = self.plan_hits + self.plan_misses
        return self.plan_hits / total if total else 0.0

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["plan_hit_rate"] = round(self.plan_hit_rate, 4)
        return d


def _coeff_fp(coefficients: Optional[Any]) -> str:
    return ("const" if coefficients is None
            else coefficients_fingerprint(coefficients))


class PlanCache:
    """In-memory plan + executable cache, optionally backed by a JSON file."""

    def __init__(self, path: str | os.PathLike | None = None) -> None:
        self.path: Optional[Path] = Path(path).expanduser() if path else None
        self.stats = CacheStats()
        self._plans: Dict[str, Plan] = {}
        self._engines: Dict[EngineKey, StencilEngine] = {}
        self._batched: Dict[EngineKey, Callable] = {}
        self._sharded: Dict[ShardedKey, Any] = {}
        self._sharded_batched: Dict[ShardedKey, Callable] = {}
        self._disk_sig: Optional[Tuple[int, int]] = None
        if self.path is not None:
            self.load(missing_ok=True)

    # -- plans ---------------------------------------------------------------
    def lookup(self, key: PlanKey) -> Optional[Plan]:
        plan = self._plans.get(key.encode())
        if plan is None:
            self.stats.plan_misses += 1
        else:
            self.stats.plan_hits += 1
        return plan

    def store(self, key: PlanKey, plan: Plan) -> None:
        self._plans[key.encode()] = plan
        if self.path is not None:
            self.save()

    def __len__(self) -> int:
        return len(self._plans)

    # -- compiled executables ------------------------------------------------
    def engine(self, spec: StencilSpec, plan: Plan,
               coefficients: Optional[Any] = None) -> StencilEngine:
        """The (memoized) compiled engine realizing ``plan`` for ``spec``.

        Variable-coefficient engines key additionally on the coefficient
        field's content fingerprint (the jitted program bakes the values).
        """
        k = (spec_fingerprint(spec), plan, _coeff_fp(coefficients))
        eng = self._engines.get(k)
        if eng is None:
            self.stats.engine_builds += 1
            eng = StencilEngine(spec, backend=plan.backend, L=plan.L,
                                fuse_rows=plan.fuse_rows,
                                temporal_steps=plan.temporal_steps,
                                coefficients=coefficients)
            self._engines[k] = eng
        else:
            self.stats.engine_hits += 1
        return eng

    def engine_plans(self, spec: StencilSpec) -> frozenset:
        """Plans that currently have a cached engine for ``spec``."""
        fp = spec_fingerprint(spec)
        plans = {p for f, p, _ in self._engines if f == fp}
        plans.update(k[1] for k in self._sharded if k[0] == fp)
        return frozenset(plans)

    def prune_engines(self, spec: StencilSpec,
                      keep: "frozenset[Plan] | set[Plan]") -> int:
        """Drop cached engines for ``spec`` whose plan is not in ``keep``.

        Used after a timed tune: losing candidates' jitted executables
        would otherwise live for the cache's lifetime. Returns #dropped.
        """
        fp = spec_fingerprint(spec)
        drop = [k for k in self._engines if k[0] == fp and k[1] not in keep]
        for k in drop:
            del self._engines[k]
            self._batched.pop(k, None)
        sdrop = [k for k in self._sharded
                 if k[0] == fp and k[1] not in keep]
        for k in sdrop:
            del self._sharded[k]
            self._sharded_batched.pop(k, None)
        return len(drop) + len(sdrop)

    def batched(self, spec: StencilSpec, plan: Plan,
                coefficients: Optional[Any] = None) -> Callable:
        """jit(vmap(engine)) over a leading batch axis, memoized."""
        k = (spec_fingerprint(spec), plan, _coeff_fp(coefficients))
        fn = self._batched.get(k)
        if fn is None:
            eng = self.engine(spec, plan, coefficients=coefficients)
            fn = jax.jit(jax.vmap(eng._fn))
            self._batched[k] = fn
        return fn

    # -- sharded executables -------------------------------------------------
    def _sharded_key(self, spec: StencilSpec, plan: Plan, mesh: Any,
                     grid_axes: Optional[Tuple[int, ...]]) -> ShardedKey:
        return (spec_fingerprint(spec), plan, mesh_desc(mesh),
                tuple(grid_axes) if grid_axes is not None else ())

    def sharded_engine(self, spec: StencilSpec, plan: Plan, mesh: Any,
                       grid_axes: Optional[Tuple[int, ...]] = None):
        """The (memoized) halo-exchange engine realizing ``plan`` on ``mesh``.

        ``mesh`` is a jax Mesh or an int/tuple of per-axis shard counts
        (see :func:`repro.distributed.halo.grid_mesh`).  Keyed by the
        canonical mesh geometry — two meshes with the same shard counts
        share one engine (they compile to the same program modulo device
        order).
        """
        from repro.distributed.halo import ShardedStencilEngine
        k = self._sharded_key(spec, plan, mesh, grid_axes)
        eng = self._sharded.get(k)
        if eng is None:
            self.stats.engine_builds += 1
            eng = ShardedStencilEngine(
                spec, mesh, backend=plan.backend, L=plan.L,
                fuse_rows=plan.fuse_rows,
                temporal_steps=plan.temporal_steps,
                grid_axes=grid_axes)
            self._sharded[k] = eng
        else:
            self.stats.engine_hits += 1
        return eng

    def sharded_batched(self, spec: StencilSpec, plan: Plan, mesh: Any,
                        grid_axes: Optional[Tuple[int, ...]] = None
                        ) -> Callable:
        """jit(vmap(sharded engine)): every job in the batch is mesh-
        partitioned; the batch axis stays unsharded."""
        k = self._sharded_key(spec, plan, mesh, grid_axes)
        fn = self._sharded_batched.get(k)
        if fn is None:
            eng = self.sharded_engine(spec, plan, mesh, grid_axes=grid_axes)
            fn = jax.jit(jax.vmap(eng._fn))
            self._sharded_batched[k] = fn
        return fn

    # -- persistence ---------------------------------------------------------
    @staticmethod
    def _signature(path: Path) -> Optional[Tuple[int, int]]:
        """Cheap change detector for the persisted file: (mtime_ns, size)."""
        try:
            st = path.stat()
        except OSError:
            return None
        return (st.st_mtime_ns, st.st_size)

    def _read_plans(self, source: Path) -> Optional[Dict[str, Plan]]:
        """Decode the persisted file, skipping bad entries with a warning.

        Returns None when the whole file is unreadable / future-versioned
        (callers treat that as empty); keys are re-canonicalized so
        version-1 entries keep matching freshly-encoded lookups.
        """
        try:
            payload = json.loads(source.read_text())
            version = payload.get("version")
            raw = payload.get("plans", {})
            if not isinstance(raw, dict):
                raise TypeError("'plans' must be a dict")
        except (OSError, ValueError, KeyError, TypeError) as e:
            warnings.warn(f"tuner cache {source}: unreadable ({e}); ignoring",
                          RuntimeWarning, stacklevel=3)
            return None
        if version not in _READABLE_VERSIONS:
            warnings.warn(
                f"tuner cache {source}: format version {version!r} not in "
                f"{_READABLE_VERSIONS}; ignoring", RuntimeWarning,
                stacklevel=3)
            return None
        plans: Dict[str, Plan] = {}
        for k, d in raw.items():
            try:
                key = PlanKey.decode(k)
                plans[key.encode()] = Plan.from_dict(d)
            except (ValueError, KeyError, TypeError) as e:
                self.stats.skipped_entries += 1
                warnings.warn(
                    f"tuner cache {source}: skipping entry {k!r} ({e})",
                    RuntimeWarning, stacklevel=3)
        return plans

    def save(self, path: str | os.PathLike | None = None) -> Path:
        """Atomically write all plans as JSON; returns the path written.

        If the target changed on disk since this cache last read it, the
        on-disk entries are merged in first (in-memory plans win), so
        concurrent tuners converge instead of clobbering each other.
        """
        target = Path(path).expanduser() if path else self.path
        if target is None:
            raise ValueError("no persistence path set for this cache")
        target.parent.mkdir(parents=True, exist_ok=True)
        if target == self.path and target.exists():
            sig = self._signature(target)
            if sig is not None and sig != self._disk_sig:
                disk = self._read_plans(target) or {}
                merged = 0
                for k, p in disk.items():
                    if k not in self._plans:
                        self._plans[k] = p
                        merged += 1
                if merged:
                    self.stats.merges += 1
        payload = {"version": _FORMAT_VERSION,
                   "plans": {k: p.to_dict() for k, p in self._plans.items()}}
        fd, tmp = tempfile.mkstemp(dir=str(target.parent),
                                   prefix=target.name, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, target)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
        if target == self.path:
            self._disk_sig = self._signature(target)
        self.stats.saves += 1
        return target

    def load(self, path: str | os.PathLike | None = None,
             missing_ok: bool = False) -> int:
        """Merge plans from a JSON file; returns the number loaded."""
        source = Path(path).expanduser() if path else self.path
        if source is None:
            raise ValueError("no persistence path set for this cache")
        if not source.exists():
            if missing_ok:
                return 0
            raise FileNotFoundError(source)
        sig = self._signature(source)
        plans = self._read_plans(source)
        if plans is None:
            return 0               # corrupt/unreadable cache: retune, don't crash
        self._plans.update(plans)
        if source == self.path:
            self._disk_sig = sig
        self.stats.loads += 1
        return len(plans)

    def clear(self, remove_file: bool = False) -> None:
        self._plans.clear()
        self._engines.clear()
        self._batched.clear()
        self._sharded.clear()
        self._sharded_batched.clear()
        self._disk_sig = None
        if remove_file and self.path is not None and self.path.exists():
            self.path.unlink()


# ---------------------------------------------------------------------------
# process-wide default cache
# ---------------------------------------------------------------------------

_default: Optional[PlanCache] = None


def default_cache() -> PlanCache:
    """The shared cache behind apply_stencil/tuned_apply.

    Persists iff ``REPRO_TUNER_CACHE`` names a file path at first use.
    """
    global _default
    if _default is None:
        _default = PlanCache(path=os.environ.get(CACHE_ENV_VAR) or None)
    return _default


def reset_default_cache() -> None:
    """Drop the process-wide cache (next default_cache() re-reads the env)."""
    global _default
    _default = None
