"""The autotuning layer: measured tile search with a persistent cache
(DESIGN.md §11).

Every plan engine (``core/plan.py`` §3, ``core/index_plan.py`` §4,
``core/stencil.py`` §9, ``core/dist_plan.py`` §10) runs the same three
steps — canonicalize, **route**, cache.  This module adds an optional
fourth step between route and cache: instead of trusting the one-shot
tiling heuristic, the planner enumerates a small neighborhood of legal
candidates (``kernels/tiling.py`` candidate API) and asks :func:`select`
to pick one.

Selection modes (resolved by :func:`resolve_mode` from ``REPRO_TUNE``):

* ``measure`` — time every candidate (:func:`time_candidates`, warmup +
  median) and persist the winner in the on-disk tuning cache, so
  steady-state serving/training pays zero tuning overhead across
  processes.  Only meaningful where kernels compile natively (TPU); under
  the Pallas interpreter, timings measure the interpreter, not the
  hardware.
* ``cost`` — rank candidates by the deterministic roofline cost model
  (``utils.roofline.movement_cost_s``), ties broken toward the heuristic
  (always the first candidate).  This is the automatic fallback off-TPU /
  under interpret mode, which is what keeps CI deterministic.

The tuner only ever changes *which* plan is cached — tile shapes, grid
order, or an engine choice between kernels proven bit-identical — never
the computed result (asserted in ``tests/test_tune.py``).

The disk cache (``REPRO_TUNE_CACHE``, default ``~/.cache/repro/tune.json``)
is a versioned JSON document keyed by plan-key string and scoped to one
``(backend, jax version)`` pair; stale, corrupt, or other-version files
are silently ignored and rebuilt, and writes are atomic
(write-temp-then-rename) so concurrent writers cannot clobber each other
into a torn file.
"""

from __future__ import annotations

import json
import os
import statistics
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Sequence

import jax

#: schema version of the on-disk cache; bump on any format change and old
#: files are rebuilt rather than misread.
SCHEMA_VERSION = 1

#: values of REPRO_TUNE that enable tuning for default (``tuned=None``) calls.
_ON_VALUES = ("on", "1", "measure", "cost")


@dataclass(frozen=True)
class Candidate:
    """One point in a planner's search space.

    ``label`` names the candidate (stable across processes — it is the
    persisted cache value); ``params`` carries the engine-specific plan
    overrides as a hashable ``((name, value), ...)`` tuple; ``cost_s`` is
    the roofline cost-model score used for deterministic selection.
    """

    label: str
    params: tuple
    cost_s: float

    def param_dict(self) -> dict:
        """The overrides as a plain dict (planner keyword arguments)."""
        return dict(self.params)


def tune_default() -> bool:
    """Whether ``tuned=None`` planner calls resolve to the tuned path.

    Off unless ``REPRO_TUNE`` is one of ``on | 1 | measure | cost`` — so
    with the variable unset or ``off`` (the CI default) every plan is the
    heuristic one, bit-identical to the untuned engines.
    """
    return os.environ.get("REPRO_TUNE", "off").lower() in _ON_VALUES


def resolve_mode() -> str:
    """The selection backend a tuned plan uses: ``measure`` or ``cost``.

    ``REPRO_TUNE=measure`` / ``REPRO_TUNE=cost`` force a backend; the
    default (``on``) measures only where timing reflects the hardware —
    a TPU, where the kernels are compiled — and cost-scores everywhere
    else (the interpreter off the chip), so CI stays deterministic
    without configuration.
    """
    v = os.environ.get("REPRO_TUNE", "off").lower()
    if v == "measure":
        return "measure"
    if v == "cost":
        return "cost"
    from repro.kernels.tiling import interpreted

    return "cost" if interpreted() else "measure"


# ---------------------------------------------------------------------------
# the persistent tuning cache
# ---------------------------------------------------------------------------


def sample_array(shape: Sequence[int], dtype_name: str):
    """Deterministic sample operand for measured-mode runners: a small
    repeating ramp (``arange % 251``), cheap to build at any size and
    identical across processes so persisted winners are comparable.
    Shared by every planner's runner factory."""
    import jax.numpy as jnp

    n = 1
    for s in shape:
        n *= int(s)
    return (
        (jnp.arange(n, dtype=jnp.float32) % 251).astype(dtype_name).reshape(shape)
    )


def cache_path() -> Path:
    """Where the tuning cache lives: ``REPRO_TUNE_CACHE`` or the default
    ``~/.cache/repro/tune.json``."""
    p = os.environ.get("REPRO_TUNE_CACHE", "")
    if p:
        return Path(p)
    return Path.home() / ".cache" / "repro" / "tune.json"


def _scope() -> dict:
    """The (schema, backend, jax) triple one cache file is valid for."""
    return {
        "schema": SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "jax": jax.__version__,
    }


def load_cache() -> dict:
    """Read the tuning cache; ``{}`` entries when the file is missing,
    unparseable, from another schema version, or recorded on a different
    backend / jax version (a stale cache is ignored, never trusted)."""
    path = cache_path()
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError):
        return {**_scope(), "entries": {}}
    scope = _scope()
    if not isinstance(doc, dict) or any(doc.get(k) != v for k, v in scope.items()):
        return {**scope, "entries": {}}
    entries = doc.get("entries")
    if not isinstance(entries, dict):
        return {**scope, "entries": {}}
    return {**scope, "entries": entries}


def store_entry(key: str, record: dict) -> None:
    """Merge one winner record into the on-disk cache, atomically.

    Load-modify-write with a temp file + ``os.replace`` in the cache's
    directory: a concurrent writer can win the race for the *file* (last
    rename wins whole-file), but no reader ever observes a torn document.
    Unwritable cache locations are ignored — tuning still works, it just
    re-measures per process.
    """
    path = cache_path()
    doc = load_cache()
    doc["entries"][key] = record
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            prefix=path.name + ".", dir=str(path.parent)
        )
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except OSError:
        pass


def lookup(key: str) -> dict | None:
    """The persisted winner record for ``key``, if any."""
    return load_cache()["entries"].get(key)


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def time_candidates(
    candidates: Sequence[Candidate],
    runner_factory: Callable[[Candidate], Callable[[], object]],
    *,
    warmup: int = 1,
    iters: int = 5,
) -> list[float]:
    """Median wall-clock seconds per candidate.

    ``runner_factory(candidate)`` builds a zero-argument callable that
    executes one full candidate run (inputs pre-built, typically jitted);
    each candidate gets ``warmup`` untimed calls (compilation) and the
    median of ``iters`` timed calls with device sync.  A candidate whose
    runner raises scores ``inf`` (illegal configurations lose, they don't
    crash the tune).
    """
    out = []
    for cand in candidates:
        try:
            fn = runner_factory(cand)
            for _ in range(warmup):
                jax.block_until_ready(fn())
            samples = []
            for _ in range(iters):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                samples.append(time.perf_counter() - t0)
            out.append(statistics.median(samples))
        except Exception:  # noqa: BLE001 — an illegal candidate just loses
            out.append(float("inf"))
    return out


def select(
    engine: str,
    key: str,
    candidates: Sequence[Candidate],
    runner_factory: Callable[[Candidate], Callable[[], object]] | None,
    *,
    mode: str | None = None,
    persist: bool = True,
) -> Candidate:
    """Pick one candidate for plan key ``key`` of ``engine``.

    The contract every planner relies on:

    * the heuristic candidate is ``candidates[0]`` and wins all ties, so
      a tuned plan degrades to the untuned plan, never past it;
    * ``cost`` mode is pure arithmetic over ``Candidate.cost_s`` —
      deterministic, no I/O;
    * ``measure`` mode consults the persistent cache first (a recorded
      winner whose label still exists in the candidate set short-circuits
      the timing entirely), then times the field and persists the winner
      (``persist=False`` for keys that are not stable across processes,
      e.g. stencil programs with opaque Python functors);
    * no runner (``runner_factory=None``) always falls back to ``cost``
      — the distributed planner tunes this way because re-materializing a
      mesh inside a cached planner is not possible.
    """
    if not candidates:
        raise ValueError(f"{engine}: empty candidate set for {key!r}")
    if len(candidates) == 1:
        return candidates[0]
    if mode is None:
        mode = resolve_mode()
    if mode == "measure" and runner_factory is not None:
        full_key = f"{engine}|{key}"
        if persist:
            rec = lookup(full_key)
            if rec is not None:
                for cand in candidates:
                    if cand.label == rec.get("label"):
                        return cand
                # recorded winner no longer enumerated (code moved on):
                # fall through and re-tune
        timings = time_candidates(candidates, runner_factory)
        best = min(range(len(candidates)), key=lambda i: (timings[i], i))
        if timings[best] == float("inf"):
            # every candidate failed to run (transient device trouble, OOM
            # on the sample input): keep the heuristic but do NOT persist —
            # a recorded winner would short-circuit re-tuning forever, and
            # Infinity is not valid strict JSON
            return candidates[0]
        if persist:
            record = {
                "label": candidates[best].label,
                "params": candidates[best].param_dict(),
                "us": round(timings[best] * 1e6, 2),
                "n_candidates": len(candidates),
                "mode": "measure",
            }
            if timings[0] != float("inf"):
                # omitted when the heuristic itself failed to run —
                # Infinity is not valid strict JSON
                record["us_heuristic"] = round(timings[0] * 1e6, 2)
            store_entry(full_key, record)
        return candidates[best]
    # deterministic fallback: roofline cost model, first-wins ties
    return min(candidates, key=lambda c: c.cost_s)
