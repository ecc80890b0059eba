"""Public wrappers: the ``pallas`` pricing backends + certification harness.

``pallas_columns`` is what ``repro.core.pricing._dispatch`` calls when
``pricing_backend="pallas"`` is selected (interpret-mode f64, certified
bit-identical); ``pallas_columns_f32`` backs ``"pallas-compiled"`` (the
f32 (8, 128)-tiled lowering, settled through the drift contract in
:mod:`.drift`). ``certify`` / ``certify_f32`` are the gates
``tools/check_pricing_backend.py`` runs in CI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .kernel import DEFAULT_TILE, f32_call, run_columns, run_columns_f32


@functools.lru_cache(maxsize=256)
def _probe_outputs(formula, in_names: tuple[str, ...]
                   ) -> tuple[tuple[str, ...], tuple[bool, ...]]:
    """Output names + bool-ness of a column formula, discovered once per
    (formula, column layout) on a neutral all-ones row — every pricing
    denominator stays non-zero, and dtype discovery (the bool capacity
    check) does not depend on the row's values. Memoised so repeated
    kernel dispatches skip the probe entirely."""
    sample = {name: np.ones(1, dtype=np.float64) for name in in_names}
    out = formula(np, sample)
    return (tuple(out),
            tuple(np.asarray(v).dtype == np.bool_ for v in out.values()))


def pallas_columns(formula, cols, tile: int = DEFAULT_TILE,
                   interpret: bool = True) -> dict[str, np.ndarray]:
    """Run an elementwise column formula on the Pallas backend.

    Output keys/dtypes come from the memoised one-row probe (floats
    travel through the kernel as float64; bool outputs — the capacity
    check — round-trip as 0.0/1.0 and are restored here).
    """
    names, is_bool = _probe_outputs(formula, tuple(cols))
    out = run_columns(formula, cols, list(names), tile=tile,
                      interpret=interpret)
    for key, flag in zip(names, is_bool):
        if flag:
            out[key] = out[key].astype(np.bool_)
    return out


def pallas_columns_f32(formula, cols,
                       interpret: bool | str = "auto"
                       ) -> dict[str, np.ndarray]:
    """Run an elementwise column formula on the compiled f32 backend.

    Float outputs are float32 with bounded relative drift vs the f64
    envelope — NOT bit-identical; consumers must route decisions through
    :mod:`.drift` (see the kernel docstring's numerics contract). Bool
    outputs are restored from their 0.0/1.0 encoding, but near-boundary
    bits (e.g. ``feasible`` within the band of the capacity) are only as
    trustworthy as f32 — the banded selection re-checks them exactly.
    """
    names, is_bool = _probe_outputs(formula, tuple(cols))
    out = run_columns_f32(formula, cols, list(names), interpret=interpret)
    for key, flag in zip(names, is_bool):
        if flag:
            out[key] = out[key].astype(np.bool_)
    return out


def lower_f32(formula, in_names, n: int, interpret: bool | str = "auto",
              sharding=None):
    """Lower, without running, the compiled f32 kernel that
    :func:`pallas_columns_f32` runs for ``n`` rows of the ``in_names``
    columns: the same cached callable, on the same argument shapes
    (:func:`.kernel.f32_call`). Its HLO holds a ``tpu_custom_call`` exactly
    when the kernel is compiled rather than interpreted; the compile tests
    pass a ``sharding`` on a described TPU device."""
    names, _ = _probe_outputs(formula, tuple(in_names))
    call, shape = f32_call(formula, in_names, names, n, interpret)
    arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    return call.lower(*[arg] * (1 + len(in_names)))


def certify(n: int = 512, seed: int = 0,
            tile: int = DEFAULT_TILE) -> dict:
    """Prove row-identity of the Pallas pricing kernel against the float64
    scalar reference on ``n`` seeded random plan vectors.

    Raises ``AssertionError`` naming the diverging columns if any output
    bit differs; returns a small report dict otherwise. This is the same
    bit-exactness story ``tools/check_pricing_backend.py`` enforces for
    the numpy and jax backends.
    """
    from repro.core.pricing import _price, stack_plans

    from .ref import price_rows_scalar, random_plan_vectors

    vectors = random_plan_vectors(n, seed)
    got = pallas_columns(_price, stack_plans(vectors), tile=tile)
    ref_rows = price_rows_scalar(vectors)
    mismatches: dict[str, int] = {}
    for key in ref_rows[0]:
        want = np.array([r[key] for r in ref_rows])
        col = got[key]
        if want.dtype == np.bool_:
            bad = int((col.astype(bool) != want).sum())
        else:
            bad = int((col.view(np.uint64) != want.view(np.uint64)).sum())
        if bad:
            mismatches[key] = bad
    if mismatches:
        raise AssertionError(
            f"pallas pricing kernel diverged from the scalar reference "
            f"(rows with differing bits per column): {mismatches}")
    return {"rows": n, "tile": tile, "outputs": len(ref_rows[0]),
            "bit_identical": True}


def certify_f32(n: int = 512, seed: int = 0,
                band: float | None = None) -> dict:
    """Prove the compiled f32 kernel honours the declared drift band
    against the float64 scalar reference on ``n`` seeded random plan
    vectors.

    Every float output's relative drift must stay within the band, and
    every ``feasible`` bit may disagree only where the exact memory
    footprint itself lies within the band of the capacity (the zone the
    banded selection re-prices exactly). Raises ``AssertionError``
    otherwise; returns a drift report dict on success.
    """
    from repro.core.pricing import _price, stack_plans

    from .drift import drift_band
    from .ref import price_rows_scalar, random_plan_vectors

    delta = drift_band() if band is None else float(band)
    vectors = random_plan_vectors(n, seed)
    cols = stack_plans(vectors)
    got = pallas_columns_f32(_price, cols)
    ref_rows = price_rows_scalar(vectors)
    drifts: dict[str, float] = {}
    violations: dict[str, float] = {}
    for key in ref_rows[0]:
        want = np.array([r[key] for r in ref_rows])
        if want.dtype == np.bool_:
            flipped = got[key].astype(bool) != want
            if flipped.any():
                mem = np.array([r["per_chip_mem_bytes"] for r in ref_rows])
                cap = cols["mem_capacity"]
                margin = np.abs(mem - cap) / np.abs(cap)
                worst = float(margin[flipped].max())
                drifts["feasible_margin"] = worst
                if worst > delta:
                    violations["feasible"] = worst
            continue
        g = got[key].astype(np.float64)
        denom = np.where(want != 0.0, np.abs(want), 1.0)
        worst = float(np.max(np.abs(g - want) / denom))
        drifts[key] = worst
        if worst > delta:
            violations[key] = worst
    if violations:
        raise AssertionError(
            f"compiled f32 pricing kernel exceeded the declared drift "
            f"band {delta:g} (worst relative drift per column): "
            f"{violations}")
    return {"rows": n, "band": delta,
            "max_drift": max(drifts.values(), default=0.0),
            "drift_by_column": drifts, "within_band": True}
