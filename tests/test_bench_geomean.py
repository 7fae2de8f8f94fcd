"""Bench report hardening (ISSUE 19 satellite): the geomean-vs-baseline
input is computed by a real helper with a pinned contract — a lane that
cannot produce a trustworthy number reports a {"fallback": true} detail
INSTEAD of a result, and any non-positive value that slips into results
anyway (a lane bug, e.g. a negative TFLOP/s from a non-monotonic timing
window) is EXCLUDED from the ratio set, never clamped into a near-zero
log-ratio that drags vs_baseline to the floor."""

import math

import pytest

import bench


def test_baseline_ratios_basic():
    ratios = bench._baseline_ratios(
        {"a": 500.0, "b": 2000.0}, {"a": 1000.0, "b": 1000.0})
    assert ratios == {"a": 0.5, "b": 2.0}


def test_baseline_ratios_ignores_metrics_without_baseline():
    # Extra result keys (TPU lanes, detail-only rates) never enter the
    # geomean: only baselined metrics are ratio inputs.
    ratios = bench._baseline_ratios(
        {"a": 1000.0, "flash_attention_tflops": 120.0}, {"a": 1000.0})
    assert ratios == {"a": 1.0}


def test_baseline_ratios_excludes_non_positive_lane_values():
    # A regression seen once in a driver record: a broken timing window produced
    # -49.6 "TFLOP/s". Under the old max(r, 1e-9) clamp a single such
    # lane contributed log(1e-9) and cratered the geomean; the contract
    # is exclusion, so the healthy lanes fully determine the mean.
    ratios = bench._baseline_ratios(
        {"a": 1000.0, "bad": -49.6, "zero": 0.0},
        {"a": 1000.0, "bad": 100.0, "zero": 100.0})
    assert ratios == {"a": 1.0}
    assert bench._ratio_geomean(ratios) == pytest.approx(1.0)


def test_ratio_geomean_matches_log_mean_and_empty_is_neutral():
    ratios = {"a": 0.5, "b": 2.0, "c": 1.0}
    expect = math.exp(sum(math.log(r) for r in ratios.values()) / 3)
    assert bench._ratio_geomean(ratios) == pytest.approx(expect)
    assert bench._ratio_geomean({}) == 1.0
