"""The raw front end's arithmetic (``gbdt-bosch-968-raw``), kept with the
benchmark so that no PR that changes the transform changes its
yardstick. It counts the WORK, not an implementation: a form that finds
a bin in 8 compares instead of 254 is read against the same bytes."""

from __future__ import annotations


def transform_least_bytes(rows: int, n_features: int) -> float:
    """Least bytes a transform of a float table into bins moves through
    HBM, whatever implements it: every f32 cell read once and every
    int32 bin written once. (The edges, 254 f32 a column, are a
    thousandth of a percent of it at a million rows.)"""
    return 8.0 * rows * n_features


def transform_compares(rows: int, n_features: int, n_edges: int) -> float:
    """Compares a transform issues that pays ``n_edges`` a cell: the
    plain compare-count pays one an edge (254), the search the program
    has run since PR 48 one a level (8), and the adapters pass what the
    program's own span says. A fact for the run's log beside the share
    of the roofline, which does not depend on it."""
    return float(rows) * n_features * n_edges
