"""The builders' account and the benchmark speak of the same cells and
metrics: every configuration, cell and metric that ``BENCHMARK.json``
declares appears in ``PERF.md`` under its exact name, not as an
abbreviation or as part of a longer name. A PR that adds or retires one
keeps both files true (ISSUE 28). ``BENCHMARK.json`` is only read."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _declared() -> list[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        doc = json.load(fh)
    return [entry["name"]
            for section in ("configs", "workloads", "end_to_end",
                            "per_layer")
            for entry in doc[section]]


@pytest.fixture(scope="module")
def perf_md() -> str:
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as fh:
        return fh.read()


@pytest.mark.parametrize("name", _declared())
def test_perf_md_names_what_the_benchmark_declares(name, perf_md):
    # whole name: `peak_hbm_gb` is not found in `bosch_peak_hbm_gb`,
    # nor the configuration `ffm-criteo` in its cell `ffm-criteo.stream-zipf`
    whole = (r"(?<![A-Za-z0-9_-])" + re.escape(name)
             + r"(?![A-Za-z0-9_-]|\.[a-z])")
    assert re.search(whole, perf_md), f"PERF.md never names {name} in full"
