"""BENCHMARK.json's cells and configurations keep the file's rules of form.

A cell is one configuration under one traffic mix, so a pair of the two
names one cell at most: a second scale of a configuration under the same
traffic is a configuration of its own, not a second cell of the first.
"""
from __future__ import annotations

import pytest
from tiny_cells import BENCHMARK


def test_each_config_and_traffic_pair_names_one_cell():
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(pairs) == len(set(pairs)), pairs


@pytest.mark.parametrize("kind,field", [("configs", "why"),
                                        ("configs", "source"),
                                        ("workloads", "why")])
def test_free_text_is_one_short_line(kind, field):
    for entry in BENCHMARK[kind]:
        text = entry[field]
        assert 1 <= len(text) <= 200, (entry["name"], len(text))
        assert "\n" not in text and "\t" not in text, entry["name"]
