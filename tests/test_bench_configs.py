"""The benchmark's one-chip and four-chip read-alignment deployments stay
one comparison: ``readmap-gotoh-4chip`` serves ``readmap-gotoh``'s pairs,
scoring and limits with the same lanes per device, and its mix
``rounds4096`` is ``rounds1024`` with four times the round, 1024 pairs a
chip."""
import json
import os

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("key", ["problem", "precision", "instance",
                                 "limits", "lanes_per_device"])
def test_four_chip_config_matches_one_chip(key):
    one = _load("configs", "readmap-gotoh.json")
    four = _load("configs", "readmap-gotoh-4chip.json")
    if key == "lanes_per_device":
        one, four = one["service"], four["service"]
    assert four[key] == one[key]


def test_four_chip_config_states_its_layout_and_cuts_nothing():
    four = _load("configs", "readmap-gotoh-4chip.json")
    assert four["reduced"] == []
    assert "4" in four["layout"]["host"]
    one = _load("configs", "readmap-gotoh.json")
    assert one["assumed"].items() <= four["assumed"].items()


def test_four_chip_mix_is_the_one_chip_mix_times_four():
    one = _load("traffic", "rounds1024.json")
    four = _load("traffic", "rounds4096.json")
    assert four == dict(one, round=4 * one["round"])


def test_four_chip_cell_names_its_config_and_mix():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"]
                if w["name"] == "gotoh.batch.4chip")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("readmap-gotoh-4chip", "rounds4096", 4)
    config = next(c for c in bench["configs"]
                  if c["name"] == cell["config"])
    assert _load(*config["file"].split("/")[1:])["name"] == config["name"]
