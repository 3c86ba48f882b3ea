"""BENCHMARK.json and discovery by name: every cell finds its
configuration, traffic mix, limits and metric readers in files of their
own."""
import json
import re

import pytest

from harness import spec, work

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]


def test_names_are_plain():
    names = [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names), names


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_parts(w):
    cell = spec.cell(w["name"], BENCH)
    assert cell.config["name"] == w["config"]
    assert set(cell.traffic) == {"num_parts", "partitioner", "use_kernel",
                                 "fused", "batch_fraction"}
    assert set(cell.limits) == {"loss", "first_update", "change"}
    assert cell.end_to_end == ["round_ms", "setup_s"]
    for name, mod in cell.per_layer.items():
        assert callable(mod.read), name
    if w["chips"] == 1:
        assert "exchange_ms" not in cell.per_layer
    else:
        assert {"exchange_ms", "exchange_exposed_ms"} <= set(cell.per_layer)


def test_every_config_file_is_named_and_used():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = spec.read_json(spec.ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert callable(spec.reader(m["name"]).read)
        assert m["moves"] in e2e


def test_bounds_within_the_contract():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.cell("no-such-cell", BENCH)


def test_peaks_known_for_the_chip():
    assert work.peaks("TPU v5 lite") == {"flops": 197e12, "bytes": 819e9}


def test_entries_have_exactly_the_contract_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_text_fields_fit():
    texts = [w["why"] for w in BENCH["workloads"] + BENCH["configs"]]
    texts += [c["source"] for c in BENCH["configs"]]
    texts += [m["layer"] for m in BENCH["per_layer"]]
    assert all(0 < len(t) <= 200 and "\n" not in t and "\t" not in t
               for t in texts)
    units = [m["unit"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", u) for u in units)
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_a_full_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
