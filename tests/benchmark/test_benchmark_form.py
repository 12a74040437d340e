"""``BENCHMARK.json`` held to the form the driver refuses a file for before
any run: the keys of each entry, names, units, lengths, one cell to a pair
of configuration and traffic, the share of four-chip cells, what each
metric's ``moves`` and ``workloads`` may name, and the time rule of
``run_seconds``.  The tiny benchmark of the CPU tests is held to it too,
because it stands for what a later PR adds."""

import json
import os
import re

import pytest

import benchmark_tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def faults(root: str) -> list:
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        spec = json.load(fh)
    out = []

    def need(ok, what):
        if not ok:
            out.append(what)

    need(os.path.getsize(path) <= 64 * 1024, "over 64 KiB")
    need(set(spec) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}, "keys")
    paths = spec["paths"]
    need(1 <= len(paths) <= 16 and all(
        PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        for p in paths), "paths")
    need(1 <= len(spec["command"]) <= 32 and all(
        _line(w) and not w.startswith("/") and ".." not in w.split("/")
        for w in spec["command"]), "command")
    for word in spec["command"]:
        if os.path.exists(os.path.join(root, word)):
            need(any(word.startswith(p + "/") for p in paths),
                 f"command names {word} outside paths")

    configs = spec["configs"]
    need(1 <= len(configs) <= 24, "number of configs")
    files = [c["file"] for c in configs]
    need(len(set(files)) == len(files), "a configuration file given twice")
    for c in configs:
        need(set(c) == {"name", "source", "file", "reduced", "why"},
             f"config keys {c.get('name')}")
        need(NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"]),
             f"config text {c['name']}")
        need(any(c["file"].startswith(p + "/") for p in paths)
             and PATH.match(c["file"])
             and os.path.isfile(os.path.join(root, c["file"])),
             f"config file {c['name']}")
        need(len(c["reduced"]) <= 16
             and all(NAME.match(k) for k in c["reduced"])
             and not any(k.endswith(("_dim", "_rank")) for k in c["reduced"]),
             f"reduced {c['name']}")

    cells = spec["workloads"]
    need(1 <= len(cells) <= 24, "number of cells")
    for w in cells:
        need(set(w) == {"name", "config", "traffic", "chips", "why"},
             f"cell keys {w.get('name')}")
        need(NAME.match(w["name"]) and NAME.match(w["traffic"])
             and _line(w["why"]) and w["chips"] in (1, 4),
             f"cell text {w['name']}")
        need(w["config"] in {c["name"] for c in configs},
             f"cell {w['name']} names no configuration")
    pairs = [(w["config"], w["traffic"]) for w in cells]
    need(len(set(pairs)) == len(pairs),
         "a pair of config and traffic given twice")
    need({c["name"] for c in configs} == {w["config"] for w in cells},
         "a configuration no cell uses")
    need(sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4),
         "too many four-chip cells")

    names = [x["name"] for k in ("configs", "workloads") for x in spec[k]]
    cell_names = {w["name"] for w in cells}
    e2e, layers = spec["end_to_end"], spec["per_layer"]
    need(1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128, "number of metrics")
    metric_names = [m["name"] for m in e2e + layers]
    need(len(set(metric_names)) == len(metric_names), "a metric given twice")
    need(len(set(names)) == len(names), "a name given twice")

    def reported_in(metric):
        return set(metric.get("workloads", cell_names))

    for m in e2e:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                        "source"}, f"keys of {m['name']}")
        need(m["source"] in ("host_clock", "device_trace"),
             f"source of {m['name']}")
        need(0 < m["bound"] <= 0.1, f"bound of {m['name']}")
    need("setup_s" in {m["name"] for m in e2e}
         and "workloads" not in next(m for m in e2e if m["name"] == "setup_s"),
         "setup_s in every cell")
    for m in layers:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                        "layer", "moves"},
             f"keys of {m['name']}")
        need(m["source"] in SOURCES and _line(m["layer"]),
             f"source or layer of {m['name']}")
        moved = next((e for e in e2e if e["name"] == m["moves"]), None)
        need(moved is not None, f"{m['name']} moves no end-to-end metric")
        if moved is not None and "workloads" in m:
            need(reported_in(m) <= reported_in(moved),
                 f"{m['name']} listed in a cell without {m['moves']}")
    for m in e2e + layers:
        need(NAME.match(m["name"]) and UNIT.match(m["unit"])
             and m["better"] in ("lower", "higher"), f"text of {m['name']}")
        need(reported_in(m) <= cell_names and reported_in(m),
             f"{m['name']} lists an unknown cell")
    for w in cells:
        mine = [m["name"] for m in e2e if w["name"] in reported_in(m)]
        need(len(mine) >= 2, f"{w['name']} has setup_s alone")
        need(any(w["name"] in reported_in(m) for m in layers),
             f"{w['name']} has no per-layer metric")

    seconds = spec["run_seconds"]
    need(isinstance(seconds, int) and 1 <= seconds <= 51
         and (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200,
         "run_seconds")
    return out


def test_the_real_benchmark_keeps_the_form():
    assert faults(benchmark_tiny.REPO) == []


def _four_chip_room(root: str) -> bool:
    """Whether ``root``'s cells are within their allowance of four-chip
    cells: a quarter of them, rounded down, and one always."""
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        cells = json.load(fh)["workloads"]
    return sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("toys", ["benchmark_tiny", "benchmark_tiny_qwen",
                                  "benchmark_tiny_sdar",
                                  "benchmark_tiny_kanana2",
                                  "benchmark_tiny_mellum2"])
def test_a_tiny_benchmark_keeps_the_form(tmp_path, toys):
    """Every toy benchmark of the CPU tests is the real one with files and
    entries added, so it keeps the form the real one keeps, but for the toy
    four-chip cell of ``benchmark_tiny`` where the real cells leave it no
    room: the allowance follows the count of ``BENCHMARK.json``'s cells,
    not a number written here."""
    import importlib

    root = importlib.import_module(toys).make(str(tmp_path))
    assert faults(root) == ([] if _four_chip_room(root)
                            else ["too many four-chip cells"])


def _every_cell_on_four_chips(spec):
    for w in spec["workloads"]:
        w.update(chips=4)


@pytest.mark.parametrize("edit,fault", [
    (lambda s: s["workloads"][3].update(traffic=s["workloads"][0]["traffic"]),
     "a pair of config and traffic given twice"),
    # (one more four-chip cell is a fault or not by the count of cells; all
    # of them on four chips is one whatever the count)
    pytest.param(_every_cell_on_four_chips, "too many four-chip cells",
                 id="every-cell-on-four-chips"),
    (lambda s: s["end_to_end"][0].update(why="a rate"),
     "keys of tokens_per_s_chip"),
    (lambda s: s["end_to_end"][0].update(unit="tokens per second"),
     "text of tokens_per_s_chip"),
    (lambda s: s["per_layer"][5].update(workloads=["gpt2s-16k"],
                                        moves="step_ms_p95"),
     "allreduce_ms listed in a cell without step_ms_p95"),
    (lambda s: s.update(run_seconds=52), "run_seconds"),
])
def test_a_fault_of_form_is_named(tmp_path, edit, fault):
    root = benchmark_tiny.make(str(tmp_path))
    with open(os.path.join(benchmark_tiny.REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    edit(spec)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    assert fault in faults(root)
