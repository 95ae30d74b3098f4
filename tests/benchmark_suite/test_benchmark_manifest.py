"""BENCHMARK.json against the files it names: everything resolves by
name, names keep to the contract's characters, and the metrics hang
together."""
import json
import os
import re

from benchmark import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = manifest.benchmark_json()


def test_workloads_resolve_by_name():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        cfg = manifest.config(w["config"])
        mix = manifest.traffic(w["traffic"])
        cell = manifest.cell_file(w["name"])
        assert configs[w["config"]]["file"] == \
            f"benchmark/configs/{w['config']}.json"
        assert hasattr(manifest.family(cfg["family"]), "build")
        assert hasattr(manifest.reference(w["config"]), "sizes")
        assert mix["kind"] in ("train_steps", "serve")
        assert cell["limits"] and cell["rehearsal"]
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        published = manifest.config(c["name"]).get("published", {})
        assert sorted(published) == sorted(c["reduced"])


def test_per_layer_metrics_resolve():
    """A metric's file names its reader and the reader's arguments and
    nothing that BENCHMARK.json already says."""
    for m in BENCH["per_layer"]:
        spec = manifest.metric_file(m["name"])
        assert set(spec) == {"reader", "args"}, m["name"]
        assert callable(manifest.reader(spec["reader"]))
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(manifest.HERE, "metrics"))}
    assert on_disk == {m["name"] for m in BENCH["per_layer"]}


def test_names_and_units_keep_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_metric_moves_what_its_cells_report():
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m.get("workloads", []):
            assert cell in cells and cell in e2e[m["moves"]], (m, cell)
    for cell in cells:
        mine = [m["name"] for m in manifest.metrics_of(cell, "end_to_end",
                                                       BENCH)]
        assert mine == [n for n, ws in e2e.items() if cell in ws]
        assert len(mine) >= 2
        layers = manifest.metrics_of(cell, "per_layer", BENCH)
        assert all(cell in e2e[m["moves"]] for m in layers)
        for metric in mine:
            if metric == "setup_s":
                continue
            assert any("mfu" in re.split(r"[._]", m["name"])
                       and m["moves"] == metric for m in layers), \
                (cell, metric)


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
