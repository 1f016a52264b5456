"""``BENCHMARK.json`` and the files it names: every cell finds its
configuration, mix, reference and metric readers by name."""

import json
import re

from benchlib.spec import BENCH, ROOT, load_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(bench()) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}


def test_names_and_files():
    b = bench()
    for entry in b["configs"] + b["workloads"] + b["end_to_end"] + b["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for w in b["workloads"]:
        assert len(w["why"]) <= 200
        assert w["chips"] == 1


def test_every_cell_loads_its_pieces():
    b = bench()
    for w in b["workloads"]:
        cell = load_cell(w["name"])
        assert (BENCH / "refs" / f"{cell.mix['scenario']}.py").is_file()
        names = {m.name for m in cell.end_to_end}
        assert {"sim_wall_s", "setup_s"} <= names
        assert cell.per_layer


def test_bounds():
    for m in bench()["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        if m["name"] == "setup_s":
            assert m["bound"] == 0.25


def test_references_import_nothing_of_the_program():
    for path in (BENCH / "refs").glob("*.py"):
        text = path.read_text()
        assert "repro" not in re.sub(r'"""[\s\S]*?"""', "", text), path
