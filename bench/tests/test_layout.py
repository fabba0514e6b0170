"""The harness is driven by data: every name in BENCHMARK.json resolves to
its files, and a new configuration, traffic mix, limits and metric reader,
added as files with BENCHMARK.json entries, are found with no edit to an
existing file."""
import json
import shutil

from bench.harness import cell as C


def test_every_entry_resolves():
    spec = C.load_json(C.ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = C.load_cell(w["name"])
        assert cell.traffic["driver"] == "train"
        assert cell.limits, w["name"]
        names = {m["name"] for m in cell.metrics(False)}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.metrics(True), w["name"]
    for kind in ("end_to_end", "per_layer"):
        for m in spec[kind]:
            assert callable(C.reader(m["name"])), m["name"]
    for c in spec["configs"]:
        assert C.load_json(C.ROOT / c["file"])["name"] == c["name"]


def test_new_cell_is_found_from_files_alone(tmp_path):
    shutil.copytree(C.BENCH, tmp_path / "bench")
    spec = C.load_json(C.ROOT / "BENCHMARK.json")
    b = tmp_path / "bench"
    cfg = C.load_json(b / "configs" / "transformer-100m.json")
    cfg["name"] = "new-model"
    (b / "configs" / "new-model.json").write_text(json.dumps(cfg))
    tr = C.load_json(b / "traffic" / "dpsgd-stacked4.json")
    tr["learners"] = 8
    (b / "traffic" / "new-mix.json").write_text(json.dumps(tr))
    (b / "limits" / "train.new.json").write_text(
        json.dumps({"loss_rel_gap": 1.0}))
    (b / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return run.counters.get('steps')\n")
    spec["configs"].append({"name": "new-model", "source": "x",
                            "file": "bench/configs/new-model.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "train.new", "config": "new-model",
                              "traffic": "new-mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_tokens_per_s_per_chip",
                              "workloads": ["train.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = C.load_cell("train.new", root=tmp_path)
    assert cell.config["name"] == "new-model"
    assert cell.traffic["learners"] == 8
    assert cell.limits == {"loss_rel_gap": 1.0}
    assert [m["name"] for m in cell.metrics(True)] == ["steps_seen"]
    run = C.Run(cell=cell, counters={"steps": 7})
    assert C.reader("steps_seen", root=tmp_path)(run) == 7
    assert C.reader("gossip_mix_roofline", root=tmp_path)(run) is None
