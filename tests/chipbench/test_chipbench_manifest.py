"""BENCHMARK.json against the rules it has to keep, and the files it
names."""
import json
import re
from pathlib import Path

import pytest

from chipbench import common

ROOT = common.ROOT
DATA = Path(__file__).resolve().parent / "data"
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TEXT = re.compile(r"^[^\t\n]{1,200}$")
E2E = {m["name"]: m for m in MAN["end_to_end"]}
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_text():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for k in ("why", "layer", "source"):
                if k in e and group != "end_to_end" \
                        and group != "per_layer":
                    assert TEXT.match(e[k]), e[k]
    metric_names = [n for is_m, n in names if is_m]
    assert len(metric_names) == len(set(metric_names))
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in MAN["per_layer"]:
        assert TEXT.match(m["layer"])
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert TEXT.match(w["why"])


def test_entry_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_bounds():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
    assert "setup_s" in E2E


def test_run_seconds_fit_the_check_with_24_cells():
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_at_most_half_the_cells_take_four_chips():
    chips = [w["chips"] for w in MAN["workloads"]]
    assert set(chips) <= {1, 4}
    assert sum(c == 4 for c in chips) <= max(len(chips) // 2, 1)
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_metric_and_a_layer(cell):
    man = common.manifest()
    e2e = [m["name"] for m in man["end_to_end"]
           if cell in m.get("workloads", [cell])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert common.per_layer_for(man, cell)


@pytest.mark.parametrize("metric", [m["name"] for m in MAN["per_layer"]])
def test_per_layer_moves_a_metric_its_cells_report(metric):
    m = next(x for x in MAN["per_layer"] if x["name"] == metric)
    mv = E2E[m["moves"]]
    for cell in m.get("workloads", CELLS):
        assert cell in CELLS
        assert cell in mv.get("workloads", CELLS)
    assert (ROOT / "chipbench" / "metrics" / f"{metric}.py").is_file()


def test_layer_names_agree_for_one_layer():
    by_layer = {}
    for m in MAN["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files(cell):
    spec = common.load_cell(cell)
    assert (ROOT / "chipbench" / "drivers"
            / f"{spec['traffic']['kind']}.py").is_file()
    assert set(spec["limits"]) and all(
        isinstance(v, (int, float)) for v in spec["limits"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_every_key_of_a_cell_file_is_read_or_descriptive(cell):
    spec = common.load_cell(cell)
    drv = common.driver(spec["traffic"]["kind"])
    common.check_keys(cell, spec["config"], drv.CONFIG_KEYS)
    common.check_keys(cell, spec["traffic"], drv.TRAFFIC_KEYS)
    with pytest.raises(ValueError, match="nothing reads"):
        common.check_keys(cell, dict(spec["traffic"], min_bucket=16),
                          drv.TRAFFIC_KEYS)
    with pytest.raises(ValueError, match="missing"):
        common.check_keys(cell, {k: v for k, v in spec["config"].items()
                                 if k != "reference"}, drv.CONFIG_KEYS)


# a width may never be cut: hidden, intermediate, latent, state or
# projection sizes, ``*_dim``/``*_rank``, head sizes, expansion
# factors, experts per token
WIDTH = re.compile(r"(_dim|_rank)$|^d_|d_ff|hidden|intermediate|latent"
                   r"|state_size|proj|expan|per_tok|top_k")


def _model_has(model, dotted):
    node = model
    for part in dotted.split("."):
        if not isinstance(node, dict) or part not in node:
            return False
        node = node[part]
    return True


def reduced_problems(cfg, listed):
    """What is wrong with a configuration file's cut: ``listed`` is its
    manifest entry's ``reduced``.  Each reduced key names a key of the
    file's ``model`` (a nested one by a dotted path) and no width; a
    cut file states ``assumed`` and a ``deployment`` with the published
    value of each reduced key and the chips that share a layer."""
    red = cfg.get("reduced")
    out = [] if red == listed else [f"reduced {red} != manifest {listed}"]
    for k in red or []:
        if not NAME.match(k):
            out.append(f"{k!r} is no name")
        if not _model_has(cfg["model"], k):
            out.append(f"{k!r} is not a key of model")
        if WIDTH.search(k.rsplit(".", 1)[-1]):
            out.append(f"{k!r} is a width")
    if red:
        dep = cfg.get("deployment")
        if not isinstance(dep, dict):
            return out + ["a cut file states its deployment as an object"]
        if set(dep.get("published", {})) != set(red):
            out.append("deployment.published names each reduced key")
        chips = dep.get("chips_per_layer")
        if not (isinstance(chips, int) and chips >= 1):
            out.append("deployment.chips_per_layer is a whole number")
        if not cfg.get("assumed"):
            out.append("a cut file states what it assumed")
    return out


def test_a_cut_configuration_passes_the_rules():
    cfg = json.loads((DATA / "cut_config.json").read_text())
    assert cfg["reduced"]
    assert reduced_problems(cfg, cfg["reduced"]) == []


@pytest.mark.parametrize("change,problem", [
    (lambda c: c["reduced"].append("moe.no_such_key"),
     "not a key of model"),
    (lambda c: c["reduced"].append("n_experts"), "not a key of model"),
    (lambda c: c["reduced"].append("moe.expert_d_ff"), "is a width"),
    (lambda c: c["reduced"].append("kv_lora_rank"), "is a width"),
    (lambda c: c["deployment"]["published"].pop("vocab_size"),
     "published"),
    (lambda c: c["deployment"].pop("chips_per_layer"), "chips_per_layer"),
    (lambda c: c.update(deployment="eight chips"), "as an object"),
    (lambda c: c.pop("assumed"), "assumed"),
])
def test_a_bad_cut_is_refused(change, problem):
    cfg = json.loads((DATA / "cut_config.json").read_text())
    change(cfg)
    got = reduced_problems(cfg, cfg["reduced"])
    assert any(problem in p for p in got), got


def test_reduced_is_the_same_list_in_the_manifest_and_the_file():
    cfg = json.loads((DATA / "cut_config.json").read_text())
    got = reduced_problems(cfg, cfg["reduced"][:-1])
    assert any("!= manifest" in p for p in got), got


@pytest.mark.parametrize("entry", MAN["configs"], ids=lambda c: c["name"])
def test_config_files(entry):
    used = {w["config"] for w in MAN["workloads"]}
    assert entry["name"] in used
    assert entry["file"].startswith("chipbench/")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert reduced_problems(cfg, entry["reduced"]) == []
    ref = common.reference(cfg)
    assert ref.__name__ == f"chipbench.references.{cfg['reference']}"
    for fn in ("init_tree", "param_count", "train_flops_per_token",
               "grads", "clip", "adam", "warmup_lr"):
        assert callable(getattr(ref, fn)), fn
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))


def test_peaks_table_is_keyed_by_device_kind():
    p = common.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("TPU v99")


def test_a_reference_no_file_holds_is_refused():
    cfg = common.load_cell(CELLS[0])["config"]
    with pytest.raises(ModuleNotFoundError):
        common.reference(dict(cfg, reference="no_such_reference"))
