"""``BENCHMARK.json`` against the contract's rules that can be checked
without a chip, and against the files it names."""

import re

import pytest

from benchmarks.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = spec.load_benchmark()


def cells_of(metric):
    return metric.get("workloads") or [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"]
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    n = len(BENCH["workloads"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, n // 4)


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_keys(group):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[group]
    names = [e["name"] for e in BENCH[group]]
    assert len(names) == len(set(names))
    for e in BENCH[group]:
        assert set(e) <= allowed, e
        assert NAME.match(e["name"]), e["name"]
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup


def test_every_cell_reports_what_it_must():
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        e2e = [m["name"] for m in BENCH["end_to_end"] if w["name"] in cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(w["name"] in cells_of(m) for m in BENCH["per_layer"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_every_cell_is_judged_by_a_latency_or_a_rate_of_bounded_noise():
    """PR 23's rule, restored by PR 51: a bound over 5% on a latency or a
    rate means the metric is not fit to judge (the two at 10% are the
    set-up, whose bound is the contract's, and the sessions cells' rate)."""
    for m in BENCH["end_to_end"]:
        if m["name"] not in ("setup_s", "out_tok_s.sessions"):
            assert m["bound"] <= 0.05, m
    for w in BENCH["workloads"]:
        judged = [m for m in BENCH["end_to_end"]
                  if w["name"] in cells_of(m) and m["name"] != "setup_s"]
        assert any(m["unit"] in ("ms", "tokens/s") for m in judged), w["name"]


def test_the_set_up_stages_before_the_judged_clock_are_on_record_everywhere():
    by = {m["name"]: m for m in BENCH["per_layer"]}
    for name in ("backend_up_s", "imports_s"):
        m = by[name]
        assert (m["moves"], m["unit"], m["layer"], m["source"]) == (
            "setup_s", "s", "device", "host_clock")
        assert "workloads" not in m  # every cell, those later PRs add too
        assert spec.load_layer_metric(name)["kind"] == "setup_stage"
    # the stall is judged; the longest gap sits beside it in the same cells
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for part in ("", ".tp4"):
        beside, judged = by["itl_max_p50_ms" + part], e2e["stall8_p50_ms" + part]
        assert beside["moves"] == judged["name"]
        assert beside["workloads"] == judged["workloads"]


def test_every_moves_names_a_metric_that_each_listed_cell_reports():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    layers = set()
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in cells_of(m):
            assert cell in cells_of(e2e[m["moves"]]), (m["name"], cell)
        layers.add(m["layer"])
    cell_names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(cells_of(m)) <= cell_names


def test_every_named_file_exists_and_loads():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        kind = cell.traffic["kind"]
        assert hasattr(spec.generator(kind), "plan")
        assert cell.config["deployment"]["chips"] == w["chips"]
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/")
        cfg = spec._load(spec.REPO_DIR / c["file"])
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced_why"])
        for key in c["reduced"]:
            assert NAME.match(key) and may_be_reduced(key), key
    for m in BENCH["per_layer"]:
        s = spec.load_layer_metric(m["name"])
        assert hasattr(spec.reader(s["kind"]), "read")
    open_cells = [w["name"] for w in BENCH["workloads"]
                  if spec.load_traffic(w["traffic"])["kind"] == "open"]
    for name in open_cells:
        assert spec.load_cell_params(name)["rate_rps"] > 0


def may_be_reduced(key: str) -> bool:
    """Widths are never cut. ``vocab_size`` is no width: the guide's slice
    of the vocabulary (model-configs, section 4) is a smaller vocabulary the
    traffic draws from."""
    return key == "vocab_size" or not key.endswith(("_dim", "_rank", "_size"))


@pytest.mark.parametrize("key, ok", [
    ("vocab_size", True), ("num_hidden_layers", True), ("n_routed_experts", True),
    ("max_position_embeddings", True), ("hidden_size", False),
    ("intermediate_size", False), ("moe_intermediate_size", False),
    ("head_dim", False), ("kv_lora_rank", False), ("q_lora_rank", False),
    ("qk_rope_head_dim", False),
])
def test_reduced_takes_the_vocabulary_slice_and_no_width(key, ok):
    assert may_be_reduced(key) is ok


def test_every_configuration_resolves_to_its_reference_and_tolerance():
    for c in BENCH["configs"]:
        cfg = spec._load(spec.REPO_DIR / c["file"])
        ref = spec.reference(cfg)
        assert callable(ref.arch_of) and callable(ref.served_gaps)
        tol = spec.load_tolerance(cfg)
        assert set(spec.TOLERANCE_KEYS) <= set(tol)
        assert tol["prompts"] >= 1 and tol["max_gap_sigmas"] > 0
    # the two that name nothing read as before
    assert spec.reference({}).__name__ == "benchmarks.reference.decoder"
    assert spec.load_tolerance({}) == spec._load(
        spec.BENCH_DIR / "reference" / "tolerance.json")
    for m in BENCH["per_layer"]:
        s = spec.load_layer_metric(m["name"])
        if "bytes_fn" in s:
            assert callable(spec.bytes_fn(s["bytes_fn"]))


def test_file_names_use_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in spec.BENCH_DIR.rglob("*"):
        if "__pycache__" in p.parts or p.suffix == ".pyc":
            continue
        assert ok.match(str(p.relative_to(spec.REPO_DIR))), p
