"""BENCHMARK.json against the files the harness finds by name."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_metric_has_a_reader():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"])
        mod = harness.load_reader(m["name"])
        assert callable(mod.read)


def test_every_cell_has_its_files_and_metrics():
    cells = {w["name"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        harness.cell_spec(SPEC, w["name"])          # config, mix, limits
        e2e = harness.cell_metrics(SPEC, w["name"], False)
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        assert harness.cell_metrics(SPEC, w["name"], True)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    e2e_names = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e_names
        # each listed cell reports the end-to-end metric this one moves
        reporting = {w for w in cells if m["moves"] in
                     {x["name"] for x in harness.cell_metrics(SPEC, w,
                                                              False)}}
        assert set(m["workloads"]) <= reporting


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cuts(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        conf = json.load(f)
    assert conf["source"] == entry["source"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    for key, cut in conf["reduced"].items():
        assert conf[key] == cut["run"] != cut["published"]
    a = conf["arch"]
    layers = conf.get("num_hidden_layers", conf.get("n_layer"))
    assert a["n_layers"] == layers
    d = conf.get("hidden_size", conf.get("d_model"))
    assert a["d_model"] == d
    if a["family"] == "dense":
        assert (a["n_heads"], a["n_kv"], a["d_ff"], a["vocab"]) == (
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["intermediate_size"], conf["vocab_size"])
        assert a["head_dim"] * a["n_heads"] == d
        assert a["rope_theta"] == conf["rope_theta"]
        assert a["norm_eps"] == conf["rms_norm_eps"]
        assert a["tie_embeddings"] == conf["tie_word_embeddings"]
    else:
        s, pub = a["ssm"], conf["ssm_cfg"]
        assert (s["d_state"], s["conv_width"], s["expand"], s["head_dim"],
                s["n_groups"], s["chunk"]) == (
            pub["d_state"], pub["d_conv"], pub["expand"], pub["headdim"],
            pub["ngroups"], pub["chunk_size"])
        m = conf["pad_vocab_size_multiple"]
        assert a["vocab"] == -(-conf["vocab_size"] // m) * m
        assert a["vocab"] == conf["assumed"]["padded_vocab"]
        assert a["tie_embeddings"] == conf["tie_embeddings"]
    # the harness builds the program's configuration from the block
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from chipbench import model
    cfg = model.arch_config(conf)
    assert cfg.n_layers == layers


def test_bounds_and_run_length_within_the_contract():
    assert 1 <= SPEC["run_seconds"] <= 51
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert "bound" not in m
