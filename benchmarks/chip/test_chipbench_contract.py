"""BENCHMARK.json against the benchmark's contract, and the command's
refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

import tiny  # noqa: F401
import harness

BENCH_FILE = harness.ROOT / "BENCHMARK.json"
BENCH = json.loads(BENCH_FILE.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH_FILE.stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (harness.ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_and_cells():
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert len(configs) == len(BENCH["configs"]) and 1 <= len(configs) <= 24
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        body = json.loads((harness.ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(body["reduced"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    cells = BENCH["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == set(configs)
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (harness.HERE / "traffic" / f"{w['traffic']}.json").is_file()


def test_metrics():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    layers = BENCH["per_layer"]
    assert 1 <= len(layers) <= 128
    names = list(e2e) + [m["name"] for m in layers]
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in layers:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"] + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        spec = harness.load_spec(w["name"])
        reported = {m["name"] for m in spec.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.per_layer
        assert all(m["moves"] in reported for m in spec.per_layer)


@pytest.mark.parametrize("traffic", sorted(
    p.stem for p in (harness.HERE / "traffic").glob("*.json")))
def test_traffic_names_its_entry_and_limits(traffic):
    body = json.loads((harness.HERE / "traffic" / f"{traffic}.json")
                      .read_text())
    assert (harness.HERE / "entries" / f"{body['entry']}.py").is_file()
    assert body["limits"] and all(v >= 0 for v in body["limits"].values())


def test_command_refuses_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    cmd = BENCH["command"] + ["--workload", "paper_p16.bulk", "--seed",
                              "1", "--seconds", "1", "--trace", "0"]
    cmd[0] = sys.executable
    r = subprocess.run(cmd, cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
