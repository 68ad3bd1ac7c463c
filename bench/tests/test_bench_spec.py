"""Everything ``BENCHMARK.json`` names is found by name; a new
configuration, architecture, mix, cell or metric is new files and entries
only; the harness refuses to run without a chip and on an unknown
device."""
import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

import run
import spec

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_name_resolves():
    for cell in BENCH["workloads"]:
        conf = spec.config(BENCH, cell)
        m = spec.arch(conf).sizes(cell["config"], conf["model"])
        assert m.vocab == conf["model"]["vocab_size"]
        hash(m)
        mix = spec.traffic(cell)
        assert mix["arrivals"] == "poisson"
        for kind in ("end_to_end", "per_layer"):
            names = [m["name"] for m in spec.metrics(BENCH, cell["name"],
                                                     kind)]
            assert names
            for n in names:
                assert callable(spec.reader(n).read)
    assert {c["config"] for c in BENCH["workloads"]} == \
        {c["name"] for c in BENCH["configs"]}


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(BENCH["paths"][0] + "/")
    four = sum(c["chips"] == 4 for c in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_adding_a_cell_edits_no_file(tmp_path):
    """A new configuration, mix, cell and per-layer metric are new files
    plus new entries; every existing file stays as it is."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench/configs/smollm-135m-x4.json")
                      .read_text())
    conf["deployment"]["devices"] = 1
    conf["deployment"]["tenants"] = conf["deployment"]["tenants"][:2]
    (root / "bench/configs/smollm-135m.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    mix["tenants"] = {"zipf_s": 1.1}
    (root / "bench/traffic/chat-zipf.json").write_text(json.dumps(mix))
    (root / "bench/metrics/rounds_per_s.py").write_text(
        "def read(run):\n    return len(run.rec.steps) / run.window_s\n")
    bench["configs"].append({"name": "smollm-135m", "source": "x",
                             "file": "bench/configs/smollm-135m.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "smollm-chat", "config":
                               "smollm-135m", "traffic": "chat-zipf",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "rounds_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "fleet", "moves": "itl_p50_ms",
                               "workloads": ["smollm-chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    b = spec.load_benchmark(root)
    cell = spec.cell(b, "smollm-chat")
    assert spec.config(b, cell, root)["deployment"]["devices"] == 1
    assert spec.traffic(cell, root)["tenants"] == {"zipf_s": 1.1}
    names = [m["name"] for m in spec.metrics(b, "smollm-chat", "per_layer")]
    assert names == ["rounds_per_s"]
    assert spec.reader("rounds_per_s", root / "bench/metrics").read(
        type("R", (), {"rec": type("Rec", (), {"steps": [1, 2]}),
                       "window_s": 4.0})) == 0.5
    for p, data in before.items():
        assert p.read_bytes() == data, p


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.cell(BENCH, "no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.traffic({"traffic": "no-such-mix"})
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.arch({"arch": "no_such_arch"})
    with pytest.raises(spec.SpecError):       # no default architecture
        spec.arch({"model": {}})


def _files(root: pathlib.Path) -> dict:
    return {p: p.read_bytes() for p in root.rglob("*")
            if "__pycache__" not in p.parts and p.is_file()}


def test_adding_an_architecture_edits_no_file(tmp_path, monkeypatch):
    """A new architecture (layers alternating a window and global
    attention: a "pattern" stage the dense module cannot describe), a
    configuration that names it and a cell are new files and entries; the
    cell runs correct on the CPU, and every existing file stays as it
    is (``BENCHMARK.json`` only gains entries)."""
    import jax
    root = tmp_path / "checkout"
    shutil.copytree(FIXTURES / "tiny", root)
    before = {**_files(root / "bench"), **_files(ROOT / "bench")}
    shutil.copytree(FIXTURES / "arch", root / "bench" / "arch")
    conf = json.loads((root / "bench/configs/tiny.json").read_text())
    conf["arch"] = "alternating"
    conf["model"].update(num_hidden_layers=4, sliding_window=48)
    (root / "bench/configs/tiny-alternating.json").write_text(
        json.dumps(conf))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-alternating", "source": "x",
                             "file": "bench/configs/tiny-alternating.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "tiny-alternating", "config":
                               "tiny-alternating", "traffic": "tiny",
                               "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    toy = spec.arch(conf, root)
    m = toy.sizes("tiny-alternating", conf["model"])
    assert toy.program(m).pattern == ("local", "global")
    assert [toy.layer_at(m, r) for r in range(4)] == [
        ("stages/0/0/", 0), ("stages/0/1/", 0), ("stages/0/0/", 1),
        ("stages/0/1/", 1)]
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
            "hbm_bytes": 1e10}
    b = spec.load_benchmark(root)
    r = run.execute(b, "tiny-alternating", 2**31 + 41, 3.0, False,
                    jax.devices(), root, peak=peak)
    assert r["correct"], (r["check"], r["readings"])
    assert r["compiles_in_window"] == 0
    # the same program judged as if every layer were windowed is not
    # correct: the cell tells the alternation from the dense model
    monkeypatch.setattr(toy, "layer", toy.dense.layer)
    wrong = run.execute(b, "tiny-alternating", 2**31 + 41, 3.0, False,
                        jax.devices(), root, peak=peak)
    assert not wrong["correct"], wrong["check"]
    after = {**_files(root / "bench"), **_files(ROOT / "bench")}
    for p, data in before.items():
        assert after[p] == data, p


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        run.peak_of("TPU v99")
    assert run.peak_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-longprompt",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_alone_it_does_not_run(tmp_path):
    """A checkout of only BENCHMARK.json and bench/ has no program."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi3-longprompt",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "{" not in p.stdout
